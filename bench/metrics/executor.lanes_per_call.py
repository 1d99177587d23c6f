"""Mean lanes the jax executor sends per device call.

From the program's counters ``engine.device_lanes`` over
``engine.device_calls``.
"""


def read(run):
    calls = run.counters.get("engine.device_calls", 0)
    if not calls:
        return None
    return run.counters.get("engine.device_lanes", 0) / calls
