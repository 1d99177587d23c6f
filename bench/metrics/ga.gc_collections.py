"""Collections of CPython's cyclic garbage collector per GA generation.

The program's counter ``ga.gc_collections`` (``core/ga.py``: the collections
run inside a search, which runs with the collector paused) over the window's
``ga.generation`` spans, the one that the window's close aborts included.
It reads 0 while the pause holds; a program without the counter reads
nothing.
"""


def read(run):
    collections = run.counters.get("ga.gc_collections")
    if collections is None:
        return None
    generations = sum(1 for sp in run.spans if sp.name == "ga.generation")
    if not generations:
        return None
    return collections / generations
