"""Host time the structure half spends per layer it derives (us/node).

The program's counters ``evaluator.structure_derive_s`` (host clock around
``compute_structure``) over ``evaluator.structure_derived_nodes`` (the
layers of the subgraphs those derivations covered; ``core/cost.py``),
summed over the searches that completed.  A program without the node
counter reads nothing.
"""


def read(run):
    nodes = run.counters.get("evaluator.structure_derived_nodes")
    derive_s = run.counters.get("evaluator.structure_derive_s")
    if not nodes or derive_s is None:
        return None
    return 1e6 * derive_s / nodes
