"""Share of raw-tier structure misses the canonical tier answered (%).

The program's counters ``evaluator.structure_canon_hits`` (an isomorphic
subgraph's structure reused; ``core/cost.py``) and
``evaluator.structure_misses`` (derived anew), summed over the searches
that completed: 100 x hits / (hits + misses).
"""


def read(run):
    hits = run.counters.get("evaluator.structure_canon_hits")
    misses = run.counters.get("evaluator.structure_misses")
    if hits is None or misses is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
