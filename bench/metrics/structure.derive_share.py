"""Share of search time spent deriving subgraph schedules (%).

The program's counter ``evaluator.structure_derive_s`` (host clock around
``compute_structure``) over the durations of the ``strategy:`` spans of the
searches that completed, whose counters it sums.
"""


def read(run):
    searched = sum(sp.dur_s for sp in run.spans
                   if sp.name.startswith("strategy:")
                   and sp.attrs.get("completed"))
    if searched <= 0 or "evaluator.structure_derive_s" not in run.counters:
        return None
    return 100.0 * run.counters["evaluator.structure_derive_s"] / searched
