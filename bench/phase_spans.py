"""Span walks shared by the readers of the GA's phase spans
(``bench/metrics/ga.*_share.py``); kept outside ``bench/metrics`` so that
it is not taken for a reader."""


def host_share(run, phase):
    """Share of ``ga.generation`` time spent in the ``phase`` spans less the
    ``evaluate_batch`` spans whose nearest enclosing ``ga.*`` span is one of
    them (%); None when the run recorded no ``phase`` or no generation."""
    spans = run.spans
    durs = [sp.dur_s for sp in spans if sp.name == phase]
    gens = sum(sp.dur_s for sp in spans if sp.name == "ga.generation")
    if not durs or gens <= 0:
        return None
    inside = 0.0
    for sp in spans:
        if sp.name != "evaluate_batch":
            continue
        p = sp.parent
        while p >= 0 and not spans[p].name.startswith("ga."):
            p = spans[p].parent
        if p >= 0 and spans[p].name == phase:
            inside += sp.dur_s
    return 100.0 * (sum(durs) - inside) / gens
