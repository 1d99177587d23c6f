"""Share of GA generation time spent outside the evaluator's batches (%).

From the program's spans: each ``ga.generation`` span's duration less that
of the ``evaluate_batch`` spans inside it — variation, repair bookkeeping
and selection on the host — over all generation time in the window.
"""


def read(run):
    spans = run.spans
    gen_total = sum(sp.dur_s for sp in spans if sp.name == "ga.generation")
    if gen_total <= 0:
        return None
    inside = 0.0
    for sp in spans:
        if sp.name != "evaluate_batch":
            continue
        p = sp.parent
        while p >= 0 and spans[p].name != "ga.generation":
            p = spans[p].parent
        if p >= 0:
            inside += sp.dur_s
    return 100.0 * (gen_total - inside) / gen_total
