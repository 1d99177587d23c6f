"""The telemetry layer's hard invariant and its exporters.

The invariant (ISSUE: observability): telemetry is a *side channel*.
With a recorder installed or absent, every strategy returns a bitwise
identical ``ExploreResult``, golden artifacts stay byte-identical, and
the store writes the same bytes.  On top of that: the recorder's span
tree has a pinned shape for a seeded GA run, the Perfetto exporters emit
schema-valid Chrome trace-event JSON, and the plan server's ``/metrics``
endpoint serves parseable Prometheus text whose counters are monotone.
"""

import json
import math
import sys
import urllib.request
from pathlib import Path

import pytest

from repro.api import ExploreSpec, GAOptions, run
from repro.api.store import ResultStore, spec_key
from repro.obs import (
    Histogram,
    NullRecorder,
    Recorder,
    chrome_trace_doc,
    recorder_events,
    render_metrics,
    traffic_events,
)
from repro.obs import recorder as obs
from repro.serve.plans import PlanService, fetch_metrics, serve_in_thread
from test_golden_workloads import canonical_dict, golden_path, golden_spec

REPO_ROOT = Path(__file__).resolve().parents[1]

ALL_STRATEGIES = ("dp", "enum", "ga", "greedy", "sa", "two_step")


def small_spec(strategy: str, **kw) -> ExploreSpec:
    kw.setdefault("workload", "synthetic:chain:6?seed=1")
    kw.setdefault("sample_budget", 200)
    kw.setdefault("seed", 0)
    return ExploreSpec(strategy=strategy, **kw)


def ga_spec() -> ExploreSpec:
    return ExploreSpec(workload="synthetic:layered:10?seed=2",
                       strategy="ga", sample_budget=150, seed=0,
                       options=GAOptions(population=10))


def validate_telemetry(doc):
    sys.path.insert(0, str(REPO_ROOT / "scripts"))
    try:
        from check_telemetry_schema import validate_telemetry_dict
    finally:
        sys.path.pop(0)
    return validate_telemetry_dict(doc)


# ---------------------------------------------------------------------------
# recorder primitives
# ---------------------------------------------------------------------------

def test_spans_nest_and_appear_in_entry_order():
    rec = Recorder()
    with rec.span("a"):
        with rec.span("b", k=1):
            rec.add("hits")
        with rec.span("c"):
            pass
    assert [sp.name for sp in rec.spans] == ["a", "b", "c"]
    assert [sp.parent for sp in rec.spans] == [-1, 0, 0]
    assert all(sp.parent < sp.index for sp in rec.spans)
    assert all(sp.dur_s >= 0 for sp in rec.spans)
    assert rec.spans[1].attrs == {"k": 1}
    assert rec.counters == {"hits": 1}
    assert rec.span_tree() == [
        {"name": "a", "children": [
            {"name": "b", "children": []},
            {"name": "c", "children": []},
        ]}]


def test_span_stack_unwinds_through_exceptions():
    rec = Recorder()
    with pytest.raises(RuntimeError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise RuntimeError("boom")
    with rec.span("after"):
        pass
    assert rec.spans[-1].name == "after"
    assert rec.spans[-1].parent == -1     # stack fully unwound


def test_merge_counters_skips_non_numeric_and_bools():
    rec = Recorder()
    rec.merge_counters({"n": 2, "flag": True, "name": "x", "f": 1.5},
                       prefix="ev.")
    assert rec.counters == {"ev.n": 2, "ev.f": 1.5}


def test_null_recorder_is_inert_and_ambient_by_default():
    assert isinstance(obs.current(), NullRecorder)
    assert not obs.enabled()
    with obs.span("nothing", k=1):
        obs.add("x")
        obs.sample("y", 2.0)
    rec = Recorder()
    with obs.recording(rec):
        assert obs.current() is rec
        with obs.span("real"):
            obs.add("x")
    assert not obs.enabled()
    assert [sp.name for sp in rec.spans] == ["real"]
    assert rec.counters == {"x": 1}


# ---------------------------------------------------------------------------
# histogram + prometheus text
# ---------------------------------------------------------------------------

def test_histogram_exact_count_sum_max_and_cumulative_buckets():
    h = Histogram(buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 5
    assert h.total == pytest.approx(56.05)
    assert h.max == 50.0
    assert h.cumulative() == [(0.1, 1), (1.0, 3), (10.0, 4),
                              (math.inf, 5)]
    # quantiles interpolate inside a bucket; the +Inf bucket reports max
    assert 0.1 <= h.quantile(0.5) <= 1.0
    assert h.quantile(0.99) == 50.0
    snap = h.snapshot_ms()
    assert set(snap) == {"count", "mean_ms", "max_ms", "p50_ms", "p95_ms"}
    assert snap["count"] == 5 and snap["max_ms"] == 50_000.0


def test_empty_histogram_snapshot_is_zeroed():
    snap = Histogram().snapshot_ms()
    assert snap == {"count": 0, "mean_ms": 0.0, "max_ms": 0.0,
                    "p50_ms": 0.0, "p95_ms": 0.0}


def test_histogram_never_drops_samples_unlike_the_old_window():
    # the regression that motivated the migration: 10k observations, the
    # quantile must reflect all of them, not the last 512
    h = Histogram()
    for i in range(10_000):
        h.observe(0.001 if i < 9_000 else 20.0)
    assert h.count == 10_000
    assert h.quantile(0.5) <= 0.001   # old window would report 20.0
    assert h.quantile(0.95) > 1.0


def test_render_metrics_text_format():
    h = Histogram(buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    text = render_metrics([
        ("t_total", "counter", "Things.", [({"tier": "a"}, 3)]),
        ("g", "gauge", "A gauge.", [(None, 1.5)]),
        ("lat", "histogram", "Latency.", [({"tier": "a"}, h)]),
    ])
    lines = text.splitlines()
    assert "# TYPE t_total counter" in lines
    assert 't_total{tier="a"} 3' in lines
    assert "g 1.5" in lines
    assert 'lat_bucket{le="1",tier="a"} 1' in lines
    assert 'lat_bucket{le="+Inf",tier="a"} 2' in lines
    assert 'lat_sum{tier="a"} 2.5' in lines
    assert 'lat_count{tier="a"} 2' in lines
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# the hard invariant: recorder on/off => bitwise identical results
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_recorder_does_not_perturb_results(strategy):
    # the golden synthetic workload, one run per registered strategy
    wl = "synthetic:layered:24?seed=7"
    plain = run(small_spec(strategy, workload=wl), store=None)
    rec = Recorder()
    with obs.recording(rec):
        recorded = run(small_spec(strategy, workload=wl), store=None)
    assert recorded.to_json() == plain.to_json()
    # ... and the recorder actually saw the run
    assert [sp.name for sp in rec.spans if sp.parent == -1] == \
        ["resolve-workload", f"strategy:{strategy}"]


def test_profile_view_rides_the_recorder_without_perturbing_results():
    plain = run(small_spec("ga"), store=None)
    profiled = run(small_spec("ga"), store=None, profile=True)
    prof = profiled.meta.pop("profile")
    assert profiled.to_json() == plain.to_json()
    assert prof["wall_s"] > 0
    assert "lookups" in prof


def test_golden_artifact_is_byte_identical_with_telemetry_on():
    spec = golden_spec("synthetic_layered24", "ga")
    golden = json.loads(
        golden_path("synthetic_layered24", "ga").read_text())
    rec = Recorder()
    with obs.recording(rec):
        got = canonical_dict(run(spec))
    assert got == golden
    assert rec.spans      # telemetry was live during the golden run


def test_store_writes_identical_bytes_with_telemetry_on(tmp_path):
    def artifact_bytes(root: Path) -> dict:
        return {p.relative_to(root): p.read_bytes()
                for p in sorted(root.rglob("*.json"))}

    spec = small_spec("ga")
    run(spec, store=ResultStore(tmp_path / "off"))
    with obs.recording(Recorder()):
        run(spec, store=ResultStore(tmp_path / "on"))
    off = artifact_bytes(tmp_path / "off")
    on = artifact_bytes(tmp_path / "on")
    assert off and off == on
    assert spec_key(spec) == spec_key(small_spec("ga"))


# ---------------------------------------------------------------------------
# pinned span-tree shape + per-generation samples for a seeded GA run
# ---------------------------------------------------------------------------

def _names(nodes):
    return [n["name"] for n in nodes]


def _parents_of(tree, name, parent=None):
    """Names of the parents of every span called ``name``, in tree order."""
    out = []
    for node in tree:
        if node["name"] == name:
            out.append(parent)
        out += _parents_of(node["children"], name, node["name"])
    return out


def test_ga_span_tree_shape_is_pinned():
    rec = Recorder()
    with obs.recording(rec):
        run(ga_spec(), store=None)
    tree = rec.span_tree()
    assert _names(tree) == ["resolve-workload", "strategy:ga"]
    # the population is built before generation 0, and each generation's
    # samples are emitted after it closes: the phases tile the search
    phases = tree[1]["children"]
    assert _names(phases) == ["ga.init"] + ["ga.generation",
                                            "ga.samples"] * 15
    assert phases[0]["children"] == []
    assert all(p["children"] == [] for p in phases[2::2])
    gens = phases[1::2]
    # generation 0 evaluates the seed population: one repair batch, then
    # one costing batch of the repaired variants
    assert _names(gens[0]["children"]) == ["ga.repair", "ga.score"]
    assert [_names(c["children"]) for c in gens[0]["children"]] == \
        [["evaluate_batch"], ["evaluate_batch"]]
    for gen in gens[1:]:
        assert _names(gen["children"]) == \
            ["ga.variation", "ga.repair", "ga.score", "ga.select"]
        variation, repair, score, select = gen["children"]
        assert variation["children"] == [] and select["children"] == []
        # every generation with cache misses nests its batches in a phase
        for phase in (repair, score):
            assert all(c["name"] == "evaluate_batch"
                       for c in phase["children"])
    assert set(_parents_of(tree, "evaluate_batch")) == {"ga.repair",
                                                         "ga.score"}
    series = {name for name, _, _ in rec.samples}
    assert series == {"ga.best_cost", "ga.mean_cost", "ga.diversity"}
    n_best = sum(1 for name, _, _ in rec.samples if name == "ga.best_cost")
    assert n_best == len(gens)
    assert rec.counters["evaluator.lookups"] > 0
    assert rec.counters["repair.rounds"] > 0


def test_jax_executor_span_tree_is_pinned():
    """Each array batch of the ``jax`` executor records its phases, and
    each device call its transfers and kernel, without perturbing the
    result."""
    pytest.importorskip("jax")
    spec = golden_spec("synthetic_layered24", "ga")
    plain = run(spec, eval_backend="jax")
    rec = Recorder()
    with obs.recording(rec):
        recorded = run(spec, eval_backend="jax")
    assert recorded.to_json() == plain.to_json()
    batches = [sp for sp in rec.spans if sp.name == "evaluate_batch"]
    assert batches
    children = {sp.index: [] for sp in rec.spans}
    for sp in rec.spans:
        if sp.parent >= 0:
            children[sp.parent].append(sp.name)
    for sp in batches:
        assert children[sp.index] in (
            ["executor.structures", "executor.pack", "executor.finish",
             "executor.stitch"],
            # every lane took the scalar path: no device call
            ["executor.structures", "executor.pack"])
    finishes = [sp for sp in rec.spans if sp.name == "executor.finish"]
    assert finishes
    for sp in finishes:
        assert children[sp.index] == \
            ["executor.put", "executor.run", "executor.fetch"]
        assert rec.spans[sp.parent].name == "evaluate_batch"
    assert len(finishes) == rec.counters["engine.device_calls"]
    assert sum(sp.attrs["lanes"] for sp in finishes) == \
        rec.counters["engine.device_lanes"]
    for sp in rec.spans:
        if sp.name in ("executor.put", "executor.run", "executor.fetch"):
            lanes, padded = sp.attrs["lanes"], sp.attrs["padded"]
            assert lanes <= padded < 2 * lanes
            assert padded & (padded - 1) == 0


# ---------------------------------------------------------------------------
# perfetto / chrome trace-event export
# ---------------------------------------------------------------------------

def test_recorder_export_is_schema_valid_chrome_trace():
    rec = Recorder()
    with obs.recording(rec):
        run(ga_spec(), store=None)
    doc = chrome_trace_doc(recorder_events(rec), counters=rec.counters,
                           meta={"kind": "search"})
    assert validate_telemetry(doc) == []
    json.dumps(doc)    # exporter output must be JSON-serializable
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"resolve-workload", "strategy:ga",
                                       "ga.generation", "evaluate_batch"}
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert {e["name"] for e in cs} == {"ga.best_cost", "ga.mean_cost",
                                       "ga.diversity"}


def test_traffic_export_is_schema_valid_chrome_trace():
    from repro.api import build_workload
    from repro.sim import simulate_plan

    res = run(small_spec("greedy"), store=None)
    g = build_workload(res.spec.workload)
    trace = simulate_plan(g, res.groups, res.acc)
    doc = chrome_trace_doc(traffic_events(trace),
                           meta={"kind": "traffic"})
    assert validate_telemetry(doc) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(trace.steps)
    # the time base is the accelerator clock: last event ends at makespan
    scale = 1e6 / trace.acc.freq_hz
    assert max(e["ts"] + e["dur"] for e in xs) == \
        pytest.approx(trace.total_cycles * scale, rel=1e-6)
    counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert counters == {"DRAM bytes", "NoC bytes", "occupancy"}


# ---------------------------------------------------------------------------
# /metrics endpoint
# ---------------------------------------------------------------------------

def parse_prom(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        assert line, "blank lines are not part of the exposition"
        if line.startswith("#"):
            parts = line.split(None, 3)
            assert parts[1] in ("HELP", "TYPE") and len(parts) == 4
            continue
        key, raw = line.rsplit(" ", 1)
        out[key] = float(raw)
    return out


def test_metrics_endpoint_parses_and_counters_are_monotone(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"))
    server = serve_in_thread(svc)
    try:
        spec = small_spec("greedy")
        body = spec.to_json().encode()
        for _ in range(2):
            req = urllib.request.Request(
                server.url + "/plan", data=body,
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=600) as resp:
                assert json.loads(resp.read())["ok"]
        with urllib.request.urlopen(server.url + "/metrics",
                                    timeout=30) as resp:
            ctype = resp.headers["Content-Type"]
            m1 = parse_prom(resp.read().decode())
        assert ctype.startswith("text/plain")
        assert m1["repro_plan_requests_total"] == 2
        assert m1['repro_plan_served_total{tier="search"}'] == 1
        assert m1['repro_plan_served_total{tier="store"}'] == 1
        for tier in ("zoo", "store", "search"):
            key = ('repro_plan_request_latency_seconds_count'
                   f'{{tier="{tier}"}}')
            assert key in m1
            # bucket counts are cumulative in le and end at _count
            buckets = [v for k, v in m1.items()
                       if k.startswith('repro_plan_request_latency_'
                                       f'seconds_bucket{{le=')
                       and f'tier="{tier}"' in k]
            assert buckets == sorted(buckets)
            assert buckets[-1] == m1[key]
        assert m1['repro_store_entries{tier="store"}'] == 1
        assert m1['repro_store_bytes{tier="store"}'] > 0

        # a third request: counters only move forward
        req = urllib.request.Request(
            server.url + "/plan", data=body,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=600) as resp:
            assert json.loads(resp.read())["served_from"] == "store"
        m2 = parse_prom(fetch_metrics(server.url))
        for key, v1 in m1.items():
            if any(s in key for s in ("_total", "_count", "_bucket",
                                      "_sum")):
                assert m2[key] >= v1, key
        assert m2["repro_plan_requests_total"] == 3
        # the back-compat JSON view still mirrors the same histograms
        with urllib.request.urlopen(server.url + "/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())["server"]
        assert set(stats["latency_ms"]) == {"zoo", "store", "search"}
        assert stats["latency_ms"]["store"]["count"] == \
            m2['repro_plan_request_latency_seconds_count{tier="store"}']
    finally:
        server.close()
