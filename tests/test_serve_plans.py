"""Plan server (`repro.serve.plans`): tiered zoo→store→search resolution,
in-flight request deduplication, warm evaluator reuse, fingerprint
revalidation of every hit, and the HTTP protocol + /stats schema.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from repro.api import ExploreSpec, ResultStore, spec_key
from repro.core import HWSpace, Objective
from repro.core.graph import graph_to_json
from repro.serve.plans import (
    PlanService,
    fetch_stats,
    request_plan,
    resolve_plan,
    serve_in_thread,
)
from repro.serve.zoo import build_zoo, verify_zoo, zoo_coverage, zoo_specs


def greedy_spec(workload="synthetic:chain:6?seed=1", **kw):
    defaults = dict(
        workload=workload,
        strategy="greedy",
        objective=Objective(metric="ema", alpha=None),
        hw=HWSpace(mode="fixed"),
        sample_budget=100,
        seed=0,
    )
    defaults.update(kw)
    return ExploreSpec(**defaults)


# ---------------------------------------------------------------------------
# resolve_plan: the tiered building block
# ---------------------------------------------------------------------------

def test_resolve_plan_cold_then_store_hit(tmp_path):
    store = ResultStore(tmp_path / "store")
    spec = greedy_spec()
    first, src1 = resolve_plan(spec, store=store)
    second, src2 = resolve_plan(spec, store=store)
    assert (src1, src2) == ("search", "store")
    assert second.to_json() == first.to_json()     # replay is bitwise
    assert store.writes == 1


def test_resolve_plan_without_store_always_searches():
    spec = greedy_spec()
    calls = []

    def searcher(s):
        calls.append(s)
        from repro.api import run
        return run(s)

    _, src = resolve_plan(spec, searcher=searcher)
    _, src2 = resolve_plan(spec, searcher=searcher)
    assert (src, src2) == ("search", "search") and len(calls) == 2


def test_resolve_plan_zoo_tier_wins_and_store_stays_clean(tmp_path):
    spec = greedy_spec()
    zoo_rw = ResultStore(tmp_path / "zoo")
    resolve_plan(spec, store=zoo_rw)               # build the zoo artifact
    zoo = ResultStore(tmp_path / "zoo", read_only=True)
    store = ResultStore(tmp_path / "store")
    res, src = resolve_plan(spec, store=store, zoo=zoo)
    assert src == "zoo"
    assert len(store) == 0                         # zoo hits are not copied
    assert res.cost == pytest.approx(res.objective.cost(res.plan, res.acc))


def test_resolve_plan_revalidates_file_workloads(tmp_path):
    """A ``file:`` URI is not content-stable: when the file changes, the
    archived plan must not replay against the new graph."""
    from conftest import chain_graph, small_graph

    path = tmp_path / "net.json"
    path.write_text(graph_to_json(small_graph()))
    spec = greedy_spec(workload=f"file:{path}")
    store = ResultStore(tmp_path / "store")
    _, src1 = resolve_plan(spec, store=store)
    _, src2 = resolve_plan(spec, store=store)
    assert (src1, src2) == ("search", "store")
    path.write_text(graph_to_json(chain_graph(8)[0]))   # file changed
    _, src3 = resolve_plan(spec, store=store)
    assert src3 == "search"


@pytest.mark.parametrize("tier", ["store", "zoo"])
def test_resolve_plan_refuses_an_artifact_of_another_graph(tmp_path, tier):
    """A stable URI is fingerprint-checked too: an archived plan whose
    graph_sha is not what the URI builds today is searched again."""
    from repro.api import ExploreResult

    spec = greedy_spec(workload="netlib:vgg16")
    fresh, _ = resolve_plan(spec)
    stale = ExploreResult.from_json(fresh.to_json())
    stale.meta["graph_sha"] = "0" * 64
    (tmp_path / "zoo").mkdir()
    ResultStore(tmp_path / tier).put(spec, stale)
    store = ResultStore(tmp_path / "store")
    zoo = ResultStore(tmp_path / "zoo", read_only=True)
    res, src = resolve_plan(spec, store=store, zoo=zoo)
    assert src == "search"
    assert res.meta["graph_sha"] == fresh.meta["graph_sha"]


# ---------------------------------------------------------------------------
# PlanService: dedup, counters, warm evaluators
# ---------------------------------------------------------------------------

def test_service_cold_then_hit(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"))
    try:
        spec = greedy_spec()
        a = svc.plan(spec)
        b = svc.plan(spec)
        assert (a.served_from, b.served_from) == ("search", "store")
        assert not a.deduped and not b.deduped
        assert svc.searches == 1 and svc.store_hits == 1
        assert b.result.to_json() == a.result.to_json()
        assert a.key == b.key == spec_key(spec)
    finally:
        svc.close()


def test_concurrent_identical_requests_search_exactly_once(tmp_path):
    """N identical concurrent requests: one search, N-1 dedup joins, and
    every caller gets the identical result."""
    n = 8
    svc = PlanService(ResultStore(tmp_path / "store"), workers=4)
    spec = greedy_spec("synthetic:layered:10?seed=5")
    out = [None] * n
    barrier = threading.Barrier(n)

    def hit(i):
        barrier.wait()
        out[i] = svc.plan(spec)

    try:
        threads = [threading.Thread(target=hit, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert svc.searches == 1
        assert svc.dedup_joins == n - 1
        assert sum(r.deduped for r in out) == n - 1
        payloads = {r.result.to_json() for r in out}
        assert len(payloads) == 1
        assert len(svc.store) == 1
    finally:
        svc.close()


def test_distinct_specs_do_not_dedup(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"), workers=2)
    try:
        a = svc.plan(greedy_spec(seed=0))
        b = svc.plan(greedy_spec(seed=1))
        assert svc.searches == 2 and svc.dedup_joins == 0
        assert a.key != b.key
    finally:
        svc.close()


def test_warm_evaluator_reused_across_same_workload_searches(tmp_path):
    """Two different specs over one workload share one cached evaluator
    (same graph fingerprint + out_tile -> the second search starts warm)."""
    svc = PlanService(ResultStore(tmp_path / "store"))
    try:
        svc.plan(greedy_spec(sample_budget=50))
        svc.plan(greedy_spec(sample_budget=60))       # different spec_key
        assert svc.searches == 2
        assert svc.stats()["server"]["warm_evaluators"] == 1
        svc.plan(greedy_spec(workload="synthetic:layered:8?seed=2"))
        assert svc.stats()["server"]["warm_evaluators"] == 2
    finally:
        svc.close()


def test_service_zoo_tier_is_read_only(tmp_path):
    spec = greedy_spec()
    build_zoo(ResultStore(tmp_path / "zoo"), [spec])
    zoo = ResultStore(tmp_path / "zoo", read_only=True)
    before = sorted(p.name for p in (tmp_path / "zoo").iterdir())
    svc = PlanService(ResultStore(tmp_path / "store"), zoo=zoo)
    try:
        resp = svc.plan(spec)
        assert resp.served_from == "zoo"
        assert svc.zoo_hits == 1 and svc.searches == 0
        assert len(svc.store) == 0
        assert sorted(p.name for p in (tmp_path / "zoo").iterdir()) == before
    finally:
        svc.close()


def test_closed_service_rejects_requests(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"))
    svc.close()
    with pytest.raises(RuntimeError):
        svc.plan(greedy_spec())


# ---------------------------------------------------------------------------
# HTTP shell + clients
# ---------------------------------------------------------------------------

def test_http_roundtrip_hit_and_stats_schema(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"))
    server = serve_in_thread(svc)
    try:
        spec = greedy_spec()
        first = request_plan(server.url, spec)
        second = request_plan(server.url, spec)
        assert first["ok"] and first["served_from"] == "search"
        assert second["served_from"] == "store"
        assert second["result"] == first["result"]
        assert second["key"] == spec_key(spec)
        stats = fetch_stats(server.url)
        assert stats["ok"]
        server_doc = stats["server"]
        for field in ("version", "uptime_s", "workers", "requests",
                      "searches", "store_hits", "zoo_hits", "dedup_joins",
                      "errors", "in_flight", "warm_evaluators", "latency_ms"):
            assert field in server_doc, field
        assert server_doc["requests"] == 2
        assert server_doc["searches"] == 1
        assert server_doc["store_hits"] == 1
        assert set(server_doc["latency_ms"]) == {"zoo", "store", "search"}
        assert stats["store"]["entries"] == 1
        assert stats["zoo"] is None
    finally:
        server.close()


def test_http_bad_spec_is_400_and_unknown_route_404(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"))
    server = serve_in_thread(svc)
    try:
        req = urllib.request.Request(
            server.url + "/plan", data=b"{not json",
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 400
        assert not json.loads(exc.value.read().decode())["ok"]
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(server.url + "/nope", timeout=10)
        assert exc.value.code == 404
        with urllib.request.urlopen(server.url + "/healthz",
                                    timeout=10) as resp:
            assert json.loads(resp.read().decode()) == {"ok": True}
    finally:
        server.close()


def test_http_search_failure_is_500(tmp_path):
    svc = PlanService(ResultStore(tmp_path / "store"))
    server = serve_in_thread(svc)
    try:
        bad = greedy_spec(workload="netlib:no-such-model")
        req = urllib.request.Request(
            server.url + "/plan", data=bad.to_json().encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=30)
        assert exc.value.code == 500
        assert fetch_stats(server.url)["server"]["errors"] == 1
    finally:
        server.close()


# ---------------------------------------------------------------------------
# zoo: grid, build resumability, coverage, verification
# ---------------------------------------------------------------------------

def test_zoo_build_is_resumable_and_coverage_tracks(tmp_path):
    specs = zoo_specs(workloads=["synthetic:chain:6?seed=1"],
                      strategies=["greedy"],
                      objectives=[("ema", None), ("energy", 0.002)],
                      budget=100)
    assert len(specs) == 2
    store = ResultStore(tmp_path / "zoo")
    assert all(r["status"] == "missing" for r in zoo_coverage(store, specs))
    first = build_zoo(store, specs)
    assert (first.built, first.replayed, first.failed) == (2, 0, 0)
    again = build_zoo(store, specs)                 # resume: all hits
    assert (again.built, again.replayed, again.failed) == (0, 2, 0)
    assert all(r["status"] == "archived" for r in zoo_coverage(store, specs))
    assert zoo_coverage(None, specs)[0]["status"] == "missing"


def test_zoo_build_reports_failures_and_continues(tmp_path):
    good = greedy_spec()
    bad = greedy_spec(workload="netlib:no-such-model")
    store = ResultStore(tmp_path / "zoo")
    report = build_zoo(store, [bad, good])
    assert (report.built, report.failed) == (1, 1)
    assert len(report.errors) == 1 and "no-such-model" in report.errors[0]


def test_zoo_verify_clean_and_detects_tampering(tmp_path):
    store = ResultStore(tmp_path / "zoo")
    build_zoo(store, [greedy_spec()])
    assert verify_zoo(store) == []
    # tamper: rename the artifact to a foreign address
    artifact = next(store.root.glob("*.json"))
    artifact.rename(store.root / ("0" * 64 + ".json"))
    problems = verify_zoo(store)
    assert len(problems) == 1 and "hashes to" in problems[0]


def test_zoo_verify_detects_cost_drift(tmp_path):
    store = ResultStore(tmp_path / "zoo")
    build_zoo(store, [greedy_spec()])
    artifact = next(store.root.glob("*.json"))
    doc = json.loads(artifact.read_text())
    doc["cost"] = doc["cost"] * 2 + 1.0
    artifact.write_text(json.dumps(doc))
    problems = verify_zoo(store, rebuild_graphs=False)
    assert len(problems) == 1 and "re-scored" in problems[0]
