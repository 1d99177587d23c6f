"""Spec-addressed `ResultStore` + parallel `compare`: hit/miss round-trips,
cross-process hash stability, serial/parallel result identity, and recovery
from corrupted store entries."""

import json
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import small_graph

from repro.api import (
    ExploreSpec,
    GAOptions,
    GreedyOptions,
    ResultStore,
    compare,
    run,
    spec_key,
)
from repro.core import AcceleratorConfig, CachedEvaluator, HWSpace, Objective

KB = 1 << 10
REPO_SRC = Path(__file__).resolve().parents[1] / "src"


def fixed_spec(**kw):
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    defaults = dict(
        workload="dd",
        strategy="ga",
        objective=Objective(metric="ema", alpha=None),
        hw=HWSpace(mode="fixed", base=acc),
        sample_budget=300,
        seed=0,
        options=GAOptions(population=20),
    )
    defaults.update(kw)
    return ExploreSpec(**defaults)


# ---------------------------------------------------------------------------
# addressing
# ---------------------------------------------------------------------------

def test_spec_key_is_deterministic_and_spec_sensitive():
    a, b = fixed_spec(), fixed_spec()
    assert spec_key(a) == spec_key(b)
    assert len(spec_key(a)) == 64 and int(spec_key(a), 16) >= 0
    # any spec field change re-addresses the result
    assert spec_key(a) != spec_key(fixed_spec(seed=1))
    assert spec_key(a) != spec_key(fixed_spec(sample_budget=301))
    assert spec_key(a) != spec_key(fixed_spec(strategy="dp", options=None))
    assert spec_key(a) != spec_key(
        fixed_spec(options=GAOptions(population=21)))


def test_spec_key_stable_across_processes(tmp_path):
    """The store key must not depend on interpreter state (hash seeds,
    dict order): a fresh process hashing the same spec gets the same key."""
    spec = fixed_spec(workload="vgg16")
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(spec.to_json())
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from repro.api import ExploreSpec, spec_key\n"
        "print(spec_key(ExploreSpec.from_json(open(sys.argv[2]).read())))\n"
    )
    keys = {
        subprocess.run(
            [sys.executable, "-c", code, str(REPO_SRC), str(spec_path)],
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        for _ in range(2)
    }
    assert keys == {spec_key(spec)}


def _scheme_uris(tmp_path):
    """One workload URI per built-in scheme (file: built on the fly)."""
    from repro.api import build_workload
    from repro.core.graph import graph_to_json

    file_path = tmp_path / "net.json"
    file_path.write_text(graph_to_json(
        build_workload("synthetic:diamond:10?seed=2")))
    return [
        "netlib:vgg16",
        "tpu:gemma3-4b:0?tokens=256",
        "synthetic:layered:12?seed=1",
        f"file:{file_path}",
    ]


def test_graph_fingerprint_stable_across_processes(tmp_path):
    """Every scheme must build the same graph — same structural digest — in
    a fresh interpreter, or the store's graph_sha replay check would
    spuriously reject cross-process artifacts."""
    from repro.api import build_workload, graph_fingerprint

    uris = _scheme_uris(tmp_path)
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from repro.api import build_workload, graph_fingerprint\n"
        "for uri in sys.argv[2:]:\n"
        "    print(graph_fingerprint(build_workload(uri)))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, str(REPO_SRC), *uris],
        capture_output=True, text=True, check=True)
    assert proc.stdout.split() == [
        graph_fingerprint(build_workload(uri)) for uri in uris]


def test_every_scheme_roundtrips_store_through_run_and_compare(tmp_path):
    """Acceptance: all four URI schemes resolve through api.run/compare and
    a second run of the same spec is a store hit with an identical
    ExploreResult."""
    from repro.api import GreedyOptions

    store = ResultStore(tmp_path / "store")
    for uri in _scheme_uris(tmp_path):
        spec = fixed_spec(workload=uri, strategy="greedy",
                          options=GreedyOptions(eval_budget=1_000))
        misses0, hits0 = store.misses, store.hits
        first = run(spec, store=store)
        assert first.feasible and store.misses == misses0 + 1
        again = run(spec, store=store)
        assert store.hits == hits0 + 1
        assert again.to_dict() == first.to_dict()
        # compare() on the same spec is served from the same addresses
        cmp_results = compare(spec, ["greedy", "dp"], store=store)
        assert cmp_results[0].to_dict() == first.to_dict()
        assert [r.strategy for r in cmp_results] == ["greedy", "dp"]


def test_file_workload_change_invalidates_store_hit(tmp_path):
    """file: URIs do not pin graph content, so a changed file under an
    unchanged URI must re-search, not replay the stale artifact."""
    from repro.api import GreedyOptions, build_workload
    from repro.core.graph import graph_to_json

    path = tmp_path / "net.json"
    path.write_text(graph_to_json(build_workload("synthetic:diamond:10?seed=2")))
    store = ResultStore(tmp_path / "store")
    spec = fixed_spec(workload=f"file:{path}", strategy="greedy",
                      options=GreedyOptions(eval_budget=1_000))
    first = run(spec, store=store)

    path.write_text(graph_to_json(build_workload("synthetic:layered:6?seed=9")))
    second = run(spec, store=store)
    assert second.meta["graph_sha"] != first.meta["graph_sha"]
    assert sum(len(s) for s in second.groups) == 6     # the *new* graph
    # the fresh artifact overwrote the stale one and now replays
    third = run(spec, store=store)
    assert third.to_dict() == second.to_dict()


def test_stored_artifact_of_another_graph_searches_again(tmp_path):
    """A netlib: URI names a model, not the graph the program builds for it:
    an artifact whose graph_sha differs from today's build (a plan of the
    model as an earlier release built it) must search again, not replay."""
    from repro.api import ExploreResult, GreedyOptions

    store = ResultStore(tmp_path / "store")
    spec = fixed_spec(workload="netlib:vgg16", strategy="greedy",
                      options=GreedyOptions(eval_budget=1_000))
    fresh = run(spec, store=store)
    stale = ExploreResult.from_json(fresh.to_json())
    stale.meta["graph_sha"] = "0" * 64
    store.put(spec, stale)

    again = run(spec, store=store)
    assert again.meta["graph_sha"] == fresh.meta["graph_sha"]
    assert again.to_dict() == fresh.to_dict()
    # the new search overwrote the stale artifact, which now replays
    assert store.get(spec).meta["graph_sha"] == fresh.meta["graph_sha"]


# ---------------------------------------------------------------------------
# hit / miss round-trip
# ---------------------------------------------------------------------------

def test_store_miss_then_hit_roundtrip(tmp_path):
    store = ResultStore(tmp_path / "store")
    spec = fixed_spec()
    g = small_graph()

    first = run(spec, graph=g, store=store)
    assert store.misses == 1 and store.hits == 0
    assert spec in store and len(store) == 1

    second = run(spec, graph=g, store=store)
    assert store.hits == 1
    assert second.to_dict() == first.to_dict()

    # a different spec is a different address
    other = run(fixed_spec(seed=9), graph=g, store=store)
    assert other.cost is not None and len(store) == 2


def test_store_hit_skips_search_entirely(tmp_path):
    store = ResultStore(tmp_path)
    spec = fixed_spec()
    g = small_graph()
    run(spec, graph=g, store=store)

    ev = CachedEvaluator(g)
    replayed = run(spec, graph=g, ev=ev, store=store)
    assert ev.lookups == 0 and ev.evaluations == 0
    assert replayed.feasible


def test_runtime_extras_bypass_store(tmp_path):
    """init_groups is not part of the spec, so the result must not be
    stored under (or served from) the spec's address."""
    store = ResultStore(tmp_path)
    g = small_graph()
    groups = [set(range(g.n))]
    res = run(fixed_spec(), graph=g, store=store, init_groups=[groups])
    assert res.feasible
    assert len(store) == 0 and store.hits == 0 and store.misses == 0


# ---------------------------------------------------------------------------
# corruption recovery
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("payload", [
    "not json at all {", json.dumps({"version": 1, "nonsense": True}),
])
def test_corrupted_entry_is_quarantined_and_resurveyed(tmp_path, payload):
    store = ResultStore(tmp_path)
    spec = fixed_spec()
    g = small_graph()
    original = run(spec, graph=g, store=store)

    path = store.path_for(spec)
    path.write_text(payload)
    assert store.get(spec) is None                     # miss, not a crash
    assert path.with_suffix(".json.corrupt").exists()  # quarantined aside

    recovered = run(spec, graph=g, store=store)        # re-search + re-store
    assert recovered.to_dict() == original.to_dict()
    assert store.get(spec) is not None


def test_same_label_different_graph_does_not_replay(tmp_path):
    """Spec keys carry no graph identity, so a custom graph sharing another
    graph's workload label must not be served that graph's artifact."""
    from repro.core.graph import Graph

    store = ResultStore(tmp_path)
    spec = fixed_spec()
    cached = run(spec, graph=small_graph(), store=store)

    other = Graph("dd")
    a = other.add_node("a", 8, 256, weight_bytes=1024, macs=1000)
    b = other.add_node("b", 8, 256, weight_bytes=1024, macs=1000,
                       is_output=True)
    other.add_edge(a, b)
    res = run(spec, graph=other, store=store)
    assert res.groups != cached.groups          # searched, not replayed
    assert sum(len(s) for s in res.groups) == 2

    # the original graph still replays its own artifact
    again = run(spec, graph=small_graph(), store=store)
    assert again.meta["graph_sha"] == cached.meta["graph_sha"]


def test_entry_for_a_different_spec_is_rejected(tmp_path):
    """A valid artifact filed under the wrong key (hand-copied file) must
    not be served."""
    store = ResultStore(tmp_path)
    g = small_graph()
    spec_a, spec_b = fixed_spec(), fixed_spec(seed=5)
    run(spec_a, graph=g, store=store)
    store.path_for(spec_b).write_bytes(
        store.path_for(spec_a).read_bytes())
    assert store.get(spec_b) is None
    assert store.get(spec_a) is not None


# ---------------------------------------------------------------------------
# parallel compare
# ---------------------------------------------------------------------------

STRATS = ["greedy", "dp", "ga", "sa", "two_step"]


def serialized(results):
    return [r.to_dict() for r in results]


def test_parallel_compare_matches_serial_bitwise():
    spec = ExploreSpec(
        workload="vgg16",
        strategy="ga",
        objective=Objective(metric="ema", alpha=None),
        hw=HWSpace(mode="fixed"),
        sample_budget=300,
        seed=0,
        options=GAOptions(population=10),
    )
    serial = compare(spec, STRATS)
    parallel = compare(spec, STRATS, jobs=2)
    assert serialized(serial) == serialized(parallel)
    assert [r.strategy for r in parallel] == STRATS


def test_parallel_compare_merges_worker_caches():
    g = small_graph()
    ev = CachedEvaluator(g)
    compare(fixed_spec(), ["greedy", "dp"], graph=g, ev=ev, jobs=2)
    assert ev.merged > 0 and ev.evaluations == 0
    # the merged entries now serve a serial follow-up run
    lookups0 = ev.lookups
    res = run(fixed_spec(strategy="dp", options=None), graph=g, ev=ev)
    assert res.feasible
    assert ev.lookups > lookups0 and ev.evaluations < res.evaluations


def test_parallel_compare_second_pass_is_all_store_hits(tmp_path):
    store = ResultStore(tmp_path)
    spec = fixed_spec(options=GAOptions(population=10), sample_budget=200)
    g = small_graph()
    first = compare(spec, ["greedy", "dp", "ga"], graph=g, jobs=2,
                    store=store)
    assert store.misses == 3

    ev = CachedEvaluator(g)
    again = compare(spec, ["greedy", "dp", "ga"], graph=g, ev=ev, jobs=2,
                    store=store)
    assert store.hits == 3
    assert ev.evaluations == 0 and ev.merged == 0   # zero new search work
    assert serialized(again) == serialized(first)


def test_compare_accepts_full_specs_and_dedupes(tmp_path):
    store = ResultStore(tmp_path)
    g = small_graph()
    spec = fixed_spec(options=GAOptions(population=10), sample_budget=200)
    variants = [
        replace_strategy(spec, "greedy"),
        replace_strategy(spec, "greedy"),            # exact duplicate
        spec,
    ]
    results = compare(spec, variants, graph=g, jobs=2, store=store)
    assert [r.strategy for r in results] == ["greedy", "greedy", "ga"]
    assert results[0].to_dict() == results[1].to_dict()
    assert len(store) == 2                            # duplicate ran once


def replace_strategy(spec, name):
    from dataclasses import replace
    return replace(spec, strategy=name,
                   options=GreedyOptions() if name == "greedy" else None)


def test_compare_rejects_mismatched_workload_specs():
    spec = fixed_spec()
    with pytest.raises(ValueError, match="share the primary spec"):
        compare(spec, [fixed_spec(workload="other")], graph=small_graph())


# ---------------------------------------------------------------------------
# evaluation-count semantics (warmth independence)
# ---------------------------------------------------------------------------

def test_evaluations_independent_of_cache_warmth():
    g = small_graph()
    cold = run(fixed_spec(strategy="dp", options=None), graph=small_graph())
    ev = CachedEvaluator(g)
    run(fixed_spec(strategy="greedy",
                   options=GreedyOptions(eval_budget=500)), graph=g, ev=ev)
    warm = run(fixed_spec(strategy="dp", options=None), graph=g, ev=ev)
    assert warm.evaluations == cold.evaluations
    # and two_step now reports its per-capacity inner GA queries
    ts = run(fixed_spec(strategy="two_step", options=None,
                        sample_budget=200), graph=small_graph())
    assert ts.evaluations > 0


# ---------------------------------------------------------------------------
# maintenance: ls / gc (cross-run eviction)
# ---------------------------------------------------------------------------

def _fill_store(tmp_path, n=4):
    store = ResultStore(tmp_path / "store")
    g = small_graph()
    specs = [fixed_spec(strategy="greedy",
                        options=GreedyOptions(eval_budget=100 + i))
             for i in range(n)]
    for i, spec in enumerate(specs):
        run(spec, graph=g, store=store)
        # well-separated mtimes so LRU order is deterministic on coarse fs
        entry = store.path_for(spec)
        import os
        os.utime(entry, (1_000_000 + i, 1_000_000 + i))
    return store, specs


def test_store_entries_are_lru_ordered(tmp_path):
    store, specs = _fill_store(tmp_path)
    entries = store.entries()
    assert [e.key for e in entries] == [spec_key(s) for s in specs]
    assert all(e.size > 0 for e in entries)
    assert all(e.workload == "dd" and e.strategy == "greedy"
               for e in entries)


def test_store_gc_evicts_oldest_down_to_cap(tmp_path):
    store, specs = _fill_store(tmp_path)
    sizes = [e.size for e in store.entries()]
    cap = sizes[-1] + sizes[-2]  # room for exactly the two newest
    removed, freed = store.gc(max_bytes=cap)
    assert removed == 2 and freed == sizes[0] + sizes[1]
    kept = {e.key for e in store.entries()}
    assert kept == {spec_key(s) for s in specs[2:]}
    assert store.total_bytes() <= cap
    # the evicted specs re-search and re-populate on the next run
    again = run(specs[0], graph=small_graph(), store=store)
    assert again.feasible and specs[0] in store


def test_store_gc_zero_cap_clears_everything_and_corrupt(tmp_path):
    store, _ = _fill_store(tmp_path, n=2)
    (store.root / "junk.json.corrupt").write_text("{}")
    removed, _ = store.gc(max_bytes=0)
    assert removed == 3
    assert store.total_bytes() == 0 and len(store) == 0
