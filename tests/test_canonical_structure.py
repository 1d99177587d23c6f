"""Canonical structure memoization: the content-fingerprint tier of
:class:`CostKernel`, the disk-backed :class:`StructureCache`, and the
shipping of canonical entries from parallel ``compare``'s workers.

The load-bearing property, fuzzed here: *equal canonical keys imply
field-for-field equal structures* (up to the ``nodes`` stamp) — so a
canonical hit is bitwise-indistinguishable from a fresh
``compute_structure`` call, and every golden artifact stays byte-identical
with the memo on.
"""

import random
from dataclasses import asdict
from dataclasses import fields as dataclass_fields

import pytest
from _hypothesis_compat import given, settings, st
from backend_parity import SYNTH_KINDS, scheme_corpus
from conftest import small_graph

from repro.api import ExploreSpec, GAOptions, build_workload, compare
from repro.core import (
    AcceleratorConfig,
    CachedEvaluator,
    CostKernel,
    Graph,
    HWSpace,
    Objective,
    compute_structure,
    evaluate_subgraph,
    random_partition,
)
from repro.core.cost import SubgraphStructure, canonical_structure_key
from repro.core.structcache import StructureCache

KB = 1 << 10

_STRUCT_PAYLOAD = tuple(f.name for f in dataclass_fields(SubgraphStructure)
                        if f.name != "nodes")


def _node_sets(g, seed=0, n_parts=4):
    """Distinct node sets from random partitions (the GA query shape)."""
    rng = random.Random(seed)
    seen, out = set(), []
    for _ in range(n_parts):
        for s in random_partition(g, rng, mean_size=rng.uniform(1.5, 6.0)):
            fs = frozenset(s)
            if fs not in seen:
                seen.add(fs)
                out.append(fs)
    return out


def _assert_structs_equal(got, want, context=""):
    ga, wa = asdict(got), asdict(want)
    assert ga == wa, (
        f"structure mismatch {context}: "
        + "; ".join(f"{k}: {ga[k]!r} != {wa[k]!r}"
                    for k in ga if ga[k] != wa[k]))


# ---------------------------------------------------------------------------
# canonical hits are bitwise-identical to fresh computation
# ---------------------------------------------------------------------------

def test_canonical_structures_match_fresh_on_scheme_corpus():
    """Every URI scheme's golden workload, warm canonical memo vs fresh
    compute_structure: field-for-field equality including the nodes stamp."""
    for label, g, _queries in scheme_corpus():
        kernel = CostKernel(g)
        for fs in _node_sets(g, seed=7):
            _assert_structs_equal(kernel.structure(fs),
                                  compute_structure(g, set(fs)),
                                  context=f"[{label}] nodes={sorted(fs)}")


def test_canonical_structures_match_fresh_on_synthetic_sweep():
    """Deterministic fuzz sweep over every synthetic kind (the
    no-hypothesis fallback path)."""
    cases = [(kind, 4 + (gseed * 7 + pseed * 3) % 13, gseed, pseed)
             for kind in SYNTH_KINDS
             for gseed in range(4)
             for pseed in range(2)]
    for kind, n, gseed, pseed in cases:
        g = build_workload(f"synthetic:{kind}:{n}?seed={gseed}")
        kernel = CostKernel(g)
        for fs in _node_sets(g, seed=pseed, n_parts=3):
            _assert_structs_equal(kernel.structure(fs),
                                  compute_structure(g, set(fs)),
                                  context=f"[{kind}:{n}?seed={gseed}] "
                                          f"nodes={sorted(fs)}")


@given(kind=st.sampled_from(SYNTH_KINDS), n=st.integers(2, 20),
       gseed=st.integers(0, 1_000), pseed=st.integers(0, 1_000))
@settings(max_examples=25, deadline=None)
def test_property_canonical_structures_match_fresh(kind, n, gseed, pseed):
    g = build_workload(f"synthetic:{kind}:{n}?seed={gseed}")
    kernel = CostKernel(g)
    for fs in _node_sets(g, seed=pseed, n_parts=3):
        _assert_structs_equal(kernel.structure(fs),
                              compute_structure(g, set(fs)))


def test_memoized_costs_equal_unmemoized():
    """The full cost (structure + finish) through the memoized kernel equals
    ``evaluate_subgraph``, which derives every query afresh."""
    g = build_workload("tpu:gemma3-4b:0?tokens=512")
    kernel = CostKernel(g)
    accs = [AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB),
            AcceleratorConfig(glb_bytes=512 * KB, wbuf_bytes=0, shared=True)]
    sets = _node_sets(g, seed=3)
    for fs in sets:
        for acc in accs:
            assert asdict(kernel.cost(fs, acc)) == \
                asdict(evaluate_subgraph(g, set(fs), acc))
    assert kernel.structure_canon_hits > 0  # the workload has repeated blocks
    assert kernel.structure_misses < len(sets)


# ---------------------------------------------------------------------------
# isomorphic subgraphs collapse to one derivation
# ---------------------------------------------------------------------------

def test_isomorphic_subgraphs_share_one_entry():
    g = small_graph()  # nodes 1 and 2 are the isomorphic diamond arms
    kernel = CostKernel(g)
    st1 = kernel.structure(frozenset({1}))
    st2 = kernel.structure(frozenset({2}))
    assert kernel.structure_misses == 1
    assert kernel.structure_canon_hits == 1
    assert st1.nodes == (1,) and st2.nodes == (2,)  # re-stamped per query
    assert all(getattr(st1, f) == getattr(st2, f) for f in _STRUCT_PAYLOAD)
    # the two-node arms {1,3} / {2,3} are isomorphic too
    kernel.structure(frozenset({1, 3}))
    kernel.structure(frozenset({2, 3}))
    assert kernel.structure_misses == 2
    assert kernel.structure_canon_hits == 2
    # raw tier answers repeats without touching the canonical tier
    kernel.structure(frozenset({2}))
    assert kernel.structure_raw_hits == 1
    assert kernel.structure_canon_hits == 2


def test_canonical_key_distinguishes_non_isomorphic():
    g = small_graph()
    keys = {canonical_structure_key(g, s)
            for s in ({1}, {0}, {1, 3}, {0, 1}, {0, 1, 2, 3})}
    assert len(keys) == 5  # {0} has no producer, {1} does; etc.
    assert canonical_structure_key(g, {1}) == canonical_structure_key(g, {2})
    assert (canonical_structure_key(g, {1, 3})
            == canonical_structure_key(g, {2, 3}))
    # out_tile is part of the fingerprint
    assert (canonical_structure_key(g, {1}, out_tile=2)
            != canonical_structure_key(g, {1}, out_tile=1))


def _stride_mismatch_graph():
    """Two disjoint isomorphic copies of a diamond whose parallel paths
    carry mismatched total strides, so ``derive_schedule`` fails with a
    message naming concrete node indices."""
    g = Graph("mismatch")
    copies = []
    for c in range(2):
        x = g.add_node(f"x{c}", 64, 1)
        y1 = g.add_node(f"y1_{c}", 32, 1)
        y2 = g.add_node(f"y2_{c}", 64, 1)
        z = g.add_node(f"z{c}", 32, 1, is_output=True)
        g.add_edge(x, y1, F=1, s=2)   # total stride to z: 2
        g.add_edge(x, y2, F=1, s=1)   # total stride to z: 1 -> mismatch
        g.add_edge(y1, z, F=1, s=1)
        g.add_edge(y2, z, F=2, s=1)
        copies.append({x, y1, y2, z})
    return g, copies


def test_sched_error_structures_never_cached_canonically():
    """Error messages embed node indices, so isomorphic failing subgraphs
    must each derive their own (label-correct) error."""
    g, (a, b) = _stride_mismatch_graph()
    kernel = CostKernel(g)
    st_a = kernel.structure(frozenset(a))
    st_b = kernel.structure(frozenset(b))
    assert st_a.sched_error is not None and st_b.sched_error is not None
    assert st_a.sched_error != st_b.sched_error  # each names its own nodes
    assert kernel.structure_misses == 2          # no canonical sharing
    assert kernel.structure_canon_hits == 0
    assert len(kernel.canon_snapshot()) == 0
    _assert_structs_equal(st_a, compute_structure(g, a))
    _assert_structs_equal(st_b, compute_structure(g, b))
    # the raw tier still answers exact repeats
    kernel.structure(frozenset(a))
    assert kernel.structure_raw_hits == 1


# ---------------------------------------------------------------------------
# the disk-backed StructureCache
# ---------------------------------------------------------------------------

def test_structcache_roundtrip_and_warm_start(tmp_path):
    g = small_graph()
    cache = StructureCache(tmp_path / "structs")
    k1 = CostKernel(g, struct_cache=cache)
    sets = [frozenset({1}), frozenset({2}), frozenset({1, 3}),
            frozenset({0, 1, 2, 3})]
    for fs in sets:
        k1.structure(fs)
    assert cache.writes == k1.structure_misses == 3  # {2},{2,3} were canon
    assert len(cache) == 3
    # a fresh kernel over the same directory derives nothing
    cache2 = StructureCache(tmp_path / "structs")
    k2 = CostKernel(g, struct_cache=cache2)
    for fs in sets:
        _assert_structs_equal(k2.structure(fs), compute_structure(g, set(fs)))
    assert k2.structure_misses == 0
    assert k2.structure_disk_hits == 3   # one per distinct fingerprint
    assert k2.structure_canon_hits == 1  # {2} hits the adopted {1} entry


def test_structcache_rejects_corrupt_and_foreign_entries(tmp_path):
    g = small_graph()
    cache = StructureCache(tmp_path)
    key = canonical_structure_key(g, {1})
    st = compute_structure(g, {1})
    cache.put(key, st)
    got = cache.get(key)
    assert got is not None and got.nodes == ()
    assert all(getattr(got, f) == getattr(st, f) for f in _STRUCT_PAYLOAD)
    # tampered payload -> miss, not a wrong answer
    path = cache._path(key)
    path.write_text("{not json")
    assert cache.get(key) is None
    # an entry whose embedded key disagrees with the query key -> miss
    other = canonical_structure_key(g, {0, 1})
    cache.put(other, compute_structure(g, {0, 1}))
    cache._path(other).replace(path)
    assert cache.get(key) is None
    assert cache.get(canonical_structure_key(g, {4})) is None  # absent


def test_structcache_refuses_sched_error_entries(tmp_path):
    g, (a, _b) = _stride_mismatch_graph()
    cache = StructureCache(tmp_path)
    st = compute_structure(g, a)
    assert st.sched_error is not None
    with pytest.raises(ValueError, match="sched_error"):
        cache.put(canonical_structure_key(g, a), st)
    assert len(cache) == 0


# ---------------------------------------------------------------------------
# cross-process shipping (parallel compare)
# ---------------------------------------------------------------------------

_STRATEGIES = ["greedy", "dp", "ga"]


def _compare_spec():
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    return ExploreSpec(workload="dd", strategy="ga",
                       objective=Objective(metric="energy", alpha=0.002),
                       hw=HWSpace(mode="shared", base=acc),
                       sample_budget=200, seed=0,
                       options=GAOptions(population=10))


def test_parallel_compare_ships_canonical_structures_back():
    g = small_graph()
    ev = CachedEvaluator(g)
    compare(_compare_spec(), _STRATEGIES, graph=g, ev=ev, jobs=2)
    canon = ev.structure_snapshot()
    assert canon, "parent adopted no canonical entries from workers"
    assert ev.kernel.structure_misses == 0  # every entry came from a worker
    assert ev.kernel.structure_merged == len(canon) > 0
    # adopted entries are real structures: each equals a fresh derivation
    # of the node set it was derived for, under the key it was filed by
    for key, st in canon.items():
        assert st.sched_error is None
        assert canonical_structure_key(g, set(st.nodes)) == key
        _assert_structs_equal(st, compute_structure(g, set(st.nodes)))
    # the parent now serves those fingerprints without deriving
    for st in canon.values():
        ev.kernel.structure(frozenset(st.nodes))
    assert ev.kernel.structure_misses == 0


def test_parallel_compare_workers_share_disk_cache(tmp_path):
    g = small_graph()
    root = tmp_path / "structs"
    first = compare(_compare_spec(), _STRATEGIES, graph=g, jobs=2,
                    struct_cache_dir=str(root))
    files = {p.name: (p.stat().st_ino, p.stat().st_mtime_ns)
             for p in root.rglob("*") if p.is_file()}
    assert files  # workers wrote through to the shared directory
    # fresh workers read every structure back: they derive (and write)
    # nothing, and the comparison comes out bitwise the same
    again = compare(_compare_spec(), _STRATEGIES, graph=g, jobs=2,
                    struct_cache_dir=str(root))
    assert {p.name: (p.stat().st_ino, p.stat().st_mtime_ns)
            for p in root.rglob("*") if p.is_file()} == files
    assert [r.to_json() for r in again] == [r.to_json() for r in first]
    # a cold serial evaluator warm-starts from the directory alone
    ev = CachedEvaluator(g, struct_cache=StructureCache(root))
    ev.subgraph({1}, AcceleratorConfig(glb_bytes=128 * KB,
                                       wbuf_bytes=144 * KB))
    assert ev.kernel.structure_misses == 0
    assert ev.kernel.structure_disk_hits == 1


# ---------------------------------------------------------------------------
# deterministic CI smoke: pinned counter values on a fixed tpu: workload
# ---------------------------------------------------------------------------

def test_canonical_hit_counts_pinned_on_tpu_block():
    """A fixed workload + fixed query corpus yields exactly reproducible
    cache-tier counters (the CI smoke for the structure-half fast path).

    The 11-node gemma3 block is attribute-heterogeneous, so only its truly
    isomorphic queries collapse (29 distinct node sets -> 27 derivations);
    the big collapses live in models with repeated blocks
    (``netlib:``/``synthetic:``), exercised by the corpus tests above and
    measured in docs/benchmarks.md."""
    g = build_workload("tpu:gemma3-4b:0?tokens=512")
    assert g.n == 11
    kernel = CostKernel(g)
    sets = list(_node_sets(g, seed=7, n_parts=12))
    singles = [frozenset({v}) for v in range(g.n)]
    sets += [fs for fs in singles if fs not in set(sets)]
    for fs in sets:
        kernel.structure(fs)
    for fs in sets:  # second pass: all raw hits
        kernel.structure(fs)
    assert len(sets) == 29
    assert kernel.structure_misses == 27
    assert kernel.structure_canon_hits == len(sets) - 27
    assert kernel.structure_raw_hits == len(sets)
