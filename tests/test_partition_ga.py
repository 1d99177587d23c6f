"""Partition validity, GA operators (paper §4.4), and search behaviour."""

import random
from dataclasses import replace

import pytest
from _hypothesis_compat import given, settings, st
from conftest import small_graph

from repro.core import (
    AcceleratorConfig,
    CachedEvaluator,
    Graph,
    HWSpace,
    Objective,
    groups_of,
    is_valid,
    normalize,
    partition_of,
    random_partition,
    run_ga,
    singleton_partition,
    split_to_fit,
)
from repro.core.ga import Genome, crossover, mutate
from repro.core.netlib import googlenet, resnet50

KB = 1 << 10
MB = 1 << 20


def test_validity_checks():
    g = small_graph()
    assert is_valid(g, [0, 0, 0, 1, 1, 2, 2, 2])
    assert not is_valid(g, [1, 0, 0, 0, 0, 0, 0, 0])     # edge order violated
    assert not is_valid(g, [0, 1, 0, 0, 0, 0, 0, 1])     # group {1,7} disconnected


def test_normalize_repairs_disconnected_and_cyclic():
    g = small_graph()
    # group {0, 3} with node 1,2 elsewhere: {0,3} is disconnected? no — 0-3 not
    # adjacent, so it must split
    raw = [{0, 3}, {1}, {2}, {4, 5, 6, 7}]
    groups = normalize(g, raw)
    P = partition_of(groups, g.n)
    assert is_valid(g, P)
    # quotient cycle: {0,2,3} and {1} -> 0->1 (g1), 1->3 (g2) ... construct one
    raw = [{0, 2, 3}, {1}, {4, 5, 6, 7}]
    groups = normalize(g, raw)
    assert is_valid(g, partition_of(groups, g.n))


def test_random_partition_always_valid():
    g = resnet50()
    rng = random.Random(0)
    for _ in range(20):
        groups = random_partition(g, rng, mean_size=4.0)
        assert is_valid(g, partition_of(groups, g.n))


@given(st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_property_crossover_and_mutations_preserve_validity(seed):
    g = small_graph()
    rng = random.Random(seed)
    hw = HWSpace(mode="separate")
    mom = Genome(random_partition(g, rng), hw.sample(rng))
    dad = Genome(random_partition(g, rng), hw.sample(rng))
    child = crossover(g, mom, dad, hw, rng)
    assert is_valid(g, partition_of(child.groups, g.n))
    for _ in range(10):
        child = mutate(g, child, hw, rng)
        assert is_valid(g, partition_of(child.groups, g.n))
        assert sum(len(s) for s in child.groups) == g.n


def test_split_to_fit_produces_feasible_plan():
    g = resnet50()
    acc = AcceleratorConfig(glb_bytes=64 * KB, wbuf_bytes=72 * KB)
    ev = CachedEvaluator(g)
    groups = split_to_fit(g, [set(range(g.n))], acc, ev=ev)
    plan = ev.plan(groups, acc)
    assert plan.feasible
    assert is_valid(g, partition_of(groups, g.n))


def test_ga_beats_singletons_on_small_graph():
    g = small_graph()
    acc = AcceleratorConfig(glb_bytes=64 * KB, wbuf_bytes=72 * KB)
    res = run_ga(g, Objective(metric="ema", alpha=None),
                 HWSpace(mode="fixed", base=acc), sample_budget=600,
                 population=30, seed=0)
    ev = CachedEvaluator(g)
    single = ev.plan(singleton_partition(g), acc)
    assert res.best.plan.ema_total <= single.ema_total
    assert res.best.plan.feasible


def test_ga_co_explore_returns_grid_capacity():
    g = small_graph()
    res = run_ga(g, Objective(metric="energy", alpha=0.002),
                 HWSpace(mode="shared"), sample_budget=400,
                 population=20, seed=1)
    from repro.core import SHARED_CANDIDATES
    assert res.best.acc.shared
    assert res.best.acc.glb_bytes in SHARED_CANDIDATES
    assert res.best.plan.feasible


def test_ga_co_explores_core_axis():
    g = small_graph()
    hw = HWSpace(mode="shared",
                 base=AcceleratorConfig(shared=True, weight_share_cores=2,
                                        n_cores=2),
                 core_candidates=(2, 4))
    res = run_ga(g, Objective(metric="energy", alpha=0.002), hw,
                 sample_budget=400, population=20, seed=1)
    assert res.best.acc.weight_share_cores in (2, 4)
    assert res.best.acc.n_cores == res.best.acc.weight_share_cores
    assert res.best.plan.feasible
    # the §5.4.2 broadcast charge is live in the searched objective
    assert res.best.plan.noc_total == sum(
        (res.best.acc.weight_share_cores - 1) * s.ema_w
        for s in res.best.plan.subgraphs)


def test_hwspace_core_ops_stay_inside_candidates():
    rng = random.Random(11)
    hw = HWSpace(mode="separate", core_candidates=(1, 2, 4))
    for _ in range(50):
        a, b = hw.sample(rng), hw.sample(rng)
        assert a.weight_share_cores in hw.core_candidates
        child = hw.blend(a, b, rng)
        assert child.weight_share_cores in hw.core_candidates
        mutant = hw.mutate(child, rng)
        assert mutant.weight_share_cores in hw.core_candidates
    with pytest.raises(ValueError, match="core_candidates"):
        HWSpace(core_candidates=(0, 2))


def test_empty_core_candidates_preserve_rng_stream():
    """The default () core axis must not draw from the rng, so existing
    seeded searches stay bitwise-identical."""
    base, cored = HWSpace(mode="separate"), \
        HWSpace(mode="separate", core_candidates=(2,))
    r1, r2 = random.Random(7), random.Random(7)
    a1, a2 = base.sample(r1), cored.sample(r2)
    assert a1 == replace(a2, weight_share_cores=1, n_cores=a1.n_cores)
    # after identical work, the un-cored space left the rng untouched by
    # the core axis: next draws agree with a fresh clone
    r3 = random.Random(7)
    base.sample(r3)
    assert r1.getstate() == r3.getstate()


def test_ga_history_monotone():
    g = small_graph()
    res = run_ga(g, Objective(metric="ema", alpha=None), HWSpace(),
                 sample_budget=300, population=20, seed=3)
    costs = [c for _, c in res.history]
    assert all(b <= a + 1e-9 for a, b in zip(costs, costs[1:]))


# ---------------------------------------------------------------------------
# normalize against the implementation it replaced
# ---------------------------------------------------------------------------
#
# `_ref_normalize` is the former `normalize`, with its helpers and the former
# `Graph.weakly_connected_components` (`_ref_components`), copied unchanged
# but for the components call.  The outputs must agree as lists of sets and
# also in each set's iteration order: the GA and later normalize calls walk
# groups in that order (a split group's root, its components' order), so
# agreeing in both keeps every search on its former course.

def _ref_components(g, nodes):
    if len(nodes) == 1:  # fast path: most GA groups are singletons
        return [set(nodes)]
    remaining = set(nodes)
    comps = []
    und = g._und
    while remaining:
        root = next(iter(remaining))
        comp = set()
        stack = [root]
        while stack:
            v = stack.pop()
            if v in comp:
                continue
            comp.add(v)
            # neighbours of an earlier component are never reachable, so
            # filtering against `remaining` equals filtering against the
            # full node set
            stack.extend(w for w in und[v]
                         if w in remaining and w not in comp)
        comps.append(comp)
        remaining -= comp
    return comps


def _ref_quotient_edges(g, gid):
    q = set()
    for e in g.edges:
        a, b = gid[e.src], gid[e.dst]
        if a < 0 or b < 0:
            raise ValueError(
                f"groups do not cover node {e.src if a < 0 else e.dst}")
        if a != b:
            q.add((a, b))
    return q


def _ref_topo_order_quotient(n_groups, qedges):
    """Kahn, smallest id first (a min-heap pops the same order the previous
    sort-per-iteration implementation did); None if cyclic."""
    import heapq

    indeg = [0] * n_groups
    out = {i: [] for i in range(n_groups)}
    for a, b in qedges:
        out[a].append(b)
        indeg[b] += 1
    heap = [i for i in range(n_groups) if indeg[i] == 0]
    heapq.heapify(heap)
    order = []
    while heap:
        v = heapq.heappop(heap)
        order.append(v)
        for w in out[v]:
            indeg[w] -= 1
            if indeg[w] == 0:
                heapq.heappush(heap, w)
    return order if len(order) == n_groups else None


def _ref_normalize(g, raw_groups):
    """Repair arbitrary groups into a valid ordered partition."""
    groups = []
    for s in raw_groups:
        if not s:
            continue
        if len(s) == 1:
            groups.append(set(s))
        else:
            groups.extend(_ref_components(g, set(s)))

    for _ in range(g.n + 1):
        gid_arr = [-1] * g.n  # -1 = uncovered; _quotient_edges raises on it
        for i, s in enumerate(groups):
            for v in s:
                gid_arr[v] = i
        qedges = _ref_quotient_edges(g, gid_arr)
        order = _ref_topo_order_quotient(len(groups), qedges)
        if order is not None:
            return [groups[i] for i in order]
        cand = max((s for s in groups if len(s) > 1), key=len, default=None)
        if cand is None:
            raise RuntimeError("cyclic quotient with singleton groups")
        med = sorted(cand)[len(cand) // 2]
        lo = {v for v in cand if v < med}
        hi = {v for v in cand if v >= med}
        groups.remove(cand)
        for part in (lo, hi):
            if not part:
                continue
            if len(part) == 1:
                groups.append(part)
            else:
                groups.extend(_ref_components(g, part))
    raise RuntimeError("normalize did not converge")


def _outcome(fn, g, raw):
    """What ``fn`` gives: its groups with each set's iteration order, or the
    error it raised."""
    try:
        return [list(s) for s in fn(g, [set(s) for s in raw])]
    except (ValueError, RuntimeError) as err:
        return (type(err).__name__, str(err))


def _groupings(g, rng, count):
    """Seeded raw groupings of every kind normalize meets, and a few more."""
    n = g.n
    for i in range(count):
        kind = i % 6
        if kind == 0:    # random labels: disconnected and cyclic groups
            m = rng.randint(1, max(1, n // 3))
            lab = [rng.randrange(m) for _ in range(n)]
            raw = [{v for v in range(n) if lab[v] == j} for j in range(m)]
        elif kind == 1:  # valid, then shuffled: acyclic but out of order
            raw = random_partition(g, rng, mean_size=rng.choice([2.0, 4.0]))
            rng.shuffle(raw)
        elif kind == 2:  # strided: cyclic, needing many split steps
            m = rng.randint(2, 5)
            raw = [{v for v in range(n) if v % m == j} for j in range(m)]
            raw[0] |= {v for v in range(n) if rng.random() < 0.2}
            raw = [s - raw[0] for s in raw[1:]] + [raw[0]]
        elif kind == 3:  # few large random groups, with empty sets
            m = rng.randint(2, 4)
            lab = [rng.randrange(m) for _ in range(n)]
            raw = [{v for v in range(n) if lab[v] == j} for j in range(m)]
            raw.insert(rng.randrange(len(raw) + 1), set())
            raw.append(set())
        elif kind == 4:  # a node with an edge left out: uncovered
            raw = [set(s) for s in random_partition(g, rng)]
            e = g.edges[rng.randrange(len(g.edges))]
            v = rng.choice([e.src, e.dst])
            raw = [s - {v} for s in raw]
        else:            # groups that share nodes
            raw = [set(s) for s in random_partition(g, rng)]
            for _ in range(rng.randint(1, 3)):
                raw[rng.randrange(len(raw))] |= set(
                    rng.sample(range(n), k=min(n, rng.randint(1, 4))))
        yield raw


@pytest.mark.parametrize("uri", ["netlib:resnet50",
                                 "synthetic:branchy:64?seed=3", "diamond"])
def test_normalize_matches_former_implementation(uri):
    from repro.api import build_workload
    from repro.obs import Recorder, recording

    g = small_graph() if uri == "diamond" else build_workload(uri)
    rng = random.Random(14)
    seen = {"ValueError": 0, "splits": 0, "most_splits": 0, "reordered": 0}
    for raw in _groupings(g, rng, 240):
        rec = Recorder()
        with recording(rec):
            got = _outcome(normalize, g, raw)
        assert got == _outcome(_ref_normalize, g, raw), raw
        if isinstance(got, tuple):
            seen[got[0]] = seen.get(got[0], 0) + 1
        splits = rec.counters.get("normalize.cycle_splits", 0)
        seen["splits"] += splits > 0
        seen["most_splits"] = max(seen["most_splits"], splits)
        seen["reordered"] += rec.counters.get("normalize.reordered", 0)
    # the cases reach every path: uncovered nodes, reordering, and cycles
    # broken in one step and in many
    assert seen["ValueError"] >= 30
    assert seen["reordered"] >= 60 and seen["splits"] >= 30
    assert seen["most_splits"] >= (10 if g.n > 20 else 2)


def test_normalize_follows_graph_growth():
    g = small_graph()
    raw = [{0, 2, 3}, {1}, {4, 5, 6, 7}]
    assert _outcome(normalize, g, raw) == _outcome(_ref_normalize, g, raw)
    assert g.edge_ends() == ((0, 0, 1, 2, 3, 4, 4, 5, 6),
                             (1, 2, 3, 3, 4, 5, 6, 7, 7))
    # a new node fed by 1 and feeding 7: the cached endpoints follow
    v = g.add_node("n8", 32, 16)
    g.add_edge(1, v)
    assert g.edge_ends()[1][-1] == v
    with pytest.raises(ValueError):   # insertion order stays topological
        g.add_edge(v, 7)
    w = g.add_node("n9", 32, 16)
    g.add_edge(v, w)
    g.add_edge(3, w)
    assert g.edge_ends() == ((0, 0, 1, 2, 3, 4, 4, 5, 6, 1, 8, 3),
                             (1, 2, 3, 3, 4, 5, 6, 7, 7, 8, 9, 9))
    for raw in ([{0, 2, 3, 9}, {1, 8}, {4, 5, 6, 7}],
                [{0, 1, 8, 9}, {2, 3, 4}, {5, 6, 7}],
                [{8, 3}, {0, 1, 2}, {4, 5, 6, 7, 9}],
                [{0, 2, 3}, {1}, {4, 5, 6, 7}]):     # node 8, 9 uncovered
        assert _outcome(normalize, g, raw) == _outcome(_ref_normalize, g, raw)
    assert _outcome(normalize, g, [{0, 2, 3}, {1}, {4, 5, 6, 7}]) == (
        "ValueError", "groups do not cover node 8")


@pytest.mark.parametrize("uri", ["netlib:resnet50",
                                 "synthetic:branchy:64?seed=3"])
def test_weakly_connected_components_match_plain_bfs(uri):
    from collections import deque

    from repro.api import build_workload

    g = build_workload(uri)
    adj = {v: set() for v in range(g.n)}
    for e in g.edges:
        adj[e.src].add(e.dst)
        adj[e.dst].add(e.src)
    rng = random.Random(5)
    for _ in range(400):
        nodes = set(rng.sample(range(g.n), k=rng.randint(1, min(g.n, 24))))
        want, left = set(), set(nodes)
        while left:
            comp, todo = set(), deque([left.pop()])
            while todo:
                v = todo.popleft()
                comp.add(v)
                for w in adj[v] & left:
                    left.discard(w)
                    todo.append(w)
            want.add(frozenset(comp))
        got = g.weakly_connected_components(nodes)
        assert {frozenset(c) for c in got} == want
        assert len(got) == len(want)
        # and the same sets, in the same list and iteration order, as before
        assert [list(c) for c in got] == [
            list(c) for c in _ref_components(g, nodes)]


def test_normalize_counts_reordering_and_cycle_splits():
    from repro.obs import Recorder, recording

    g = small_graph()
    rec = Recorder()
    with recording(rec):
        normalize(g, [{v} for v in range(g.n)])           # already ordered
        normalize(g, [{0, 1, 2, 3}, {4, 5, 6, 7}])
    assert "normalize.reordered" not in rec.counters
    assert "normalize.cycle_splits" not in rec.counters

    rec = Recorder()
    with recording(rec):
        normalize(g, [{4, 5, 6, 7}, {0, 1, 2, 3}])        # reordered only
    assert rec.counters == {"normalize.reordered": 1}

    rec = Recorder()
    with recording(rec):
        groups = normalize(g, [{0, 2, 3}, {1}, {4, 5, 6, 7}])  # 0->1->3
    assert is_valid(g, partition_of(groups, g.n))
    assert rec.counters["normalize.reordered"] == 1
    assert rec.counters["normalize.cycle_splits"] >= 1
