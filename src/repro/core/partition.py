"""Graph-level partition schemes (paper §4.1.1).

A partition ``P : V -> N`` assigns each layer to a subgraph; validity requires
``P(u) <= P(v)`` for every edge (computed before use) and every subgraph to be
weakly connected.  Subgraphs execute in id order.

``normalize`` repairs an arbitrary grouping into a valid scheme (used after GA
crossover/mutations): split disconnected groups, break quotient-graph cycles by
topological bisection, then renumber groups in quotient-topological order.
``split_to_fit`` is the paper's in-situ tuning (§4.4.4): oversized subgraphs
are split during evaluation instead of discarding the genome.
"""

from __future__ import annotations

import heapq
import operator
import random
from typing import Dict, List, Optional, Sequence, Set, Tuple

from repro.obs import recorder as obs

from .cost import AcceleratorConfig, PlanCost, evaluate_partition, evaluate_subgraph
from .graph import Graph


Partition = List[int]  # P[node] = subgraph id


def groups_of(P: Sequence[int]) -> List[Set[int]]:
    """Group node sets ordered by subgraph id."""
    byid: Dict[int, Set[int]] = {}
    for v, pid in enumerate(P):
        byid.setdefault(pid, set()).add(v)
    return [byid[k] for k in sorted(byid)]


def partition_of(groups: Sequence[Set[int]], n: int) -> Partition:
    P = [0] * n
    for i, s in enumerate(groups):
        for v in s:
            P[v] = i
    return P


def is_valid(g: Graph, P: Sequence[int]) -> bool:
    for e in g.edges:
        if P[e.src] > P[e.dst]:
            return False
    for s in groups_of(P):
        if not g.is_connected(s):
            return False
    return True


def _group_ids(n: int, groups: Sequence[Set[int]]) -> List[int]:
    """Node -> index of the last group that holds it; -1 = uncovered."""
    gid = [-1] * n
    for i, s in enumerate(groups):
        for v in s:
            gid[v] = i
    return gid


def _quotient_ends(g: Graph, gid: Sequence[int]
                   ) -> Tuple[List[int], List[int]]:
    """Group ids of every edge's source and destination, in edge order."""
    srcs, dsts = g.edge_ends()
    a, b = list(map(gid.__getitem__, srcs)), list(map(gid.__getitem__, dsts))
    if -1 in a or -1 in b:
        for u, v, ga in zip(srcs, dsts, a):
            if ga < 0 or gid[v] < 0:
                raise ValueError(
                    f"groups do not cover node {u if ga < 0 else v}")
    return a, b


def _kahn(n_groups: int, a: Sequence[int], b: Sequence[int]
          ) -> Tuple[List[int], List[int]]:
    """Kahn over the quotient edges ``a[j] -> b[j]``, smallest id first (the
    lexicographically least topological order, whatever the order of the
    edges).  Returns ``(order, indeg)``: ``order`` holds every id iff the
    quotient is acyclic; ids left out keep a non-zero ``indeg``."""
    indeg = [0] * n_groups
    out: List[List[int]] = [[] for _ in range(n_groups)]
    for x, y in zip(a, b):
        if x != y:
            out[x].append(y)
            indeg[y] += 1
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
    return order, indeg


def _cycle(a: Sequence[int], b: Sequence[int], indeg: Sequence[int]
           ) -> Set[int]:
    """The ids of one quotient cycle, from the ids :func:`_kahn` left out:
    each has a predecessor among them, so walking back from one repeats."""
    back = {}
    for x, y in zip(a, b):
        if x != y and indeg[x] and indeg[y]:
            back[y] = x
    v = next(iter(back))
    seen = set()
    while v not in seen:
        seen.add(v)
        v = back[v]
    cyc = {v}
    u = back[v]
    while u != v:
        cyc.add(u)
        u = back[u]
    return cyc


def _split_largest(g: Graph, groups: List[Set[int]]
                   ) -> Optional[Tuple[Set[int], List[Set[int]]]]:
    """One cycle-breaking step: split the largest multi-node group (first in
    list order on ties) at its node-index median into the weak components
    of the part below and of the rest; None if every group is a single."""
    cand = max(groups, key=len, default=None)   # first of the largest
    if cand is None or len(cand) == 1:
        return None
    med = sorted(cand)[len(cand) // 2]
    pieces: List[Set[int]] = []
    # both parts are non-empty: the median's index in sorted order is >= 1
    for part in ({v for v in cand if v < med}, {v for v in cand if v >= med}):
        if len(part) == 1:
            pieces.append(part)
        else:
            pieces.extend(g.weakly_connected_components(part))
    return cand, pieces


def _break_cycles(g: Graph, groups: List[Set[int]], labels: List[int],
                  cycle: Set[int]) -> List[Set[int]]:
    """Split groups by :func:`_split_largest`, each step removing the split
    group and appending its pieces, up to the first step whose quotient is
    acyclic (at most ``g.n`` steps).  ``labels`` maps nodes to ids of
    ``groups`` and is updated in place; ``cycle`` holds ids of a cycle.

    Acyclicity is checked on node labels, not list positions: a step gives
    only the split group's nodes fresh labels, one per piece (with groups
    that share a node the last holds it, and so does the piece appended
    last), so nothing is rebuilt.  A step that relabels no node of the
    known cycle leaves that cycle's groups, and the edges between them, as
    they were: it ends cyclic too and is not checked.  Only a step that
    cuts into the cycle is checked, and a failed check names the next one.

    (With disjoint groups a split never makes an acyclic quotient cyclic:
    node index order is topological, ``src < dst``, so every edge between
    the part below the median and the rest runs upwards, and a cycle
    through the pieces would map onto one through the split group.  The
    cycle argument above does not need that, so groups sharing nodes get
    the same steps as before too.)
    """
    n = g.n
    cur = list(groups)
    n_labels = len(groups)
    splits = 0
    while True:
        while True:     # the grouping after `splits` steps holds `cycle`
            step = _split_largest(g, cur)
            if step is None:
                raise RuntimeError("cyclic quotient with singleton groups")
            if splits == n:
                raise RuntimeError("normalize did not converge")
            cand, pieces = step
            cut = not cycle.isdisjoint(map(labels.__getitem__, cand))
            for lab, p in enumerate(pieces, n_labels):
                for v in p:
                    labels[v] = lab
            n_labels += len(pieces)
            cur.remove(cand)
            cur.extend(pieces)
            splits += 1
            if cut:
                break
        a, b = _quotient_ends(g, labels)
        order, indeg = _kahn(n_labels, a, b)
        if len(order) == n_labels:
            obs.add("normalize.cycle_splits", splits)
            return cur
        cycle = _cycle(a, b, indeg)


def normalize(g: Graph, raw_groups: Sequence[Set[int]]) -> List[Set[int]]:
    """Repair arbitrary groups into a valid ordered partition."""
    # 1. split disconnected groups into weak components (singletons are
    # trivially connected — GA offspring are mostly singletons, so skip
    # the component scan for them)
    groups: List[Set[int]] = []
    for s in raw_groups:
        if not s:
            continue
        if len(s) == 1:
            groups.append(set(s))
        else:
            groups.extend(g.weakly_connected_components(set(s)))

    # 2. already ordered: Kahn's smallest-id-first order is the identity
    # exactly when no edge runs from a later group to an earlier one
    gid = _group_ids(g.n, groups)
    a, b = _quotient_ends(g, gid)
    if not any(map(operator.gt, a, b)):
        return groups
    obs.add("normalize.reordered")
    order, indeg = _kahn(len(groups), a, b)
    if len(order) < len(groups):
        # 3. break quotient cycles by splitting groups at their median
        groups = _break_cycles(g, groups, gid, _cycle(a, b, indeg))
        order, _ = _kahn(len(groups),
                         *_quotient_ends(g, _group_ids(g.n, groups)))
    # renumber groups in quotient topological order
    return [groups[i] for i in order]


def split_group_topo(g: Graph, s: Set[int], pieces: int = 2) -> List[Set[int]]:
    """Split a group into ~equal topological slices (each then re-split into
    weak components)."""
    order = sorted(s)
    k = max(1, len(order) // pieces)
    out: List[Set[int]] = []
    for i in range(0, len(order), k):
        chunk = set(order[i: i + k])
        if len(chunk) == 1:
            out.append(chunk)
        else:
            out.extend(g.weakly_connected_components(chunk))
    return out


def split_to_fit(
    g: Graph,
    groups: List[Set[int]],
    acc: AcceleratorConfig,
    out_tile: int = 1,
    max_rounds: int = 64,
    ev: Optional["CachedEvaluator"] = None,
) -> List[Set[int]]:
    """In-situ tuning (paper §4.4.4): bisect any infeasible subgraph until all
    fit the buffers (singletons stream, always feasible)."""
    from .cost import CachedEvaluator  # local import to avoid cycle at module load

    ev = ev or CachedEvaluator(g, out_tile=out_tile)
    return split_to_fit_batch(g, [(groups, acc)], ev, max_rounds=max_rounds)[0]


def split_to_fit_batch(
    g: Graph,
    items: Sequence[Tuple[List[Set[int]], AcceleratorConfig]],
    ev: "CachedEvaluator",
    max_rounds: int = 64,
) -> List[List[Set[int]]]:
    """Batched in-situ tuning: repair many plans against one evaluator batch
    per round.

    Round ``k`` collects every still-unrepaired plan's multi-node subgraphs
    into one feasibility batch (where the engine's executor parallelism
    applies), then applies the split decisions — the same decisions, in the
    same order, as running :func:`split_to_fit` per item, since feasibility
    of one subgraph never depends on the others.
    """
    out: List[Optional[List[Set[int]]]] = [None] * len(items)
    groups_of_item: List[List[Set[int]]] = [list(gr) for gr, _ in items]
    active = list(range(len(items)))
    for _ in range(max_rounds):
        if not active:
            break
        obs.add("repair.rounds")
        queries = [(s, items[i][1]) for i in active
                   for s in groups_of_item[i] if len(s) > 1]
        costs = ev.evaluate_batch(queries)
        pos = 0
        n_splits = 0
        still_active: List[int] = []
        for i in active:
            changed = False
            new: List[Set[int]] = []
            for s in groups_of_item[i]:
                if len(s) == 1:
                    new.append(s)
                    continue
                c = costs[pos]
                pos += 1
                if c.feasible:
                    new.append(s)
                else:
                    new.extend(split_group_topo(g, s, pieces=2))
                    changed = True
                    n_splits += 1
            groups_of_item[i] = new
            if changed:
                still_active.append(i)
            else:
                out[i] = normalize(g, new)
        if n_splits:
            obs.add("repair.splits", n_splits)
        active = still_active
    for i in active:  # max_rounds exhausted: fall back to singletons
        out[i] = normalize(g, [{v} for s in groups_of_item[i] for v in s])
    return out  # type: ignore[return-value]


def singleton_partition(g: Graph) -> List[Set[int]]:
    return [{v} for v in range(g.n)]


def random_partition(g: Graph, rng: random.Random,
                     mean_size: float = 3.0) -> List[Set[int]]:
    """Random valid partition: walk nodes in topological order; each node joins
    a random open predecessor group or starts a new one (paper §4.4.1)."""
    gid: Dict[int, int] = {}
    groups: List[Set[int]] = []
    p_join = 1.0 - 1.0 / max(mean_size, 1.0)
    for v in g.topo_order():
        cands = {gid[u] for u in g.preds(v) if u in gid}
        if cands and rng.random() < p_join:
            c = rng.choice(sorted(cands))
            groups[c].add(v)
            gid[v] = c
        else:
            gid[v] = len(groups)
            groups.append({v})
    return normalize(g, groups)


def evaluate_groups(
    g: Graph,
    groups: List[Set[int]],
    acc: AcceleratorConfig,
    out_tile: int = 1,
    repair: bool = True,
    ev: Optional["CachedEvaluator"] = None,
) -> Tuple[List[Set[int]], PlanCost]:
    """Evaluate (optionally repairing in-situ); returns (repaired groups, cost)."""
    from .cost import CachedEvaluator

    ev = ev or CachedEvaluator(g, out_tile=out_tile)
    if repair:
        groups = split_to_fit(g, groups, acc, out_tile=out_tile, ev=ev)
    plan = ev.plan(groups, acc)
    return groups, plan
