"""Plain reference of the planner's cost model, for deciding ``correct``.

Written from the semantics the configuration states (Cocco, arXiv:2402.00629,
§3 and §5.1.2) and imports nothing of the program: it reads the workload
graph from the configuration file, and plans and device lanes as plain
numbers.

* :func:`finish_lanes` — the hardware-dependent half of one subgraph's cost
  (capacity, single-layer weight streaming, weight sharing) over arrays of
  lanes, in exact int64.
* :func:`subgraph_cost` — one subgraph's whole cost at one accelerator
  point, from its node set.
* :class:`RefGraph` / :func:`plan_cost` — the whole cost of a plan
  (partition + accelerator point) from the graph: external memory access,
  the consumption-centric buffer footprint, on-chip access bytes, and the
  objective (Formula 1: EMA; Formula 2: buffer size + alpha * energy).

The configuration states that plans are exact: traffic and footprints are
integers, energy is float64, summed in plan order.  ``float_type`` lets the
control compute the same energy in float32.
"""

from __future__ import annotations

import math
from fractions import Fraction
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

import numpy as np

KB = 1024
METRICS = ("ema", "energy")   # the objectives :func:`objective` scores


def finish_lanes(fp, w_total, single, glb, wbuf, shared, share):
    """Exact hardware-dependent half over lanes; the kernel's output order.

    Each lane is one (subgraph structure, accelerator point) query: a
    footprint ``fp`` and weight total against a global buffer ``glb`` (one
    shared buffer when ``shared``) and a weight buffer ``wbuf``, with
    weights divided over ``share`` cores.  A single layer that overflows
    streams its output in ``ceil(fp / glb)`` row blocks and reloads its
    weights once per block; a multi-layer subgraph that overflows is
    infeasible.
    """
    fp, w_total, glb, wbuf, share = (np.asarray(a, dtype=np.int64) for a in
                                     (fp, w_total, glb, wbuf, share))
    single, shared = (np.asarray(a, dtype=bool) for a in (single, shared))
    wr = w_total // share
    n_blocks = np.maximum(-(-fp // np.maximum(glb, 1)), 1)
    overflow = np.where(shared, fp + wr > glb, fp > glb)
    infeasible_buf = overflow & ~single
    stream = overflow & single
    ema_w = np.where(stream, wr * n_blocks, w_total)
    fp_out = np.where(stream, np.minimum(fp, glb), fp)
    w_cap = np.where(shared, glb, wbuf)
    w_overflow = ~shared & ~single & ~infeasible_buf & (wr > w_cap)
    feasible = ~(infeasible_buf | w_overflow)
    noc = (share - 1) * ema_w
    return (wr, n_blocks, ema_w, fp_out, noc, infeasible_buf, w_overflow,
            stream, feasible)


def lane_mismatches(inputs: Sequence[np.ndarray],
                    outputs: Sequence[np.ndarray]) -> int:
    """Lanes on which any of the nine outputs differs from the reference."""
    want = finish_lanes(*inputs)
    bad = np.zeros(len(inputs[0]), dtype=bool)
    for got, exp in zip(outputs, want):
        got = np.asarray(got)
        if got.shape != exp.shape:
            return len(inputs[0])
        bad |= got.astype(exp.dtype) != exp
    return int(np.count_nonzero(bad))


class RefGraph:
    """The configuration's graph: nodes are layers with ``out_len`` rows of
    ``line_bytes`` each; edges carry a sliding window (``F``, ``s``) or a
    ``full`` dependency.  Node order is topological."""

    def __init__(self, doc: dict) -> None:
        self.nodes = [(int(n["out_len"]), int(n["line_bytes"]),
                       int(n["weight_bytes"]), int(n["macs"]),
                       bool(n["is_output"])) for n in doc["nodes"]]
        self.edges = [(int(e["src"]), int(e["dst"]), int(e["F"]), int(e["s"]),
                       str(e["kind"])) for e in doc["edges"]]
        self.n = len(self.nodes)
        self.ins: List[List[Tuple]] = [[] for _ in self.nodes]
        self.outs: List[List[Tuple]] = [[] for _ in self.nodes]
        for e in self.edges:
            self.outs[e[0]].append(e)
            self.ins[e[1]].append(e)
        self._memo: Dict[FrozenSet[int], Tuple] = {}

    def out_bytes(self, v: int) -> int:
        return self.nodes[v][0] * self.nodes[v][1]

    def structure(self, nodes: FrozenSet[int]) -> Tuple:
        """``(macs, weight_total, ema_in, ema_out, footprint, glb_access,
        schedulable)`` of one subgraph; memoized by node set."""
        st = self._memo.get(nodes)
        if st is None:
            st = self._memo[nodes] = self._structure(nodes)
        return st

    def _structure(self, nodes: FrozenSet[int]) -> Tuple:
        macs = sum(self.nodes[v][3] for v in nodes)
        weights = sum(self.nodes[v][2] for v in nodes)
        producers_in = {e[0] for v in nodes for e in self.ins[v]
                        if e[0] not in nodes}
        ema_in = sum(self.out_bytes(t) for t in producers_in)
        written = {v for v in nodes
                   if self.nodes[v][4] or any(e[1] not in nodes
                                              for e in self.outs[v])}
        ema_out = sum(self.out_bytes(t) for t in written)
        rows = _resident_rows(self, nodes, producers_in)
        if rows is None:  # no consistent row schedule: infeasible subgraph
            return (macs, weights, ema_in, ema_out, 0, 0, False)
        footprint = sum(x * max(1, self.nodes[t][1]) for t, x in rows.items())
        glb = 0
        for t in rows:
            b = self.out_bytes(t)
            glb += b
            for (_, dst, F, s, kind) in self.outs[t]:
                if dst in nodes:
                    glb += int(b * (F / s if kind == "sliding" else 1.0))
        return (macs, weights, ema_in, ema_out, footprint, glb, True)


def _resident_rows(g: RefGraph, nodes: FrozenSet[int],
                   producers_in: Iterable[int]) -> Optional[Dict[int, int]]:
    """Rows each tensor holds in the global buffer (paper §3.1, out tile 1).

    A subgraph output advances one row at a time.  Walking back from the
    outputs, a tensor read through sliding windows advances by the least
    common multiple of ``advance(consumer) * stride``, capped at its length,
    and holds the widest window ``F + (advance // s - 1) * s`` any consumer
    needs; a tensor read through a ``full`` edge is held whole.  Returns
    ``None`` when two paths demand different rates of one tensor (no
    steady state exists).
    """
    tensors = sorted(set(nodes) | set(producers_in))
    cons = {t: [e for e in g.outs[t] if e[1] in nodes] for t in tensors}
    adv: Dict[int, int] = {}
    rows: Dict[int, int] = {}
    for t in reversed(tensors):
        length = g.nodes[t][0]
        sliding = [e for e in cons[t] if e[4] == "sliding"]
        if t in nodes and not cons[t]:
            adv[t] = rows[t] = min(1, length)
            continue
        if sliding:
            a = 1
            for (_, dst, _, s, _) in sliding:
                a = math.lcm(a, adv[dst] * s)
            a = min(a, length)
            need = max(F + (max(1, a // s) - 1) * s
                       for (_, _, F, s, _) in sliding)
            x = min(need, length)
        else:
            a = x = length
        if any(e[4] == "full" for e in cons[t]):
            x = length
        adv[t], rows[t] = a, x
    # steady state: rate(src) * adv(src) == rate(dst) * adv(dst) * s on every
    # sliding edge, over each connected component
    rate: Dict[int, Fraction] = {}
    links = {t: [] for t in tensors}
    for t in tensors:
        for e in cons[t]:
            if e[4] == "sliding":
                links[t].append(e)
                links[e[1]].append(e)
    for root in tensors:
        if root in rate:
            continue
        rate[root] = Fraction(1)
        stack = [root]
        while stack:
            u = stack.pop()
            for (src, dst, _, s, _) in links[u]:
                v = dst if u == src else src
                r = (rate[u] * adv[u] / (adv[v] * s) if u == src
                     else rate[u] * adv[u] * s / adv[v])
                if v not in rate:
                    rate[v] = r
                    stack.append(v)
                elif rate[v] != r:
                    return None
    return rows


SUBGRAPH_FIELDS = ("macs", "ema_in", "ema_out", "ema_w", "footprint",
                   "glb_access_bytes", "wbuf_access_bytes", "noc_bytes",
                   "feasible")


def subgraph_cost(g: RefGraph, nodes: FrozenSet[int], acc: dict) -> Tuple:
    """One subgraph's cost at accelerator point ``acc`` (``glb_bytes``,
    ``wbuf_bytes``, ``shared``, ``weight_share_cores``), as the values of
    :data:`SUBGRAPH_FIELDS`.  A subgraph with no row schedule is
    infeasible at every point: its weights load once and nothing is
    buffered."""
    macs, weights, ema_in, ema_out, fp, glb_access, ok = g.structure(nodes)
    share = int(acc["weight_share_cores"])
    if not ok:
        return (macs, ema_in, ema_out, weights, 0, 0, 0,
                (share - 1) * weights, False)
    (wr, _, ema_w, fp_out, noc, _, _, _, feasible) = (
        x[0].item() for x in finish_lanes(
            [fp], [weights], [len(nodes) == 1], [acc["glb_bytes"]],
            [acc["wbuf_bytes"]], [acc["shared"]], [share]))
    return (macs, ema_in, ema_out, ema_w, fp_out, glb_access, wr, noc,
            feasible)


def sram_pj_per_byte(capacity: int) -> float:
    """Buffer access energy, growing with the square root of capacity."""
    return 0.2 + 0.25 * math.sqrt(max(capacity, 1) / (64 * KB))


def subgraph_terms(g: RefGraph, nodes: FrozenSet[int],
                   acc: dict) -> Tuple[int, ...]:
    """``(ema_total, glb_access, wbuf_access, noc, macs)`` of one subgraph
    at accelerator point ``acc`` (a dict of AcceleratorConfig fields)."""
    (macs, ema_in, ema_out, ema_w, _, glb_access, wbuf_access, noc,
     _) = subgraph_cost(g, nodes, acc)
    return (ema_in + ema_out + ema_w, glb_access, wbuf_access, noc, macs)


def plan_cost(g: RefGraph, groups: Sequence[Iterable[int]], acc: dict,
              metric: str, alpha: Optional[float],
              float_type=float) -> float:
    """The objective of a plan, from the graph alone.

    ``metric`` is ``ema`` (bytes to and from DRAM) or ``energy`` (pJ:
    DRAM, buffer accesses, fabric and MACs), summed over subgraphs in plan
    order; with ``alpha`` the objective is Formula 2, ``buffer bytes +
    alpha * metric``.
    """
    terms = [subgraph_terms(g, frozenset(s), acc) for s in groups]
    return objective(terms, acc, metric, alpha, float_type)


def objective(terms: Sequence[Tuple[int, ...]], acc: dict, metric: str,
              alpha: Optional[float], float_type=float) -> float:
    """The objective from each subgraph's ``(ema_total, glb_access,
    wbuf_access, noc, macs)``, computed in ``float_type``."""
    f = float_type
    if metric == "ema":
        m = f(sum(t[0] for t in terms))
    elif metric == "energy":
        e_glb = f(sram_pj_per_byte(acc["glb_bytes"]))
        e_w = e_glb if acc["shared"] else f(sram_pj_per_byte(acc["wbuf_bytes"]))
        e_dram, e_noc, e_mac = (f(acc["e_dram_pj_per_byte"]),
                                f(acc["e_noc_pj_per_byte"]), f(acc["e_mac_pj"]))
        per = [f(ema) * e_dram + f(glb) * e_glb + f(wb) * e_w
               + f(noc) * e_noc + f(macs) * e_mac
               for ema, glb, wb, noc, macs in terms]
        m = sum(per) if f is float else _sum_in(per, f)
    else:
        raise ValueError(f"the reference scores ema and energy, not {metric!r}")
    if alpha is None:
        return float(m)
    buf = acc["glb_bytes"] if acc["shared"] else acc["glb_bytes"] + acc["wbuf_bytes"]
    return float(f(buf) + f(alpha) * m)


def _sum_in(values: Sequence, f) -> object:
    total = f(0)
    for v in values:
        total = f(total + v)
    return total


def partition_faults(g: RefGraph, groups: Sequence[Iterable[int]]) -> int:
    """Nodes missing from, or repeated in, a plan's groups (0 for a
    partition of the graph)."""
    seen: Dict[int, int] = {}
    for s in groups:
        for v in s:
            seen[int(v)] = seen.get(int(v), 0) + 1
    missing = sum(1 for v in range(g.n) if v not in seen)
    extra = sum(c - 1 for c in seen.values()) + sum(
        1 for v in seen if not 0 <= v < g.n)
    return missing + extra
