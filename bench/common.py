"""Pieces the traffic drivers share: the spec a configuration describes, the
device-call recorder, the plan check against the reference, run data for
the per-layer readers."""

from __future__ import annotations

import json
import math
import os
import random
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference
from bench.device import finish_batch_bytes, next_pow2

ROOT = Path(__file__).resolve().parents[1]
GRAPH_DIR = ROOT / "runs" / "bench" / "graphs"
# strategies that search the partition alone, at the base accelerator
FIXED_HW_STRATEGIES = ("greedy", "dp", "enum")
ACC_CONSTANTS = ("macs_per_cycle", "freq_hz", "dram_bytes_per_sec",
                 "e_dram_pj_per_byte", "e_mac_pj", "n_cores",
                 "e_noc_pj_per_byte", "weight_share_cores")
# the configuration keys make_spec hands to the program, and those that
# only document the configuration; any other key is refused, so a setting
# the harness would not pass on can never be run as if it were
SPEC_KEYS = frozenset({"workload", "strategy", "hw_mode", "objective",
                       "population", "sample_budget", "accelerator",
                       "glb_candidates", "wbuf_candidates",
                       "shared_candidates"})
NOTE_KEYS = frozenset({"name", "source", "deployment", "reduced", "assumed",
                       "guarantees", "graph", "graph_note",
                       "sample_budget_published"})
# subgraph costs of the window re-derived from the graph in each run
SUBGRAPH_SAMPLE = 2048


def config_faults(config: dict) -> List[str]:
    """What in a configuration the harness or the reference cannot honour
    (empty when it runs as the file states)."""
    faults = [f"unknown key {k!r}" for k in
              sorted(set(config) - SPEC_KEYS - NOTE_KEYS)]
    faults += [f"missing key {k!r}" for k in sorted(SPEC_KEYS - set(config))]
    obj = config.get("objective", {})
    if set(obj) != {"metric", "alpha"}:
        faults.append(f"objective keys {sorted(obj)}, not metric and alpha")
    if obj.get("metric") not in reference.METRICS:
        faults.append(f"the reference scores {list(reference.METRICS)}, "
                      f"not {obj.get('metric')!r}")
    return faults


def workload_uri(config: dict) -> str:
    """The workload the program is given: the configuration's ``workload``
    URI, or, where that is ``"file"``, the configuration's own ``graph``
    as a Graph JSON netlist (``explore --workload file:<path>``), written
    once to a fixed path in the checkout."""
    if config["workload"] != "file":
        return config["workload"]
    text = json.dumps(config["graph"], sort_keys=True)
    path = GRAPH_DIR / f"{config['name']}.json"
    if not path.exists() or path.read_text() != text:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f".{path.name}.{os.getpid()}")
        tmp.write_text(text)
        tmp.replace(path)
    return f"file:{path}"


class WindowClosed(Exception):
    """Raised into a search that is still running when the window closes."""


@dataclass
class Check:
    """One number compared for ``correct``: ``value`` against ``limit``,
    which is a most (``<=``) or a least (``>=``)."""

    name: str
    value: float
    limit: float
    rule: str = "<="

    @property
    def ok(self) -> bool:
        if self.rule == "<=":
            return self.value <= self.limit
        return self.value >= self.limit


@dataclass
class RunData:
    """What one run leaves for the per-layer readers (``bench/metrics``)."""

    spans: list = field(default_factory=list)        # repro.obs Span objects
    counters: Dict[str, float] = field(default_factory=dict)
    device_calls: list = field(default_factory=list)  # DeviceCall
    trace: Optional[dict] = None
    peaks: Optional[dict] = None


def make_spec(config: dict, seed: int):
    """The ``ExploreSpec`` the configuration describes, with ``seed``."""
    from repro.api import ExploreSpec, GAOptions
    from repro.core import AcceleratorConfig, HWSpace, Objective

    hw = HWSpace(mode=config["hw_mode"],
                 base=AcceleratorConfig(**config["accelerator"]),
                 glb_candidates=tuple(config["glb_candidates"]),
                 wbuf_candidates=tuple(config["wbuf_candidates"]),
                 shared_candidates=tuple(config["shared_candidates"]))
    return ExploreSpec(
        workload=workload_uri(config), strategy=config["strategy"],
        objective=Objective(**config["objective"]), hw=hw,
        sample_budget=config["sample_budget"], seed=seed,
        options=(GAOptions(population=config["population"])
                 if config["strategy"] == "ga" else None))


def graph_diff(g, doc: dict) -> int:
    """Nodes and edges in which the program's graph differs from the
    configuration's (0 when it builds the configured workload)."""
    want_nodes = [(n["out_len"], n["line_bytes"], n["weight_bytes"],
                   n["macs"], n["is_output"]) for n in doc["nodes"]]
    got_nodes = [(n.out_len, n.line_bytes, n.weight_bytes, n.macs,
                  bool(n.is_output)) for n in g.nodes]
    want_edges = [(e["src"], e["dst"], e["F"], e["s"], e["kind"])
                  for e in doc["edges"]]
    got_edges = [(e.src, e.dst, e.F, e.s, e.kind) for e in g.edges]
    diff = sum(a != b for a, b in zip(want_nodes, got_nodes))
    diff += sum(a != b for a, b in zip(want_edges, got_edges))
    diff += abs(len(want_nodes) - len(got_nodes))
    return diff + abs(len(want_edges) - len(got_edges))


def acc_in_space(acc: dict, config: dict) -> bool:
    """Whether a plan's accelerator point lies in the design space."""
    base = config["accelerator"]
    mode = config["hw_mode"]
    if any(acc[k] != base[k] for k in ACC_CONSTANTS):
        return False
    if mode == "fixed" or config["strategy"] in FIXED_HW_STRATEGIES:
        return all(acc[k] == base[k] for k in base)
    if mode == "separate":
        return (not acc["shared"]
                and acc["glb_bytes"] in config["glb_candidates"]
                and acc["wbuf_bytes"] in config["wbuf_candidates"])
    return (bool(acc["shared"]) and acc["wbuf_bytes"] == 0
            and acc["glb_bytes"] in config["shared_candidates"])


class PlanChecker:
    """Re-scores returned plans with the reference, from the graph alone."""

    def __init__(self, config: dict) -> None:
        self.config = config
        self.graph = reference.RefGraph(config["graph"])
        self.cost_gap = 0.0
        self.bad_plans = 0
        self.plans = 0

    def check(self, groups: Sequence[Sequence[int]], acc: dict,
              cost) -> None:
        self.plans += 1
        if (reference.partition_faults(self.graph, groups)
                or not acc_in_space(acc, self.config)):
            self.bad_plans += 1
            return
        obj = self.config["objective"]
        want = reference.plan_cost(self.graph, groups, acc, obj["metric"],
                                   obj["alpha"])
        if cost is None or not math.isfinite(cost):
            gap = math.inf
        else:
            gap = abs(cost - want) / max(abs(want), 1e-300)
        self.cost_gap = max(self.cost_gap, gap)


@dataclass
class DeviceCall:
    t0: float
    t1: float
    lanes: int
    inputs: Optional[Tuple[np.ndarray, ...]] = None
    outputs: Optional[Tuple[np.ndarray, ...]] = None

    @property
    def bytes(self) -> int:
        return finish_batch_bytes(self.lanes)


class DeviceCalls:
    """Records, while ``active``, every call of the program's batched
    kernel entry (``repro.kernels.finish_batch.finish_cost_batch``): host
    wall from call to NumPy results in hand, and the lanes with their
    outputs; and every batch of the ``jax`` executor: each query's node
    set and accelerator point with the subgraph cost it returned.  Both
    wrappers pass arguments and results through."""

    def __init__(self) -> None:
        from repro.core.engine import JaxExecutor
        from repro.kernels import finish_batch

        self.module = finish_batch
        self.inner = finish_batch.finish_cost_batch
        self.executor = JaxExecutor
        self.inner_evaluate = JaxExecutor.evaluate
        self.calls: List[DeviceCall] = []
        self.batches: List[Tuple[list, list]] = []
        self.active = False
        finish_batch.finish_cost_batch = self._call
        JaxExecutor.evaluate = self._evaluate()

    def _evaluate(self):
        inner, calls = self.inner_evaluate, self

        def evaluate(executor, kernel, queries):
            queries = list(queries)
            out = inner(executor, kernel, queries)
            if calls.active:
                calls.batches.append((queries, out))
            return out

        return evaluate

    def _call(self, *args):
        t0 = time.perf_counter()
        out = self.inner(*args)
        t1 = time.perf_counter()
        if self.active:
            self.calls.append(DeviceCall(
                t0, t1, len(args[0]),
                tuple(np.array(a) for a in args),
                tuple(np.array(o) for o in out)))
        return out

    def warm(self, max_lanes: int) -> int:
        """Run every power-of-two batch up to ``max_lanes`` once (neutral
        lanes), so the window finds each kernel compiled or in the
        persistent cache.  Returns the number of shapes."""
        n, shapes = 1, 0
        while n <= next_pow2(max_lanes):
            z = np.zeros(n, dtype=np.int64)
            one = np.ones(n, dtype=np.int64)
            f = np.zeros(n, dtype=bool)
            self.inner(z, z, f, one, one, f, one)
            n *= 2
            shapes += 1
        return shapes

    def lane_mismatches(self) -> Tuple[int, int]:
        """``(lanes checked, lanes whose outputs differ from the
        reference)`` over every recorded call."""
        lanes = bad = 0
        for c in self.calls:
            lanes += c.lanes
            bad += reference.lane_mismatches(c.inputs, c.outputs)
        return lanes, bad

    def subgraph_mismatches(self, graph: reference.RefGraph,
                            seed: int) -> Tuple[int, int]:
        """``(subgraphs checked, subgraphs whose cost differs from the
        reference's)`` over a sample, drawn from ``seed``, of the queries
        the executor answered: each re-derived from its node set and
        accelerator point alone, so a wrong footprint or weight total of
        the program's structure half shows, which ``lane_mismatches``
        cannot see (it takes the lanes' inputs from the program)."""
        pairs = [(q, r) for queries, out in self.batches
                 for q, r in zip(queries, out)]
        rng = random.Random(seed)
        sample = (pairs if len(pairs) <= SUBGRAPH_SAMPLE
                  else rng.sample(pairs, SUBGRAPH_SAMPLE))
        bad = 0
        for (nodes, acc), got in sample:
            want = reference.subgraph_cost(
                graph, frozenset(int(v) for v in nodes),
                {k: getattr(acc, k) for k in ("glb_bytes", "wbuf_bytes",
                                              "shared",
                                              "weight_share_cores")})
            bad += want != tuple(getattr(got, k)
                                 for k in reference.SUBGRAPH_FIELDS)
        return len(sample), bad

    def close(self) -> None:
        self.module.finish_cost_batch = self.inner
        self.executor.evaluate = self.inner_evaluate
