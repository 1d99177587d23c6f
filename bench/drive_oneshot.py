"""Driver of ``oneshot`` traffic: one client, cold one-shot GA searches back
to back, through ``repro.api.run`` as ``explore`` calls it.

Each search has a fresh evaluator and no result store or structure-cache
directory.  Generations are read from the program's ``ga.generation``
spans; a span opened after the window closes aborts the search in flight,
so the run never waits for it.  ``samples_per_s`` is the samples of every
generation that completed in the window over the seconds from the window's
start to the end of the last of them.  ``plan_cost`` is the objective of
the plan the run's first search returned: every run completes one, and a
faster program completes more searches, so only the first is the same work
on both sides of a change.
"""

from __future__ import annotations

import time
from dataclasses import replace
from typing import List, Optional, Tuple

from bench import common
from bench.traffic import derive_seed


def samples_per_s(t0: float, t_end: float,
                  generations: List[Tuple[float, int]]) -> Optional[float]:
    """Samples of the generations that completed by ``t_end``, over the
    seconds from ``t0`` to the last of them; ``generations`` holds
    ``(completion time, samples)``."""
    done = [(t, n) for t, n in generations if t <= t_end]
    if not done:
        return None
    last = max(t for t, _ in done)
    return sum(n for _, n in done) / (last - t0)


def _window_recorder(t_end: float, population: int, budget: int, tracer):
    from repro.obs import Recorder

    class WindowRecorder(Recorder):
        """The program's recorder, closing the window and mirroring spans
        into the profiler trace while it runs."""

        def __init__(self) -> None:
            super().__init__()
            self.generations: List[Tuple[float, int]] = []
            self._annotations = []

        def _open(self, name, attrs):
            now = time.perf_counter()
            if now >= t_end:
                raise common.WindowClosed(name)
            if tracer is not None and name == "ga.generation":
                tracer.maybe_start(now)
            sp = super()._open(name, attrs)
            self._annotations.append(
                tracer.annotate(name) if tracer is not None else None)
            return sp

        def _close(self, sp):
            super()._close(sp)
            if self._annotations:
                ann = self._annotations.pop()
                if ann is not None:
                    ann.__exit__(None, None, None)
            if sp.name == "ga.generation":
                now = time.perf_counter()
                gen = sp.attrs.get("gen", 0)
                n = (sp.attrs.get("population", population) if gen == 0
                     else min(population, budget - sp.attrs["samples"]))
                self.generations.append((now, n))
                if tracer is not None:
                    tracer.maybe_stop(now)

    return WindowRecorder()


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, tracer=None) -> None:
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.tracer = seed, seconds, tracer
        self.results = []
        self.errors: List[str] = []
        self.searches = 0

    def setup(self) -> None:
        from repro.api import build_workload

        self.graph_diff = common.graph_diff(
            build_workload(common.workload_uri(self.config)),
            self.config["graph"])
        self.spec = common.make_spec(self.config, 0)
        self.calls = common.DeviceCalls()
        self.calls.warm(int(self.traffic["warm_lanes"]))

    def window(self, t0: float) -> None:
        from repro.api import run
        from repro.obs import recording

        self.t0, self.t_end = t0, t0 + self.seconds
        self.rec = _window_recorder(self.t_end, self.config["population"],
                                    self.config["sample_budget"], self.tracer)
        self.calls.active = True
        try:
            with recording(self.rec):
                while time.perf_counter() < self.t_end:
                    spec = replace(self.spec,
                                   seed=derive_seed(self.seed, self.searches))
                    self.searches += 1
                    try:
                        self.results.append(
                            run(spec, eval_backend=self.traffic["eval_backend"]))
                    except common.WindowClosed:
                        break
                    except Exception as err:  # a failed search is reported
                        self.errors.append(f"{type(err).__name__}: {err}")
                        break
                    # the last strategy span is this search's: it completed
                    for sp in reversed(self.rec.spans):
                        if sp.name.startswith("strategy:"):
                            sp.attrs["completed"] = True
                            break
        finally:
            self.calls.active = False
            if self.tracer is not None:
                self.tracer.stop()

    def close(self) -> None:
        self.calls.close()

    @property
    def attempted(self) -> int:
        return self.searches

    @property
    def failed(self) -> int:
        return len(self.errors)

    def e2e(self) -> dict:
        """``samples_per_s`` from the window's clock, and ``plan_cost``:
        the objective of the plan the first search returned (seeded
        ``derive_seed(seed, 0)``), as the program reported it.  Each is
        left out where the window gave it nothing to read."""
        out = {}
        rate = samples_per_s(self.t0, self.t_end, self.rec.generations)
        if rate is not None:
            out["samples_per_s"] = rate
        if self.results:
            out["plan_cost"] = self.results[0].cost
        return out

    def checks(self) -> List[common.Check]:
        plans = common.PlanChecker(self.config)
        for res in self.results:
            acc = {k: getattr(res.acc, k) for k in self.config["accelerator"]}
            plans.check([sorted(s) for s in res.groups], acc, res.cost)
        lanes, bad_lanes = self.calls.lane_mismatches()
        self.lanes_checked = lanes
        self.subgraphs_checked, bad_subgraphs = \
            self.calls.subgraph_mismatches(plans.graph,
                                           derive_seed(self.seed, -2))
        return [
            common.Check("graph_diff", self.graph_diff, 0),
            common.Check("errors", len(self.errors), 0),
            common.Check("plans", plans.plans, 1, ">="),
            common.Check("bad_plans", plans.bad_plans, 0),
            common.Check("cost_gap", plans.cost_gap, 0.0),
            common.Check("lane_mismatch", bad_lanes, 0),
            common.Check("subgraph_mismatch", bad_subgraphs, 0),
        ]

    def notes(self) -> str:
        lanes = [c.lanes for c in self.calls.calls]
        return (f"{len(lanes)} device calls, at most {max(lanes, default=0)} "
                f"lanes in one (shapes warmed up to "
                f"{self.traffic['warm_lanes']})")

    def rundata(self) -> common.RunData:
        return common.RunData(spans=self.rec.spans,
                              counters=dict(self.rec.counters),
                              device_calls=self.calls.calls)
