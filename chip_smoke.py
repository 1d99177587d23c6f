#!/usr/bin/env python3
"""Bring-up check of the planner's device path on one TPU chip.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py

Everything runs in this one process (the chip belongs to one process at a
time); nothing it starts touches JAX.  Each phase prints one line:

1. ``device``  — fail unless JAX's first device is a TPU.  There is no CPU
   fallback.
2. ``parity``  — the device kernel (``finish_cost_batch``) against the NumPy
   ``vector`` arithmetic on seeded random lanes, with lanes placed on the
   ceiling-division edges; every output must match bit for bit.
3. ``explore`` — GA co-exploration through ``python -m repro explore`` (called
   in process) on ``netlib:randwire_a`` and ``netlib:resnet50`` at the
   paper's population, once with ``--eval-backend jax`` and once with
   ``serial``: the two result files must be byte-identical, and lanes must
   have reached the device.
4. ``serve``   — a ``PlanServer`` on a thread, with the ``jax`` backend: a
   cold request is searched, the repeat replays from the store with the same
   bytes, and four concurrent identical misses run one search.

Store, zoo and structure-cache directories are fresh under a temporary root;
``$REPRO_STORE_DIR`` and friends are ignored.  The last line of standard
output is ``{"ok": true, "device": {...}}``; any failed phase exits nonzero
before it.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import tempfile
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

EXPLORE_WORKLOADS = ("netlib:randwire_a", "netlib:resnet50")
# the paper's GA population, for about ten generations
EXPLORE_ARGS = ["--strategy", "ga", "--hw-mode", "shared",
                "--metric", "energy", "--alpha", "0.002",
                "--opt", "population=100", "--budget", "1000"]
SERVE_WORKLOAD = "tpu:glm4-9b:0?tokens=4096"
PARITY_LANES = 1 << 16


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (process-wide, so it also sees the server's threads)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.cache_hits


def phase_device():
    import jax

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX's first device is {dev.platform!r} ({dev.device_kind}), not "
          f"a TPU; this check runs only on the chip")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _parity_lanes(rng):
    """Seeded lanes inside the engine's scalar-fallback guards, a third of
    them on the edges of ``ceil(fp / glb)``."""
    import numpy as np

    n = PARITY_LANES
    share = rng.integers(1, 9, n, dtype=np.int64)
    w_total = rng.integers(0, (1 << 31) // share, dtype=np.int64)
    glb = np.where(rng.random(n) < 0.5,
                   rng.integers(1, 1 << 22, n, dtype=np.int64),
                   rng.integers(1, 1 << 53, n, dtype=np.int64))
    fp = rng.integers(0, 1 << 31, n, dtype=np.int64)
    # fp = k * glb + {-1, 0, 1}: the lanes a misrounded division breaks
    edge = np.arange(n) % 3 == 0
    small = rng.integers(1, 1 << 16, n, dtype=np.int64)
    k = rng.integers(1, 1 << 15, n, dtype=np.int64)
    fp_edge = np.clip(k * small + rng.integers(-1, 2, n), 0, (1 << 31) - 1)
    fp = np.where(edge, fp_edge, fp)
    glb = np.where(edge, small, glb)
    wbuf = rng.integers(0, 1 << 53, n, dtype=np.int64)
    single = rng.random(n) < 0.5
    shared = rng.random(n) < 0.5
    return fp, w_total, single, glb, wbuf, shared, share


def phase_parity(clock: CompileClock) -> None:
    import numpy as np

    from repro.core.engine import VectorExecutor
    from repro.kernels.finish_batch import compile_cache_dir, \
        finish_cost_batch

    lanes = _parity_lanes(np.random.default_rng(0))
    c0, _ = clock.snapshot()
    t0 = time.perf_counter()
    got = finish_cost_batch(*lanes)
    wall = time.perf_counter() - t0
    want = VectorExecutor()._finish_arrays(*lanes)
    names = ("wr", "n_blocks", "ema_w", "fp_out", "noc", "infeasible_buf",
             "w_overflow", "stream", "feasible")
    bad = {name: int(np.count_nonzero(g != w))
           for name, g, w in zip(names, got, want)}
    streamed = int(np.count_nonzero(got[7]))
    print(f"parity: {len(lanes[0])} lanes ({streamed} streaming), "
          f"mismatches={sum(bad.values())} wall={wall:.3f}s "
          f"compile={clock.snapshot()[0] - c0:.3f}s "
          f"cache_dir={compile_cache_dir()}", flush=True)
    check(not any(bad.values()),
          f"device kernel differs from the NumPy reference: {bad}")


def _explore(workload: str, backend: str, tmp: Path, clock: CompileClock):
    from repro.api.cli import main
    from repro.obs import Recorder, recording

    run_dir = tmp / "explore" / f"{workload.split(':')[1]}-{backend}"
    out = run_dir / "result.json"
    argv = (["explore", "--workload", workload] + EXPLORE_ARGS
            + ["--eval-backend", backend, "--store-dir", str(run_dir / "store"),
               "--struct-cache-dir", str(run_dir / "structs"),
               "--out", str(out)])
    rec = Recorder()
    log = io.StringIO()
    c0, h0 = clock.snapshot()
    t0 = time.perf_counter()
    with recording(rec), contextlib.redirect_stdout(log), \
            contextlib.redirect_stderr(log):
        rc = main(argv)
    wall = time.perf_counter() - t0
    c1, h1 = clock.snapshot()
    check(rc == 0, f"explore {workload} [{backend}] exited {rc}:\n"
                   f"{log.getvalue()}")
    counters = rec.counters
    stats = {
        "wall": wall,
        "compile": c1 - c0,
        "cache_hits": h1 - h0,
        "device_calls": int(counters.get("engine.device_calls", 0)),
        "device_lanes": int(counters.get("engine.device_lanes", 0)),
        "fallback": int(counters.get("engine.scalar_fallback", 0)),
        "generations": sum(sp.name == "ga.generation" for sp in rec.spans),
    }
    return out.read_bytes(), stats


def phase_explore(tmp: Path, clock: CompileClock) -> None:
    for workload in EXPLORE_WORKLOADS:
        jax_bytes, dev = _explore(workload, "jax", tmp, clock)
        serial_bytes, ref = _explore(workload, "serial", tmp, clock)
        same = jax_bytes == serial_bytes
        cost = json.loads(jax_bytes)["cost"]
        print(f"explore {workload}: generations={dev['generations']} "
              f"cost={cost!r} jax_vs_serial="
              f"{'identical' if same else 'DIFFERENT'} | "
              f"jax wall={dev['wall']:.2f}s compile={dev['compile']:.3f}s "
              f"cache_hits={dev['cache_hits']} "
              f"device_calls={dev['device_calls']} "
              f"device_lanes={dev['device_lanes']} "
              f"scalar_fallback={dev['fallback']} | "
              f"serial wall={ref['wall']:.2f}s", flush=True)
        check(same, f"explore {workload}: jax result differs from serial")
        check(dev["device_lanes"] > 0,
              f"explore {workload}: no lane reached the device")


def _spec(seed: int):
    from repro.api import ExploreSpec, GAOptions
    from repro.core import HWSpace, Objective

    return ExploreSpec(
        workload=SERVE_WORKLOAD, strategy="ga",
        objective=Objective(metric="energy", alpha=0.002),
        hw=HWSpace(mode="shared"), sample_budget=1000, seed=seed,
        options=GAOptions(population=100))


def phase_serve(tmp: Path, clock: CompileClock) -> None:
    from repro.api.store import ResultStore
    from repro.serve.plans import (
        PlanService,
        fetch_stats,
        request_plan,
        serve_in_thread,
    )

    zoo_dir = tmp / "serve" / "zoo"
    zoo_dir.mkdir(parents=True)
    service = PlanService(ResultStore(str(tmp / "serve" / "store")),
                          zoo=ResultStore(str(zoo_dir), read_only=True),
                          workers=2, eval_backend="jax")
    server = serve_in_thread(service)
    c0, _ = clock.snapshot()
    try:
        t0 = time.perf_counter()
        cold = request_plan(server.url, _spec(0))
        t_cold = time.perf_counter() - t0
        check(cold["served_from"] == "search",
              f"cold request served from {cold['served_from']!r}")
        t0 = time.perf_counter()
        warm = request_plan(server.url, _spec(0))
        t_warm = time.perf_counter() - t0
        check(warm["served_from"] == "store",
              f"repeat request served from {warm['served_from']!r}")
        same = (json.dumps(warm["result"], sort_keys=True)
                == json.dumps(cold["result"], sort_keys=True))
        check(same, "store replay differs from the searched result")

        docs = [None] * 4
        gate = threading.Barrier(len(docs))

        def post(i: int) -> None:
            gate.wait()
            docs[i] = request_plan(server.url, _spec(1))

        threads = [threading.Thread(target=post, args=(i,))
                   for i in range(len(docs))]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        t_dedup = time.perf_counter() - t0
        check(all(d is not None for d in docs),
              "a concurrent request failed")
        stats = fetch_stats(server.url)["server"]
        executors = sorted({w.ev.executor.name
                            for w in service._evaluators.values()})
    finally:
        server.close()
    sources = sorted(d["served_from"] for d in docs)
    print(f"serve {SERVE_WORKLOAD}: cold=search {t_cold:.2f}s, "
          f"repeat=store {t_warm * 1e3:.1f}ms identical={same}, "
          f"4 concurrent misses {t_dedup:.2f}s served_from={sources} "
          f"searches={stats['searches']} dedup_joins={stats['dedup_joins']} "
          f"store_hits={stats['store_hits']} executors={executors} "
          f"compile={clock.snapshot()[0] - c0:.3f}s", flush=True)
    check(sources == ["search"] * 4,
          f"concurrent misses served from {sources}")
    check(stats["searches"] == 2 and stats["dedup_joins"] == 3,
          f"expected 2 searches and 3 dedup joins, got {stats}")
    check(executors == ["jax"], f"searches ran on {executors}")


def main() -> int:
    for var in ("REPRO_STORE_DIR", "REPRO_ZOO_DIR", "REPRO_STRUCT_CACHE_DIR"):
        os.environ.pop(var, None)
    # the plan server listens on the loopback; keep any proxy out of it
    os.environ["no_proxy"] = os.environ["NO_PROXY"] = "127.0.0.1,localhost"
    t_start = time.perf_counter()
    try:
        device = phase_device()
        clock = CompileClock()
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
            phase_parity(clock)
            phase_explore(Path(tmp), clock)
            phase_serve(Path(tmp), clock)
    except SmokeFailure as err:
        print(f"FAIL: {err}", file=sys.stderr, flush=True)
        return 1
    print(f"total: {time.perf_counter() - t_start:.2f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
