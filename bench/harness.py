"""The benchmark harness: one process, one cell, one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration (its ``file``), its traffic (``bench/traffic/<name>.json``),
whose ``kind`` names the driver (``bench/drive_<kind>.py``), and each
per-layer metric's reader (``bench/metrics/<name>.py``).  Adding a cell, a
traffic mix or a metric adds files and entries and edits none.

A run: check that JAX's devices are TPUs (else exit 1, no result), set up
(build the graph, warm every kernel shape), measure for
``--seconds``, read peak device memory, close the program, then compare
what the window produced with the plain reference (``bench/reference.py``).
The numbers compared go to standard error and, last, into the result line.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, List, Optional

from bench.common import config_faults

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
TRACE_DIR = ROOT / "runs" / "bench" / "trace"


class BenchError(Exception):
    """The benchmark cannot run as asked; no result is printed."""


# -- finding things by name -------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, cell_name: str, root: Path = ROOT,
              bench_dir: Path = BENCH):
    """``(cell, configuration, traffic)`` of one cell, read from files."""
    cell = _named(bench["workloads"], cell_name, "workload")
    entry = _named(bench["configs"], cell["config"], "configuration")
    config = json.loads((root / entry["file"]).read_text())
    faults = config_faults(config)
    if faults:
        raise BenchError(f"configuration {entry['name']!r}: "
                         + "; ".join(faults))
    traffic_path = bench_dir / "traffic" / f"{cell['traffic']}.json"
    if not traffic_path.exists():
        raise BenchError(f"no traffic file {traffic_path}")
    return cell, config, json.loads(traffic_path.read_text())


def driver_class(kind: str):
    try:
        return importlib.import_module(f"bench.drive_{kind}").Driver
    except ModuleNotFoundError as err:
        raise BenchError(f"no driver for traffic kind {kind!r}") from err


def metric_reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """``read(run)`` from ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        raise BenchError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(bench: dict, cell_name: str, section: str) -> List[dict]:
    """The metrics of ``section`` a cell reports: those that list it, and
    those with no list (a per-layer one then wherever its ``moves`` is)."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


# -- the device ---------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        raise BenchError(
            f"this cell needs {chips} TPU chip(s); JAX sees {len(devices)} "
            f"{dev.platform} device(s) ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Tracer:
    """A JAX profiler trace of part of the window, and host spans mirrored
    into it (``TraceAnnotation``) while it runs.  ``maybe_start`` starts
    it at the first call ``start_after_s`` or more into the window;
    ``maybe_stop`` stops it at the first call once it has run ``min_s``
    seconds.  Drivers call both where a step begins and ends, so the
    trace holds whole steps."""

    def __init__(self, logdir: Path, start_after_s: float, min_s: float) -> None:
        self.logdir, self.start_after_s, self.min_s = logdir, start_after_s, min_s
        self.t0: Optional[float] = None
        self.on = self.off = None
        self._window = None

    def arm(self, t0: float) -> None:
        self.t0 = t0

    def maybe_start(self, now: float) -> None:
        import jax

        if self.on is None and self.t0 is not None \
                and now >= self.t0 + self.start_after_s:
            shutil.rmtree(self.logdir, ignore_errors=True)
            # no Python tracer: it records every Python call, grows the
            # trace to tens of MB a second and slows the host it measures
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.logdir),
                                     profiler_options=options)
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
            self.on = time.perf_counter()

    def maybe_stop(self, now: float) -> None:
        if self.on is not None and self.off is None \
                and now >= self.on + self.min_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.on is not None and self.off is None:
            self._window.__exit__(None, None, None)
            self.off = time.perf_counter()
            jax.profiler.stop_trace()

    def annotate(self, name: str):
        import jax

        if self.on is None or self.off is not None:
            return None
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann


def reduce_trace(tracer: Tracer, calls) -> dict:
    """The trace's device numbers, with the kernel bytes of the calls made
    while it ran (matched only when the trace saw as many kernel calls)."""
    from bench.device import FINISH_KERNEL
    from bench.trace_reduce import find_xplane, reduce_xplane

    path = find_xplane(str(tracer.logdir))
    if path is None:
        raise BenchError("the profiler wrote no trace")
    out = reduce_xplane(path, FINISH_KERNEL)
    inside = [c for c in calls if tracer.on <= c.t0 and c.t1 <= tracer.off]
    out["host_window_s"] = tracer.off - tracer.on
    out["kernel_host_calls"] = len(inside)
    out["kernel_bytes"] = (sum(c.bytes for c in inside)
                           if len(inside) == out["kernel_calls"] else None)
    if out["window_s"] is None:
        out["window_s"] = out["host_window_s"]
    return out


# -- one run ------------------------------------------------------------------

def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float) -> dict:
    """Set up, measure and check one cell; return the result document."""
    from bench.device import CompileClock, peaks_for

    cell, config, traffic = load_cell(bench, cell_name)
    device = device_info(int(cell["chips"]))
    t_device = time.perf_counter()
    clock = CompileClock()
    tracer = (Tracer(TRACE_DIR, float(traffic["trace_after_s"]),
                     float(traffic["trace_min_s"])) if trace else None)
    driver = driver_class(traffic["kind"])(config, traffic, seed, seconds,
                                           tracer)
    driver.setup()
    c_setup = clock.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if tracer is not None:
        tracer.arm(t0)
    driver.window(t0)
    c_window = clock.snapshot()
    device["memory_peak_bytes"] = memory_peak_bytes(int(cell["chips"]))
    driver.close()
    say(f"set-up {setup_s:.3f} s ({t_device - t_start:.3f} s to the "
        f"device check): compile {c_setup[0]:.3f} s, "
        f"{c_setup[1]} compiles, {c_setup[2]} cache hits; window: "
        f"{c_window[1] - c_setup[1]} compiles "
        f"({c_window[0] - c_setup[0]:.3f} s), "
        f"{c_window[2] - c_setup[2]} cache hits")
    say(driver.notes())
    t_check = time.perf_counter()
    checks = driver.checks()
    say(f"reference check took {time.perf_counter() - t_check:.3f} s over "
        f"{driver.lanes_checked} device lanes and "
        f"{driver.subgraphs_checked} subgraphs")
    doc = {"correct": all(c.ok for c in checks),
           "attempted": driver.attempted,
           "failed": driver.failed}
    if trace:
        run = driver.rundata()
        run.trace = reduce_trace(tracer, run.device_calls)
        run.peaks = peaks_for(device["kind"], BENCH / "peaks.json")
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        metrics = {}
        for m in cell_metrics(bench, cell_name, "per_layer"):
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        doc["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
        say("trace: " + json.dumps({k: v for k, v in run.trace.items()
                                    if k not in doc["breakdown"]}))
    else:
        values = dict(driver.e2e(), setup_s=setup_s)
        metrics = {}
        for m in cell_metrics(bench, cell_name, "end_to_end"):
            if m["name"] not in values:
                raise BenchError(f"the run measured no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    doc["metrics"] = metrics
    doc["device"] = device
    doc["checks"] = {c.name: {"value": c.value,
                              "limit": f"{c.rule} {c.limit}"} for c in checks}
    for c in checks:
        say(f"check {c.name}: {c.value} (limit {c.rule} {c.limit})"
            f"{'' if c.ok else '  FAILS'}")
    return doc


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_env() -> None:
    """Keep the run inside its checkout: JAX's persistent compile cache at
    a fixed path in it, and no store or cache directory from outside."""
    for var in ("REPRO_STORE_DIR", "REPRO_STRUCT_CACHE_DIR",
                "REPRO_STRUCT_CANON"):
        os.environ.pop(var, None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv: List[str], t_start: float) -> int:
    args = parse_args(argv)
    prepare_env()
    try:
        doc = run_cell(load_benchmark(), args.workload, args.seed,
                       args.seconds, bool(args.trace), t_start)
    except BenchError as err:
        say(f"error: {err}")
        return 1
    print(json.dumps(doc), flush=True)
    return 0
