"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy seconds: the union of the intervals in which an operation ran on
  the device (the ``XLA Ops`` line of each device plane);
* kernel seconds and calls: the device durations of the events of one
  jitted program on the ``XLA Modules`` line, found by its name;
* the device operations that took most time, and the longest idle gaps,
  each labelled with the innermost host span open at its middle (spans the
  benchmark wrote into the trace with ``TraceAnnotation``).

The traced window is the host annotation ``bench.window``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# host spans worth naming a gap after: the program's own spans, which the
# benchmark mirrors into the trace, and the benchmark's
HOST_SPAN = re.compile(r"^(bench\.|ga\.|evaluate_batch|strategy:|resolve-"
                       r"workload|executor\.|client\.)")


DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def op_kind(name: str) -> str:
    """A device op's kind from its HLO text: ``%fusion.12 = ...`` is
    ``fusion``; a custom call adds its target (``custom-call X64SplitLow``)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    kind = re.sub(r"\.\d+$", "", head)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{kind} {target.group(1)}" if target else kind


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def reduce_xplane(path: str, kernel: str) -> dict:
    """Device metrics of one trace; ``kernel`` names the jitted program
    whose events count as the kernel (``jit__finish_jnp``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and any(
                line.name in DEVICE_LINES for line in plane.lines):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif HOST_SPAN.match(ev.name):
                    host.append((ev.start_ns, ev.end_ns, ev.name))
    if not devices:
        raise ValueError(f"{path}: no device plane in the trace")
    busy_ns = 0.0
    kernel_ns = 0.0
    kernel_calls = 0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = lines.get("XLA Ops") or lines.get("XLA Modules")
        spans = []
        for ev in (ops.events if ops is not None else ()):
            if window and (ev.end_ns <= window[0] or ev.start_ns >= window[1]):
                continue
            spans.append((ev.start_ns, ev.end_ns))
            kind = op_kind(ev.name)
            per_op[kind] = per_op.get(kind, 0.0) + ev.duration_ns
        merged = _union(spans)
        busy_ns += sum(b - a for a, b in merged)
        lo, hi = window if window else (
            merged[0][0] if merged else 0, merged[-1][1] if merged else 0)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        modules = lines.get("XLA Modules")
        for ev in (modules.events if modules is not None else ()):
            if ev.name.startswith(kernel) and (
                    not window or window[0] <= ev.start_ns < window[1]):
                kernel_ns += ev.duration_ns
                kernel_calls += 1
    n = len(devices)
    window_ns = (window[1] - window[0]) if window else None
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "devices": n,
        "busy_s": busy_ns / n * 1e-9,
        "window_s": window_ns * 1e-9 if window_ns else None,
        "kernel_s": kernel_ns / n * 1e-9,
        "kernel_calls": kernel_calls,
        "device_ops": [[name, ns * 1e-9] for name, ns in top_ops],
        "idle_gaps": [[_label(host, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in top_gaps],
    }


def _label(host: List[Tuple[float, float, str]], t: float) -> str:
    best = None
    for a, b, name in host:
        if a <= t <= b and (best is None or a > best[0]):
            best = (a, name)
    return best[1] if best else "no host span"
