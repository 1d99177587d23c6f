"""What the benchmark knows of the device: peaks, kernel bytes, compile time.

* :func:`peaks_for` — the published peaks of a chip, keyed by JAX's
  ``device_kind`` (``bench/peaks.json``); a chip not in the table is an
  error, never a default.
* :func:`finish_batch_bytes` — bytes the batched ``finish_cost`` kernel
  moves for one call: it is elementwise, so every padded lane reads its
  inputs and writes its outputs once.  Per lane: five int64 inputs
  (footprint, weight total, global buffer, weight buffer, share) and two
  bool masks (single layer, shared buffer); five int64 outputs (resident
  weights, blocks, weight traffic, footprint, fabric bytes) and four bool
  masks.  It does a handful of integer operations per lane, so memory
  bandwidth bounds it: its roofline time is bytes over peak HBM bandwidth.
* :class:`CompileClock` — seconds JAX spends tracing, lowering and
  compiling, and its persistent-cache hits, from JAX's monitoring events.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Tuple

import numpy as np

FINISH_INPUT_DTYPES = (np.int64,) * 2 + (np.bool_,) + (np.int64,) * 2 \
    + (np.bool_,) + (np.int64,)
FINISH_OUTPUT_DTYPES = (np.int64,) * 5 + (np.bool_,) * 4
FINISH_LANE_BYTES = sum(np.dtype(d).itemsize
                        for d in FINISH_INPUT_DTYPES + FINISH_OUTPUT_DTYPES)
FINISH_KERNEL = "jit__finish_jnp"


def next_pow2(n: int) -> int:
    m = 1
    while m < n:
        m *= 2
    return m


def finish_batch_bytes(lanes: int) -> int:
    """HBM bytes of one ``finish_cost_batch`` call of ``lanes`` real lanes
    (the kernel runs on the batch padded to a power of two)."""
    return FINISH_LANE_BYTES * next_pow2(lanes) if lanes else 0


def peaks_for(device_kind: str, table: Path) -> dict:
    peaks = json.loads(table.read_text())
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {table}")
    return peaks[device_kind]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events (process-wide, so it also sees the program's
    threads), with the count of backend compiles and cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += secs
            if event == self.EVENTS[2]:
                self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits
