"""Batched ``finish_cost`` arithmetic as one jit-compiled jnp kernel.

The accelerator-resident half of the ``jax`` executor backend
(:class:`repro.core.engine.JaxExecutor`): a whole GA generation's distinct
``(structure, AcceleratorConfig)`` queries arrive as struct-of-arrays int64
buffers and :func:`repro.core.cost.finish_arrays` — the array form of
:func:`repro.core.cost.finish_cost` that the ``vector`` backend runs over
NumPy — runs over ``jax.numpy`` as one device call.

Bitwise parity with the scalar kernel is the contract (the engine's guards
keep every lane below ``2**53`` / int64-product-safe, see
:func:`repro.core.engine.needs_scalar_fallback`), which pins the numerics:

* all integer work is int64 under ``jax.enable_x64(True)`` (the context
  manager keeps x64 scoped to these calls — the rest of the repo's jax code
  stays in its default 32-bit world);
* the streaming block count mirrors ``_stream_single_layer`` exactly:
  ``ceil`` of a float64 true division, whose operands are exact below
  ``2**53`` and whose IEEE result is therefore identical to the scalar
  ``math.ceil(fp / glb)``.

Batches are padded to the next power of two so GA generations of drifting
size (cache warmth changes the miss count every round) reuse a handful of
compiled kernels instead of recompiling per shape; the arithmetic is
element-wise, so padding lanes can never perturb real lanes.

On an accelerator, compiled kernels go to JAX's persistent compilation
cache: the directory ``$JAX_COMPILATION_CACHE_DIR`` names when it is set,
else ``.jax_cache/`` at the root of the checkout (see
:func:`compile_cache_dir`).
"""

from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import finish_arrays
from repro.obs import recorder as obs

# fixed in-checkout cache path: the directory is part of the cache key, so
# it must not move between runs
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


@functools.cache
def compile_cache_dir() -> Optional[str]:
    """Place JAX's persistent compilation cache; return its directory.

    Runs once, on the first batch (never at import).  When
    ``$JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and no other
    directory is set here.  These kernels compile in milliseconds, below
    JAX's default one-second floor for writing an entry, so the floor is
    dropped to make the entries land.  On the CPU backend nothing is placed
    (``None``): XLA:CPU compiles the kernel in milliseconds and logs a
    machine-feature warning for every entry it loads back.
    """
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# the profiler trace names this kernel jit__finish_jnp, and the benchmark's
# trace reduction finds it by that name: renaming it blanks its trace metrics
@jax.jit
def _finish_jnp(fp, w_total, single, glb, wbuf, shared, share):
    """Whole-batch ``finish_cost`` arithmetic as one jitted jnp expression:
    :func:`repro.core.cost.finish_arrays` over ``jax.numpy``."""
    return finish_arrays(jnp, fp, w_total, single, glb, wbuf, shared, share)


def _pad(arr: np.ndarray, m: int, fill) -> np.ndarray:
    if m == len(arr):
        return arr
    out = np.full(m, fill, dtype=arr.dtype)
    out[:len(arr)] = arr
    return out


def finish_cost_batch(fp, w_total, single, glb, wbuf, shared,
                      share) -> Tuple[np.ndarray, ...]:
    """Evaluate a batch of ``finish_cost`` queries on the jax device.

    Inputs are index-aligned equal-length arrays (int64 values, bool
    masks); every lane must already satisfy the engine's scalar-fallback
    guards.  Returns ``(wr, n_blocks, ema_w, fp_out, noc, infeasible_buf,
    w_overflow, stream, feasible)`` as NumPy arrays, bit-identical to the
    scalar kernel: :func:`repro.core.cost.finish_arrays` on the device.

    A call records three host spans, each with ``lanes`` and ``padded``:
    ``executor.put`` (padding, and enqueueing the 7 inputs' transfer),
    ``executor.run`` (dispatching the kernel) and ``executor.fetch``
    (waiting for the kernel and the 9 outputs' transfer back).  Nothing
    waits between them, so the transfer in overlaps the dispatch, and the
    call is the same with and without a recorder.
    """
    n = len(fp)
    if n == 0:
        empty_i = np.zeros(0, dtype=np.int64)
        empty_b = np.zeros(0, dtype=bool)
        return (empty_i,) * 5 + (empty_b,) * 4
    compile_cache_dir()
    m = 1 << (n - 1).bit_length()  # the next power of two
    rec = obs.current()
    with jax.enable_x64(True):
        with rec.span("executor.put", lanes=n, padded=m):
            # pad to the next power of two: neutral lanes (glb/share=1
            # avoids any divide-by-zero path) that the element-wise
            # arithmetic cannot couple into real lanes
            args = tuple(jnp.asarray(a) for a in (
                _pad(np.asarray(fp, dtype=np.int64), m, 0),
                _pad(np.asarray(w_total, dtype=np.int64), m, 0),
                _pad(np.asarray(single, dtype=bool), m, False),
                _pad(np.asarray(glb, dtype=np.int64), m, 1),
                _pad(np.asarray(wbuf, dtype=np.int64), m, 1),
                _pad(np.asarray(shared, dtype=bool), m, False),
                _pad(np.asarray(share, dtype=np.int64), m, 1),
            ))
        with rec.span("executor.run", lanes=n, padded=m):
            outs = _finish_jnp(*args)
        with rec.span("executor.fetch", lanes=n, padded=m):
            return tuple(np.asarray(o)[:n] for o in outs)
