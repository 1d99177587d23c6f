"""Batched evaluation engine: pluggable executors over the pure cost kernel.

:class:`~repro.core.cost.CachedEvaluator` is cache + counters; *how* a batch
of cache misses is computed is an :class:`Executor`'s job.  All backends run
the same pure kernel (:class:`~repro.core.cost.CostKernel`), so they return
identical costs and search results never depend on the backend:

* ``serial`` — evaluate misses inline, one by one (the default and the
  reference every other backend is tested against).
* ``vector`` — compute each distinct node-set's structure once through the
  kernel memo, then batch the hardware-dependent half
  (:func:`~repro.core.cost.finish_cost`) through NumPy in one vectorized
  pass.  Bit-identical to the scalar kernel; inputs that could round
  differently in float64 (``> 2**53``) or overflow int64 products fall
  back to the scalar path element-wise.
* ``jax``    — the same struct-of-arrays batching, with the arithmetic run
  as a jit-compiled kernel on whatever device jax targets
  (:mod:`repro.kernels.finish_batch`): a whole GA generation's distinct
  queries become one device call.  The same guards route out-of-range
  inputs to the scalar path, so it is bit-identical to ``serial`` too.
  jax is an optional dependency: when it is not installed,
  :func:`make_executor` reports *why* and the other backends keep
  working; any other failure to load the kernel raises.

Both array backends run one array form of the arithmetic,
:func:`~repro.core.cost.finish_arrays`, over NumPy or jnp.

Pick a backend by name via :func:`make_executor` — the seam the API layer's
``eval_backend`` option threads through; :func:`backend_status` answers
"would that name resolve?" without building anything (the CLI's pre-flight
check).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from repro.obs import recorder as obs

from .cost import (
    STREAM_REASON,
    AcceleratorConfig,
    CostKernel,
    SubgraphCost,
    SubgraphStructure,
    finish_arrays,
    finish_cost,
)

EvalQuery = Tuple[frozenset, AcceleratorConfig]

# element-wise scalar-fallback guards for the array backends (vector/jax):
# float64 stays exact below 2**53; int64 products of two values below 2**31
# cannot overflow
_FLOAT_EXACT = 1 << 53
_PROD_SAFE = 1 << 31


def needs_scalar_fallback(st: SubgraphStructure,
                          acc: AcceleratorConfig) -> bool:
    """True when one query must take the scalar ``finish_cost`` path.

    The array backends batch the capacity/streaming arithmetic through
    float64-capable numerics, which are exact only while every operand stays
    below ``2**53`` and every int64 product's factors stay below ``2**31``;
    a failed schedule short-circuits in ``finish_cost`` and has nothing to
    batch.  The boundary is inclusive (``>=``) so the batched path never
    touches the first representable value that *could* round differently.
    The ``share * weight_total`` clause bounds the NoC product: with it (and
    the footprint bound on the block count), ``(share - 1) * ema_w`` stays
    below ``2**62`` even for a streamed sweep, so int64 cannot overflow.
    It also keeps ``share`` itself below ``2**31`` (a zero weight total
    counts as one), so the array arithmetic can divide in int32.
    """
    return (st.sched_error is not None
            or max(st.footprint, st.weight_total) >= _PROD_SAFE
            or acc.weight_share_cores * max(st.weight_total, 1) >= _PROD_SAFE
            or max(acc.glb_bytes, acc.wbuf_bytes) >= _FLOAT_EXACT)


class Executor:
    """How a batch of distinct cost-kernel queries gets computed."""

    name = "abstract"

    def evaluate(self, kernel: CostKernel,
                 queries: Sequence[EvalQuery]) -> List[SubgraphCost]:
        raise NotImplementedError


class SerialExecutor(Executor):
    """Default backend: inline, one query at a time (pre-engine behaviour)."""

    name = "serial"

    def evaluate(self, kernel: CostKernel,
                 queries: Sequence[EvalQuery]) -> List[SubgraphCost]:
        return [kernel.cost(nodes, acc) for nodes, acc in queries]


# -- array backends (vector / jax) -------------------------------------------

class _BatchedFinishExecutor(Executor):
    """Shared struct-of-arrays structure for the array backends.

    Structures come from the kernel memo (one ``derive_schedule`` per
    distinct node set, like every backend).  The base class handles the
    guard partition (:func:`needs_scalar_fallback` lanes take the scalar
    ``finish_cost`` path element-wise), the int64 struct-of-arrays packing,
    and stitching array results back into :class:`SubgraphCost`s; a
    subclass only supplies :meth:`_finish_arrays` — where
    :func:`~repro.core.cost.finish_arrays`, the one array form of the
    arithmetic, runs.  Keeping one packing/stitching path and one
    arithmetic means a new array backend can differ from ``vector`` only in
    the numerics of the array library it runs on, which the parity tests
    pin.
    A batch records its phases as the spans ``executor.structures``,
    ``executor.pack``, ``executor.finish`` and ``executor.stitch``.
    """

    def _finish_arrays(self, fp, w_total, single, glb, wbuf, shared, share):
        """Batched ``finish_cost`` arithmetic over equal-length arrays.

        Returns ``(wr, n_blocks, ema_w, fp_out, noc, infeasible_buf,
        w_overflow, stream, feasible)`` arrays (int64 / bool), index-aligned
        with the inputs.
        """
        raise NotImplementedError

    def evaluate(self, kernel: CostKernel,
                 queries: Sequence[EvalQuery]) -> List[SubgraphCost]:
        import numpy as np

        queries = list(queries)
        rec = obs.current()
        with rec.span("executor.structures", queries=len(queries)):
            structs = [kernel.structure(nodes) for nodes, _ in queries]
        with rec.span("executor.pack", queries=len(queries)):
            results: List[Optional[SubgraphCost]] = [None] * len(queries)
            vec_idx = []
            for i, ((_, acc), st) in enumerate(zip(queries, structs)):
                if needs_scalar_fallback(st, acc):
                    results[i] = finish_cost(st, acc)  # scalar fallback
                else:
                    vec_idx.append(i)
            n_fallback = len(queries) - len(vec_idx)
            if n_fallback:
                rec.add("engine.scalar_fallback", n_fallback)
            if not vec_idx:
                return results  # type: ignore[return-value]

            sts = [structs[i] for i in vec_idx]
            accs = [queries[i][1] for i in vec_idx]
            fp = np.array([s.footprint for s in sts], dtype=np.int64)
            w_total = np.array([s.weight_total for s in sts], dtype=np.int64)
            single = np.array([len(s.nodes) == 1 for s in sts], dtype=bool)
            glb = np.array([a.glb_bytes for a in accs], dtype=np.int64)
            wbuf = np.array([a.wbuf_bytes for a in accs], dtype=np.int64)
            shared = np.array([a.shared for a in accs], dtype=bool)
            # construction validates weight_share_cores >= 1, no clamp needed
            share = np.array([a.weight_share_cores for a in accs],
                             dtype=np.int64)

        with rec.span("executor.finish", lanes=len(vec_idx)):
            (wr, n_blocks, ema_w, fp_out, noc, infeasible_buf, w_overflow,
             stream, feasible) = self._finish_arrays(fp, w_total, single, glb,
                                                     wbuf, shared, share)

        with rec.span("executor.stitch", lanes=len(vec_idx)):
            for j, i in enumerate(vec_idx):
                st = sts[j]
                if infeasible_buf[j]:
                    reason = ("shared buffer overflow" if shared[j]
                              else "global buffer overflow")
                elif w_overflow[j]:
                    reason = "weight buffer overflow"
                elif stream[j]:
                    reason = f"{STREAM_REASON} in {int(n_blocks[j])} blocks"
                else:
                    reason = ""
                results[i] = SubgraphCost(
                    nodes=st.nodes,
                    ema_in=st.ema_in,
                    ema_out=st.ema_out,
                    ema_w=int(ema_w[j]),
                    macs=st.macs,
                    footprint=int(fp_out[j]),
                    weight_resident=int(wr[j]),
                    glb_access_bytes=st.glb_access_bytes,
                    wbuf_access_bytes=int(wr[j]),
                    noc_bytes=int(noc[j]),
                    feasible=bool(feasible[j]),
                    reason=reason,
                )
        return results  # type: ignore[return-value]


class VectorExecutor(_BatchedFinishExecutor):
    """NumPy-vectorized ``finish_cost`` over a whole batch.

    The capacity/streaming/weight-sharing arithmetic runs as one vectorized
    pass over the batch.  Wins when one subgraph is probed at many hardware
    points (co-exploration populations).
    """

    name = "vector"

    def _finish_arrays(self, fp, w_total, single, glb, wbuf, shared, share):
        import numpy as np

        return finish_arrays(np, fp, w_total, single, glb, wbuf, shared,
                             share)


# -- jax backend --------------------------------------------------------------

# probed lazily and cached: (available, detail); detail is the import
# failure when unavailable, so callers can say *why* jax is missing
_JAX_STATUS: Optional[Tuple[bool, str]] = None


def jax_status() -> Tuple[bool, str]:
    """``(available, detail)`` for the ``jax`` backend.

    ``detail`` is ``""`` when the batched kernel module imports cleanly and
    the import failure (``ModuleNotFoundError: No module named 'jax'``)
    when jax is not installed.  jax is an optional dependency, so its
    absence is a normal, reportable state.  Any other failure to import the
    kernel module (an installed jax that lacks an API the kernel uses, say)
    is a bug and raises.  The probe runs once per process.
    """
    global _JAX_STATUS
    if _JAX_STATUS is None:
        try:
            import jax  # noqa: F401
        except ModuleNotFoundError as err:
            if err.name != "jax":
                raise
            _JAX_STATUS = (False, f"{type(err).__name__}: {err}")
        else:
            from repro.kernels import finish_batch  # noqa: F401
            _JAX_STATUS = (True, "")
    return _JAX_STATUS


class JaxExecutor(_BatchedFinishExecutor):
    """jit-compiled jnp ``finish_cost`` over a whole generation.

    The same struct-of-arrays batching as ``vector``, evaluated on-device
    through :func:`repro.kernels.finish_batch.finish_cost_batch` (int64
    arithmetic under ``jax.enable_x64``, batches padded to powers of two so
    GA generations of drifting size reuse compiled kernels).  Bit-identical
    to ``serial``.  Each batch adds to the ``engine.device_calls`` and
    ``engine.device_lanes`` counters.
    """

    name = "jax"

    def _finish_arrays(self, fp, w_total, single, glb, wbuf, shared, share):
        from repro.kernels import finish_batch

        obs.add("engine.device_calls")
        obs.add("engine.device_lanes", len(fp))
        return finish_batch.finish_cost_batch(
            fp, w_total, single, glb, wbuf, shared, share)


BACKENDS = ("serial", "vector", "jax")


def backend_status(backend: str) -> Tuple[bool, str]:
    """Would ``make_executor(backend)`` succeed?  ``(ok, why_not)``.

    The messages here are the single source for both :func:`make_executor`
    errors and the CLI's ``--eval-backend`` pre-flight check, mirroring
    ``Objective.metric`` validation: an unknown name lists the valid
    backends; an unavailable ``jax`` reports the underlying import failure.
    """
    if backend not in BACKENDS:
        return (False,
                f"unknown eval backend {backend!r}; valid backends: "
                f"{', '.join(BACKENDS)}")
    if backend == "jax":
        ok, detail = jax_status()
        if not ok:
            return (False,
                    f"eval backend 'jax' is unavailable ({detail}); "
                    f"install jax (CPU wheel: pip install jax) or use one "
                    f"of: {', '.join(b for b in BACKENDS if b != 'jax')}")
    return (True, "")


def make_executor(backend: Optional[str] = None) -> Executor:
    """Resolve an ``eval_backend`` name to an executor (``None``: ``serial``).

    Unknown names raise a :class:`ValueError` listing :data:`BACKENDS`; an
    unavailable ``jax`` raises one explaining why (the import failure).
    """
    if backend is None:
        backend = "serial"
    ok, why = backend_status(backend)
    if not ok:
        raise ValueError(why)
    if backend == "serial":
        return SerialExecutor()
    if backend == "vector":
        return VectorExecutor()
    return JaxExecutor()
