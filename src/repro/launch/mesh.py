"""Production meshes + per-arch/per-cell sharding rules.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = (data, model), 256 chips.
Multi-pod: (2, 16, 16) = (pod, data, model), 512 chips — the pod axis
composes with data parallelism (hierarchical gradient all-reduce) by default
and can be re-bound to pipeline stages via parallel/pipeline.py.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax

from repro.models.config import ModelConfig
from repro.parallel.sharding import DEFAULT_RULES, LogicalRules


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    The sharding rules place arrays through ``with_sharding_constraint`` and
    leave propagation to the compiler, which JAX allows only on ``Auto``
    axes; ``jax.make_mesh`` itself defaults to ``Explicit`` ones.
    """
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


MODEL_AXIS = 16  # TP/EP degree on the production meshes


def rules_for(cfg: ModelConfig, kind: str,
              overrides: Optional[LogicalRules] = None) -> LogicalRules:
    """Sharding rules per (arch, cell-kind).

    Baseline strategy (paper-faithful starting point, tuned in §Perf):
      * train/prefill: batch -> (pod, data); TP on heads/ff/vocab/experts;
        ZeRO on the second weight axis of experts (fsdp).
      * decode: additionally shard the KV cache sequence on `model` (the
        per-chip cache would not fit otherwise at 32k x 128).
    Archs whose head counts don't divide the 16-way model axis shard inner
    projection dims instead (xlstm) — see DESIGN.md §5.
    """
    rules = dict(DEFAULT_RULES)
    if kind == "decode":
        rules["seq_kv"] = "model"
    if kind in ("prefill", "decode"):
        rules["fsdp"] = None        # no ZeRO at inference; params TP-only
    # head-count divisibility fixes
    if cfg.n_heads % MODEL_AXIS != 0:
        rules["heads"] = None
    if cfg.n_kv_heads % MODEL_AXIS != 0:
        rules["kv_heads"] = None
    if cfg.n_experts and cfg.n_experts % MODEL_AXIS != 0:
        rules["expert"] = None
    if cfg.d_ff and cfg.d_ff % MODEL_AXIS != 0:
        rules["ff"] = None
    if cfg.vocab % MODEL_AXIS != 0:
        rules["vocab"] = None
    if (2 * cfg.mamba_expand * cfg.d_model) % MODEL_AXIS != 0:
        rules["mamba_inner"] = None
    if (4 * cfg.d_model) % MODEL_AXIS != 0:
        rules["lstm_inner"] = None
    # long-context decode with batch 1: spread the sequence over everything
    if kind == "decode_long":
        rules["seq_kv"] = ("data", "model")
        rules["batch"] = None
        rules["fsdp"] = None
    if overrides:
        rules.update(overrides)
    return rules
