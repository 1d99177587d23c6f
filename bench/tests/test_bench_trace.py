"""The trace reduction, on a trace recorded on a TPU v5 lite chip, and the
kernel-bytes function against the dtypes the kernel really moves."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import common, harness
from bench.device import (FINISH_KERNEL, FINISH_LANE_BYTES,
                          finish_batch_bytes, peaks_for)
from bench.trace_reduce import op_kind, reduce_xplane

DATA = Path(__file__).parent / "data"
TRACE = DATA / "resnet50_oneshot.xplane.pb"
RECORDED = json.loads((DATA / "resnet50_oneshot.result.json").read_text())


def test_kernel_bytes_match_the_kernels_dtypes(monkeypatch):
    import jax

    from repro.kernels import finish_batch

    seen = []
    inner = finish_batch._finish_jnp

    def spy(*args):
        seen.append([np.dtype(a.dtype) for a in args])
        return inner(*args)

    monkeypatch.setattr(finish_batch, "_finish_jnp", spy)
    n = 5
    z = np.zeros(n, dtype=np.int64)
    f = np.zeros(n, dtype=bool)
    finish_batch.finish_cost_batch(z, z, f, z + 1, z + 1, f, z + 1)
    (ins,) = seen
    with jax.enable_x64(True):
        outs = jax.eval_shape(inner, *(jax.ShapeDtypeStruct((8,), d)
                                       for d in ins))
    moved = sum(d.itemsize for d in ins) + sum(
        np.dtype(o.dtype).itemsize for o in outs)
    assert moved == FINISH_LANE_BYTES == 86
    assert finish_batch_bytes(n) == 86 * 8
    assert finish_batch_bytes(4096) == 86 * 4096
    assert finish_batch_bytes(0) == 0


def test_recorded_trace_reduces_to_idle_share_and_roofline():
    out = reduce_xplane(str(TRACE), FINISH_KERNEL)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["kernel_calls"] >= 1 and out["kernel_s"] > 0
    assert out["device_ops"] and out["idle_gaps"]
    # the same numbers the chip run reported
    dev = RECORDED["device"]
    assert out["busy_s"] == pytest.approx(dev["busy_s"])
    assert out["window_s"] == pytest.approx(dev["window_s"])
    # every gap is named after the host span open in its middle
    assert all(label != "no host span" for label, _ in out["idle_gaps"])
    run = common.RunData(trace=dict(
        out, kernel_bytes=RECORDED["kernel_bytes"]),
        peaks=peaks_for("TPU v5 lite", harness.BENCH / "peaks.json"))
    idle = harness.metric_reader("device.idle_share.explore")(run)
    roof = harness.metric_reader("kernel.finish_roofline")(run)
    assert idle == pytest.approx(
        RECORDED["metrics"]["device.idle_share.explore"]["value"])
    assert roof == pytest.approx(
        RECORDED["metrics"]["kernel.finish_roofline"]["value"])
    assert 0 < roof < 100 and 0 < idle < 100


def test_op_kinds_are_short():
    assert op_kind('%custom-call.9 = u32[64]{0} custom-call(s64[64]{0} '
                   '%wbuf.1), custom_call_target="X64SplitLow"') == \
        "custom-call X64SplitLow"
    assert op_kind("%fusion.12 = s64[1024]{0} fusion(%a)") == "fusion"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary", harness.BENCH / "peaks.json")
