"""The end-to-end arithmetic and each per-layer reader, on synthetic runs."""

from types import SimpleNamespace

import pytest

from bench import common, harness
from bench.drive_oneshot import samples_per_s


def test_samples_per_s_counts_to_the_last_completed_generation():
    t0, t_end = 100.0, 140.0
    gens = [(103.0, 500), (106.0, 500), (139.0, 500), (141.0, 500)]
    # the generation that ends after the window is not counted, and the
    # rate runs to the end of the last one that completed, not the window's
    assert samples_per_s(t0, t_end, gens) == pytest.approx(1500 / 39.0)
    assert samples_per_s(t0, t_end, [(141.0, 500)]) is None


def _span(i, parent, name, dur, **attrs):
    return SimpleNamespace(index=i, parent=parent, name=name, t0_s=0.0,
                           dur_s=dur, attrs=attrs)


def _explore_run():
    spans = [
        _span(0, -1, "strategy:ga", 10.0, completed=True),
        _span(1, 0, "ga.generation", 4.0, gen=0),
        _span(2, 1, "evaluate_batch", 1.0),
        _span(3, 0, "ga.generation", 6.0, gen=1, samples=500),
        _span(4, 3, "evaluate_batch", 2.0),
        _span(5, -1, "strategy:ga", 5.0),  # aborted at the window's close
        _span(6, 5, "ga.generation", 2.0, gen=0),
    ]
    calls = [common.DeviceCall(0.0, 0.002, 1000),
             common.DeviceCall(1.0, 1.004, 3000)]
    trace = {"busy_s": 0.01, "window_s": 4.0, "kernel_s": 1e-4,
             "kernel_bytes": 86 * 4096}
    return common.RunData(
        spans=spans,
        counters={"evaluator.lookups": 1000, "evaluator.evaluations": 40,
                  "evaluator.structure_derive_s": 0.5,
                  "engine.device_calls": 4, "engine.device_lanes": 6000},
        device_calls=calls, trace=trace,
        peaks={"hbm_bytes_per_s": 819e9})


READINGS = {
    "ga.host_share": (_explore_run, 100.0 * (12.0 - 3.0) / 12.0),
    "evaluator.miss_rate": (_explore_run, 4.0),
    "structure.derive_share": (_explore_run, 5.0),
    "executor.lanes_per_call": (_explore_run, 1500.0),
    "executor.device_call_ms": (_explore_run, 3.0),
    "kernel.finish_roofline": (_explore_run,
                               100.0 * 86 * 4096 / 819e9 / 1e-4),
    "device.idle_share.explore": (_explore_run, 100.0 * (1 - 0.01 / 4.0)),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader(name):
    make, want = READINGS[name]
    assert harness.metric_reader(name)(make()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_with_nothing_to_read_returns_nothing(name):
    assert harness.metric_reader(name)(common.RunData()) is None


def test_roofline_needs_the_kernel_bytes_matched_to_the_trace():
    run = _explore_run()
    run.trace["kernel_bytes"] = None
    assert harness.metric_reader("kernel.finish_roofline")(run) is None
