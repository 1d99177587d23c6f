"""``correct`` comes out false under the control and every planted fault.

These drive whole runs of the harness on the CPU, at a size a test can
hold, with the look for a chip skipped and the timed path broken
underneath (``bench/control.py``).  A sound run of the same size comes out
true, so what fails is the fault and not the size.
"""

import time

import numpy as np
import pytest

from bench import control, harness, reference

BENCH = harness.load_benchmark()
SMALL_SEARCH = {"population": 16, "sample_budget": 160}
SMALL_TRAFFIC = {"warm_lanes": 2048}


def off_the_chip(monkeypatch, config=None, traffic=None):
    """Skip the harness's look for a chip and shrink the cell's files."""
    load = harness.load_cell

    def load_small(*args, **kwargs):
        cell, cfg, trf = load(*args, **kwargs)
        return cell, dict(cfg, **(config or {})), dict(trf, **(traffic or {}))

    monkeypatch.setattr(harness, "load_cell", load_small)
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})


@pytest.fixture
def small(monkeypatch):
    off_the_chip(monkeypatch, SMALL_SEARCH, SMALL_TRAFFIC)


def _oneshot(seconds=3.0):
    return harness.run_cell(BENCH, "resnet50.oneshot", 2**33 + 1, seconds,
                            False, time.perf_counter())


def test_sound_oneshot_run_is_correct(small):
    doc = _oneshot()
    assert doc["correct"], doc["checks"]
    assert doc["checks"]["plans"]["value"] >= 1
    assert doc["checks"]["cost_gap"]["value"] == 0.0
    assert doc["checks"]["subgraph_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault,number", [
    ("f32", "cost_gap"),       # the control
    ("lane", "lane_mismatch"),
    ("half", "lane_mismatch"),
    ("stale", "lane_mismatch"),
    ("footprint", "subgraph_mismatch"),
])
def test_oneshot_fault_is_not_correct(small, fault, number):
    with control.CONTROLS[fault]():
        doc = _oneshot()
    assert not doc["correct"]
    assert doc["checks"][number]["value"] > 0
    if fault == "footprint":  # the lanes agree with their own inputs
        assert doc["checks"]["lane_mismatch"]["value"] == 0


def test_float32_control_misses_on_every_plan():
    """The control's gap comes from its precision: on random partitions of
    both configurations, float64 matches the reference exactly and float32
    never does."""
    import json
    from pathlib import Path

    for name in ("resnet50", "randwire_a"):
        doc = json.loads((Path(harness.BENCH) / "configs" /
                          f"{name}.json").read_text())
        g = reference.RefGraph(doc["graph"])
        acc = dict(doc["accelerator"], shared=True, wbuf_bytes=0,
                   glb_bytes=doc["shared_candidates"][10])
        rng = np.random.default_rng(0)
        for _ in range(5):
            cuts = sorted(rng.choice(np.arange(1, g.n), 12, replace=False))
            groups = [list(range(a, b)) for a, b in
                      zip([0] + list(cuts), list(cuts) + [g.n])]
            exact = reference.plan_cost(g, groups, acc, "energy", 0.002)
            low = reference.plan_cost(g, groups, acc, "energy", 0.002,
                                      np.float32)
            assert exact == reference.plan_cost(g, groups, acc, "energy",
                                                0.002)
            assert low != exact
