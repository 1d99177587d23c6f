"""``plan_cost`` is the cost the program returned for the run's first
search, read after the window, and no run reports one it did not get.

These drive whole runs of the harness on the CPU at a size a test can hold,
with the look for a chip skipped (as ``test_bench_controls.py`` does).
"""

import json
import struct
import time

import pytest

from bench import common, harness, plan_cost_seeds
from bench.traffic import derive_seed

BENCH = harness.load_benchmark()
SMALL_SEARCH = {"population": 16, "sample_budget": 160}
SEED = 2**33 + 7
CELLS = ("resnet50.oneshot", "randwire_a.oneshot")


def _config(cell: str) -> dict:
    _, config, _ = harness.load_cell(BENCH, cell)
    return dict(config, **SMALL_SEARCH)


class _Drivers(list):
    """Every driver a run builds, kept for the test to read."""

    def wrap(self, cls):
        drivers = self

        class Kept(cls):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                drivers.append(self)

        return Kept


def _run_cell(monkeypatch, cell="resnet50.oneshot", seconds=2.0,
              backend="jax"):
    """One run of ``cell`` off the chip, at the small size; returns
    ``(result document, driver)``."""
    load, find = harness.load_cell, harness.driver_class
    drivers = _Drivers()

    def load_small(*args, **kwargs):
        c, cfg, trf = load(*args, **kwargs)
        return c, dict(cfg, **SMALL_SEARCH), dict(
            trf, warm_lanes=2048, eval_backend=backend)

    monkeypatch.setattr(harness, "load_cell", load_small)
    monkeypatch.setattr(harness, "driver_class",
                        lambda kind: drivers.wrap(find(kind)))
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    doc = harness.run_cell(BENCH, cell, SEED, seconds, False,
                           time.perf_counter())
    assert len(drivers) == 1
    return doc, drivers[0]


def _searches(monkeypatch, fail_at: int, after_search: bool):
    """Make the program's ``run`` raise ``WindowClosed`` at search
    ``fail_at``: before it starts, or once it has run to its end (so its
    generations count but its plan never reaches the driver)."""
    import repro.api

    real, calls = repro.api.run, []

    def run(*args, **kwargs):
        calls.append(args[0].seed)
        if len(calls) - 1 == fail_at:
            if after_search:
                real(*args, **kwargs)
            raise common.WindowClosed("ga.generation")
        return real(*args, **kwargs)

    monkeypatch.setattr(repro.api, "run", run)
    return calls


def _bits(x: float) -> bytes:
    return struct.pack("<d", x)


def test_plan_cost_is_the_first_search_cost_bit_for_bit(monkeypatch):
    doc, driver = _run_cell(monkeypatch)
    assert doc["correct"], doc["checks"]
    assert len(driver.results) >= 2  # later searches completed too
    value = doc["metrics"]["plan_cost"]["value"]
    first = driver.results[0].cost
    assert type(value) is float and type(first) is float
    assert _bits(value) == _bits(first)
    assert {_bits(r.cost) for r in driver.results[1:]} != {_bits(first)}
    # the line the run prints carries the same float64
    line = json.loads(json.dumps(doc))
    assert _bits(line["metrics"]["plan_cost"]["value"]) == _bits(first)
    unit = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}["plan_cost"]
    assert doc["metrics"]["plan_cost"]["unit"] == unit
    # and it is the search seeded derive_seed(seed, 0), run on its own
    alone = plan_cost_seeds.search0_cost(_config("resnet50.oneshot"), SEED,
                                         "serial")
    assert _bits(value) == _bits(alone)


def test_plan_cost_when_the_window_aborts_the_second_search(monkeypatch):
    calls = _searches(monkeypatch, fail_at=1, after_search=False)
    doc, driver = _run_cell(monkeypatch, seconds=30.0)
    assert calls == [derive_seed(SEED, 0), derive_seed(SEED, 1)]
    assert len(driver.results) == 1 and doc["correct"], doc["checks"]
    assert _bits(doc["metrics"]["plan_cost"]["value"]) == \
        _bits(driver.results[0].cost)


def test_no_completed_search_fails_loudly(monkeypatch):
    _searches(monkeypatch, fail_at=0, after_search=True)
    with pytest.raises(harness.BenchError, match="plan_cost"):
        _run_cell(monkeypatch, seconds=30.0)


def test_e2e_leaves_plan_cost_out_with_no_plan():
    from types import SimpleNamespace

    from bench.drive_oneshot import Driver

    driver = Driver({}, {}, SEED, 1.0)
    driver.t0, driver.t_end = 0.0, 1.0
    driver.rec = SimpleNamespace(generations=[(0.5, 16)])
    assert driver.e2e() == {"samples_per_s": 32.0}
    driver.results.append(SimpleNamespace(cost=8.25e6))
    assert driver.e2e() == {"samples_per_s": 32.0, "plan_cost": 8.25e6}


@pytest.fixture(scope="module")
def search0():
    """Search 0 of the run seeded ``SEED``, on the scalar path alone."""
    return {cell: plan_cost_seeds.search0_cost(_config(cell), SEED, "serial")
            for cell in CELLS}


@pytest.mark.parametrize("backend", ["serial", "vector", "jax"])
@pytest.mark.parametrize("cell", CELLS)
def test_plan_cost_is_the_same_on_every_backend(monkeypatch, search0, cell,
                                                backend):
    _searches(monkeypatch, fail_at=1, after_search=False)
    doc, _ = _run_cell(monkeypatch, cell, seconds=60.0, backend=backend)
    assert doc["correct"], doc["checks"]
    assert _bits(doc["metrics"]["plan_cost"]["value"]) == \
        _bits(search0[cell])


def test_set_spreads_follow_the_statistics_quartiles():
    import random
    import statistics

    costs = [8.4e6 + 1e4 * i for i in range(12)]
    q1, med, q3 = statistics.quantiles(costs, n=4)
    assert plan_cost_seeds.spread(costs) == (q3 - q1) / med
    assert plan_cost_seeds.without_farthest([1.0, 2.0, 3.0, 10.0]) == \
        [1.0, 2.0, 3.0]
    out = plan_cost_seeds.set_spreads(costs, random.Random(0))
    assert 0 < out["set5_q05"] <= out["set5_q50"] <= out["set5_q95"]
    assert 0 < out["set6_q05"] <= out["set6_q50"] <= out["set6_q95"]
