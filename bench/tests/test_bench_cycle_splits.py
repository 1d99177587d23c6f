"""The reader of ``normalize.cycle_splits_per_sample`` on a synthetic run."""

from types import SimpleNamespace

import pytest

from bench import common, harness

NAME = "normalize.cycle_splits_per_sample"


def _span(i, parent, name, dur, **attrs):
    return SimpleNamespace(index=i, parent=parent, name=name, t0_s=0.0,
                           dur_s=dur, attrs=attrs)


def _repair_run():
    """One completed search and one the window's close aborted inside its
    first generation's scoring: 1300 samples scored."""
    spans = [
        _span(0, -1, "strategy:ga", 10.0, completed=True),
        _span(1, 0, "ga.init", 1.0, population=500),
        _span(2, 0, "ga.generation", 3.0, gen=0, population=500),
        _span(3, 2, "ga.repair", 1.0, genomes=500),
        _span(4, 2, "ga.score", 1.0, genomes=500),
        _span(5, 0, "ga.samples", 0.1),
        _span(6, 0, "ga.generation", 4.0, gen=1, samples=500),
        _span(7, 6, "ga.variation", 1.0, children=500),
        _span(8, 6, "ga.repair", 1.0, genomes=300),
        _span(9, 6, "ga.score", 1.0, genomes=300),
        _span(10, 6, "ga.select", 0.1),
        _span(11, 0, "ga.samples", 0.1),
        _span(12, -1, "strategy:ga", 3.0),
        _span(13, 12, "ga.init", 1.0, population=500),
        _span(14, 12, "ga.generation", 2.0, gen=0, population=500),
        _span(15, 14, "ga.repair", 1.0, genomes=500),
        _span(16, 14, "ga.score", 0.5, genomes=500),
        _span(17, 16, "evaluate_batch", 0.2),
    ]
    return common.RunData(spans=spans,
                          counters={"normalize.cycle_splits": 8000})


def test_splits_over_every_scored_sample():
    read = harness.metric_reader(NAME)
    assert read(_repair_run()) == pytest.approx(8000 / 1300)


def test_nothing_to_read_returns_nothing():
    assert harness.metric_reader(NAME)(common.RunData()) is None


def test_cycle_splits_need_the_program_counter():
    """A program that does not count its splits reads nothing, not 0."""
    run = _repair_run()
    del run.counters["normalize.cycle_splits"]
    assert harness.metric_reader(NAME)(run) is None
