"""The reader of ``ga.gc_collections`` on a synthetic run."""

from types import SimpleNamespace

import pytest

from bench import common, harness

NAME = "ga.gc_collections"


def _span(i, parent, name, **attrs):
    return SimpleNamespace(index=i, parent=parent, name=name, t0_s=0.0,
                           dur_s=1.0, attrs=attrs)


def _run(collections):
    """One completed search of two generations and one that the window's
    close aborted inside its first: three generation spans."""
    spans = [
        _span(0, -1, "strategy:ga", completed=True),
        _span(1, 0, "ga.init", population=500),
        _span(2, 0, "ga.generation", gen=0, population=500),
        _span(3, 2, "ga.score", genomes=500),
        _span(4, 0, "ga.generation", gen=1, samples=500),
        _span(5, 4, "ga.select"),
        _span(6, -1, "strategy:ga"),
        _span(7, 6, "ga.init", population=500),
        _span(8, 6, "ga.generation", gen=0, population=500),
    ]
    return common.RunData(spans=spans,
                          counters={"ga.gc_collections": collections})


@pytest.mark.parametrize("collections", [0, 6])
def test_collections_over_every_generation(collections):
    assert harness.metric_reader(NAME)(_run(collections)) == \
        pytest.approx(collections / 3)


def test_nothing_to_read_returns_nothing():
    assert harness.metric_reader(NAME)(common.RunData()) is None


def test_collections_need_the_program_counter():
    """A program that does not count collections reads nothing, not 0."""
    run = _run(0)
    del run.counters["ga.gc_collections"]
    assert harness.metric_reader(NAME)(run) is None
