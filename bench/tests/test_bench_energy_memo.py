"""The reader of ``plan.energy_memo_hit_rate`` on a synthetic run."""

import pytest

from bench import common, harness

NAME = "plan.energy_memo_hit_rate"


def _run(terms, misses):
    return common.RunData(counters={"plan.energy_terms": terms,
                                    "plan.energy_term_misses": misses})


@pytest.mark.parametrize("terms, misses, rate", [
    (575000, 18700, 100.0 * (1 - 18700 / 575000)),
    (1000, 0, 100.0),
    (1000, 1000, 0.0),
])
def test_hits_over_every_term(terms, misses, rate):
    assert harness.metric_reader(NAME)(_run(terms, misses)) == \
        pytest.approx(rate)


def test_nothing_to_read_returns_nothing():
    assert harness.metric_reader(NAME)(common.RunData()) is None


@pytest.mark.parametrize("counter", ["plan.energy_terms",
                                     "plan.energy_term_misses"])
def test_hit_rate_needs_both_program_counters(counter):
    """A program that does not count its terms reads nothing, not 0."""
    run = _run(1000, 40)
    del run.counters[counter]
    assert harness.metric_reader(NAME)(run) is None


def test_no_terms_read_nothing():
    assert harness.metric_reader(NAME)(_run(0, 0)) is None
