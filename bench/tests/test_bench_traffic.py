"""The traffic generator: a run's searches repeat for its seed, and no two
searches share one."""

import pytest

from bench import traffic as tr

BIG_SEED = 2**33 + 12345


def test_search_seeds_are_distinct_and_fit_63_bits():
    seeds = [tr.derive_seed(BIG_SEED, i) for i in range(1000)]
    assert len(set(seeds)) == 1000
    assert all(0 <= s < 2**63 for s in seeds)
    assert tr.derive_seed(BIG_SEED, 3) == tr.derive_seed(BIG_SEED, 3)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_runs_of_different_seeds_share_no_search(seed):
    mine = {tr.derive_seed(seed, i) for i in range(200)}
    other = {tr.derive_seed(seed + 1, i) for i in range(200)}
    assert not mine & other
    # the checks' sample seed (-2) is none of the searches'
    assert tr.derive_seed(seed, -2) not in mine
