#!/usr/bin/env python3
"""``plan_cost`` of search 0 over many run seeds, and how widely a set of
runs spreads, from the host alone.

    python3 bench/plan_cost_seeds.py --config <name> --seeds <first>:<end>
        [--jobs 4] [--budget <samples>]

A run seeded ``s`` reports as ``plan_cost`` the cost of the plan its first
search returns, and that search is seeded ``derive_seed(s, 0)``.  The
search is deterministic for its seed and returns the same plan on every
evaluation backend, so the value a chip run reads can be had here, on the
``vector`` backend, where seeds are cheap.  One JSON line per run seed,
then a summary: one search's spread over all the seeds, and the spread of
a set of 6 runs (each a different seed) resampled from them, over all 6
and with the run farthest from the set's median left out.  A spread is the
distance between the first and the third quartile
(``statistics.quantiles(n=4)``) over the median.
``--budget`` replaces the configuration's sample budget (the published
50 000 shows what ten times the search buys).
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import random
import statistics
import sys
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path
from typing import List, Optional, Sequence

if __name__ == "__main__":
    import pathsetup  # noqa: F401  (the harness and the program)

SET_RUNS = 6
RESAMPLES = 20000


def spread(values: Sequence[float]) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def without_farthest(values: Sequence[float]) -> List[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def search0_cost(config: dict, seed: int, backend: str,
                 budget: Optional[int] = None) -> float:
    """The cost of the plan a run seeded ``seed`` returns first."""
    from repro.api import run

    from bench import common
    from bench.traffic import derive_seed

    if budget is not None:
        config = dict(config, sample_budget=budget)
    spec = common.make_spec(config, derive_seed(seed, 0))
    return run(spec, eval_backend=backend).cost


def set_spreads(costs: Sequence[float], rng: random.Random) -> dict:
    """Quantiles of a 6-run set's spread, over sets drawn from ``costs``."""
    whole, trimmed = [], []
    for _ in range(RESAMPLES):
        runs = rng.sample(list(costs), SET_RUNS)
        whole.append(spread(runs))
        trimmed.append(spread(without_farthest(runs)))
    out = {}
    for name, vals in (("set6", whole), ("set5", trimmed)):
        vals.sort()
        for q in (0.05, 0.5, 0.95):
            out[f"{name}_q{int(q * 100):02d}"] = vals[int(q * (len(vals) - 1))]
    return out


def _one(args):
    config, seed, budget = args
    return seed, search0_cost(config, seed, "vector", budget)


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True, help="first:end, end excluded")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--budget", type=int)
    args = ap.parse_args(argv)
    first, end = (int(x) for x in args.seeds.split(":"))
    path = Path(__file__).resolve().parent / "configs" / f"{args.config}.json"
    config = json.loads(path.read_text())
    work = [(config, s, args.budget) for s in range(first, end)]
    with ProcessPoolExecutor(max_workers=args.jobs,
                             mp_context=multiprocessing.get_context("spawn")
                             ) as pool:
        results = sorted(pool.map(_one, work))
    for seed, cost in results:
        print(json.dumps({"config": args.config, "seed": seed,
                          "plan_cost": cost}), flush=True)
    costs = [c for _, c in results]
    summary = {"config": args.config, "seeds": len(costs),
               "min": min(costs), "median": statistics.median(costs),
               "max": max(costs)}
    if len(costs) >= SET_RUNS:
        summary["search_spread"] = spread(costs)
        summary.update(set_spreads(costs, random.Random(first)))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
