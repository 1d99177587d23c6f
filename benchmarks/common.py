"""Shared benchmark utilities.

Budgets default to a reduced mode so `python -m benchmarks.run` finishes on a
laptop; set REPRO_BENCH_FULL=1 to use the paper's sample counts (400k
partition / 50k co-opt samples).

The partition benchmarks run every search through :func:`run_cached` /
:func:`compare_cached`, which honor the orchestrator's ``--store-dir`` /
``--jobs`` / ``--no-store`` flags (see :func:`configure`): with a store
configured, an interrupted sweep resumes from the already-searched specs
instead of re-searching them, and independent strategy runs fan out over
worker processes.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Union

from repro.api import ExploreResult, ExploreSpec, ResultStore
from repro.api import compare as api_compare
from repro.api import run as api_run

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"

# process-wide sweep configuration, set once by benchmarks.run (or by tests)
STORE: Optional[ResultStore] = None
JOBS: int = 1
EVAL_BACKEND: Optional[str] = None


def configure(store_dir: Optional[str] = None, jobs: int = 1,
              eval_backend: Optional[str] = None) -> None:
    """Point every subsequent run_cached/compare_cached at one store/pool.

    ``jobs`` fans out whole strategies; ``eval_backend`` picks how cost
    queries *within* one strategy are evaluated (`repro.core.engine`:
    serial | vector | jax) — results are identical either way, so both are
    safe under the result store.
    """
    global STORE, JOBS, EVAL_BACKEND
    STORE = ResultStore(store_dir) if store_dir else None
    JOBS = max(1, jobs)
    EVAL_BACKEND = eval_backend


def new_evaluator(g, out_tile: int = 1):
    """A `CachedEvaluator` wired to the sweep-wide evaluation backend."""
    from repro.core.cost import CachedEvaluator
    from repro.core.engine import make_executor

    return CachedEvaluator(g, out_tile=out_tile,
                           executor=make_executor(EVAL_BACKEND))


def run_cached(spec: ExploreSpec, graph=None, ev=None) -> ExploreResult:
    """`repro.api.run` against the sweep-wide result store."""
    return api_run(spec, graph=graph, ev=ev, store=STORE,
                   eval_backend=EVAL_BACKEND)


def compare_cached(spec: ExploreSpec,
                   strategies: Sequence[Union[str, ExploreSpec]],
                   graph=None, ev=None) -> List[ExploreResult]:
    """`repro.api.compare` with the sweep-wide store and process pool."""
    return api_compare(spec, strategies, graph=graph, ev=ev,
                       jobs=JOBS, store=STORE, eval_backend=EVAL_BACKEND)

PARTITION_SAMPLES = 400_000 if FULL else 2_500
COOPT_SAMPLES = 50_000 if FULL else 1_500
POPULATION = 500 if FULL else 40
GREEDY_EVALS = 10**9 if FULL else 5_000
ENUM_STATES = 2_000_000 if FULL else 60_000

SMALL_MODELS = ["vgg16", "resnet50", "googlenet", "nasnet"]
LARGE_MODELS = ["resnet152", "transformer", "gpt", "randwire_a", "randwire_b"]
COOPT_MODELS = ["resnet50", "googlenet", "randwire_a", "nasnet"]


class Timer:
    def __init__(self):
        self.t0 = time.time()

    @property
    def us(self) -> float:
        return (time.time() - self.t0) * 1e6


def emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.0f},{derived}")


def fmt_mb(x: float) -> str:
    return f"{x / 1e6:.2f}MB"
