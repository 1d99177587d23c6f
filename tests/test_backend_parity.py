"""The cross-backend differential-parity suite (acceptance gate for the
``jax`` executor backend, and for any future backend).

Sweeps the harness corpus (``tests/backend_parity.py``: golden workloads
from all four URI schemes + seeded ``synthetic:`` fuzz graphs + adversarial
guard-boundary hardware points) through every available backend and asserts
exact ``SubgraphCost`` equality field-by-field, plus full-strategy bitwise
invariance: all six strategies produce byte-identical ``ExploreResult``s
across all backends for fixed seeds.

When jax is not installed the jax rows *skip* (they never fail) — the
``test-jax-backend`` CI job runs them, the default job proves the skips.
"""

import random

import pytest
from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from backend_parity import (
    SYNTH_KINDS,
    assert_backend_parity,
    assert_costs_equal,
    available_backends,
    backend_params,
    corpus_queries,
    fuzz_corpus,
    scheme_corpus,
    strategy_results,
)
from conftest import small_graph

from repro.api import (
    EnumOptions,
    ExploreSpec,
    GAOptions,
    SAOptions,
    build_workload,
    list_strategies,
)
from repro.core import (
    AcceleratorConfig,
    CachedEvaluator,
    HWSpace,
    Objective,
    compute_structure,
    evaluate_subgraph,
    finish_cost,
    make_executor,
    random_partition,
)

KB = 1 << 10


# ---------------------------------------------------------------------------
# corpus sweeps: SubgraphCost equality field-by-field
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,jobs", backend_params())
def test_scheme_corpus_parity(backend, jobs):
    """Golden workloads of all four URI schemes, adversarial HW points."""
    for label, g, queries in scheme_corpus():
        assert_backend_parity(g, queries, backend, jobs)


@pytest.mark.parametrize("backend,jobs", backend_params())
def test_fuzz_corpus_parity(backend, jobs):
    """Seeded synthetic fuzz graphs of every generator kind."""
    for label, g, queries in fuzz_corpus():
        assert_backend_parity(g, queries, backend, jobs)


def test_jax_executor_handles_empty_and_all_fallback_batches():
    if ("jax", 1) not in available_backends():
        pytest.skip("jax not installed")
    from repro.core.cost import CostKernel

    g = small_graph()
    ex = make_executor("jax")
    assert ex.evaluate(CostKernel(g), []) == []
    # every lane beyond the float64-exact guard -> pure scalar-fallback batch
    acc = AcceleratorConfig(glb_bytes=1 << 60, wbuf_bytes=1 << 60)
    queries = [(frozenset({v}), acc) for v in range(4)]
    got = ex.evaluate(CostKernel(g), queries)
    want = [CostKernel(g).cost(n, a) for n, a in queries]
    for a, b in zip(got, want):
        assert_costs_equal(a, b, "all-fallback batch")


# ---------------------------------------------------------------------------
# full-strategy bitwise invariance (all six strategies x all backends)
# ---------------------------------------------------------------------------

def _strategy_spec(strategy, workload="dd"):
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    options = {
        "ga": GAOptions(population=16),
        "sa": SAOptions(),
        "enum": EnumOptions(state_budget=20_000),
    }.get(strategy)
    return ExploreSpec(
        workload=workload,
        strategy=strategy,
        objective=Objective(metric="energy", alpha=0.002),
        hw=HWSpace(mode="shared", base=acc),
        sample_budget=240,
        seed=0,
        options=options,
    )


@pytest.mark.parametrize("strategy", sorted(list_strategies()))
def test_all_strategies_bitwise_invariant_across_backends(strategy):
    spec = _strategy_spec(strategy)
    results = strategy_results(spec, small_graph())
    assert len(results) >= 2  # serial + at least one batched backend
    reference = results.pop("serial")
    for backend, got in results.items():
        assert got == reference, (
            f"strategy {strategy!r}: backend {backend!r} diverged from "
            f"serial")


def test_strategy_invariance_on_a_real_workload():
    """One heavier cross-check on a resolver workload (GA, co-exploration
    HW space) so invariance is not only pinned on the toy graph."""
    spec = _strategy_spec("ga", workload="synthetic:layered:24?seed=7")
    g = build_workload(spec.workload)
    results = strategy_results(spec, g)
    reference = results.pop("serial")
    for backend, got in results.items():
        assert got == reference, f"{backend} diverged"


# ---------------------------------------------------------------------------
# property-based fuzz: random feasible (graph, plan, acc) triples
# (hypothesis when present; the manual sweep below is the no-hypothesis
#  fallback and always runs)
# ---------------------------------------------------------------------------

def _check_triple(kind, n, gseed, pseed):
    """One fuzz case: parity of every backend on a random partition of a
    random synthetic graph at random + stress hardware points, plus the
    pure-kernel identity ``evaluate_subgraph == finish_cost(
    compute_structure(...))``."""
    g = build_workload(f"synthetic:{kind}:{n}?seed={gseed}")
    rng = random.Random(pseed)
    hw = HWSpace(mode="separate")
    accs = [hw.sample(rng),
            AcceleratorConfig(glb_bytes=2 * KB, wbuf_bytes=2 * KB),
            AcceleratorConfig(glb_bytes=96 * KB, wbuf_bytes=0, shared=True)]
    groups = random_partition(g, rng, mean_size=rng.uniform(1.5, 5.0))
    queries = [(frozenset(s), acc) for acc in accs for s in groups]
    for acc in accs:
        for s in groups:
            assert evaluate_subgraph(g, set(s), acc) == \
                finish_cost(compute_structure(g, set(s)), acc)
    serial_plans = [CachedEvaluator(g).plan(groups, acc) for acc in accs]
    for backend, jobs in available_backends(include_serial=False):
        assert_backend_parity(g, queries, backend, jobs)
        # plan-level: the batched plan path reproduces the serial plans
        ev = CachedEvaluator(g, executor=make_executor(backend, jobs))
        try:
            plans = ev.plan_batch([(groups, acc) for acc in accs])
        finally:
            ev.close()
        for got, want in zip(plans, serial_plans):
            assert len(got.subgraphs) == len(want.subgraphs)
            for a, b in zip(got.subgraphs, want.subgraphs):
                assert_costs_equal(a, b, f"plan_batch[{backend}]")
            assert got.ema_total == want.ema_total
            assert got.energy_pj == want.energy_pj


@given(kind=st.sampled_from(SYNTH_KINDS), n=st.integers(2, 20),
       gseed=st.integers(0, 1_000), pseed=st.integers(0, 1_000))
@settings(max_examples=25, deadline=None)
def test_property_backend_parity_random_triples(kind, n, gseed, pseed):
    _check_triple(kind, n, gseed, pseed)


def test_manual_sweep_backend_parity_random_triples():
    """Deterministic fuzz sweep, >= 100 cases: the no-hypothesis fallback
    (this is the path CPU-only/no-dev containers exercise)."""
    cases = [(kind, 4 + (gseed * 7 + pseed * 3) % 13, gseed, pseed)
             for kind in SYNTH_KINDS
             for gseed in range(7)
             for pseed in range(3)]
    assert len(cases) >= 100
    for kind, n, gseed, pseed in cases:
        _check_triple(kind, n, gseed, pseed)


def test_manual_sweep_runs_even_with_hypothesis_present():
    """The fallback sweep is not itself hypothesis-gated."""
    import inspect

    src = inspect.getsource(test_manual_sweep_backend_parity_random_triples)
    assert "@given" not in src
    assert HAVE_HYPOTHESIS in (True, False)  # the shim always defines it
