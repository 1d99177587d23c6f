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
    CostKernel,
    HWSpace,
    Objective,
    compute_structure,
    evaluate_subgraph,
    finish_cost,
    make_executor,
    random_partition,
)
from repro.core.engine import needs_scalar_fallback
from repro.obs import recorder as obs

KB = 1 << 10


# ---------------------------------------------------------------------------
# corpus sweeps: SubgraphCost equality field-by-field
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", backend_params())
def test_scheme_corpus_parity(backend):
    """Golden workloads of all four URI schemes, adversarial HW points."""
    for label, g, queries in scheme_corpus():
        assert_backend_parity(g, queries, backend)


@pytest.mark.parametrize("backend", backend_params())
def test_fuzz_corpus_parity(backend):
    """Seeded synthetic fuzz graphs of every generator kind."""
    for label, g, queries in fuzz_corpus():
        assert_backend_parity(g, queries, backend)


def test_jax_executor_handles_empty_and_all_fallback_batches():
    if "jax" not in available_backends():
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


def _lane_mix(n_lanes):
    """``(graph, queries, n_fallback)``: ``n_lanes`` distinct queries that
    the array backends batch, with distinct scalar-fallback queries
    interleaved (one after every fourth batched lane, at least one)."""
    g = build_workload("synthetic:layered:24?seed=7")
    kernel = CostKernel(g)
    rng = random.Random(n_lanes)
    spaces = (HWSpace(mode="separate"), HWSpace(mode="shared"))
    sets = []
    for _ in range(4):
        for s in random_partition(g, rng, mean_size=rng.uniform(1.5, 6.0)):
            if frozenset(s) not in sets:
                sets.append(frozenset(s))
    batched = []
    seen = set()
    while len(batched) < n_lanes:
        # every finish_cost branch: streaming and overflow at starved
        # buffers, weight overflow, multi-core sharing, roomy points
        acc = rng.choice([
            spaces[len(batched) % 2].sample(rng),
            AcceleratorConfig(glb_bytes=2 * KB, wbuf_bytes=2 * KB),
            AcceleratorConfig(glb_bytes=512 * KB, wbuf_bytes=1 * KB),
            AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB,
                              weight_share_cores=rng.choice((2, 4))),
        ])
        q = (rng.choice(sets), acc)
        if q not in seen and not needs_scalar_fallback(
                kernel.structure(q[0]), acc):
            seen.add(q)
            batched.append(q)
    # capacities from 2**53 up take the scalar path (float64 exactness)
    n_fallback = max(1, n_lanes // 4)
    fallback = [(sets[j % len(sets)],
                 AcceleratorConfig(glb_bytes=(1 << 53) + j,
                                   wbuf_bytes=144 * KB))
                for j in range(n_fallback)]
    assert all(needs_scalar_fallback(kernel.structure(s), a)
               for s, a in fallback)
    queries = []
    for i, q in enumerate(batched):
        queries.append(q)
        if i % 4 == 0 and fallback:
            queries.append(fallback.pop())
    return g, queries + fallback, n_fallback


@pytest.mark.parametrize("n_lanes", [1, 2, 3, 255, 256, 257])
def test_padding_boundary_parity(n_lanes):
    """The device kernel pads its lanes to the next power of two: at
    ``2**k`` and ``2**k + 1`` batched lanes, every real lane of the array
    backends still equals ``serial`` field for field, next to the
    scalar-fallback lanes of the same batch."""
    g, queries, n_fallback = _lane_mix(n_lanes)
    reference = CostKernel(g)
    want = [reference.cost(nodes, acc) for nodes, acc in queries]
    for backend in available_backends(include_serial=False):
        rec = obs.Recorder()
        with obs.recording(rec):
            got = make_executor(backend).evaluate(CostKernel(g), queries)
        assert rec.counters["engine.scalar_fallback"] == n_fallback
        if backend == "jax":
            assert rec.counters["engine.device_lanes"] == n_lanes
        assert len(got) == len(want)
        for i, (a, b) in enumerate(zip(got, want)):
            assert_costs_equal(a, b, f"[{backend}] lane {i} of {n_lanes}")


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
    for backend in available_backends(include_serial=False):
        assert_backend_parity(g, queries, backend)
        # plan-level: the batched plan path reproduces the serial plans
        ev = CachedEvaluator(g, executor=make_executor(backend))
        plans = ev.plan_batch([(groups, acc) for acc in accs])
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
