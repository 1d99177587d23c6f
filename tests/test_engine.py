"""Batched evaluation engine: pure kernel, executor backends, and the
backend-invariance contract (every backend returns identical results)."""

import random
from dataclasses import asdict, replace

import pytest
from backend_parity import available_backends, backend_params
from conftest import small_graph

from repro.api import ExploreSpec, GAOptions, run
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
    split_to_fit,
    split_to_fit_batch,
)
from repro.core.cost import SubgraphStructure
from repro.core.engine import (
    BACKENDS,
    SerialExecutor,
    VectorExecutor,
    backend_status,
    needs_scalar_fallback,
)
from repro.core.netlib import build

KB = 1 << 10


def fixed_spec(**kw):
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    defaults = dict(
        workload="dd",
        strategy="ga",
        objective=Objective(metric="energy", alpha=0.002),
        hw=HWSpace(mode="shared", base=acc),
        sample_budget=300,
        seed=0,
        options=GAOptions(population=20),
    )
    defaults.update(kw)
    return ExploreSpec(**defaults)


def random_queries(g, n_parts=12, seed=0):
    """A corpus of (subgraph, hardware-point) queries over random partitions."""
    rng = random.Random(seed)
    hw = HWSpace(mode="separate")
    queries = []
    for _ in range(n_parts):
        acc = hw.sample(rng)
        for s in random_partition(g, rng, mean_size=rng.uniform(1.5, 6.0)):
            queries.append((frozenset(s), acc))
    return queries


# ---------------------------------------------------------------------------
# the pure kernel
# ---------------------------------------------------------------------------

def test_kernel_equals_evaluate_subgraph():
    g = build("resnet50")
    kernel = CostKernel(g)
    for nodes, acc in random_queries(g, n_parts=4):
        assert asdict(kernel.cost(nodes, acc)) == \
            asdict(evaluate_subgraph(g, set(nodes), acc))


def test_structure_finish_split_is_pure():
    g = small_graph()
    nodes = {0, 1, 2, 3}
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    st1 = compute_structure(g, nodes)
    st2 = compute_structure(g, nodes)
    assert st1 == st2                      # deterministic, value-comparable
    assert finish_cost(st1, acc) == finish_cost(st2, acc)
    # the structure half never depends on the hardware point
    assert st1 == compute_structure(g, set(nodes))


# ---------------------------------------------------------------------------
# evaluate_batch
# ---------------------------------------------------------------------------

def test_evaluate_batch_matches_serial_subgraph_calls():
    g = small_graph()
    queries = random_queries(g, n_parts=6)
    ev_a, ev_b = CachedEvaluator(g), CachedEvaluator(g)
    batch = ev_a.evaluate_batch([(set(n), acc) for n, acc in queries])
    serial = [ev_b.subgraph(set(n), acc) for n, acc in queries]
    assert [asdict(c) for c in batch] == [asdict(c) for c in serial]
    assert ev_a.lookups == ev_b.lookups
    assert ev_a.evaluations == ev_b.evaluations  # distinct misses only


def test_evaluate_batch_dedupes_and_preserves_order():
    g = small_graph()
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    ev = CachedEvaluator(g)
    qs = [({0}, acc), ({1}, acc), ({0}, acc), ({0, 1}, acc), ({1}, acc)]
    costs = ev.evaluate_batch(qs)
    assert [c.nodes for c in costs] == [(0,), (1,), (0,), (0, 1), (1,)]
    assert ev.evaluations == 3             # {0}, {1}, {0,1} computed once each
    assert ev.lookups == 5


def test_split_to_fit_batch_matches_per_item():
    g = build("resnet50")
    rng = random.Random(3)
    acc = AcceleratorConfig(glb_bytes=64 * KB, wbuf_bytes=72 * KB)
    items = [([set(range(g.n))], acc)]
    items += [(random_partition(g, rng, mean_size=8.0), acc)
              for _ in range(3)]
    batched = split_to_fit_batch(g, [([set(s) for s in gr], a)
                                     for gr, a in items], CachedEvaluator(g))
    for (gr, a), got in zip(items, batched):
        assert got == split_to_fit(g, [set(s) for s in gr], a,
                                   ev=CachedEvaluator(g))


# ---------------------------------------------------------------------------
# executor backends
# ---------------------------------------------------------------------------

def test_vector_backend_equals_scalar_kernel_exactly():
    g = build("resnet50")
    queries = random_queries(g, n_parts=12, seed=7)
    scalar = CostKernel(g)
    vec = VectorExecutor()
    got = vec.evaluate(CostKernel(g), queries)
    want = [scalar.cost(nodes, acc) for nodes, acc in queries]
    for a, b in zip(got, want):
        assert asdict(a) == asdict(b)      # exact equality, floats included


def test_vector_backend_streaming_and_overflow_paths():
    g = build("resnet50")
    # tiny buffers force streaming (singletons) and overflow (multi-node)
    accs = [AcceleratorConfig(glb_bytes=2 * KB, wbuf_bytes=2 * KB),
            AcceleratorConfig(glb_bytes=4 * KB, wbuf_bytes=0, shared=True),
            AcceleratorConfig(glb_bytes=512 * KB, wbuf_bytes=1 * KB)]
    queries = [(frozenset({v}), acc) for v in range(0, g.n, 5)
               for acc in accs]
    queries += [(frozenset({v, v + 1}), acc)
                for v in range(0, g.n - 1, 7) for acc in accs]
    got = VectorExecutor().evaluate(CostKernel(g), queries)
    kernel = CostKernel(g)
    reasons = set()
    for (nodes, acc), a in zip(queries, got):
        assert asdict(a) == asdict(kernel.cost(nodes, acc))
        reasons.add(a.reason.split(" in ")[0])
    assert "streamed" in reasons           # the corpus exercised streaming


def test_pool_context_avoids_forking_a_jax_parent():
    """Once jax is imported, parallel ``compare``'s process pool must not
    use the raw ``fork`` start method: jax's at-fork hook warns (and the
    runtime can deadlock).  ``pool_mp_context`` switches to ``forkserver``;
    with no jax in the process it keeps the platform default."""
    import sys

    from repro.api.strategies import pool_mp_context

    ctx = pool_mp_context()
    if "jax" in sys.modules:
        assert ctx.get_start_method() == "forkserver"
    else:
        import multiprocessing as mp

        assert ctx.get_start_method() == mp.get_context().get_start_method()


def test_make_executor_resolution():
    assert BACKENDS == ("serial", "vector", "jax")
    assert isinstance(make_executor(None), SerialExecutor)
    assert isinstance(make_executor("serial"), SerialExecutor)
    assert isinstance(make_executor("vector"), VectorExecutor)
    with pytest.raises(ValueError, match="unknown eval backend"):
        make_executor("process")
    with pytest.raises(ValueError, match="unknown eval backend"):
        make_executor("gpu")


def test_make_executor_unknown_backend_lists_valid_backends():
    with pytest.raises(ValueError) as exc:
        make_executor("gpu")
    for backend in BACKENDS:
        assert backend in str(exc.value)


def test_backend_status_reports_why_unavailable(monkeypatch):
    import repro.core.engine as engine

    ok, why = backend_status("bogus")
    assert not ok and "valid backends" in why
    # simulate a missing jax install regardless of this container
    monkeypatch.setattr(engine, "_JAX_STATUS",
                        (False, "ModuleNotFoundError: No module named 'jax'"))
    ok, why = backend_status("jax")
    assert not ok
    assert "No module named 'jax'" in why and "pip install jax" in why
    with pytest.raises(ValueError, match="unavailable"):
        make_executor("jax")


def test_jax_backend_resolves_whenever_jax_imports():
    """An installed jax must give a working ``jax`` backend: the device path
    may not drop to a skip because the kernel module broke."""
    pytest.importorskip("jax")
    assert backend_status("jax") == (True, "")


def test_jax_status_reports_only_a_missing_jax(monkeypatch):
    import sys

    import repro.core.engine as engine

    monkeypatch.setattr(engine, "_JAX_STATUS", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    ok, detail = engine.jax_status()
    assert not ok and detail.startswith("ModuleNotFoundError")


def test_jax_status_raises_when_the_kernel_module_breaks(monkeypatch):
    """Any import failure other than a missing jax is a bug, not an
    unavailable backend."""
    import sys

    import repro.core.engine as engine
    import repro.kernels

    pytest.importorskip("jax")
    monkeypatch.setattr(engine, "_JAX_STATUS", None)
    monkeypatch.delattr(repro.kernels, "finish_batch", raising=False)
    monkeypatch.setitem(sys.modules, "repro.kernels.finish_batch", None)
    with pytest.raises(ImportError):
        engine.jax_status()
    assert engine._JAX_STATUS is None


_CACHE_PROBE = """
import os, sys
import jax
import numpy as np
from repro.kernels import finish_batch
# the cache is placed only off the CPU backend; steer that check here so
# the CPU compile stands in for the accelerator's
jax.default_backend = lambda: "tpu"
lanes = np.arange(1, 9, dtype=np.int64)
finish_batch.finish_cost_batch(lanes, lanes, lanes % 2 == 0, lanes, lanes,
                               lanes % 3 == 0, np.ones(8, dtype=np.int64))
path = finish_batch.compile_cache_dir()
print(path)
print(len(os.listdir(path)))
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    """The kernel's compiles land in ``$JAX_COMPILATION_CACHE_DIR`` when it
    is set, else in the fixed ``.jax_cache/`` at the checkout root."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    pytest.importorskip("jax")
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = root / ".jax_cache"
    if from_env:
        want = tmp_path / "jax-cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    path, n_entries = out.stdout.split()
    assert Path(path) == want
    assert int(n_entries) > 0


def test_no_compile_cache_on_the_cpu_backend():
    pytest.importorskip("jax")
    import jax

    from repro.kernels import finish_batch

    if jax.default_backend() != "cpu":
        pytest.skip("checks the CPU backend")
    assert finish_batch.compile_cache_dir() is None


# ---------------------------------------------------------------------------
# scalar-fallback guard boundaries (pinned exactly for vector and jax)
# ---------------------------------------------------------------------------

def test_fallback_guard_boundary_capacity_2_53():
    """Capacities become unsafe for float64 division at exactly 2**53."""
    st = SubgraphStructure(nodes=(0,), footprint=10 * KB, weight_total=KB)
    wbuf = 144 * KB
    edge = 1 << 53
    assert not needs_scalar_fallback(
        st, AcceleratorConfig(glb_bytes=edge - 1, wbuf_bytes=wbuf))
    assert needs_scalar_fallback(
        st, AcceleratorConfig(glb_bytes=edge, wbuf_bytes=wbuf))
    assert needs_scalar_fallback(
        st, AcceleratorConfig(glb_bytes=edge + 1, wbuf_bytes=wbuf))
    # the wbuf capacity is guarded identically
    assert needs_scalar_fallback(
        st, AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=edge))


def test_fallback_guard_boundary_sizes_2_31():
    """Footprint / total weights above 2**31 could overflow the int64
    block-count product, so they fall back at exactly 2**31."""
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    edge = 1 << 31
    ok = SubgraphStructure(nodes=(0,), footprint=edge - 1,
                           weight_total=edge - 1)
    assert not needs_scalar_fallback(ok, acc)
    assert needs_scalar_fallback(replace(ok, footprint=edge), acc)
    assert needs_scalar_fallback(replace(ok, weight_total=edge), acc)
    # schedule failures always take the scalar path (reason strings)
    assert needs_scalar_fallback(replace(ok, sched_error="no schedule"), acc)


def test_fallback_guard_boundary_noc_product():
    """The §5.4.2 broadcast charge multiplies weight bytes by the share
    count, so the guard scales with ``weight_share_cores``: the product
    falls back at exactly 2**31 (bounding the int64 noc term well below
    2**62)."""
    edge = 1 << 31
    acc4 = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB,
                             weight_share_cores=4, n_cores=4)
    ok = SubgraphStructure(nodes=(0,), footprint=KB,
                           weight_total=edge // 4 - 1)
    assert not needs_scalar_fallback(ok, acc4)
    assert needs_scalar_fallback(
        replace(ok, weight_total=edge // 4), acc4)
    # a single core keeps the original weight_total boundary
    acc1 = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    assert not needs_scalar_fallback(
        replace(ok, weight_total=edge - 1), acc1)
    assert needs_scalar_fallback(replace(ok, weight_total=edge), acc1)
    # with no weights the share count itself stays below 2**31 (the device
    # kernel divides in int32)
    no_w = replace(ok, weight_total=0)
    assert not needs_scalar_fallback(no_w, replace(acc1,
                                                   weight_share_cores=edge - 1))
    assert needs_scalar_fallback(no_w, replace(acc1, weight_share_cores=edge))


@pytest.mark.parametrize("backend", backend_params())
def test_fallback_boundary_queries_stay_bitwise_exact(backend):
    """Batched backends answer guard-straddling queries identically to the
    scalar kernel (the fallback partition is an implementation detail)."""
    g = small_graph()
    edge_accs = [
        AcceleratorConfig(glb_bytes=(1 << 53) - 1, wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=(1 << 53), wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=(1 << 53) + 1, wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=(1 << 53)),
        AcceleratorConfig(glb_bytes=2 * KB, wbuf_bytes=2 * KB),
    ]
    queries = [(frozenset({v}), acc) for v in range(g.n)
               for acc in edge_accs]
    queries += [(frozenset({v, v + 1}), acc) for v in range(g.n - 1)
                for acc in edge_accs]
    kernel = CostKernel(g)
    got = make_executor(backend).evaluate(CostKernel(g), queries)
    for (nodes, acc), a in zip(queries, got):
        assert asdict(a) == asdict(kernel.cost(nodes, acc)), (nodes, acc)


# ---------------------------------------------------------------------------
# backend invariance of whole strategy runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", backend_params())
def test_parallel_ga_bitwise_identical_to_serial(backend):
    spec = fixed_spec()
    serial = run(spec, graph=small_graph())
    other = run(spec, graph=small_graph(), eval_backend=backend)
    assert other.to_json() == serial.to_json()


def test_count_run_distinct_queries_invariant_across_backends():
    spec = fixed_spec()
    counts = {}
    for backend in available_backends():
        res = run(spec, graph=small_graph(), eval_backend=backend)
        counts[backend] = res.evaluations
    assert len(counts) >= 2  # serial + vector always resolve
    assert len(set(counts.values())) == 1, counts


def test_evaluations_count_distinct_queries_despite_canonical_hits():
    """``evaluations`` (and run()'s distinct-query count) are pinned to the
    raw (nodes, hw-point) key: a canonical structure hit still counts as a
    distinct evaluation — the canonical memo accelerates, never re-defines,
    the accounting."""
    g = small_graph()  # nodes 1 and 2 are isomorphic singletons
    acc = AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB)
    ev = CachedEvaluator(g)
    with ev.count_run() as touched:
        ev.subgraph({1}, acc)
        ev.subgraph({2}, acc)
    assert ev.evaluations == 2            # two distinct raw queries...
    assert len(touched) == 2
    assert ev.kernel.structure_misses == 1  # ...but one schedule derivation
    assert ev.kernel.structure_canon_hits == 1


def test_search_result_evaluations_invariant_across_backends():
    """run_ga's raw SearchResult.evaluations (true cache misses), not just
    the distinct-query count run() reports, must not depend on the backend."""
    from repro.core import run_ga
    counts = []
    for backend in available_backends():
        g = small_graph()
        ev = CachedEvaluator(g, executor=make_executor(backend))
        res = run_ga(g, Objective(metric="ema", alpha=None), HWSpace(),
                     sample_budget=60, population=10, seed=0, ev=ev)
        counts.append(res.evaluations)
    assert len(set(counts)) == 1, counts
