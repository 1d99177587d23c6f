"""Differential-parity harness: the reusable fixture layer behind the
cross-backend acceptance gate.

Every executor backend (``serial`` / ``vector`` / ``jax``)
must return *bitwise-identical* :class:`SubgraphCost`s and whole-strategy
``ExploreResult``s.  This module is importable (not collected — no
``test_`` prefix) and supplies:

* :func:`backend_params` — pytest params over the backend matrix, with
  unavailable backends (jax not installed) rendered as *skips*, never
  silent holes, so ``tests/test_engine.py`` / ``tests/test_golden_
  workloads.py`` / ``tests/test_backend_parity.py`` parametrize over new
  backends with zero per-test edits;
* the query corpus: golden workloads from all four URI schemes, seeded
  ``synthetic:`` fuzz graphs, and adversarial hardware points sitting on
  the scalar-fallback guard boundaries (near ``2**53`` capacities,
  ``2**31`` footprint/weight products);
* :func:`assert_costs_equal` / :func:`assert_backend_parity` — exact
  field-by-field ``SubgraphCost`` comparison of every backend against the
  scalar serial reference;
* :func:`strategy_results` — full-strategy bitwise invariance (one search
  per backend, compared as serialized JSON).
"""

import random
from dataclasses import asdict
from dataclasses import fields as dataclass_fields
from pathlib import Path

import pytest

from repro.api import build_workload
from repro.core import AcceleratorConfig, CostKernel, HWSpace
from repro.core.cost import SubgraphCost
from repro.core.engine import BACKENDS, backend_status, make_executor
from repro.core.partition import random_partition

KB = 1 << 10
GOLDEN_DIR = Path(__file__).resolve().parent / "golden"

# backends every invariance test parametrizes over; serial is the
# reference most tests compare *against*, so it is excluded by default
BACKEND_MATRIX = BACKENDS

# one golden workload per URI scheme (the same four the golden-artifact
# suite pins)
SCHEME_WORKLOADS = (
    "netlib:resnet50",
    "tpu:gemma3-4b:0?tokens=512",
    "synthetic:layered:24?seed=7",
    f"file:{GOLDEN_DIR / 'workload_diamond.json'}",
)

SYNTH_KINDS = ("layered", "branchy", "diamond", "chain", "pyramid")

_COST_FIELDS = tuple(f.name for f in dataclass_fields(SubgraphCost))


def backend_params(include_serial=False):
    """``pytest.param(backend)`` rows over :data:`BACKEND_MATRIX`.

    Unavailable backends come back marked ``skip`` with the engine's
    why-not message (e.g. the jax import failure), so a missing optional
    dependency shows up as a skip in the test report instead of silently
    shrinking coverage.
    """
    params = []
    for backend in BACKEND_MATRIX:
        if backend == "serial" and not include_serial:
            continue
        ok, why = backend_status(backend)
        marks = [] if ok else [pytest.mark.skip(reason=why)]
        params.append(pytest.param(backend, id=backend, marks=marks))
    return params


def available_backends(include_serial=True):
    """The backends that resolve right now, for plain loops."""
    return [b for b in BACKEND_MATRIX
            if (include_serial or b != "serial") and backend_status(b)[0]]


def adversarial_accs():
    """Hardware points that stress every ``finish_cost`` branch and both
    sides of the scalar-fallback guards."""
    return [
        # paper-ish separate and shared points
        AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=512 * KB, wbuf_bytes=0, shared=True),
        # starvation buffers: single-layer streaming + multi-node overflow
        AcceleratorConfig(glb_bytes=2 * KB, wbuf_bytes=2 * KB),
        AcceleratorConfig(glb_bytes=4 * KB, wbuf_bytes=0, shared=True),
        # weight buffer overflow with a roomy global buffer
        AcceleratorConfig(glb_bytes=512 * KB, wbuf_bytes=1 * KB),
        # multi-core weight sharing
        AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=144 * KB,
                          weight_share_cores=4),
        # float64-exactness boundary: last batchable capacity, first
        # scalar-fallback capacity, and one past it
        AcceleratorConfig(glb_bytes=(1 << 53) - 1, wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=(1 << 53), wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=(1 << 53) + 1, wbuf_bytes=144 * KB),
        AcceleratorConfig(glb_bytes=128 * KB, wbuf_bytes=(1 << 53)),
    ]


def corpus_queries(g, seed=0, n_parts=4):
    """Distinct (frozenset, acc) queries over ``g``: random partitions
    probed at every adversarial hardware point plus sampled HW-space
    points (the co-exploration shape)."""
    rng = random.Random(seed)
    hw = HWSpace(mode="separate")
    parts = [random_partition(g, rng, mean_size=rng.uniform(1.5, 6.0))
             for _ in range(n_parts)]
    queries = []
    for acc in adversarial_accs() + [hw.sample(rng) for _ in range(4)]:
        for part in parts:
            for s in part:
                queries.append((frozenset(s), acc))
    # de-dup while preserving order, like CachedEvaluator's miss batching
    # (AcceleratorConfig is a frozen dataclass, so queries hash directly)
    seen = set()
    out = []
    for q in queries:
        if q not in seen:
            seen.add(q)
            out.append(q)
    return out


def scheme_corpus():
    """(label, graph, queries) for one golden workload per URI scheme."""
    for uri in SCHEME_WORKLOADS:
        g = build_workload(uri)
        yield uri.split(":", 1)[0], g, corpus_queries(g, seed=7)


def fuzz_corpus(n_graphs_per_kind=2):
    """(label, graph, queries) for seeded synthetic fuzz graphs."""
    for kind in SYNTH_KINDS:
        for seed in range(n_graphs_per_kind):
            uri = f"synthetic:{kind}:14?seed={100 + seed}"
            g = build_workload(uri)
            yield uri, g, corpus_queries(g, seed=seed)


def assert_costs_equal(got, want, context=""):
    """Exact field-by-field ``SubgraphCost`` equality (floats included)."""
    ga, wa = asdict(got), asdict(want)
    if ga == wa:
        return
    diffs = [f"{name}: {ga[name]!r} != {wa[name]!r}"
             for name in _COST_FIELDS if ga[name] != wa[name]]
    raise AssertionError(
        f"SubgraphCost mismatch {context}: " + "; ".join(diffs))


def assert_backend_parity(g, queries, backend):
    """One backend's batch answers equal the scalar serial reference."""
    reference = CostKernel(g)
    got = make_executor(backend).evaluate(CostKernel(g), queries)
    assert len(got) == len(queries)
    for (nodes, acc), cost in zip(queries, got):
        assert_costs_equal(
            cost, reference.cost(nodes, acc),
            context=f"[{backend}] nodes={sorted(nodes)} "
                    f"glb={acc.glb_bytes} wbuf={acc.wbuf_bytes} "
                    f"shared={acc.shared} share={acc.weight_share_cores}")


def strategy_results(spec, graph, backends=None):
    """Run ``spec`` once per backend; return ``{backend: result_json}``.

    The caller asserts all values are identical — full-strategy bitwise
    invariance, the acceptance gate for any new backend.
    """
    from repro.api import run

    out = {}
    for backend in (backends or available_backends()):
        res = run(spec, graph=graph, eval_backend=backend)
        out[backend] = res.to_json()
    return out
