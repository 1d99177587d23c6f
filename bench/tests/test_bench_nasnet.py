"""A GA search of the program's own ``netlib:nasnet`` agrees with the plain
reference on the whole published graph: its plan, the plan's cost and every
device lane the search made.  The configuration's graph is what
``bench/nasnet.py`` makes, and the structure half's two readers read the
program's counters."""

import json

import numpy as np
import pytest

from bench import common, harness, nasnet, reference

SEED = 2**33 + 19
CONFIG = harness.BENCH / "configs" / "nasnet.json"


@pytest.fixture(scope="module")
def search():
    """One seeded search on the ``jax`` backend, population 20, 200 samples,
    in the configuration's design space, with its device calls and the
    program's spans and counters recorded."""
    from repro.api import run
    from repro.obs import Recorder, recording

    config = json.loads(CONFIG.read_text())
    spec = common.make_spec(dict(config, workload="netlib:nasnet",
                                 population=20, sample_budget=200), SEED)
    calls, rec = common.DeviceCalls(), Recorder()
    calls.active = True
    try:
        with recording(rec):
            result = run(spec, eval_backend="jax")
    finally:
        calls.active = False
        calls.close()
    return config, result, calls, rec


def test_the_configuration_graph_is_what_the_generator_makes():
    assert json.loads(CONFIG.read_text())["graph"] == nasnet.nasnet_mobile()


def test_the_program_runs_the_configuration_graph():
    from repro.core.netlib import build

    config = json.loads(CONFIG.read_text())
    assert common.graph_diff(build("nasnet"), config["graph"]) == 0


@pytest.mark.parametrize("mode", ["fixed", "separate", "shared"])
def test_reference_plan_cost_matches_the_program(mode):
    """On contiguous-run partitions of the whole graph at every kind of
    buffer point, the reference scores each objective exactly as the
    program's scalar cost path, and its float32 objective never does."""
    from repro.core import AcceleratorConfig, Objective
    from repro.core.cost import evaluate_partition
    from repro.core.netlib import build

    config = json.loads(CONFIG.read_text())
    g, ref = build("nasnet"), reference.RefGraph(config["graph"])
    rng = np.random.default_rng(len(mode))
    for _ in range(4):
        acc = dict(config["accelerator"])
        if mode == "separate":
            acc["glb_bytes"] = int(rng.choice(config["glb_candidates"]))
            acc["wbuf_bytes"] = int(rng.choice(config["wbuf_candidates"]))
        elif mode == "shared":
            acc.update(shared=True, wbuf_bytes=0,
                       glb_bytes=int(rng.choice(config["shared_candidates"])))
        cuts = sorted(rng.choice(np.arange(1, g.n), int(rng.integers(8, 120)),
                                 replace=False).tolist())
        groups = [list(range(a, b)) for a, b in zip([0] + cuts, cuts + [g.n])]
        plan = evaluate_partition(g, [set(s) for s in groups],
                                  AcceleratorConfig(**acc))
        for metric, alpha in (("ema", None), ("energy", 0.002),
                              ("energy", None)):
            want = Objective(metric=metric, alpha=alpha).cost(
                plan, AcceleratorConfig(**acc))
            assert reference.plan_cost(ref, groups, acc, metric,
                                       alpha) == want
        low = reference.plan_cost(ref, groups, acc, "energy", 0.002,
                                  np.float32)
        assert low != reference.plan_cost(ref, groups, acc, "energy", 0.002)


def test_reference_subgraph_cost_matches_the_program():
    """Every field the benchmark compares, on random node sets (connected
    runs and scattered sets) at every kind of buffer point."""
    from repro.core import AcceleratorConfig
    from repro.core.cost import compute_structure, finish_cost
    from repro.core.netlib import build

    config = json.loads(CONFIG.read_text())
    g, ref = build("nasnet"), reference.RefGraph(config["graph"])
    rng = np.random.default_rng(19)
    for trial in range(120):
        if trial % 2:
            a = int(rng.integers(0, g.n - 1))
            nodes = set(range(a, min(g.n, a + int(rng.integers(1, 34)))))
        else:
            nodes = set(rng.choice(g.n, int(rng.integers(1, 6)),
                                   replace=False).tolist())
        shared = bool(rng.integers(2))
        acc = dict(glb_bytes=int(rng.choice(config["shared_candidates"])),
                   wbuf_bytes=0 if shared else int(
                       rng.choice(config["wbuf_candidates"])),
                   shared=shared, weight_share_cores=int(rng.integers(1, 4)))
        got = finish_cost(compute_structure(g, nodes),
                          AcceleratorConfig(**acc))
        assert reference.subgraph_cost(ref, frozenset(nodes), acc) == \
            tuple(getattr(got, k) for k in reference.SUBGRAPH_FIELDS)


def test_the_plan_is_a_partition_on_the_design_grid(search):
    config, result, _, _ = search
    groups = [sorted(s) for s in result.groups]
    graph = reference.RefGraph(config["graph"])
    assert reference.partition_faults(graph, groups) == 0
    acc = {k: getattr(result.acc, k) for k in config["accelerator"]}
    assert common.acc_in_space(acc, config)


def test_the_plan_cost_is_the_reference_cost(search):
    config, result, _, _ = search
    obj = config["objective"]
    acc = {k: getattr(result.acc, k) for k in config["accelerator"]}
    want = reference.plan_cost(reference.RefGraph(config["graph"]),
                               [sorted(s) for s in result.groups], acc,
                               obj["metric"], obj["alpha"])
    assert result.cost == want


def test_every_device_lane_is_the_reference_lane(search):
    config, _, calls, _ = search
    lanes, bad = calls.lane_mismatches()
    assert lanes > 0 and bad == 0
    checked, bad = calls.subgraph_mismatches(
        reference.RefGraph(config["graph"]), SEED)
    assert checked > 0 and bad == 0


def test_structure_readers_read_the_recorded_search(search):
    _, _, _, rec = search
    counters = dict(rec.counters)
    run = common.RunData(spans=rec.spans, counters=counters)
    derived = counters["evaluator.structure_derived_nodes"]
    misses = counters["evaluator.structure_misses"]
    hits = counters["evaluator.structure_canon_hits"]
    # each derivation covers at least one layer, and the canonical tier
    # answers some of the raw misses on this graph of repeated cells
    assert derived >= misses > 0 and hits > 0
    assert harness.metric_reader("structure.derive_us_per_node")(run) == \
        pytest.approx(1e6 * counters["evaluator.structure_derive_s"]
                      / derived)
    assert harness.metric_reader("structure.canon_hit_rate")(run) == \
        pytest.approx(100.0 * hits / (hits + misses))


@pytest.mark.parametrize("counters, want", [
    ({"evaluator.structure_derive_s": 0.25,
      "evaluator.structure_derived_nodes": 5000}, 50.0),
    ({"evaluator.structure_derive_s": 0.25}, None),   # no node counter
    ({"evaluator.structure_derive_s": 0.0,
      "evaluator.structure_derived_nodes": 0}, None),
    ({}, None),
])
def test_derive_us_per_node_reader(counters, want):
    read = harness.metric_reader("structure.derive_us_per_node")
    got = read(common.RunData(counters=counters))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("counters, want", [
    ({"evaluator.structure_canon_hits": 270,
      "evaluator.structure_misses": 730}, 27.0),
    ({"evaluator.structure_canon_hits": 0,
      "evaluator.structure_misses": 0}, None),
    ({"evaluator.structure_misses": 730}, None),
    ({}, None),
])
def test_canon_hit_rate_reader(counters, want):
    read = harness.metric_reader("structure.canon_hit_rate")
    got = read(common.RunData(counters=counters))
    assert got == (None if want is None else pytest.approx(want))
