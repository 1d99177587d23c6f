"""A GA search of the program's own ``netlib:randwire_a`` agrees with the
plain reference on the whole published graph: its plan, the plan's cost and
every device lane the search made."""

import json

import pytest

from bench import common, harness, reference

SEED = 2**33 + 15


@pytest.fixture(scope="module")
def search():
    """One seeded search on the ``jax`` backend, population 20, 200 samples,
    in the configuration's design space, with its device calls and the
    program's spans and counters recorded."""
    from repro.api import run
    from repro.obs import Recorder, recording

    config = json.loads(
        (harness.BENCH / "configs" / "randwire_a.json").read_text())
    spec = common.make_spec(dict(config, workload="netlib:randwire_a",
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


def test_cycle_splits_per_sample_reads_the_recorded_search(search):
    _, result, _, rec = search
    run = common.RunData(spans=rec.spans, counters=dict(rec.counters))
    read = harness.metric_reader("normalize.cycle_splits_per_sample")
    splits = rec.counters["normalize.cycle_splits"]
    assert result.samples == 200 and splits > 0
    assert read(run) == splits / 200
