"""The plain reference agrees with the program's scalar cost path exactly,
on random partitions of both configurations at every kind of buffer
point, and its lane arithmetic agrees with the scalar ``finish_cost``."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import common, reference

CONFIGS = Path(reference.__file__).parent / "configs"


def _random_groups(rng, n, k):
    """Contiguous runs of the topological order: partitions with
    multi-layer subgraphs, some of which overflow the buffer."""
    cuts = sorted(rng.choice(np.arange(1, n), k, replace=False).tolist())
    return [list(range(a, b)) for a, b in zip([0] + cuts, cuts + [n])]


@pytest.mark.parametrize("name", ["resnet50", "randwire_a"])
@pytest.mark.parametrize("mode", ["fixed", "separate", "shared"])
def test_plan_cost_matches_the_program(name, mode):
    from repro.api import build_workload
    from repro.core import AcceleratorConfig, Objective
    from repro.core.cost import evaluate_partition

    config = json.loads((CONFIGS / f"{name}.json").read_text())
    g = build_workload(common.workload_uri(config))
    assert common.graph_diff(g, config["graph"]) == 0
    ref = reference.RefGraph(config["graph"])
    rng = np.random.default_rng(len(name) + len(mode))
    base = config["accelerator"]
    for trial in range(6):
        acc = dict(base)
        if mode == "separate":
            acc["glb_bytes"] = int(rng.choice(config["glb_candidates"]))
            acc["wbuf_bytes"] = int(rng.choice(config["wbuf_candidates"]))
        elif mode == "shared":
            acc.update(shared=True, wbuf_bytes=0,
                       glb_bytes=int(rng.choice(config["shared_candidates"])))
        groups = _random_groups(rng, g.n, int(rng.integers(4, g.n // 2)))
        plan = evaluate_partition(g, [set(s) for s in groups],
                                  AcceleratorConfig(**acc))
        for metric, alpha in (("ema", None), ("energy", 0.002),
                              ("energy", None)):
            want = Objective(metric=metric, alpha=alpha).cost(
                plan, AcceleratorConfig(**acc))
            assert reference.plan_cost(ref, groups, acc, metric,
                                       alpha) == want


def test_finish_lanes_match_scalar_finish_cost():
    from repro.core import AcceleratorConfig
    from repro.core.cost import SubgraphStructure, finish_cost

    rng = np.random.default_rng(1)
    lanes = []
    for _ in range(400):
        shared = bool(rng.integers(2))
        single = bool(rng.integers(2))
        glb = int(rng.integers(1, 1 << 22))
        acc = AcceleratorConfig(glb_bytes=glb,
                                wbuf_bytes=0 if shared else int(
                                    rng.integers(1, 1 << 22)),
                                shared=shared,
                                weight_share_cores=int(rng.integers(1, 5)))
        st = SubgraphStructure(nodes=(0,) if single else (0, 1),
                               weight_total=int(rng.integers(0, 1 << 24)),
                               footprint=int(rng.integers(0, 1 << 23)))
        sc = finish_cost(st, acc)
        lanes.append(((st.footprint, st.weight_total, single, glb,
                       acc.wbuf_bytes, shared, acc.weight_share_cores),
                      sc))
    cols = list(zip(*[x for x, _ in lanes]))
    wr, n_blocks, ema_w, fp_out, noc, inf_buf, w_over, stream, feasible = \
        reference.finish_lanes(*cols)
    for j, (_, sc) in enumerate(lanes):
        assert (int(wr[j]), int(ema_w[j]), int(fp_out[j]), int(noc[j]),
                bool(feasible[j])) == (sc.weight_resident, sc.ema_w,
                                       sc.footprint, sc.noc_bytes,
                                       sc.feasible)
        assert bool(stream[j]) == sc.reason.startswith("streamed")


def test_subgraph_cost_matches_the_program():
    """Every field the benchmark compares, on random node sets (connected
    runs and scattered sets, some with no row schedule) at every kind of
    buffer point."""
    from repro.api import build_workload
    from repro.core import AcceleratorConfig
    from repro.core.cost import compute_structure, finish_cost

    for name in ("resnet50", "randwire_a"):
        config = json.loads((CONFIGS / f"{name}.json").read_text())
        g = build_workload(common.workload_uri(config))
        ref = reference.RefGraph(config["graph"])
        rng = np.random.default_rng(7)
        for trial in range(120):
            if trial % 2:
                a = int(rng.integers(0, g.n - 1))
                nodes = set(range(a, min(g.n, a + int(rng.integers(1, 12)))))
            else:
                nodes = set(rng.choice(g.n, int(rng.integers(1, 6)),
                                       replace=False).tolist())
            shared = bool(rng.integers(2))
            acc = dict(glb_bytes=int(rng.choice(config["shared_candidates"])),
                       wbuf_bytes=0 if shared else int(
                           rng.choice(config["wbuf_candidates"])),
                       shared=shared,
                       weight_share_cores=int(rng.integers(1, 4)))
            got = finish_cost(compute_structure(g, nodes),
                              AcceleratorConfig(**acc))
            assert reference.subgraph_cost(ref, frozenset(nodes), acc) == \
                tuple(getattr(got, k) for k in reference.SUBGRAPH_FIELDS)


def test_randwire_graph_is_the_published_small_regime():
    """The configuration's graph is what ``bench/randwire.py`` makes, and
    it has the shapes of arXiv:1904.01569 Table 2 (small regime)."""
    from collections import Counter

    from bench import randwire

    config = json.loads((CONFIGS / "randwire_a.json").read_text())
    graph = randwire.randwire_small()
    assert config["graph"] == graph
    by_name = {n["name"]: n for n in graph["nodes"]}
    assert (by_name["conv1"]["out_len"], by_name["conv1"]["line_bytes"]) \
        == (112, 112 * 39)
    assert (by_name["conv2"]["out_len"], by_name["conv2"]["line_bytes"]) \
        == (56, 56 * 78)
    pointwise = Counter((n["name"].split(".")[0], n["out_len"],
                         n["line_bytes"]) for n in graph["nodes"]
                        if n["name"].endswith(".pw"))
    assert pointwise == {("conv3", 28, 28 * 78): 16,
                         ("conv4", 14, 14 * 156): 32,
                         ("conv5", 7, 7 * 312): 32}
    head = by_name["classifier.conv"]
    assert (head["out_len"], head["line_bytes"]) == (7, 7 * 1280)
    weights = sum(n["weight_bytes"] for n in graph["nodes"])
    assert abs(weights / 5.6e6 - 1) < 0.01   # Table 3: 5.6 M parameters
