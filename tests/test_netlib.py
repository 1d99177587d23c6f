"""Paper workload graphs: structure, statistics, schedulability."""

import json
from collections import Counter
from pathlib import Path

import pytest

from repro.core import AcceleratorConfig, CachedEvaluator
from repro.core.netlib import PAPER_MODELS, build
from repro.core.partition import is_valid, partition_of, singleton_partition


@pytest.mark.parametrize("name", sorted(PAPER_MODELS))
def test_graph_wellformed(name):
    g = build(name)
    assert g.n > 5
    for e in g.edges:
        assert e.src < e.dst
    # exactly one model input (the virtual source), >=1 output
    assert len(g.sources()) >= 1
    assert any(v.is_output for v in g.nodes)


@pytest.mark.parametrize("name", sorted(PAPER_MODELS))
def test_singleton_plan_always_feasible(name):
    g = build(name)
    acc = AcceleratorConfig()
    ev = CachedEvaluator(g)
    plan = ev.plan(singleton_partition(g), acc)
    assert plan.feasible, [
        (s.nodes, s.reason) for s in plan.subgraphs if not s.feasible
    ]
    assert plan.ema_total > 0


def test_model_scale_ordering():
    """ResNet152 > ResNet50 in MACs; GPT > Transformer in weights."""
    r50, r152 = build("resnet50"), build("resnet152")
    tr, gp = build("transformer"), build("gpt")
    assert r152.total_macs() > r50.total_macs()
    assert gp.total_weight_bytes() > tr.total_weight_bytes()


def test_randwire_is_irregular_and_seeded():
    a1, a2 = build("randwire_a"), build("randwire_a")
    assert a1.n == a2.n and len(a1.edges) == len(a2.edges)  # deterministic
    b = build("randwire_b")
    # multi-input merge nodes exist (irregular wiring)
    multi = [v for v in range(a1.n) if len(a1.in_edges(v)) > 2]
    assert multi
    assert b.n != a1.n or b.total_weight_bytes() != a1.total_weight_bytes()


def test_randwire_a_is_the_published_small_regime():
    """``randwire_a`` is node for node and edge for edge the graph of the
    benchmark's RandWire-A configuration (arXiv:1904.01569 Table 2, small
    regime)."""
    path = (Path(__file__).resolve().parents[1] / "bench" / "configs" /
            "randwire_a.json")
    doc = json.loads(path.read_text())["graph"]
    g = build("randwire_a")
    assert (g.n, len(g.edges)) == (216, 313)
    assert [(v.out_len, v.line_bytes, v.weight_bytes, v.macs,
             bool(v.is_output)) for v in g.nodes] == \
        [(n["out_len"], n["line_bytes"], n["weight_bytes"], n["macs"],
          n["is_output"]) for n in doc["nodes"]]
    assert [(e.src, e.dst, e.F, e.s, e.kind) for e in g.edges] == \
        [(e["src"], e["dst"], e["F"], e["s"], e["kind"]) for e in doc["edges"]]


def test_nasnet_is_the_published_mobile_network():
    """``nasnet`` is node for node and edge for edge the graph of the
    benchmark's NASNet-A configuration (arXiv:1707.07012 Table 2, 4 @ 1056,
    after TF-slim's ``nets/nasnet``)."""
    path = (Path(__file__).resolve().parents[1] / "bench" / "configs" /
            "nasnet.json")
    doc = json.loads(path.read_text())["graph"]
    g = build("nasnet")
    assert (g.n, len(g.edges)) == (511, 666)
    assert [(v.out_len, v.line_bytes, v.weight_bytes, v.macs,
             bool(v.is_output)) for v in g.nodes] == \
        [(n["out_len"], n["line_bytes"], n["weight_bytes"], n["macs"],
          n["is_output"]) for n in doc["nodes"]]
    assert [(e.src, e.dst, e.F, e.s, e.kind) for e in g.edges] == \
        [(e["src"], e["dst"], e["F"], e["s"], e["kind"]) for e in doc["edges"]]


def test_nasnet_has_the_published_shape():
    """Table 2's NASNet-A (4 @ 1056): 5.3 M parameters, 564 M mult-adds;
    four normal cells at each of 28x28x264, 14x14x528 and 7x7x1056 behind a
    VALID stem conv of 111 rows; a normal cell concatenates six states, a
    reduction cell four."""
    g = build("nasnet")
    by_name = {v.name: v for v in g.nodes}
    assert by_name["conv0"].out_len == 111
    stages = Counter((v.out_len, v.line_bytes) for v in g.nodes
                     if v.name.startswith("cell_") and v.name.endswith(".out")
                     and not v.name.startswith("cell_stem"))
    assert stages == {(28, 28 * 264): 4, (14, 14 * 528): 4,
                      (7, 7 * 1056): 4}
    assert abs(g.total_weight_bytes() / 5.3e6 - 1) < 0.02
    weighted_macs = sum(v.macs for v in g.nodes if v.weight_bytes)
    assert abs(weighted_macs / 564e6 - 1) < 0.02
    fan_in = {v.name: len(g.in_edges(v.idx)) for v in g.nodes
              if v.name.endswith(".out")}
    assert {n: k for n, k in fan_in.items() if "reduction" in n
            or "stem" in n} == {"cell_stem_0.out": 4, "cell_stem_1.out": 4,
                                "reduction_cell_0.out": 4,
                                "reduction_cell_1.out": 4}
    assert [fan_in[f"cell_{i}.out"] for i in range(12)] == [6] * 12


def test_randwire_b_has_the_regular_regime_stages():
    """Table 2, regular regime (C = 109): conv1 to C/2 at 112, then random
    stages of N/2, N, N and N nodes with C, 2C, 4C and 8C channels at 56,
    28, 14 and 7."""
    g = build("randwire_b")
    conv1 = next(v for v in g.nodes if v.name == "conv1")
    assert (conv1.out_len, conv1.line_bytes) == (112, 112 * 54)
    pointwise = Counter((v.name.split(".")[0], v.out_len, v.line_bytes)
                        for v in g.nodes if v.name.endswith(".pw"))
    assert pointwise == {("conv2", 56, 56 * 109): 16,
                         ("conv3", 28, 28 * 218): 32,
                         ("conv4", 14, 14 * 436): 32,
                         ("conv5", 7, 7 * 872): 32}


def test_single_netlib_table_no_drift():
    """PAPER_MODELS, netlib.build, and the `netlib:` workload scheme all
    consume one table: the names each surface accepts are identical."""
    from repro.core.netlib import list_models
    from repro.api import build_workload, list_workloads

    assert list_models() == sorted(PAPER_MODELS)
    resolver_names = [uri.split(":", 1)[1]
                      for uri, _ in list_workloads("netlib")]
    assert resolver_names == list_models()
    # build() and the resolver reject unknown names from the same table
    with pytest.raises(ValueError, match="unknown netlib model"):
        build("missing_model")
    with pytest.raises(ValueError, match="unknown netlib model"):
        build_workload("netlib:missing_model")


def test_large_models_have_enough_nodes_for_search():
    for name in ("transformer", "gpt", "randwire_a", "randwire_b", "nasnet"):
        g = build(name)
        assert g.n >= 50, (name, g.n)
