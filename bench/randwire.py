"""The RandWire-WS graph of the small regime as published (Xie et al.,
"Exploring Randomly Wired Neural Networks for Image Recognition",
arXiv:1904.01569, Table 2: N = 32, C = 78), in Cocco's layer model.

    python3 bench/randwire.py bench/configs/randwire_a.json

rewrites the ``graph`` of that configuration file.  Table 2, small regime:
conv1 3x3 stride 2 to C/2 at 112x112, conv2 3x3 stride 2 to C at 56x56,
then randomly wired stages of N/2, N and N nodes with C, 2C and 4C channels
at 28x28, 14x14 and 7x7, and the classifier (1x1 conv to 1280 at 7x7,
global average pool, 1000-way fc).  A stage is a Watts-Strogatz graph
(K = 4, P = 0.75) with edges directed from the lower to the higher index;
each node sums its inputs and applies ReLU-SepConv3x3-BN; nodes with no
input read the stage input at stride 2; the stage output averages the nodes
with no output (paper §3).

Cocco's layer model (arXiv:2402.00629 §5.1.1): INT8 activations and
weights; a separable conv is a depthwise and a pointwise layer; sums and
pools are weightless depthwise layers; an fc is a 1x1 conv; a tensor is
``out_len`` rows (its height) of ``line_bytes = width * channels``; 'same'
padding, so a stride-``s`` layer has ``ceil(h / s)`` rows.  BN and ReLU
are hidden in the PE pipeline.
"""

from __future__ import annotations

import json
import math
import sys
from typing import Dict, List, Tuple

C, N, K, P = 78, 32, 4, 0.75
STAGE_SEEDS = (11, 12, 13)   # the Watts-Strogatz instance of each stage


class _Net:
    def __init__(self, name: str) -> None:
        self.name = name
        self.nodes: List[dict] = []
        self.edges: List[dict] = []
        self.shape: Dict[int, Tuple[int, int, int]] = {}

    def node(self, name: str, h: int, w: int, c: int, weights: int,
             macs: int, srcs: List[Tuple[int, int, int]]) -> int:
        idx = len(self.nodes)
        self.nodes.append({"name": name, "out_len": h, "line_bytes": w * c,
                           "weight_bytes": weights, "macs": macs,
                           "is_output": False})
        for src, f, s in srcs:
            self.edges.append({"src": src, "dst": idx, "F": f, "s": s,
                               "kind": "sliding"})
        self.shape[idx] = (h, w, c)
        return idx

    def conv(self, src: int, cout: int, f: int, s: int, name: str) -> int:
        h, w, c = self.shape[src]
        ho, wo = math.ceil(h / s), math.ceil(w / s)
        return self.node(name, ho, wo, cout, f * f * c * cout,
                         ho * wo * cout * f * f * c, [(src, min(f, h), s)])

    def depthwise(self, src: int, f: int, s: int, name: str,
                  weightless: bool = False) -> int:
        h, w, c = self.shape[src]
        ho, wo = math.ceil(h / s), math.ceil(w / s)
        return self.node(name, ho, wo, c, 0 if weightless else f * f * c,
                         ho * wo * c * f * f, [(src, min(f, h), s)])

    def sum(self, srcs: List[int], name: str) -> int:
        h, w, c = self.shape[srcs[0]]
        return self.node(name, h, w, c, 0, h * w * c * len(srcs),
                         [(s, 1, 1) for s in srcs])


def _stage(net: _Net, x: int, n: int, c: int, seed: int, tag: str) -> int:
    import networkx as nx

    ws = nx.connected_watts_strogatz_graph(n, K, P, seed=seed)
    ins: Dict[int, List[int]] = {i: [] for i in range(n)}
    outs: Dict[int, List[int]] = {i: [] for i in range(n)}
    for a, b in ws.edges():
        a, b = min(a, b), max(a, b)
        ins[b].append(a)
        outs[a].append(b)
    made: Dict[int, int] = {}
    for i in range(n):
        if ins[i]:
            srcs = [made[j] for j in sorted(ins[i])]
            agg = srcs[0] if len(srcs) == 1 else net.sum(srcs, f"{tag}.n{i}.sum")
            dw = net.depthwise(agg, 3, 1, f"{tag}.n{i}.dw")
        else:
            dw = net.depthwise(x, 3, 2, f"{tag}.n{i}.dw")
        made[i] = net.conv(dw, c, 1, 1, f"{tag}.n{i}.pw")
    sinks = [made[i] for i in range(n) if not outs[i]]
    return sinks[0] if len(sinks) == 1 else net.sum(sinks, f"{tag}.out")


def randwire_small() -> dict:
    """The graph, in the Graph JSON format ``explore --workload file:``
    reads."""
    net = _Net("randwire_a")
    x = net.node("input", 224, 224, 3, 0, 0, [])
    x = net.conv(x, C // 2, 3, 2, "conv1")
    x = net.conv(x, C, 3, 2, "conv2")
    for si, (n, mult) in enumerate(((N // 2, 1), (N, 2), (N, 4))):
        x = _stage(net, x, n, C * mult, STAGE_SEEDS[si], f"conv{si + 3}")
    x = net.conv(x, 1280, 1, 1, "classifier.conv")
    h, w, c = net.shape[x]
    x = net.node("classifier.pool", 1, 1, c, 0, h * w * c, [(x, h, h)])
    x = net.conv(x, 1000, 1, 1, "classifier.fc")
    net.nodes[x]["is_output"] = True
    return {"format": "cocco-graph", "version": 1, "name": net.name,
            "nodes": net.nodes, "edges": net.edges}


def main(argv: List[str]) -> int:
    path = argv[0]
    with open(path) as f:
        config = json.load(f)
    config["graph"] = randwire_small()
    with open(path, "w") as f:
        f.write(json.dumps(config, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
