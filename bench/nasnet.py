"""NASNet-A (4 @ 1056), the mobile ImageNet network as published (Zoph et
al., "Learning Transferable Architectures for Scalable Image Recognition",
arXiv:1707.07012, Table 2: 5.3 M parameters, 564 M mult-adds), in Cocco's
layer model.

    python3 bench/nasnet.py bench/configs/nasnet.json

rewrites the ``graph`` of that configuration file.  The layer equations are
TF-slim's (``tensorflow/models`` ``research/slim/nets/nasnet``):
``mobile_imagenet_config`` (12 cells, 44 filters, stem multiplier 1, filter
scaling rate 2, 2 reduction layers, ``skip_reduction_layer_input`` 0),
``_imagenet_stem`` (conv0 3x3 stride 2 VALID to 32, then two reduction
cells at filter scaling 1/4 and 1/2), and ``NasNetABaseCell`` with the
operations and hidden-state wiring of ``NasNetANormalCell`` and
``NasNetAReductionCell``.  The auxiliary head trains only and is left out.

Cocco's layer model (arXiv:2402.00629 §5.1.1): INT8 activations and
weights; a separable conv is a depthwise and a pointwise layer; sums and
pools (max or average alike) are weightless depthwise layers; an fc is a
1x1 conv; a tensor is ``out_len`` rows (its height) of ``line_bytes =
width * channels``; 'same' padding gives ``ceil(h / s)`` rows, 'valid'
``(h - f) // s + 1``.  BN and ReLU are hidden in the PE pipeline.  The
second path of a factorized reduction reads row ``2i + 1`` for its row
``i``; its edge is ``F = 2, s = 2`` so that the window holds that row.
"""

from __future__ import annotations

import json
import re
import sys
from typing import Dict, List, Optional, Tuple

NUM_CELLS, FILTERS, STEM_FILTERS = 12, 44, 32
REDUCTION_AT = (4, 8)       # calc_reduction_layers(12, 2)

# (operation, hidden state) of each block's left and right operand, and
# the states the cell does not concatenate; states 0 and 1 are the cell's
# inputs (h after its 1x1, and h_{i-1} brought to shape)
NORMAL = ([(("separable_5x5_2", 0), ("separable_3x3_2", 1)),
           (("separable_5x5_2", 1), ("separable_3x3_2", 1)),
           (("avg_pool_3x3", 0), ("none", 1)),
           (("avg_pool_3x3", 1), ("avg_pool_3x3", 1)),
           (("separable_3x3_2", 0), ("none", 0))],
          {0})
REDUCTION = ([(("separable_5x5_2", 0), ("separable_7x7_2", 1)),
              (("max_pool_3x3", 0), ("separable_7x7_2", 1)),
              (("avg_pool_3x3", 0), ("separable_5x5_2", 1)),
              (("none", 3), ("avg_pool_3x3", 2)),
              (("separable_3x3_2", 2), ("max_pool_3x3", 0))],
             {0, 1, 2})


class _Net:
    def __init__(self, name: str) -> None:
        self.name = name
        self.nodes: List[dict] = []
        self.edges: List[dict] = []
        self.hwc: Dict[int, Tuple[int, int, int]] = {}

    def node(self, name: str, hwc: Tuple[int, int, int], weights: int,
             macs: int, srcs: List[Tuple[int, int, int]]) -> int:
        idx = len(self.nodes)
        h, w, c = hwc
        self.nodes.append({"name": name, "out_len": h, "line_bytes": w * c,
                           "weight_bytes": weights, "macs": macs,
                           "is_output": False})
        for src, f, s in srcs:
            self.edges.append({"src": src, "dst": idx, "F": f, "s": s,
                               "kind": "sliding"})
        self.hwc[idx] = hwc
        return idx

    def conv(self, x: int, cout: int, k: int, s: int, name: str,
             valid: bool = False, window: Optional[int] = None) -> int:
        h, w, c = self.hwc[x]
        if valid:
            ho, wo = (h - k) // s + 1, (w - k) // s + 1
        else:
            ho, wo = -(-h // s), -(-w // s)
        return self.node(name, (ho, wo, cout), k * k * c * cout,
                         ho * wo * cout * k * k * c,
                         [(x, window or min(k, h), s)])

    def depthwise(self, x: int, k: int, s: int, name: str,
                  weights: bool = True) -> int:
        h, w, c = self.hwc[x]
        ho, wo = -(-h // s), -(-w // s)
        return self.node(name, (ho, wo, c), k * k * c if weights else 0,
                         ho * wo * c * k * k, [(x, min(k, h), s)])

    def add(self, xs: List[int], name: str) -> int:
        h, w, c = self.hwc[xs[0]]
        return self.node(name, (h, w, c), 0, h * w * c * len(xs),
                         [(x, 1, 1) for x in xs])

    def concat(self, xs: List[int], name: str) -> int:
        h, w, _ = self.hwc[xs[0]]
        return self.node(name, (h, w, sum(self.hwc[x][2] for x in xs)), 0,
                         0, [(x, 1, 1) for x in xs])

    def factorized_reduction(self, x: int, filters: int, name: str) -> int:
        """Stride 2: path 1 a strided 1x1 to filters/2; path 2 the same on
        the input padded below and right and cropped by one (row i reads
        row 2i + 1), to the rest; concatenated."""
        half = filters // 2
        p1 = self.conv(x, half, 1, 2, f"{name}.path1")
        p2 = self.conv(x, filters - half, 1, 2, f"{name}.path2", window=2)
        return self.concat([p1, p2], f"{name}.path")


def _operation(net: _Net, x: int, op: str, filters: int, stride: int,
               name: str) -> int:
    if op == "none":
        if stride == 1 and net.hwc[x][2] == filters:
            return x
        return net.conv(x, filters, 1, stride, f"{name}.1x1")
    k = int(re.search(r"(\d+)x\d+", op).group(1))
    if op.startswith("separable"):
        for layer in range(int(op.rsplit("_", 1)[1])):
            x = net.depthwise(x, k, stride if layer == 0 else 1,
                              f"{name}.sep{layer + 1}.dw")
            x = net.conv(x, filters, 1, 1, f"{name}.sep{layer + 1}.pw")
        return x
    needs_1x1 = net.hwc[x][2] != filters
    x = net.depthwise(x, k, stride, f"{name}.pool", weights=False)
    return net.conv(x, filters, 1, 1, f"{name}.1x1") if needs_1x1 else x


def _cell(net: _Net, spec, h: int, prev: Optional[int], filters: int,
          stride: int, name: str) -> int:
    blocks, used = spec
    if prev is None:
        prev = h
    elif net.hwc[prev][1] != net.hwc[h][1]:
        prev = net.factorized_reduction(prev, filters, f"{name}.prev")
    elif net.hwc[prev][2] != filters:
        prev = net.conv(prev, filters, 1, 1, f"{name}.prev_1x1")
    states = [net.conv(h, filters, 1, 1, f"{name}.1x1"), prev]
    for b, block in enumerate(blocks):
        outs = [_operation(net, states[src], op, filters,
                           stride if src < 2 else 1, f"{name}.comb{b}.{side}")
                for (op, src), side in zip(block, ("left", "right"))]
        states.append(net.add(outs, f"{name}.comb{b}"))
    last_w, last_c = net.hwc[states[-1]][1:]
    keep = []
    for i, x in enumerate(states):
        if i in used:
            continue
        if net.hwc[x][1] != last_w:
            x = net.factorized_reduction(x, last_c, f"{name}.reduce{i}")
        elif net.hwc[x][2] != last_c:
            x = net.conv(x, last_c, 1, 1, f"{name}.reduce{i}")
        keep.append(x)
    return net.concat(keep, f"{name}.out")


def nasnet_mobile() -> dict:
    """The graph, in the Graph JSON format ``explore --workload file:``
    reads."""
    net = _Net("nasnet")
    x = net.node("input", (224, 224, 3), 0, 0, [])
    x = net.conv(x, STEM_FILTERS, 3, 2, "conv0", valid=True)
    outputs = [None, x]
    for i, scale in enumerate((0.25, 0.5)):
        x = _cell(net, REDUCTION, x, outputs[-2], int(FILTERS * scale), 2,
                  f"cell_stem_{i}")
        outputs.append(x)
    scale = 1
    for i in range(NUM_CELLS):
        if i in REDUCTION_AT:
            scale *= 2
            x = _cell(net, REDUCTION, x, outputs[-2], FILTERS * scale, 2,
                      f"reduction_cell_{REDUCTION_AT.index(i)}")
            outputs.append(x)
        x = _cell(net, NORMAL, x, outputs[-2], FILTERS * scale, 1,
                  f"cell_{i}")
        outputs.append(x)
    h, w, c = net.hwc[x]
    x = net.node("final_layer.pool", (1, 1, c), 0, h * w * c, [(x, h, h)])
    x = net.conv(x, 1000, 1, 1, "final_layer.fc")
    net.nodes[x]["is_output"] = True
    return {"format": "cocco-graph", "version": 1, "name": net.name,
            "nodes": net.nodes, "edges": net.edges}


def main(argv: List[str]) -> int:
    path = argv[0]
    with open(path) as f:
        config = json.load(f)
    config["graph"] = nasnet_mobile()
    with open(path, "w") as f:
        f.write(json.dumps(config, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
