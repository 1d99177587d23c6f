"""Paper workloads as Cocco computation graphs (paper §5.1.1).

plain:        VGG16 [57]
multi-branch: ResNet50 / ResNet152 [20], GoogleNet [59], Transformer [64], GPT [52]
irregular:    RandWire-A/B [68] (RandWire-WS small / regular regime of
              arXiv:1904.01569 Table 2; Watts-Strogatz instances from
              assumed seeds, networkx),
              NasNet-A [75] (the mobile NASNet-A 4 @ 1056 of
              arXiv:1707.07012 Table 2, after TF-slim's nets/nasnet;
              no auxiliary head)

Modelling conventions (paper §5.1.1): FC layers are 1x1 convolutions; pooling
and element-wise layers are depth-wise convolutions without weights; scalar
ops (activations) are hidden in the PE pipeline.  Activations and weights are
INT8 (1 byte/element).  The sliding axis is the feature-map height (rows);
``line_bytes = W_out * C_out``.  'same' padding: H_out = ceil(H/s);
'valid' padding (NASNet's conv0): H_out = (H - F) // s + 1.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from .graph import FULL, Graph


class NetBuilder:
    """Tracks (H, W, C) through the net and emits graph nodes."""

    def __init__(self, name: str, h: int, w: int, c: int) -> None:
        self.g = Graph(name)
        # virtual input: a source node with the input tensor, no weights
        self.input = self.g.add_node("input", h, w * c)
        self.shapes: Dict[int, Tuple[int, int, int]] = {self.input: (h, w, c)}

    def shape(self, node: int) -> Tuple[int, int, int]:
        return self.shapes[node]

    def conv(self, src: int, cout: int, f: int = 1, s: int = 1,
             name: str = "conv", depthwise: bool = False,
             weightless: bool = False, padding: str = "same",
             shift: int = 0) -> int:
        """A ``f``x``f`` stride-``s`` layer.  ``padding`` 'valid' gives
        ``(H - f) // s + 1`` rows; ``shift`` rows widen the edge's window
        for a layer that reads its input shifted down by that many rows."""
        h, w, c = self.shapes[src]
        if padding == "valid":
            ho, wo = (h - f) // s + 1, (w - f) // s + 1
        else:
            ho, wo = math.ceil(h / s), math.ceil(w / s)
        if depthwise:
            cout = c
            wbytes = 0 if weightless else f * f * c
            macs = ho * wo * c * f * f
        else:
            wbytes = 0 if weightless else f * f * c * cout
            macs = ho * wo * cout * f * f * c
        idx = self.g.add_node(name, ho, wo * cout, wbytes, macs)
        self.g.add_edge(src, idx, F=min(f, h) + shift, s=s)
        self.shapes[idx] = (ho, wo, cout)
        return idx

    def pool(self, src: int, f: int, s: int, name: str = "pool") -> int:
        return self.conv(src, 0, f, s, name=name, depthwise=True,
                         weightless=True)

    def global_pool(self, src: int, name: str = "gap") -> int:
        h, w, c = self.shapes[src]
        idx = self.g.add_node(name, 1, c, 0, h * w * c)
        self.g.add_edge(src, idx, F=h, s=h)
        self.shapes[idx] = (1, 1, c)
        return idx

    def fc(self, src: int, cout: int, name: str = "fc") -> int:
        """FC over a (possibly spatial) input: flattens the window."""
        h, w, c = self.shapes[src]
        wbytes = h * w * c * cout
        macs = wbytes
        idx = self.g.add_node(name, 1, cout, wbytes, macs)
        self.g.add_edge(src, idx, F=h, s=max(h, 1))
        self.shapes[idx] = (1, 1, cout)
        return idx

    def eltwise(self, srcs: Sequence[int], name: str = "add") -> int:
        h, w, c = self.shapes[srcs[0]]
        idx = self.g.add_node(name, h, w * c, 0, h * w * c * len(srcs))
        for s in srcs:
            self.g.add_edge(s, idx, F=1, s=1)
        self.shapes[idx] = (h, w, c)
        return idx

    def concat(self, srcs: Sequence[int], name: str = "concat") -> int:
        h, w, _ = self.shapes[srcs[0]]
        ctot = sum(self.shapes[s][2] for s in srcs)
        idx = self.g.add_node(name, h, w * ctot, 0, 0)
        for s in srcs:
            self.g.add_edge(s, idx, F=1, s=1)
        self.shapes[idx] = (h, w, ctot)
        return idx

    def attention(self, src: int, name: str = "attn") -> int:
        """Sequence-global op: full dependency on the producer."""
        h, w, c = self.shapes[src]
        idx = self.g.add_node(name, h, w * c, 0, 0)
        self.g.add_edge(src, idx, kind=FULL)
        self.shapes[idx] = (h, w, c)
        return idx

    def mark_output(self, node: int) -> None:
        self.g.nodes[node].is_output = True

    def done(self, out: Optional[int] = None) -> Graph:
        if out is not None:
            self.mark_output(out)
        else:
            for v in self.g.sinks():
                self.g.nodes[v].is_output = True
        return self.g


# ---------------------------------------------------------------------------
# plain: VGG16
# ---------------------------------------------------------------------------

def vgg16() -> Graph:
    b = NetBuilder("vgg16", 224, 224, 3)
    x = b.input
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
           512, 512, 512, "M", 512, 512, 512, "M"]
    i = 0
    for v in cfg:
        if v == "M":
            x = b.pool(x, 2, 2, name=f"pool{i}")
        else:
            x = b.conv(x, v, 3, 1, name=f"conv{i}")
            i += 1
    x = b.fc(x, 4096, "fc6")
    x = b.fc(x, 4096, "fc7")
    x = b.fc(x, 1000, "fc8")
    return b.done(x)


# ---------------------------------------------------------------------------
# multi-branch: ResNet-50 / ResNet-152
# ---------------------------------------------------------------------------

def _bottleneck(b: NetBuilder, x: int, cmid: int, stride: int,
                tag: str, project: bool) -> int:
    cout = cmid * 4
    y = b.conv(x, cmid, 1, 1, name=f"{tag}.c1")
    y = b.conv(y, cmid, 3, stride, name=f"{tag}.c2")
    y = b.conv(y, cout, 1, 1, name=f"{tag}.c3")
    if project:
        sc = b.conv(x, cout, 1, stride, name=f"{tag}.proj")
    else:
        sc = x
    return b.eltwise([y, sc], name=f"{tag}.add")


def _resnet(name: str, blocks: Sequence[int]) -> Graph:
    b = NetBuilder(name, 224, 224, 3)
    x = b.conv(b.input, 64, 7, 2, name="conv1")
    x = b.pool(x, 3, 2, name="pool1")
    cmid = 64
    for li, n in enumerate(blocks):
        for bi in range(n):
            stride = 2 if (li > 0 and bi == 0) else 1
            project = bi == 0
            x = _bottleneck(b, x, cmid, stride, f"l{li+1}b{bi}", project)
        cmid *= 2
    x = b.global_pool(x)
    x = b.fc(x, 1000, "fc")
    return b.done(x)


def resnet50() -> Graph:
    return _resnet("resnet50", [3, 4, 6, 3])


def resnet152() -> Graph:
    return _resnet("resnet152", [3, 8, 36, 3])


# ---------------------------------------------------------------------------
# multi-branch: GoogleNet
# ---------------------------------------------------------------------------

def _inception(b: NetBuilder, x: int, c1: int, c3r: int, c3: int,
               c5r: int, c5: int, cp: int, tag: str) -> int:
    br1 = b.conv(x, c1, 1, 1, name=f"{tag}.1x1")
    br2 = b.conv(x, c3r, 1, 1, name=f"{tag}.3x3r")
    br2 = b.conv(br2, c3, 3, 1, name=f"{tag}.3x3")
    br3 = b.conv(x, c5r, 1, 1, name=f"{tag}.5x5r")
    br3 = b.conv(br3, c5, 5, 1, name=f"{tag}.5x5")
    br4 = b.pool(x, 3, 1, name=f"{tag}.pool")
    br4 = b.conv(br4, cp, 1, 1, name=f"{tag}.poolp")
    return b.concat([br1, br2, br3, br4], name=f"{tag}.cat")


def googlenet() -> Graph:
    b = NetBuilder("googlenet", 224, 224, 3)
    x = b.conv(b.input, 64, 7, 2, name="conv1")
    x = b.pool(x, 3, 2, name="pool1")
    x = b.conv(x, 64, 1, 1, name="conv2r")
    x = b.conv(x, 192, 3, 1, name="conv2")
    x = b.pool(x, 3, 2, name="pool2")
    x = _inception(b, x, 64, 96, 128, 16, 32, 32, "i3a")
    x = _inception(b, x, 128, 128, 192, 32, 96, 64, "i3b")
    x = b.pool(x, 3, 2, name="pool3")
    x = _inception(b, x, 192, 96, 208, 16, 48, 64, "i4a")
    x = _inception(b, x, 160, 112, 224, 24, 64, 64, "i4b")
    x = _inception(b, x, 128, 128, 256, 24, 64, 64, "i4c")
    x = _inception(b, x, 112, 144, 288, 32, 64, 64, "i4d")
    x = _inception(b, x, 256, 160, 320, 32, 128, 128, "i4e")
    x = b.pool(x, 3, 2, name="pool4")
    x = _inception(b, x, 256, 160, 320, 32, 128, 128, "i5a")
    x = _inception(b, x, 384, 192, 384, 48, 128, 128, "i5b")
    x = b.global_pool(x)
    x = b.fc(x, 1000, "fc")
    return b.done(x)


# ---------------------------------------------------------------------------
# multi-branch: Transformer / GPT (tokens are rows; attention is seq-global)
# ---------------------------------------------------------------------------

def _tf_layer(b: NetBuilder, x: int, d: int, dff: int, tag: str) -> int:
    qkv = b.conv(x, 3 * d, 1, 1, name=f"{tag}.qkv")
    att = b.attention(qkv, name=f"{tag}.attn")
    # attention output has width d (scores are transient inside the PE array)
    h, w, _ = b.shapes[att]
    b.shapes[att] = (h, 1, d)
    b.g.nodes[att].line_bytes = d
    # score+context matmuls: 2 * S^2 * d MACs
    b.g.nodes[att].macs = 2 * h * h * d
    proj = b.conv(att, d, 1, 1, name=f"{tag}.proj")
    add1 = b.eltwise([proj, x], name=f"{tag}.add1")
    f1 = b.conv(add1, dff, 1, 1, name=f"{tag}.ffn1")
    f2 = b.conv(f1, d, 1, 1, name=f"{tag}.ffn2")
    return b.eltwise([f2, add1], name=f"{tag}.add2")


def transformer(layers: int = 6, d: int = 512, dff: int = 2048,
                seq: int = 512) -> Graph:
    """Vaswani base: 6 encoder + 6 decoder layers with cross-attention."""
    b = NetBuilder("transformer", seq, 1, d)
    x = b.input
    for i in range(layers):
        x = _tf_layer(b, x, d, dff, f"E{i}")
    memory = x
    # decoder input: second virtual source
    y = b.g.add_node("dec_input", seq, d)
    b.shapes[y] = (seq, 1, d)
    for i in range(layers):
        tag = f"D{i}"
        qkv = b.conv(y, 3 * d, 1, 1, name=f"{tag}.qkv")
        att = b.attention(qkv, name=f"{tag}.self")
        h, _, _ = b.shapes[att]
        b.shapes[att] = (h, 1, d)
        b.g.nodes[att].line_bytes = d
        b.g.nodes[att].macs = 2 * h * h * d
        proj = b.conv(att, d, 1, 1, name=f"{tag}.proj")
        add1 = b.eltwise([proj, y], name=f"{tag}.add1")
        # cross-attention: query from decoder (per-token), memory from encoder
        q = b.conv(add1, d, 1, 1, name=f"{tag}.q")
        ca = b.g.add_node(f"{tag}.cross", seq, d, weight_bytes=2 * d * d,
                          macs=2 * seq * seq * d + 2 * seq * d * d)
        b.g.add_edge(q, ca, F=1, s=1)
        b.g.add_edge(memory, ca, kind=FULL)
        b.shapes[ca] = (seq, 1, d)
        proj2 = b.conv(ca, d, 1, 1, name=f"{tag}.cproj")
        add2 = b.eltwise([proj2, add1], name=f"{tag}.add2")
        f1 = b.conv(add2, dff, 1, 1, name=f"{tag}.ffn1")
        f2 = b.conv(f1, d, 1, 1, name=f"{tag}.ffn2")
        y = b.eltwise([f2, add2], name=f"{tag}.add3")
    return b.done(y)


def gpt(layers: int = 12, d: int = 768, dff: int = 3072,
        seq: int = 512, vocab: int = 40478) -> Graph:
    b = NetBuilder("gpt", seq, 1, d)
    x = b.input
    for i in range(layers):
        x = _tf_layer(b, x, d, dff, f"L{i}")
    x = b.conv(x, vocab, 1, 1, name="lm_head")  # per-token projection d->vocab
    return b.done(x)


# ---------------------------------------------------------------------------
# irregular: RandWire (Watts–Strogatz, seeded) and NasNet-A
# ---------------------------------------------------------------------------

# RandWire-WS, Xie et al., "Exploring Randomly Wired Neural Networks for Image
# Recognition", arXiv:1904.01569, Table 2.  The paper publishes the generator
# law (Watts-Strogatz, K = 4, P = 0.75), not its instances: the seeds of each
# stage's graph below are assumed.
RANDWIRE_N, RANDWIRE_K, RANDWIRE_P = 32, 4, 0.75
RANDWIRE_SEEDS = {"A": (11, 12, 13), "B": (21, 22, 23, 24)}


def _randwire_stage(b: NetBuilder, x: int, n: int, c: int, seed: int,
                    tag: str) -> int:
    """One randomly wired stage of ``n`` nodes with ``c`` channels (paper
    §3): a Watts-Strogatz graph with edges directed from the lower to the
    higher index.  Each node sums its inputs (when it has more than one) and
    applies a ReLU-SepConv3x3 (depthwise + pointwise); a node with no input
    reads the stage input at stride 2; the stage output sums the nodes with
    no output."""
    import networkx as nx

    ws = nx.connected_watts_strogatz_graph(n, RANDWIRE_K, RANDWIRE_P,
                                           seed=seed)
    ins: Dict[int, List[int]] = {i: [] for i in range(n)}
    outs: Dict[int, List[int]] = {i: [] for i in range(n)}
    for (i, j) in ws.edges():
        i, j = min(i, j), max(i, j)
        ins[j].append(i)
        outs[i].append(j)
    nodes: Dict[int, int] = {}
    for i in range(n):
        if ins[i]:
            srcs = [nodes[j] for j in sorted(ins[i])]
            agg = (srcs[0] if len(srcs) == 1
                   else b.eltwise(srcs, f"{tag}.n{i}.sum"))
            dw = b.conv(agg, 0, 3, 1, name=f"{tag}.n{i}.dw", depthwise=True)
        else:
            dw = b.conv(x, 0, 3, 2, name=f"{tag}.n{i}.dw", depthwise=True)
        nodes[i] = b.conv(dw, c, 1, 1, name=f"{tag}.n{i}.pw")
    sinks = [nodes[i] for i in range(n) if not outs[i]]
    return sinks[0] if len(sinks) == 1 else b.eltwise(sinks, f"{tag}.out")


def randwire(variant: str = "A") -> Graph:
    """RandWire-WS of Table 2: A is the small regime (C = 78), B the
    regular regime (C = 109).

    Small: conv1 3x3/2 to C/2 at 112, conv2 3x3/2 to C at 56, random stages
    conv3-conv5 of N/2, N and N nodes with C, 2C and 4C channels at 28, 14
    and 7.  Regular: conv1 3x3/2 to C/2 at 112, random stages conv2-conv5
    of N/2, N, N and N nodes with C, 2C, 4C and 8C channels at 56, 28, 14
    and 7.  Both end in the classifier: a 1x1 conv to 1280, global pooling
    and a 1000-way fc."""
    n, c = RANDWIRE_N, 78 if variant == "A" else 109
    b = NetBuilder(f"randwire_{variant.lower()}", 224, 224, 3)
    x = b.conv(b.input, c // 2, 3, 2, name="conv1")
    if variant == "A":
        x = b.conv(x, c, 3, 2, name="conv2")
        stages = [(3, n // 2, c), (4, n, 2 * c), (5, n, 4 * c)]
    else:
        stages = [(2, n // 2, c), (3, n, 2 * c), (4, n, 4 * c),
                  (5, n, 8 * c)]
    for (k, size, ch), seed in zip(stages, RANDWIRE_SEEDS[variant]):
        x = _randwire_stage(b, x, size, ch, seed, f"conv{k}")
    x = b.conv(x, 1280, 1, 1, name="classifier.conv")
    x = b.global_pool(x, "classifier.pool")
    x = b.fc(x, 1000, "classifier.fc")
    return b.done(x)


# NASNet-A (4 @ 1056), the mobile ImageNet model of Zoph et al., "Learning
# Transferable Architectures for Scalable Image Recognition",
# arXiv:1707.07012, Table 2, with the layer equations of TF-slim's
# ``nets/nasnet`` (``mobile_imagenet_config``, ``_imagenet_stem``,
# ``NasNetABaseCell``).  A cell is (operations, hiddenstate_indices,
# used_hiddenstates) as TF-slim publishes it: block ``i`` adds
# ``operations[2i]`` of state ``hiddenstate_indices[2i]`` to
# ``operations[2i+1]`` of state ``hiddenstate_indices[2i+1]``; states 0 and 1
# are the cell's two inputs, state ``2 + i`` is block ``i``'s sum, and the
# cell concatenates the unused states.
NASNET_NORMAL = (
    ("separable_5x5_2", "separable_3x3_2", "separable_5x5_2",
     "separable_3x3_2", "avg_pool_3x3", "none", "avg_pool_3x3",
     "avg_pool_3x3", "separable_3x3_2", "none"),
    (0, 1, 1, 1, 0, 1, 1, 1, 0, 0),
    (1, 0, 0, 0, 0, 0, 0),
)
NASNET_REDUCTION = (
    ("separable_5x5_2", "separable_7x7_2", "max_pool_3x3",
     "separable_7x7_2", "avg_pool_3x3", "separable_5x5_2", "none",
     "avg_pool_3x3", "separable_3x3_2", "max_pool_3x3"),
    (0, 1, 0, 1, 0, 1, 3, 2, 2, 0),
    (1, 1, 1, 0, 0, 0, 0),
)
NASNET_CELLS, NASNET_FILTERS, NASNET_REDUCTIONS = 12, 44, (4, 8)


def _nasnet_factorized(b: NetBuilder, x: int, c: int, tag: str) -> int:
    """TF-slim ``factorized_reduction`` at stride 2: two strided 1x1 convs of
    c/2 channels, the second on the input shifted by one row and column,
    concatenated."""
    p1 = b.conv(x, c // 2, 1, 2, name=f"{tag}.path1")
    p2 = b.conv(x, c - c // 2, 1, 2, name=f"{tag}.path2", shift=1)
    return b.concat([p1, p2], name=f"{tag}.path")


def _nasnet_op(b: NetBuilder, x: int, op: str, c: int, s: int,
               tag: str) -> int:
    """TF-slim ``_apply_conv_operation``: one operand of a block."""
    cin = b.shape(x)[2]
    if op.startswith("separable"):
        # separable_kxk_n: n ReLU-SepConv-BN layers, the stride on the first
        k, n = int(op.split("_")[1].split("x")[0]), int(op.split("_")[2])
        for i in range(1, n + 1):
            x = b.conv(x, 0, k, s if i == 1 else 1, name=f"{tag}.sep{i}.dw",
                       depthwise=True)
            x = b.conv(x, c, 1, 1, name=f"{tag}.sep{i}.pw")
        return x
    if op == "none":
        if s > 1 or cin != c:
            x = b.conv(x, c, 1, s, name=f"{tag}.1x1")
        return x
    x = b.pool(x, 3, s, name=f"{tag}.pool")     # avg_pool_3x3 / max_pool_3x3
    if cin != c:
        x = b.conv(x, c, 1, 1, name=f"{tag}.1x1")
    return x


def _nasnet_cell(b: NetBuilder, h: int, hm1: Optional[int], c: int, s: int,
                 cell: Tuple[Tuple, Tuple, Tuple], tag: str) -> int:
    """One NASNet-A cell of ``c`` filters at stride ``s`` on the last output
    ``h`` and the one before it ``hm1`` (TF-slim ``NasNetABaseCell``)."""
    ops, inputs, used = cell
    # _reduce_prev_layer: bring h_{i-1} to h's width and c channels
    if hm1 is None:
        prev = h
    elif b.shape(hm1)[1] != b.shape(h)[1]:
        prev = _nasnet_factorized(b, hm1, c, f"{tag}.prev")
    elif b.shape(hm1)[2] != c:
        prev = b.conv(hm1, c, 1, 1, name=f"{tag}.prev_1x1")
    else:
        prev = hm1
    states = [b.conv(h, c, 1, 1, name=f"{tag}.1x1"), prev]
    for i in range(5):
        operands = [
            _nasnet_op(b, states[inputs[j]], ops[j], c,
                       s if inputs[j] < 2 else 1, f"{tag}.comb{i}.{side}")
            for j, side in ((2 * i, "left"), (2 * i + 1, "right"))]
        states.append(b.eltwise(operands, name=f"{tag}.comb{i}"))
    # _combine_unused_states: reduce unused states unlike the last, concat
    _, fw, fc = b.shape(states[-1])
    out = []
    for i, state in enumerate(states):
        if used[i]:
            continue
        _, w, ch = b.shape(state)
        if w != fw:
            state = _nasnet_factorized(b, state, fc, f"{tag}.reduce{i}")
        elif ch != fc:
            state = b.conv(state, fc, 1, 1, name=f"{tag}.reduce{i}")
        out.append(state)
    return b.concat(out, name=f"{tag}.out")


def nasnet() -> Graph:
    """NASNet-A (4 @ 1056): conv0 3x3/2 VALID to 32 at 111; two stem
    reduction cells of 11 and 22 filters (56, 28); 12 normal cells of 44, 88
    and 176 filters at 28, 14 and 7, a reduction cell of twice the filters
    before cells 4 and 8; each cell reads the last two outputs.  Then global
    pooling and a 1000-way fc.  The auxiliary head trains only and is left
    out."""
    b = NetBuilder("nasnet", 224, 224, 3)
    x = b.conv(b.input, 32, 3, 2, name="conv0", padding="valid")
    outs: List[Optional[int]] = [None, x]
    for i in range(2):    # filter scaling 1/4, then 1/2
        x = _nasnet_cell(b, x, outs[-2], NASNET_FILTERS >> (2 - i), 2,
                         NASNET_REDUCTION, f"cell_stem_{i}")
        outs.append(x)
    c = NASNET_FILTERS
    for i in range(NASNET_CELLS):
        if i in NASNET_REDUCTIONS:
            c *= 2
            x = _nasnet_cell(b, x, outs[-2], c, 2, NASNET_REDUCTION,
                             f"reduction_cell_{NASNET_REDUCTIONS.index(i)}")
            outs.append(x)
        x = _nasnet_cell(b, x, outs[-2], c, 1, NASNET_NORMAL, f"cell_{i}")
        outs.append(x)
    x = b.global_pool(x, "final_layer.pool")
    x = b.fc(x, 1000, "final_layer.fc")
    return b.done(x)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# The single netlib table.  Every resolution surface — ``build`` here, the
# ``netlib:`` workload scheme in :mod:`repro.api.workloads`, and the CLI's
# ``workloads ls`` — consumes this dict, so the set of names cannot drift
# between them (tests/test_netlib.py pins the parity).
PAPER_MODELS = {
    "vgg16": vgg16,
    "resnet50": resnet50,
    "resnet152": resnet152,
    "googlenet": googlenet,
    "transformer": transformer,
    "gpt": gpt,
    "randwire_a": lambda: randwire("A"),
    "randwire_b": lambda: randwire("B"),
    "nasnet": nasnet,
}


def list_models() -> List[str]:
    return sorted(PAPER_MODELS)


def build(name: str) -> Graph:
    """Build the named paper model; the one netlib resolution path."""
    try:
        builder = PAPER_MODELS[name]
    except KeyError:
        raise ValueError(
            f"unknown netlib model {name!r}; known: {list_models()}"
        ) from None
    return builder()
