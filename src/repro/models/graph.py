"""Layer-graph IR for the paper's CNNs + the execution policy.

The DSLR-CNN evaluation networks (AlexNet / VGG-16 / ResNet-18) are expressed
as a small static graph of typed nodes —

    conv | bias_relu | maxpool | avgpool | residual_add | downsample | dense

— instead of an implicit conv-only loop, so the topologies are *faithful*
(real pooling stages, real residual skip connections with 1x1 projection
shortcuts) and the execution engine (models/engine.py) can fuse a conv with
its bias+ReLU epilogue into a single Pallas kernel launch.

Graph shapes derive from ``core.cycle_model``: the conv dimensions are the
paper's Table 3 layer lists (``NETWORKS``), pooling placement is
``POOLINGS``, and the ResNet-18 block structure is ``resnet18_blocks`` — the
same tables the cycle/energy model evaluates, so the numerical reproduction
and the analytical model stay in sync.

``ExecutionPolicy`` replaces the old ``mode=`` string + kwarg threading: one
frozen (hashable, jit-static) dataclass carrying the execution mode, digit
precision, *per-layer* digit budgets (the paper's P_i), recoding, epilogue
fusion, backend/interpret selection, and kernel block shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

from repro.core.cycle_model import NETWORKS, POOLINGS, ConvLayer, resnet18_blocks
from . import common as cm
from .common import ParamSpec

MODES = ("float", "dslr", "dslr_planes")
RECODINGS = ("greedy", "csd", "binary")

GRAPH_INPUT = "input"  # the reserved name every graph's first node consumes


@dataclasses.dataclass(frozen=True)
class CnnConfig:
    name: str  # alexnet | vgg16 | resnet18
    width: float = 1.0  # channel scale for smoke runs
    num_classes: int = 10
    frac_bits: int = 8

    def layers(self) -> List[ConvLayer]:
        def s(c):  # scale channels, keep >= 4
            return max(4, int(c * self.width))

        out = []
        for l in NETWORKS[self.name]:
            n = l.n if l.n == 3 else s(l.n)
            out.append(ConvLayer(l.name, l.k, s(l.m), n, l.r, l.c, l.stride))
        return out


@dataclasses.dataclass(frozen=True)
class Node:
    """One typed operation in the layer graph.

    ``inputs`` name producer nodes (``GRAPH_INPUT`` for the graph input);
    ``param`` is the key into the param tree for ops that carry weights
    (conv / downsample / dense).  ``kernel`` doubles as the pooling window
    (0 on ``avgpool`` = global average pool); ``relu`` only applies to
    ``bias_relu`` (False = bias add without activation, e.g. the second conv
    of a residual block whose ReLU comes after the add).
    """

    name: str
    op: str  # conv | bias_relu | maxpool | avgpool | residual_add | downsample | dense
    inputs: Tuple[str, ...]
    kernel: int = 0
    stride: int = 1
    padding: int = 0
    features: int = 0
    relu: bool = True
    param: str = ""


@dataclasses.dataclass(frozen=True)
class LayerGraph:
    network: str
    nodes: Tuple[Node, ...]

    def by_op(self, *ops: str) -> Tuple[Node, ...]:
        return tuple(n for n in self.nodes if n.op in ops)

    @property
    def conv_nodes(self) -> Tuple[Node, ...]:
        """Weight-carrying conv-shaped nodes, in execution order (these are
        the layers a per-layer digit budget indexes)."""
        return self.by_op("conv", "downsample")

    def node(self, name: str) -> Node:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(name)

    def epilogue_of(self, conv: Node) -> Optional[Node]:
        """The unique ``bias_relu`` consumer of a conv node, if any — the
        candidate for in-kernel fusion."""
        consumers = [n for n in self.nodes if conv.name in n.inputs]
        if len(consumers) == 1 and consumers[0].op == "bias_relu":
            return consumers[0]
        return None

    def pipeline_pairs(self) -> Tuple[Tuple[str, str], ...]:
        """Greedy non-overlapping conv→conv chains eligible for cross-layer
        digit pipelining (``ExecutionPolicy.pipeline``): pairs ``(a, b)``
        where conv ``a``'s sole consumer is its bias_relu epilogue and that
        epilogue feeds exactly one node, conv ``b`` (which has an epilogue of
        its own).  A pool, residual add, or fan-out between the two breaks
        the chain — those boundaries fall back to the serial f32 path.
        Greedy left-to-right: in a run C1→C2→C3→C4 the pairs are
        (C1, C2), (C3, C4)."""
        consumers: Dict[str, List[Node]] = {}
        for n in self.nodes:
            for src in n.inputs:
                consumers.setdefault(src, []).append(n)
        pairs: List[Tuple[str, str]] = []
        used: set = set()
        for node in self.nodes:
            if node.op != "conv" or node.name in used:
                continue
            epi = self.epilogue_of(node)
            if epi is None:
                continue
            nxt = consumers.get(epi.name, [])
            if len(nxt) != 1 or nxt[0].op != "conv" or nxt[0].name in used:
                continue
            b = nxt[0]
            if self.epilogue_of(b) is None:
                continue
            pairs.append((node.name, b.name))
            used.update((node.name, b.name))
        return tuple(pairs)


# ---------------------------------------------------------------------------
# execution policy (replaces the mode= string + kwarg threading)
# ---------------------------------------------------------------------------

BudgetSpec = Union[Mapping[str, int], Sequence[int], None]


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How a compiled engine executes the graph.  Frozen + hashable, so it is
    a valid jit static argument: one compiled program per policy.

    ``digit_budget`` is the uniform anytime budget (MSDF planes kept);
    ``layer_budgets`` overrides it per conv layer — the paper's per-layer
    precision P_i — as a tuple of ``(layer_name, planes)`` pairs (use
    ``with_layer_budgets`` to build one from a dict or per-layer list).

    ``packed`` (default on, ``dslr_planes`` only) keeps the conv path's
    digit planes in the 2-bit packed interchange format across the HBM
    boundary (4 MSDF digits per int8 byte, bitmap-driven dead-plane skip) —
    bitwise identical to unpacked execution, ~4x less traffic on the
    dominant operand.  ``block_m``/``block_n`` of ``None`` (the default)
    defer to the block-shape table (``kernels/tuning.py``);
    explicit ints pin the tile shape.
    """

    mode: str = "dslr_planes"  # float | dslr | dslr_planes
    n_digits: int = 8
    recoding: str = "csd"
    digit_budget: Optional[int] = None
    layer_budgets: Optional[Tuple[Tuple[str, int], ...]] = None
    fuse_epilogue: bool = True
    interpret: Optional[bool] = None  # None = auto (interpret off-TPU)
    block_m: Optional[int] = None  # None = autotuned per conv geometry
    block_n: Optional[int] = None
    skip_zero_planes: bool = True
    packed: bool = True  # 2-bit packed digit interchange (dslr_planes only)
    # cross-layer digit pipelining: eligible conv→conv chains
    # (LayerGraph.pipeline_pairs) exchange packed MSDF digit planes directly —
    # the intermediate activation is quantized in-kernel onto an analytic
    # a-priori grid (core/dslr.py::pipeline_mid_scale) and never exists as
    # f32 in HBM.  Needs the packed interchange and the fused epilogue (the
    # digit emitter rides the flush step).
    pipeline: bool = False
    # per-batch-row activation quantization scales: each sample's digit grid
    # depends on that sample alone, so batch composition (an outlier
    # batchmate, bucket zero-padding) cannot perturb a sample's output —
    # the request-level serving contract (serve/).
    per_sample_scales: bool = False
    # batch-padding multiple for DslrEngine.serve (None = the device count);
    # policy rather than a per-call knob so every execution detail that
    # shapes a compiled program lives on one hashable identity
    serve_pad_to: Optional[int] = None
    # route the conv digit-plane launches through the pure-jnp oracle scan
    # (kernels/ref.py) instead of the Pallas kernel — the serving
    # guardrails' trusted fallback when a kernel wave fails its output
    # checks twice.  Bitwise-coupled to the kernel by construction (same
    # MSDF accumulation order and scale folding), so a healthy kernel and
    # the oracle agree exactly.
    use_ref: bool = False

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode={self.mode!r} not in {MODES}")
        if self.recoding not in RECODINGS:
            raise ValueError(f"recoding={self.recoding!r} not in {RECODINGS}")
        if self.per_sample_scales and self.mode != "dslr_planes":
            raise ValueError(
                f"per_sample_scales only applies to mode='dslr_planes', "
                f"got {self.mode!r}"
            )
        if self.digit_budget is not None:
            if self.mode != "dslr_planes":
                raise ValueError(
                    f"digit budgets only apply to mode='dslr_planes', got {self.mode!r}"
                )
            if not 1 <= self.digit_budget <= self.n_planes:
                raise ValueError(
                    f"digit_budget={self.digit_budget} outside [1, {self.n_planes}]"
                )
        if self.layer_budgets is not None:
            if self.mode != "dslr_planes":
                raise ValueError(
                    f"digit budgets only apply to mode='dslr_planes', got {self.mode!r}"
                )
            for name, k in self.layer_budgets:
                if not 1 <= int(k) <= self.n_planes:
                    raise ValueError(
                        f"layer budget {name}={k} outside [1, {self.n_planes}]"
                    )
        if self.pipeline:
            if self.mode != "dslr_planes":
                raise ValueError(
                    f"pipeline=True only applies to mode='dslr_planes', "
                    f"got {self.mode!r}"
                )
            if not self.packed or not self.fuse_epilogue:
                raise ValueError(
                    "pipeline=True requires packed=True and fuse_epilogue=True "
                    "(the digit emitter writes the packed interchange format "
                    "from the fused flush epilogue)"
                )
        if self.serve_pad_to is not None and self.serve_pad_to < 1:
            raise ValueError(
                f"serve_pad_to={self.serve_pad_to} must be >= 1 (or None)"
            )
        if self.use_ref and self.mode != "dslr_planes":
            raise ValueError(
                f"use_ref=True only applies to mode='dslr_planes', "
                f"got {self.mode!r}"
            )

    @property
    def n_planes(self) -> int:
        """Full MSDF plane count (n_digits fractional digits + slot 0)."""
        return self.n_digits + 1

    def budget_for(self, layer: str) -> Optional[int]:
        """Effective digit budget of a conv layer (None = all planes)."""
        if self.layer_budgets is not None:
            for name, k in self.layer_budgets:
                if name == layer:
                    return int(k)
        return self.digit_budget

    def with_layer_budgets(self, graph: LayerGraph, budgets: BudgetSpec):
        """Policy copy with per-layer budgets from a dict (conv-node name ->
        planes) or a sequence (one entry per conv node, graph order)."""
        if budgets is None:
            return dataclasses.replace(self, layer_budgets=None)
        convs = graph.conv_nodes
        if isinstance(budgets, Mapping):
            names = {n.name for n in convs}
            unknown = set(budgets) - names
            if unknown:
                raise ValueError(f"unknown conv layers {sorted(unknown)}")
            pairs = tuple((n.name, int(budgets[n.name])) for n in convs if n.name in budgets)
        else:
            if len(budgets) != len(convs):
                raise ValueError(
                    f"{len(budgets)} budgets for {len(convs)} conv layers "
                    f"({[n.name for n in convs]})"
                )
            pairs = tuple((n.name, int(k)) for n, k in zip(convs, budgets))
        return dataclasses.replace(self, layer_budgets=pairs)

    def with_plan(self, plan):
        """Policy copy taking its per-layer budgets from a solved planner
        ``BudgetPlan`` (core/planner.py) — equivalent to
        ``with_layer_budgets(graph, plan.budget_dict)`` since plans carry
        their budgets in graph conv order.  Layer names are validated against
        the graph when the engine is built."""
        pairs = tuple((str(name), int(k)) for name, k in plan.budgets)
        return dataclasses.replace(self, layer_budgets=pairs)


# ---------------------------------------------------------------------------
# graph builders (faithful topologies, dims from cycle_model.NETWORKS)
# ---------------------------------------------------------------------------


def _sequential_graph(cfg: CnnConfig) -> LayerGraph:
    """AlexNet / VGG-16: conv -> bias+ReLU chains with max-pool stages, then
    global average pool + dense head."""
    pools = POOLINGS[cfg.name]
    nodes: List[Node] = []
    prev = GRAPH_INPUT
    for l in cfg.layers():
        pad = (l.k - 1) // 2
        nodes.append(
            Node(l.name, "conv", (prev,), kernel=l.k, stride=l.stride,
                 padding=pad, features=l.m, param=l.name)
        )
        nodes.append(Node(f"{l.name}.act", "bias_relu", (l.name,), features=l.m, param=l.name))
        prev = f"{l.name}.act"
        if l.name in pools:
            w, s = pools[l.name]
            # valid (unpadded) pooling — AlexNet 55->27->13 and VGG /2 stages
            # per Table 3; only the ResNet stem pool (built separately) pads
            nodes.append(
                Node(f"{l.name}.pool", "maxpool", (prev,), kernel=w, stride=s, padding=0)
            )
            prev = f"{l.name}.pool"
    nodes.append(Node("gap", "avgpool", (prev,)))
    nodes.append(Node("head", "dense", ("gap",), features=cfg.num_classes, param="head"))
    return LayerGraph(cfg.name, tuple(nodes))


def _resnet18_graph(cfg: CnnConfig) -> LayerGraph:
    """ResNet-18: stem conv + max-pool, 8 basic blocks with real residual
    adds (1x1 strided projection shortcuts at stage transitions), global
    average pool, dense head."""
    layers = {l.name: l for l in cfg.layers()}
    w, s = POOLINGS["resnet18"]["C1"]
    l1 = layers["C1"]
    nodes: List[Node] = [
        Node("C1", "conv", (GRAPH_INPUT,), kernel=l1.k, stride=l1.stride,
             padding=(l1.k - 1) // 2, features=l1.m, param="C1"),
        Node("C1.act", "bias_relu", ("C1",), features=l1.m, param="C1"),
        Node("C1.pool", "maxpool", ("C1.act",), kernel=w, stride=s, padding=(w - 1) // 2),
    ]
    prev = "C1.pool"
    for a, b, needs_ds in resnet18_blocks():
        la, lb = layers[a], layers[b]
        nodes.append(
            Node(a, "conv", (prev,), kernel=la.k, stride=la.stride,
                 padding=(la.k - 1) // 2, features=la.m, param=a)
        )
        nodes.append(Node(f"{a}.act", "bias_relu", (a,), features=la.m, param=a))
        nodes.append(
            Node(b, "conv", (f"{a}.act",), kernel=lb.k, stride=lb.stride,
                 padding=(lb.k - 1) // 2, features=lb.m, param=b)
        )
        # bias only: the block's ReLU comes after the residual add
        nodes.append(Node(f"{b}.act", "bias_relu", (b,), features=lb.m, relu=False, param=b))
        skip = prev
        if needs_ds:
            nodes.append(
                Node(f"{a}.ds", "downsample", (skip,), kernel=1, stride=la.stride,
                     padding=0, features=lb.m, param=f"{a}.ds")
            )
            skip = f"{a}.ds"
        nodes.append(Node(f"{b}.add", "residual_add", (f"{b}.act", skip)))
        prev = f"{b}.add"
    nodes.append(Node("gap", "avgpool", (prev,)))
    nodes.append(Node("head", "dense", ("gap",), features=cfg.num_classes, param="head"))
    return LayerGraph("resnet18", tuple(nodes))


def build_graph(cfg: CnnConfig) -> LayerGraph:
    if cfg.name == "resnet18":
        return _resnet18_graph(cfg)
    if cfg.name in NETWORKS:
        return _sequential_graph(cfg)
    raise ValueError(f"unknown network {cfg.name!r} (have {sorted(NETWORKS)})")


# ---------------------------------------------------------------------------
# parameter spec (channel counts propagated through the graph)
# ---------------------------------------------------------------------------


def input_channels(graph: LayerGraph, in_channels: int = 3) -> Dict[str, int]:
    """Channel count seen at each node's *input* (walks the graph once)."""
    chans = {GRAPH_INPUT: in_channels}
    out: Dict[str, int] = {}
    for n in graph.nodes:
        cin = chans[n.inputs[0]]
        out[n.name] = cin
        chans[n.name] = n.features if n.op in ("conv", "downsample", "dense") else cin
    return out


def graph_spec(cfg: CnnConfig, in_channels: int = 3):
    """ParamSpec tree for a graph: one {w, b} entry per conv/downsample node
    plus the dense head (same leaf layout as the old conv-only ``cnn_spec``,
    extended with the projection-shortcut convs)."""
    graph = build_graph(cfg)
    cin_of = input_channels(graph, in_channels)
    spec = {}
    for n in graph.conv_nodes:
        spec[n.param] = {
            "w": ParamSpec((n.kernel, n.kernel, cin_of[n.name], n.features),
                           (None, None, None, "mlp"), "normal"),
            "b": ParamSpec((n.features,), ("mlp",), "zeros"),
        }
    head = graph.node("head")
    spec["head"] = cm.dense_spec(cin_of["head"], head.features, (None, None), bias=True)
    return spec
