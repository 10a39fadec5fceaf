"""Compiled layer-graph execution engine for the paper's CNNs.

``compile_cnn(cfg, params, policy)`` walks the faithful topology graph
(models/graph.py), flattens every conv's stationary weights **once** at
build time, and returns a ``DslrEngine``:

  * ``engine(x)``            — jit-cached forward (one compiled program per
                               (graph, policy, shape) — policies are frozen
                               hashable dataclasses, so the cache is shared
                               across engines with the same policy),
  * ``engine.serve(x_batch)`` — batch-level thin shim: the same program with
                               the batch mesh-sharded across devices (data
                               axis from launch/mesh.py); request-level
                               serving lives in ``repro.serve.DslrServer``,
  * ``engine.with_policy(p)`` — derived engine sharing this engine's
                               flattened weights (how the server builds one
                               engine per SLO tier from a single build),
  * ``engine.error_bounds()`` — per-conv-layer anytime error bounds at the
                               policy's (per-layer) digit budgets.

On the ``dslr_planes`` path each conv + bias + ReLU executes as a *single*
Pallas kernel launch: the digit-plane accumulation keeps the output tile in
VMEM across all MSDF planes and the epilogue rides the flush step (the
memory-system image of the paper's digit-level pipelining into the
activation stage, cf. DSLOT-NN's pooled MSDF datapath).

``execute_graph`` is the underlying pure function — the eager per-call
path (weights flattened on every call) that the engine's build-once
precomputation is asserted bitwise against in tests/test_engine.py.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import cycle_model as cyc
from repro.core import dslr as core_dslr
from repro.core import online
from repro.core import planner as core_planner
from repro.kernels import ops as kops
from . import common as cm
from .graph import (
    GRAPH_INPUT,
    CnnConfig,
    ExecutionPolicy,
    LayerGraph,
    Node,
    build_graph,
)

# per-conv-node build-time precomputation: name -> (w_flat (T, Cout), bias (Cout,))
ConvWeights = Dict[str, Tuple[jax.Array, jax.Array]]


# ---------------------------------------------------------------------------
# node execution
# ---------------------------------------------------------------------------


def _maxpool(x: jax.Array, window: int, stride: int, padding: int) -> jax.Array:
    # smoke-sized inputs can shrink below the window; the pool then
    # degenerates to identity instead of emitting an empty feature map
    if min(x.shape[1], x.shape[2]) < window:
        return x
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        (1, window, window, 1),
        (1, stride, stride, 1),
        [(0, 0), (padding, padding), (padding, padding), (0, 0)],
    )


def _conv_node(
    node: Node,
    x: jax.Array,
    w: jax.Array,
    w_flat: jax.Array,
    b: jax.Array,
    policy: ExecutionPolicy,
    epilogue: Optional[Node],
) -> jax.Array:
    """One conv/downsample layer under the policy.  The planes path consumes
    the pre-flattened stationary ``w_flat``; float/dslr consume raw ``w``.
    Returns the *post-epilogue* value when the epilogue fuses into the
    kernel launch; the caller then skips the bias_relu node."""
    if policy.mode == "dslr_planes":
        fuse = policy.fuse_epilogue
        out = kops.dslr_conv2d_planes_flat(
            x,
            w_flat,
            kernel_size=node.kernel,
            n_digits=policy.n_digits,
            stride=node.stride,
            padding=node.padding,
            recoding=policy.recoding,
            digit_budget=policy.budget_for(node.name),
            bias=b if fuse else None,
            relu=fuse and (epilogue is not None and epilogue.relu),
            per_sample=policy.per_sample_scales,
            packed=policy.packed,
            block_m=policy.block_m,
            block_n=policy.block_n,
            skip_zero_planes=policy.skip_zero_planes,
            interpret=policy.interpret,
            use_ref=policy.use_ref,
        )
        if fuse:
            return out
    elif policy.mode == "dslr":
        out = online.dslr_conv2d(
            x, w, frac_bits=policy.n_digits, stride=node.stride, padding=node.padding
        )
    else:  # float oracle
        out = online.conv2d_ref(x, w, stride=node.stride, padding=node.padding)
    if node.op == "downsample":  # projection shortcut: bias, no activation
        out = out + b
    return out


def _fused_pair(
    graph: LayerGraph,
    a_node: Node,
    b_node: Node,
    x: jax.Array,
    w1_flat: jax.Array,
    b1: jax.Array,
    w2_flat: jax.Array,
    b2: jax.Array,
    policy: ExecutionPolicy,
) -> Tuple[jax.Array, jax.Array]:
    """Execute one pipelined conv→conv pair (both epilogues fused, packed
    digit interchange in between).  Returns ``(out, witness)`` where ``out``
    is the post-epilogue value of ``b`` and ``witness`` is a ``(B, 1, 1, 1)``
    stand-in for the never-materialized f32 mid activation: its per-sample
    amax is ``mid_scale / (1 + 2**-f)``, so amax-based machinery
    (``calibration_scales``, the cascade's ``_stage_forward``) reads off
    exactly the interchange grid the pair *used* — which an observed amax of
    the true mid value could understate."""
    epi_a, epi_b = graph.epilogue_of(a_node), graph.epilogue_of(b_node)
    out, mid_scale = kops.dslr_conv2d_pipelined(
        x,
        w1_flat,
        w2_flat,
        kernel_size1=a_node.kernel,
        kernel_size2=b_node.kernel,
        n_digits=policy.n_digits,
        stride1=a_node.stride,
        padding1=a_node.padding,
        stride2=b_node.stride,
        padding2=b_node.padding,
        recoding=policy.recoding,
        budget1=policy.budget_for(a_node.name),
        budget2=policy.budget_for(b_node.name),
        bias1=b1,
        relu1=epi_a.relu,
        bias2=b2,
        relu2=epi_b.relu,
        per_sample=policy.per_sample_scales,
        block_m=policy.block_m,
        block_n=policy.block_n,
        skip_zero_planes=policy.skip_zero_planes,
        interpret=policy.interpret,
    )
    wit = mid_scale / (1.0 + 2.0 ** -policy.n_digits)
    wit = (wit * jnp.ones((x.shape[0],), jnp.float32)).reshape(-1, 1, 1, 1)
    return out, wit


def execute_graph(
    graph: LayerGraph,
    params,
    x: jax.Array,
    policy: ExecutionPolicy,
    weights: Optional[ConvWeights] = None,
    return_all: bool = False,
) -> jax.Array:
    """Run the layer graph.  ``weights`` carries the engine's build-time
    flattened conv weights; without it (the deprecated ``mode=`` shim) they
    are flattened in-trace — numerically identical, just re-done per call.
    ``return_all`` returns every node's value (planner calibration) instead
    of just the head's.

    Under ``policy.pipeline`` the eligible conv→conv chains
    (``graph.pipeline_pairs``) execute as fused pairs exchanging packed MSDF
    digit planes; the pair's first conv and its epilogue then map to a scale
    *witness* tensor rather than the (never-materialized) f32 activation —
    see ``_fused_pair``."""
    vals = {GRAPH_INPUT: x}
    fused_done = set()
    pair_for = (
        dict(graph.pipeline_pairs())
        if policy.mode == "dslr_planes" and policy.pipeline
        else {}
    )
    for node in graph.nodes:
        if node.name in vals:  # produced by a fused conv→conv pair
            continue
        a = vals[node.inputs[0]]
        if node.op in ("conv", "downsample"):
            if weights is not None:
                # engine path: only the flattened stationary copy is used (the
                # raw 'w' leaves are stripped from the planes-mode param tree)
                w = None
                w_flat, b = weights[node.name]
            elif policy.mode == "dslr_planes":
                w = params[node.param]["w"]
                w_flat, b = core_dslr.flatten_conv_weights(w), params[node.param]["b"]
            else:
                w = params[node.param]["w"]
                w_flat, b = None, params[node.param]["b"]
            epilogue = graph.epilogue_of(node)
            if node.name in pair_for:
                b_node = graph.node(pair_for[node.name])
                if weights is not None:
                    w2_flat, b2 = weights[b_node.name]
                else:
                    w2_flat = core_dslr.flatten_conv_weights(params[b_node.param]["w"])
                    b2 = params[b_node.param]["b"]
                out, wit = _fused_pair(
                    graph, node, b_node, a, w_flat, b, w2_flat, b2, policy
                )
                vals[node.name] = wit
                vals[epilogue.name] = wit
                vals[b_node.name] = out
                vals[graph.epilogue_of(b_node).name] = out
                continue
            vals[node.name] = _conv_node(node, a, w, w_flat, b, policy, epilogue)
            if (
                policy.mode == "dslr_planes"
                and policy.fuse_epilogue
                and epilogue is not None
            ):
                fused_done.add(epilogue.name)
        elif node.op == "bias_relu":
            if node.name in fused_done:  # already applied inside the kernel
                vals[node.name] = a
            else:
                out = a + params[node.param]["b"]
                vals[node.name] = jax.nn.relu(out) if node.relu else out
        elif node.op == "maxpool":
            vals[node.name] = _maxpool(a, node.kernel, node.stride, node.padding)
        elif node.op == "avgpool":
            vals[node.name] = jnp.mean(a, axis=(1, 2))  # kernel=0: global
        elif node.op == "residual_add":
            vals[node.name] = jax.nn.relu(a + vals[node.inputs[1]])
        elif node.op == "dense":
            vals[node.name] = cm.dense(params[node.param], a)
        else:
            raise ValueError(f"unknown node op {node.op!r}")
    if return_all:
        return vals
    return vals[graph.nodes[-1].name]


@functools.partial(jax.jit, static_argnames=("graph", "policy"))
def _jit_execute(graph: LayerGraph, policy: ExecutionPolicy, params, weights, x):
    return execute_graph(graph, params, x, policy, weights=weights)


# ---------------------------------------------------------------------------
# cycle-model dims for every weight-carrying graph node (planner input)
# ---------------------------------------------------------------------------


def conv_layers_for_graph(cfg: CnnConfig, graph: LayerGraph) -> Dict[str, cyc.ConvLayer]:
    """Cycle-model ``ConvLayer`` dims for each conv/downsample node.

    Named conv nodes take the config's (width-scaled) Table-3 dims directly.
    A ResNet projection shortcut ``Ca.ds`` is a 1x1 conv over the block's
    input (``Ca``'s input channels) striding like ``Ca``, so it shares
    ``Ca``'s output extent.  At ``width=1.0`` the totals reproduce the
    paper's Eq.-3 conv cycle counts exactly.
    """
    layers = {l.name: l for l in cfg.layers()}
    out: Dict[str, cyc.ConvLayer] = {}
    for node in graph.conv_nodes:
        if node.op == "conv":
            out[node.name] = layers[node.name]
        else:  # downsample "Ca.ds"
            la = layers[node.name.removesuffix(".ds")]
            out[node.name] = cyc.ConvLayer(
                node.name, 1, node.features, la.n, la.r, la.c, la.stride
            )
    return out


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class DslrEngine:
    """Compiled CNN: topology graph + build-time weight precomputation +
    jit-cached execution under one ``ExecutionPolicy``."""

    def __init__(self, cfg: CnnConfig, params, policy: ExecutionPolicy,
                 graph: Optional[LayerGraph] = None,
                 weights: Optional[ConvWeights] = None):
        self.cfg = cfg
        self.policy = policy
        self.graph = build_graph(cfg) if graph is None else graph
        # validate per-layer budget names against this graph
        conv_names = {n.name for n in self.graph.conv_nodes}
        for name, _ in policy.layer_budgets or ():
            if name not in conv_names:
                raise ValueError(f"budget for unknown conv layer {name!r}")
        # raw tree kept BY REFERENCE for with_policy derivations (including
        # cross-mode ones, which need the unflattened conv 'w' leaves): no
        # arrays are copied, so this costs nothing while the caller also
        # holds params — the pruned _exec_params below is what keeps the raw
        # leaves out of the jit call signature
        self._params = params
        if weights is not None:
            # derived engine (with_policy): share the already-flattened
            # stationary weights, re-flatten nothing
            self._weights = weights
        else:
            # build-time precompute: flatten/transpose every stationary weight
            # exactly once — forward passes only quantize the activations
            self._weights = {}
            for node in self.graph.conv_nodes:
                w = params[node.param]["w"]
                self._weights[node.name] = (
                    core_dslr.flatten_conv_weights(w),
                    params[node.param]["b"],
                )
        if policy.mode == "dslr_planes":
            # the compiled program reads only the flattened copies: drop the
            # raw conv 'w' leaves so the weights are not held (and hashed into
            # the jit call) twice
            conv_params = {n.param for n in self.graph.conv_nodes}
            self._exec_params = {
                k: ({kk: vv for kk, vv in v.items() if kk != "w"}
                    if k in conv_params else v)
                for k, v in params.items()
            }
            self._exec_weights = self._weights
        else:
            self._exec_params = params
            self._exec_weights = None  # float/dslr consume the raw weights
        self._serve_sharding = None  # (n_dev, NamedSharding), built lazily
        # with_policy memo + lock: the request server resolves engines from
        # concurrent dispatcher/submitter threads, and every policy must map
        # to ONE derived engine (so its jit/program identity is stable)
        self._derived: Dict[ExecutionPolicy, "DslrEngine"] = {}
        self._cache_lock = threading.Lock()

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (B, H, W, 3) -> logits (B, num_classes).  One compiled program
        per (graph, policy, input shape)."""
        return _jit_execute(
            self.graph, self.policy, self._exec_params, self._exec_weights, x
        )

    def lower(self, x: jax.Array) -> jax.stages.Lowered:
        """The lowered program ``self(x)`` runs; ``.compile()`` on it is
        served from the jit cache once ``self(x)`` has run, so inspecting
        the compiled HLO costs no second compile."""
        return _jit_execute.lower(
            self.graph, self.policy, self._exec_params, self._exec_weights, x
        )

    def with_policy(self, policy: ExecutionPolicy) -> "DslrEngine":
        """Derived engine under a different policy, sharing this engine's
        already-flattened stationary weights (re-flattens nothing) — how the
        request-level server (serve/) materializes one engine per SLO class
        from a single weight build.  Memoized and thread-safe: concurrent
        lookups of the same policy (dispatcher thread racing submitters)
        return the same engine object."""
        if policy == self.policy:
            return self
        with self._cache_lock:
            engine = self._derived.get(policy)
            if engine is None:
                engine = DslrEngine(
                    self.cfg, self._params, policy,
                    graph=self.graph, weights=self._weights,
                )
                self._derived[policy] = engine
        return engine

    def serve(self, x_batch: jax.Array) -> jax.Array:
        """Batch-sharded inference — kept as a thin batch-level shim over
        ``__call__`` (request-level serving lives in ``repro.serve``).  The
        batch axis spreads across the data axis of a device mesh (rules from
        launch/mesh.py), everything else is replicated.  Ragged batches are
        zero-padded up to ``policy.serve_pad_to`` (default: the device count)
        rounded to a device multiple, then sliced back: zero rows cannot
        raise the per-tensor quantization scale, and under per-sample scales
        every row quantizes independently, so the padding is exact by
        construction either way.  (The PR-6-deprecated ``pad_to=`` keyword
        is gone: padding is batching *policy*, so it lives on
        ``ExecutionPolicy.serve_pad_to`` with the rest of the execution
        knobs — one hashable identity per program.)"""
        pad_to = self.policy.serve_pad_to
        with self._cache_lock:
            if self._serve_sharding is None:
                from repro.launch import mesh as mesh_lib

                devs = jax.devices()
                # Auto axes: the program's reshapes (im2col) carry no
                # sharding rules of their own, which Explicit axes demand
                mesh = jax.make_mesh(
                    (len(devs), 1),
                    ("data", "model"),
                    axis_types=(jax.sharding.AxisType.Auto,) * 2,
                )
                batch_axis = mesh_lib.rules_for(mesh)["batch"]
                self._serve_sharding = (
                    len(devs), NamedSharding(mesh, P(batch_axis))
                )
            n_dev, sharding = self._serve_sharding
        mult = n_dev if pad_to is None else math.lcm(int(pad_to), n_dev)
        B = x_batch.shape[0]
        Bp = -(-B // mult) * mult
        if Bp != B:
            x_batch = jnp.pad(x_batch, ((0, Bp - B), (0, 0), (0, 0), (0, 0)))
        out = self(jax.device_put(x_batch, sharding))
        return out[:B]

    def error_bounds(self, scale: float = 1.0) -> Dict[str, float]:
        """Per-conv-layer anytime error bound at the policy's effective digit
        budget, per unit activation quantization scale (multiply by a layer's
        actual ``DslrQuant.scale`` for absolute bounds).

        Under ``policy.pipeline`` the consumer of each fused pair carries the
        online-recoding term instead (``core.planner.recode_bound``): its
        input was re-quantized onto the interchange grid, so even at full
        budget it pays one grid step ``2**-f`` on top of the truncation
        tail."""
        pipe_consumers = (
            {b for _, b in self.graph.pipeline_pairs()}
            if self.policy.pipeline
            else set()
        )
        out = {}
        for node in self.graph.conv_nodes:
            w_flat, _ = self._weights[node.name]
            k = self.policy.budget_for(node.name) or self.policy.n_planes
            if node.name in pipe_consumers:
                row_l1 = self._weight_gain(node.name, node.param, node.op)
                out[node.name] = core_planner.recode_bound(
                    row_l1, scale, self.policy.n_digits, k
                )
            else:
                out[node.name] = float(
                    core_dslr.anytime_error_bound(w_flat, jnp.float32(scale), k)
                )
        return out

    def pipeline_divergence_bound(self, x: jax.Array) -> float:
        """Upper bound on the max-abs logit deviation between this engine
        under ``pipeline=True`` and the serial (``pipeline=False``) path on
        batch ``x``.

        Both paths run layer-identical arithmetic everywhere except at each
        fused pair's mid activation: the serial path quantizes the f32 mid
        on its *observed* amax grid with the policy recoding, the pipelined
        path emits greedy digits onto the analytic grid ``s_mid`` (an upper
        bound on the observed grid).  Each quantization deviates from the
        true mid by at most one grid step plus the truncation tail, so the
        two paths' mids differ by at most
        ``2 * s_mid * (2**-f + [k < n_planes] * 2**-(k-1))``, amplified
        through the consumer's column-L1 mass and the downstream worst-case
        Lipschitz gains (``node_gains``).  First-order like the rest of the
        gain machinery: downstream re-quantization grids shifting in
        response is a second-order effect (see adaptive/decision.py)."""
        pairs = self.graph.pipeline_pairs()
        if not pairs:
            return 0.0
        pol = self.policy
        f = pol.n_digits
        gains = self.node_gains()
        serial = self.with_policy(dataclasses.replace(pol, pipeline=False))
        scales = serial.calibration_scales(x)
        total = 0.0
        for a, b in pairs:
            w1, b1 = self._weights[a]
            s_mid = float(
                core_dslr.pipeline_mid_scale(w1, b1, jnp.float32(scales[a]), f)
            )
            row_l1_b = self._weight_gain(b, self.graph.node(b).param, "conv")
            k2 = pol.budget_for(b) or pol.n_planes
            tail = 2.0 ** -(k2 - 1) if k2 < pol.n_planes else 0.0
            total += gains[b] * row_l1_b * 2.0 * s_mid * (2.0 ** -f + tail)
        return total

    def _weight_gain(self, name: str, param: str, op: str) -> float:
        """Induced ∞-norm (max column L1) of a weight-carrying node."""
        if op in ("conv", "downsample"):
            w = self._weights[name][0]
        else:  # dense ({"kernel", "bias"} leaves, see common.dense_spec)
            w = self._exec_params[param]["kernel"]
        return float(jnp.max(jnp.sum(jnp.abs(w.astype(jnp.float32)), axis=0)))

    def node_gains(self) -> Dict[str, float]:
        """First-order ∞-norm sensitivity of the network output to a
        perturbation at each node's *output*: one reverse graph walk.
        conv/downsample/dense consumers amplify by their induced ∞-norm;
        bias add, ReLU, max/avg pooling are 1-Lipschitz; a residual add sums
        the gains of its two branches."""
        gains: Dict[str, float] = {n.name: 0.0 for n in self.graph.nodes}
        gains[self.graph.nodes[-1].name] = 1.0
        for node in reversed(self.graph.nodes):
            local = (
                self._weight_gain(node.name, node.param, node.op)
                if node.op in ("conv", "downsample", "dense")
                else 1.0
            )
            for src in node.inputs:
                if src != GRAPH_INPUT:
                    gains[src] += gains[node.name] * local
        return gains

    def calibration_scales(self, x: jax.Array) -> Dict[str, float]:
        """Per-conv-layer activation quantization scale observed on a
        calibration batch: one (eager) forward under this engine's policy,
        then the same amax-based formula ``digits.to_planes`` applies
        (``amax * (1 + 2**-n_digits)``) at every conv/downsample input."""
        vals = execute_graph(
            self.graph, self._exec_params, x, self.policy,
            weights=self._exec_weights, return_all=True,
        )
        f = self.policy.n_digits
        out = {}
        for node in self.graph.conv_nodes:
            amax = float(jnp.max(jnp.abs(vals[node.inputs[0]])))
            out[node.name] = max(amax, 1e-30) * (1.0 + 2.0 ** -f)
        return out

    def probe_sensitivities(
        self, x: jax.Array, budgets: Optional[Sequence[int]] = None
    ) -> Dict[str, Tuple[float, ...]]:
        """Measured per-layer anytime sensitivity sweep: for each conv layer
        and each probed budget, the max-abs logit deviation when THAT layer
        alone is truncated while every other layer stays at full precision.
        One eager full-network forward per (layer, budget) pair plus the
        full-precision reference — use a small calibration batch; in
        interpret mode on CPU this costs seconds per network, which is why
        the CLIs default to the analytic ``bound`` frontier.  The payoff:
        probes see the true activation scales *and* the true (not
        worst-case) downstream error propagation; the worst-case Lipschitz
        composition (``node_gains``) can overestimate deep layers' gains by
        orders of magnitude (see docs/NUMERICS.md).  Returns, per layer, one
        error per entry of ``budgets`` (default: every budget 1..n_planes;
        the full budget probes as exactly 0 without a forward)."""
        if self.policy.mode != "dslr_planes":
            raise ValueError("probe_sensitivities needs a dslr_planes-mode engine")
        n_planes = self.policy.n_planes
        budgets = tuple(budgets) if budgets is not None else tuple(range(1, n_planes + 1))
        base = dataclasses.replace(self.policy, digit_budget=None, layer_budgets=None)
        y_full = execute_graph(
            self.graph, self._exec_params, x, base, weights=self._exec_weights
        )
        out = {}
        for node in self.graph.conv_nodes:
            errs = []
            for k in budgets:
                if k >= n_planes:  # full precision: identical by construction
                    errs.append(0.0)
                    continue
                probed = dataclasses.replace(base, layer_budgets=((node.name, int(k)),))
                y = execute_graph(
                    self.graph, self._exec_params, x, probed, weights=self._exec_weights
                )
                errs.append(float(jnp.max(jnp.abs(y - y_full))))
            out[node.name] = tuple(errs)
        return out

    def budget_curves(
        self,
        x: Optional[jax.Array] = None,
        scale: float = 1.0,
        method: str = "auto",
    ) -> Tuple[core_planner.LayerCurve, ...]:
        """Per-conv-layer (digit budget -> predicted cycles, error) Pareto
        frontier — the planner's input, ordered like ``graph.conv_nodes``.
        Cycles always come from Eq. (3) at this config's layer dims; the
        error side depends on ``method``:

          * ``"bound"`` — the analytic anytime bound at the layer's actual
            weight column-L1 mass (exactly ``error_bounds``'s model), per
            unit activation ``scale``, or at calibrated per-layer scales
            when ``x`` is given (``calibration_scales``).
          * ``"measured"`` — the probed per-(layer, budget) logit deviations
            (``probe_sensitivities``), made non-increasing in the budget by
            a reverse running-minimum envelope (raw probes can wiggle where
            CSD tails cancel).  Needs ``x``.
          * ``"auto"`` — ``"measured"`` when ``x`` is given, else ``"bound"``.
        """
        if method == "auto":
            method = "measured" if x is not None else "bound"
        dims = conv_layers_for_graph(self.cfg, self.graph)
        n_planes = self.policy.n_planes
        if method == "measured":
            if x is None:
                raise ValueError("method='measured' needs a calibration batch x")
            sens = self.probe_sensitivities(x)
            budgets = tuple(range(1, n_planes + 1))
            curves = []
            for node in self.graph.conv_nodes:
                raw = sens[node.name]
                # non-increasing envelope, right to left: a budget is charged
                # at least any larger budget's measured error (raw probes can
                # wiggle upward where CSD tails happen to cancel)
                env, ceil = [], 0.0
                for e in reversed(raw):
                    ceil = max(ceil, e)
                    env.append(ceil)
                curves.append(
                    core_planner.LayerCurve(
                        name=node.name,
                        budgets=budgets,
                        cycles=tuple(
                            cyc.dslr_cycles(dims[node.name], precision=k)
                            for k in budgets
                        ),
                        errors=tuple(reversed(env)),
                    )
                )
            return tuple(curves)
        if method != "bound":
            raise ValueError(f"method={method!r} not in ('auto', 'bound', 'measured')")
        scales = self.calibration_scales(x) if x is not None else None
        curves = []
        for node in self.graph.conv_nodes:
            row_l1 = self._weight_gain(node.name, node.param, node.op)
            s = scales[node.name] if scales is not None else scale
            curves.append(
                core_planner.layer_curve(dims[node.name], row_l1, n_planes, scale=s)
            )
        return tuple(curves)

    def plan(
        self,
        max_cycles: Optional[int] = None,
        max_error: Optional[float] = None,
        x: Optional[jax.Array] = None,
        scale: float = 1.0,
        method: str = "auto",
    ) -> core_planner.BudgetPlan:
        """Solve per-layer digit budgets on this engine's frontier under a
        latency target (``max_cycles``, accelerator cycles) or an error
        target (``max_error``, predicted output error).  ``x`` is an
        optional calibration batch; with it the frontier is measured
        (``method='measured'``), without it analytic (``method='bound'`` —
        see ``budget_curves``).  Apply the result with
        ``compile_cnn(cfg, params, policy.with_plan(plan))`` or
        ``compile_cnn(..., plan=plan)``."""
        return core_planner.plan_budgets(
            self.budget_curves(x=x, scale=scale, method=method),
            max_cycles=max_cycles,
            max_error=max_error,
            network=self.cfg.name,
        )


def compile_cnn(
    cfg: CnnConfig,
    params,
    policy: ExecutionPolicy | None = None,
    plan: core_planner.BudgetPlan | None = None,
) -> DslrEngine:
    """Build a compiled engine for one of the paper's networks: faithful
    topology graph, weights flattened once, one jit program per policy.
    ``plan`` (a planner ``BudgetPlan``) installs its per-layer digit budgets
    on the policy via ``ExecutionPolicy.with_plan``."""
    policy = policy if policy is not None else ExecutionPolicy()
    if plan is not None:
        policy = policy.with_plan(plan)
    return DslrEngine(cfg, params, policy)
