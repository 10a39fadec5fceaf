"""Name scopes in wave programs compiled for a described TPU v5e.

``execute_graph`` runs each graph node in a ``jax.named_scope`` of the
node's name, and the conv wrapper each of its steps (``quantize``, ``pack``,
``im2col``, ``kernel``).  The scopes must reach the compiled program's
``op_name`` metadata and nothing else: the Pallas kernels keep the HLO
instruction names the benchmark finds them by (``bench.trace.KERNEL``).
Two programs are compiled with ``interpret=False``, as
``tests/test_tpu_compile.py`` does; no chip is needed and nothing runs:

* a two-node engine (a 3x3 conv with its fused epilogue and a strided 1x1
  projection shortcut);
* VGG-16 at its published widths, served as a bucket-8 wave: 13 convs, 5
  max-pools, global average pooling and the dense head, on 64x64 images
  (the names do not depend on the image size, and the 224x224 program
  takes minutes to compile).

A pipelined ResNet-18, lowered on the CPU, shows each fused conv pair's
scope, ``"<a>+<b>"``.
"""
from __future__ import annotations

import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.models import common as cm
from repro.models import engine as engine_mod
from repro.models.graph import (
    GRAPH_INPUT,
    CnnConfig,
    ExecutionPolicy,
    LayerGraph,
    Node,
    build_graph,
    graph_spec,
)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

CIN, COUT = 64, 128
GRAPH = LayerGraph("two_node", (
    Node("C1", "conv", (GRAPH_INPUT,), kernel=3, stride=1, padding=1, features=COUT,
         param="C1"),
    Node("C1.act", "bias_relu", ("C1",), features=COUT, param="C1"),
    Node("C2a.ds", "downsample", ("C1.act",), kernel=1, stride=2, features=COUT,
         param="C2a.ds"),
))
VGG16 = CnnConfig("vgg16")
VGG16_GRAPH = build_graph(VGG16)
STEPS = ("quantize", "pack", "im2col", "kernel")


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _two_node_text(chip) -> str:
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
    weights = {
        "C1": (sds((9 * CIN, COUT)), sds((COUT,))),
        "C2a.ds": (sds((COUT, COUT)), sds((COUT,))),
    }
    policy = ExecutionPolicy(per_sample_scales=True, interpret=False)
    lowered = engine_mod._jit_execute.lower(GRAPH, policy, {}, weights, sds((2, 14, 14, CIN)))
    return lowered.compile().as_text()


def _vgg16_text(chip) -> str:
    """The served wave program: the engine's own parameter trees, as the
    server's exact tier runs them, at bucket 8."""
    params = cm.init_params(graph_spec(VGG16), jax.random.PRNGKey(0))
    engine = engine_mod.compile_cnn(VGG16, params, ExecutionPolicy(per_sample_scales=True))
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    policy = dataclasses.replace(engine.policy, interpret=False)
    lowered = engine_mod._jit_execute.lower(
        engine.graph, policy, jax.tree.map(sds, engine._exec_params),
        jax.tree.map(sds, engine._exec_weights),
        jax.ShapeDtypeStruct((8, 64, 64, 3), jnp.float32, sharding=chip))
    return lowered.compile().as_text()


@pytest.fixture(scope="module")
def compiled_text(chip):
    return _two_node_text(chip)


@pytest.fixture(scope="module")
def vgg16_text(chip):
    return _vgg16_text(chip)


def _kernel_names(text) -> list:
    return [
        trace.op_name(line.strip().removeprefix("ROOT "))
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def _assert_scoped(text, node):
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    if node.op in ("conv", "downsample"):
        for step in STEPS:
            assert any(f"/{node.name}/{step}/" in n for n in op_names), (node.name, step)
    else:
        assert any(f"/{node.name}/" in n for n in op_names), node.name


def test_kernels_keep_the_names_the_benchmark_matches(compiled_text):
    calls = _kernel_names(compiled_text)
    assert len(calls) == len(GRAPH.conv_nodes)
    assert all(trace.KERNEL.match(name) for name in calls), calls


@pytest.mark.parametrize("node", [n.name for n in GRAPH.conv_nodes])
def test_each_node_and_step_names_its_operations(compiled_text, node):
    _assert_scoped(compiled_text, GRAPH.node(node))


def test_vgg16_kernels_keep_the_names_the_benchmark_matches(vgg16_text):
    calls = _kernel_names(vgg16_text)
    assert len(calls) == len(VGG16_GRAPH.conv_nodes) == 13
    assert all(trace.KERNEL.match(name) for name in calls), calls


# every VGG-16 node that computes something of its own: a conv's ``.act``
# epilogue is fused into its kernel
VGG16_SCOPED = [n.name for n in VGG16_GRAPH.nodes if n.op != "bias_relu"]


@pytest.mark.parametrize("node", VGG16_SCOPED)
def test_vgg16_each_node_and_step_names_its_operations(vgg16_text, node):
    _assert_scoped(vgg16_text, VGG16_GRAPH.node(node))


def test_fused_pairs_and_nodes_scope_the_lowered_program():
    cfg = CnnConfig("resnet18", width=0.05, num_classes=4)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(0))
    engine = engine_mod.compile_cnn(
        cfg, params, ExecutionPolicy(pipeline=True, per_sample_scales=True))
    text = engine.lower(jnp.zeros((1, 16, 16, 3), jnp.float32)).as_text(debug_info=True)
    scopes = set(re.findall(r'"jit\(_jit_execute\)/([^/"]+)/', text))
    pairs = engine.graph.pipeline_pairs()
    fused = {name for pair in pairs for name in pair}
    assert pairs and {f"{a}+{b}" for a, b in pairs} <= scopes
    assert {n.name for n in engine.graph.conv_nodes} - fused <= scopes
