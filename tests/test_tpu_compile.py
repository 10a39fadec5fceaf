"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode never checks Mosaic's tiling rules, so these tests lower and
compile the kernels with ``interpret=False`` at the widths that are served:
ResNet-18's first and last conv geometries through the packed conv path
(per-sample row scales, fused bias and ReLU), the packed conv wrapper at the
served bucket-8 patch matrices (its dead-plane bitmap copies no digit
views), the unpacked conv kernel, and the packed matmul at qwen2-0.5b
decode and prefill widths.  No chip is
needed — the TPU compiler targets a topology that is described, not
attached — and nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that loads it at import
would leave the other workers collecting different tests.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dslr_conv2d as dc
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (name, input (B, H, W, Cin), kernel, stride, padding, Cout): ResNet-18 conv1
# (T = 7*7*3 = 147, M = 112*112) and a layer4 conv (T = 3*3*512 = 4608, M = 49,
# padded to the row tile)
RESNET18_CONVS = [
    ("conv1", (1, 224, 224, 3), 7, 2, 3, 64),
    ("layer4", (1, 7, 7, 512), 3, 1, 1, 512),
]


def _packed_conv_text(sharding, x_shape, k, stride, pad, cout, budget=None) -> str:
    """The whole packed conv compiled: quantize, pack, im2col and kernel."""
    T = k * k * x_shape[-1]
    fn = functools.partial(
        ops.dslr_conv2d_planes_flat,
        kernel_size=k,
        n_digits=8,
        stride=stride,
        padding=pad,
        digit_budget=budget,
        relu=True,
        per_sample=True,
        packed=True,
        interpret=False,
    )
    return _compile_text(
        lambda x, w, b: fn(x, w, bias=b),
        [(x_shape, jnp.float32), ((T, cout), jnp.float32), ((cout,), jnp.float32)],
        sharding,
    )


@pytest.mark.parametrize("budget", [None, 4], ids=["D9", "k4"])
@pytest.mark.parametrize("geom", RESNET18_CONVS, ids=[g[0] for g in RESNET18_CONVS])
def test_packed_conv_compiles_for_v5e(one_chip, geom, budget):
    _, x_shape, k, stride, pad, cout = geom
    text = _packed_conv_text(one_chip, x_shape, k, stride, pad, cout, budget)
    assert text.count("tpu_custom_call") >= 1


# (name, input (B, H, W, Cin), kernel, stride, padding): the served bucket-8
# wave's C1 (M = 8*112*112 rows of T = 7*7*3), a layer1 conv (T = 3*3*64) and
# a layer2 conv (T = 3*3*128)
BUCKET8_CONVS = [
    ("C1", (8, 224, 224, 3), 7, 2, 3),
    ("C2", (8, 56, 56, 64), 3, 1, 1),
    ("C8", (8, 28, 28, 128), 3, 1, 1),
]


@pytest.mark.parametrize("geom", BUCKET8_CONVS, ids=[g[0] for g in BUCKET8_CONVS])
def test_packed_conv_bitmap_copies_no_digit_views(one_chip, geom):
    """The dead-plane bitmap is one OR-reduction over the packed bytes: the
    compiled conv wrapper holds no gather loop over the digit axis and no
    (D, M, T) array of digit views.  Compiles the wrapper alone at the
    packed patch matrix the geometry gives."""
    _, (B, H, W, cin), k, stride, pad = geom
    D = 9  # n_digits=8 serves 9 MSDF planes, in 3 byte groups
    Ho, Wo = (H + 2 * pad - k) // stride + 1, (W + 2 * pad - k) // stride + 1
    M, T, N = B * Ho * Wo, k * k * cin, 64
    fn = functools.partial(dc.dslr_conv2d_planes_packed_mxu, apply_relu=True)
    text = _compile_text(
        lambda p, w, s, b, r: fn(p, w, s, bias=b, row_scale=r),
        [
            ((3, M, T), jnp.int8),
            ((T, N), jnp.float32),
            ((D,), jnp.float32),
            ((N,), jnp.float32),
            ((M,), jnp.float32),
        ],
        one_chip,
    )
    assert "tpu_custom_call" in text
    gathers = [
        line for line in text.splitlines()
        if " while(" in line
        and re.search(r'op_name="[^"]*dslr_conv2d_planes_packed_mxu[^"]*/gather"', line)
    ]
    assert not gathers, gathers[:1]
    assert f"s8[{D},{M},{T}]" not in text


@pytest.mark.parametrize("geom", BUCKET8_CONVS[1:], ids=[g[0] for g in BUCKET8_CONVS[1:]])
def test_packed_conv_bitmap_reads_the_patch_matrix(one_chip, geom):
    """The bitmap's OR-reduction reads the packed patch matrix the kernel
    reads, and is not pushed back into the im2col's slices of the image,
    where it would reduce every slice over its channels.  Not for C1, whose
    bucket-8 prologue takes minutes to compile."""
    _, x_shape, k, stride, pad = geom
    text = _packed_conv_text(one_chip, x_shape, k, stride, pad, 64)
    reduces = [
        line.split(" = ", 1)[1].split(" reduce(")[0]
        for line in text.splitlines()
        if " reduce(" in line
        and re.search(r'op_name="[^"]*dslr_conv2d_planes_packed_mxu\)/reduce"', line)
    ]
    assert reduces
    # an image slice's reduce keeps the batch and both spatial axes
    B, H = x_shape[0], (x_shape[1] + 2 * pad - k) // stride + 1
    image_shaped = [r for r in reduces if f",{B},{H},{H}" in r]
    assert not image_shaped, image_shaped[:2]


def test_unpacked_conv_compiles_for_v5e(one_chip):
    D, M, T, N = 9, 49, 4608, 512
    fn = functools.partial(
        dc.dslr_conv2d_planes_mxu, block_m=128, block_n=128, apply_relu=True
    )
    text = _compile_text(
        lambda p, w, s, b, r: fn(p, w, s, bias=b, row_scale=r),
        [
            ((D, M, T), jnp.int8),
            ((T, N), jnp.float32),
            ((D,), jnp.float32),
            ((N,), jnp.float32),
            ((M,), jnp.float32),
        ],
        one_chip,
    )
    assert "tpu_custom_call" in text


# qwen2-0.5b: d_model 896, d_ff 4864 — a decode step (8 token rows) through
# the FFN up projection and a 512-token prefill through the down projection
QWEN_MATMULS = [("decode", 8, 896, 4864), ("prefill", 512, 4864, 896)]


@pytest.mark.parametrize("shape", QWEN_MATMULS, ids=[s[0] for s in QWEN_MATMULS])
def test_packed_matmul_compiles_for_v5e(one_chip, shape):
    _, M, K, N = shape
    fn = functools.partial(
        ops.dslr_matmul_packed, n_digits=8, per_sample=True, interpret=False
    )
    text = _compile_text(
        lambda x, w, b: fn(x, w, bias=b),
        [((M, K), jnp.float32), ((K, N), jnp.float32), ((N,), jnp.float32)],
        one_chip,
    )
    assert "tpu_custom_call" in text
