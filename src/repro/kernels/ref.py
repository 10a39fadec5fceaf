"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Every digit-plane dot goes through the kernels' own ``plane_dot`` over the
weights' three exact bf16 limbs (``weight_limbs``): each product is exactly
+-w or 0, as the plane is exact in bf16 and w is exactly its three limbs,
and the passes add in the kernels' order.  The two f32 x f32 products that
are not plane dots (the digit-scale contraction and the sLSTM recurrence)
name ``Precision.HIGHEST``: on a TPU the default f32 precision rounds
operands to bf16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import digits as dig
from repro.core import dslr as core_dslr

from .dslr_conv2d import plane_dot, weight_limbs

# for the f32 x f32 products that are not digit-plane dots
F32_PRECISION = jax.lax.Precision.HIGHEST


def dslr_conv2d_planes_ref(
    x: jax.Array,
    w: jax.Array,
    n_digits: int = 8,
    stride: int = 1,
    padding: int = 0,
    recoding: str = "csd",
    digit_budget: int | None = None,
    bias: jax.Array | None = None,
    relu: bool = False,
    per_sample: bool = False,
    packed: bool = False,
) -> jax.Array:
    """Pure-jnp oracle for the digit-plane conv kernel (kernels/dslr_conv2d.py).

    Quantizes + im2cols exactly like the wrapper, then accumulates the digit
    planes in the same MSDF order (scan over d, f32 `acc += 2**-d * plane @ W`)
    so the Pallas kernel must match bit-for-bit in interpret mode.  With
    ``bias``/``relu`` it mirrors the fused epilogue: the quantization scale
    reaches the accumulator before the bias — folded into the digit scales
    (per-tensor) or multiplied per output row (``per_sample``) — then bias
    add + ReLU on the accumulator.

    ``packed=True`` routes the patches through the 2-bit packed interchange
    format exactly like the packed kernel path — pack the image planes,
    im2col the bytes, truncate at nibble granularity, unpack — which must be
    a digit-level no-op (packing is a bijection and the zero digit is the
    zero byte), so the packed oracle equals the unpacked one bit for bit.
    """
    B, H, W, Cin = x.shape
    K = w.shape[0]
    q = core_dslr.quantize_conv_planes(x, n_digits, recoding, per_sample=per_sample)
    budget = digit_budget if digit_budget is not None else q.planes.shape[0]
    if packed:
        bytes_ = core_dslr.im2col_planes(dig.pack_planes(q.planes), K, stride, padding)
        patches = dig.unpack_planes(bytes_[: dig.packed_group_count(budget)], budget)
    else:
        patches = core_dslr.im2col_planes(q.planes, K, stride, padding)[:budget]
    D, _, Ho, Wo, T = patches.shape
    planes = patches.reshape(D, B * Ho * Wo, T)
    w_flat = core_dslr.flatten_conv_weights(w).astype(jnp.float32)
    limbs = weight_limbs(w_flat)
    fused = bias is not None or relu
    scales = core_dslr.digit_scales(D)
    if fused and not per_sample:
        scales = q.scale * scales
    row_scale = None
    if fused and per_sample:
        # mirror the kernel: the per-row sample scale multiplies each plane's
        # digit scale inside the accumulation step (not the accumulator at
        # the end), so the flush epilogue is a pure add on both sides
        row_scale = jnp.repeat(q.scale.astype(jnp.float32), Ho * Wo)[:, None]

    def body(acc, jp):
        s, plane = jp
        if row_scale is not None:
            s = s * row_scale
        return acc + s * plane_dot(plane, limbs), None

    zeros = jnp.zeros((B * Ho * Wo, w_flat.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(body, zeros, (scales, planes))
    if not fused:
        s = q.scale.astype(jnp.float32)
        acc = acc * (jnp.repeat(s, Ho * Wo)[:, None] if per_sample else s)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.reshape(B, Ho, Wo, w_flat.shape[1])


def planes_scan_flat_ref(
    planes: jax.Array,
    w_flat: jax.Array,
    digit_scales: jax.Array,
    n_planes: int,
    packed: bool,
    bias: jax.Array | None = None,
    row_scale: jax.Array | None = None,
    apply_relu: bool = False,
) -> jax.Array:
    """Kernel-shaped jnp oracle over pre-built patch planes: the exact
    computation ``ops.dslr_conv2d_planes_flat`` hands the Pallas kernel —
    ``planes`` (D, M, T) signed digits or (G, M, T) packed bytes, ``w_flat``
    (T, N) stationary weights, the (possibly scale-folded) ``digit_scales``
    and optional per-row ``row_scale``/``bias``/ReLU of the fused epilogue —
    accumulated in the same MSDF order as :func:`dslr_conv2d_planes_ref`'s
    scan.  The serving guardrails' trusted fallback path
    (``ExecutionPolicy.use_ref``): bitwise-coupled to the kernel, so a
    healthy kernel and this oracle agree exactly.  Returns the (M, N)
    accumulator (the wrapper reshapes and, when unfused, scales)."""
    if packed:
        planes = dig.unpack_planes(planes, n_planes)
    limbs = weight_limbs(w_flat)
    rs = None if row_scale is None else row_scale.astype(jnp.float32)[:, None]

    def body(acc, jp):
        s, plane = jp
        if rs is not None:
            s = s * rs
        return acc + s * plane_dot(plane, limbs), None

    zeros = jnp.zeros((planes.shape[1], w_flat.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(
        body, zeros, (digit_scales.astype(jnp.float32), planes)
    )
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if apply_relu:
        acc = jnp.maximum(acc, 0.0)
    return acc


def dslr_matmul_planes_ref(
    planes: jax.Array, w: jax.Array, digit_scales: jax.Array
) -> jax.Array:
    """sum_d scales[d] * (planes[d] @ w) — dense, no skipping."""
    contribs = plane_dot(planes, weight_limbs(w))
    return jnp.tensordot(
        digit_scales.astype(jnp.float32), contribs, axes=1, precision=F32_PRECISION
    )


def dslr_matmul_packed_ref(
    x: jax.Array,
    w: jax.Array,
    n_digits: int = 8,
    recoding: str = "csd",
    digit_budget: int | None = None,
    bias: jax.Array | None = None,
    per_sample: bool = False,
) -> jax.Array:
    """Pure-jnp oracle for ``ops.dslr_matmul_packed`` (the LM projection path).

    Quantizes exactly like the wrapper, routes the planes through the packed
    interchange (pack, truncate at nibble granularity, unpack — a digit-level
    no-op), then accumulates in the same MSDF order with the same scale
    folding (per-tensor: into the digit scales; per-sample: each token row's
    scale multiplies inside the accumulation step), bias after the flush —
    so the Pallas kernel must match bit-for-bit in interpret mode.
    """
    q = core_dslr.quantize_msdf(x, n_digits, recoding, per_sample=per_sample)
    n_planes = q.planes.shape[0]
    budget = digit_budget if digit_budget is not None else n_planes
    planes = dig.unpack_planes(
        dig.pack_planes(q.planes)[: dig.packed_group_count(budget)], budget
    )
    scales = core_dslr.digit_scales(budget)
    row_scale = None
    if per_sample:
        row_scale = q.scale.astype(jnp.float32)[:, None]
    else:
        scales = q.scale * scales
    limbs = weight_limbs(w)

    def body(acc, jp):
        s, plane = jp
        if row_scale is not None:
            s = s * row_scale
        return acc + s * plane_dot(plane, limbs), None

    zeros = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(body, zeros, (scales, planes))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return acc


def msdf_quantize_ref(
    x: jax.Array, scale: jax.Array, frac_bits: int, n_digits: int | None = None
) -> jax.Array:
    """``scale``: scalar, or (M,) per-row (one quantization grid per row)."""
    if n_digits is None:
        n_digits = frac_bits + 1
    # multiply by the reciprocal exactly like the kernel does, so round-half
    # ties fall identically
    inv = 1.0 / scale
    if jnp.ndim(inv) == 1:
        inv = inv[:, None]
    xi = dig.quantize(x * inv, frac_bits)
    d = dig.sd_from_fixed(xi, frac_bits, frac_bits)  # (..., frac_bits + 1)
    return jnp.moveaxis(d[..., :n_digits], -1, 0)


def online_sop_exact_ref(
    x_fixed: jax.Array, y_digits: jax.Array, frac_bits: int
) -> jax.Array:
    xv = x_fixed.astype(jnp.float32) * 2.0**-frac_bits
    yv = dig.digits_to_float(y_digits, jnp.float32)
    return jnp.sum(xv * yv, axis=-1)


def slstm_sweep_ref(wx: jax.Array, r_w: jax.Array, n_heads: int):
    """Pure-jnp oracle for the weight-stationary sLSTM sweep kernel."""
    B, S, d4 = wx.shape
    d = d4 // 4
    Dh = d // n_heads
    zeros = jnp.zeros((B, d), jnp.float32)
    state0 = (zeros, zeros, zeros, jnp.full((B, d), -30.0, jnp.float32))

    def step(state, g_in):
        c, n, h, m = state
        rec = jnp.einsum(
            "bhd,hde->bhe",
            h.reshape(B, n_heads, Dh),
            r_w.astype(jnp.float32),
            precision=F32_PRECISION,
        ).reshape(B, 4 * d)
        g = g_in.astype(jnp.float32) + rec
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        m_new = jnp.maximum(gf + m, gi)
        ie = jnp.exp(gi - m_new)
        fe = jnp.exp(gf + m - m_new)
        c_new = fe * c + ie * jnp.tanh(gz)
        n_new = fe * n + ie
        h_new = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1.0)
        return (c_new, n_new, h_new, m_new), h_new

    fin, hs = jax.lax.scan(step, state0, jnp.moveaxis(wx, 1, 0))
    return jnp.moveaxis(hs, 0, 1), fin
