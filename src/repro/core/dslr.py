"""TPU-native adaptation of DSLR arithmetic: MSDF digit-plane matmul.

The ASIC streams one digit per clock into serial-parallel multipliers.  The
TPU has no serial datapath, but the *insight* — most-significant-digit-first
evaluation with weights stationary, enabling early (anytime) results and
runtime precision scaling — maps onto the MXU as follows:

    x (quantized to n SD digits)  ->  planes[j] in {-1,0,1},  j = 0..n (MSDF)
    y = scale * sum_j 2**-j * (planes[j] @ W)

Evaluated MSDF, the partial sum after k planes is a bounded-error k-MSB
approximation of the exact product — the online-arithmetic property in
tensor form.  ``dslr_matmul`` exposes:

  * ``n_digits``      — static digit budget (the paper's P_i),
  * ``digit_planes``  — MSDF accumulation order (anytime semantics),
  * error bounds per digit count (``anytime_error_bound``),
  * CSD recoding (~1/3 non-zero digits) whose plane-level sparsity the
    Pallas kernel (kernels/dslr_matmul.py) exploits by skipping all-zero
    tiles, mirroring the paper's signal-activity argument.

This module is the pure-jnp reference implementation; the Pallas kernel in
``kernels/`` is the performance path and is validated against this.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import digits as dig


class DslrQuant(NamedTuple):
    planes: jax.Array  # (D+1, *x.shape) int8, MSDF
    scale: jax.Array  # scalar


def quantize_msdf(
    x: jax.Array, n_digits: int = 8, recoding: str = "csd", per_sample: bool = False
) -> DslrQuant:
    """Digit-plane quantization.  ``per_sample=True`` gives every row of
    axis 0 its own scale (``scale`` shape ``(B,)``) so batchmates cannot
    couple through a shared amax — see ``digits.to_planes``."""
    planes, scale = dig.to_planes(
        x, frac_bits=n_digits, n_digits=n_digits, recoding=recoding,
        per_sample=per_sample,
    )
    return DslrQuant(planes, scale)


@functools.partial(
    jax.jit, static_argnames=("n_digits", "recoding", "keep_partials", "per_sample")
)
def dslr_matmul(
    x: jax.Array,
    w: jax.Array,
    n_digits: int = 8,
    recoding: str = "csd",
    keep_partials: bool = False,
    per_sample: bool = False,
) -> jax.Array:
    """MSDF digit-plane matmul: ``x @ w`` with activations digit-serialized.

    x: (..., K) float; w: (K, N) float (stationary, bit-parallel — exactly
    the paper's weight-stationary LR-SPM operand roles).

    Returns (..., N) float32, or (D+1, ..., N) MSDF partials if
    ``keep_partials`` (partial k includes planes 0..k — the anytime series).

    ``per_sample=True`` mirrors the conv path's request-level contract for
    the scan-serial mode: axis 0 of ``x`` (which must then be >= 2-D) is a
    batch of independent samples, each quantized against its own amax.  Row
    i's digits — and therefore its output — depend on row i alone, so an
    outlier batchmate or zero-padding row cannot perturb it (bitwise).
    """
    if per_sample and x.ndim < 2:
        raise ValueError("per_sample needs a batch axis (x.ndim >= 2)")
    q = quantize_msdf(x, n_digits, recoding, per_sample=per_sample)
    wf = w.astype(jnp.float32)

    def body(acc, jk):
        j, plane = jk
        contrib = jnp.tensordot(plane.astype(jnp.float32), wf, axes=1)
        acc = acc + contrib * jnp.exp2(-j.astype(jnp.float32))
        return acc, acc if keep_partials else None

    zeros = jnp.zeros(x.shape[:-1] + (w.shape[-1],), jnp.float32)
    js = jnp.arange(q.planes.shape[0])
    acc, partials = jax.lax.scan(body, zeros, (js, q.planes))
    # per-sample: scale is (B,), broadcast over each sample's trailing axes
    # (the multiply is elementwise per row, so batch decoupling is exact)
    s = q.scale
    if keep_partials:
        if per_sample:
            s = s.reshape((1, -1) + (1,) * (partials.ndim - 2))
        return partials * s
    if per_sample:
        s = s.reshape((-1,) + (1,) * (acc.ndim - 1))
    return acc * s


def dslr_matmul_exact_ref(x: jax.Array, w: jax.Array, n_digits: int = 8) -> jax.Array:
    """Oracle: quantize identically, then one dense matmul (must match)."""
    q = quantize_msdf(x, n_digits, "csd")
    xq = dig.planes_to_value(q.planes, q.scale)
    return jnp.tensordot(xq, w.astype(jnp.float32), axes=1)


def digit_scales(n_planes: int) -> jax.Array:
    """MSDF plane weights 2**-j, j = 0..n_planes-1 (slot 0 = integer digit)."""
    return jnp.exp2(-jnp.arange(n_planes, dtype=jnp.float32))


def anytime_error_bound(w: jax.Array, scale: jax.Array, digits_used: int) -> jax.Array:
    """|exact - partial_k| <= scale * 2**-(k) * max_row ||W||_1  (SD tail
    mass sum_{j>k} 2**-j < 2**-k; worst case every tail digit is +/-1)."""
    row_l1 = jnp.max(jnp.sum(jnp.abs(w.astype(jnp.float32)), axis=0))
    return scale * (2.0 ** -(digits_used)) * row_l1 * 2.0


def pipeline_mid_scale(
    w_flat: jax.Array,
    bias: jax.Array | None,
    scale: jax.Array,
    frac_bits: int,
) -> jax.Array:
    """Analytic a-priori quantization grid for a pipelined conv→conv
    interchange (the digit-streaming executor's mid scale).

    The serial path quantizes a layer's f32 output against its *observed*
    amax — unavailable when the output is emitted digit-by-digit inside the
    kernel.  Instead the pipeline uses the worst-case output magnitude,
    known before the launch from the producer's weights and input grid:

        |out| <= max_c ||W_{.,c}||_1 * scale_in + max|bias|

    inflated by ``(1 + 2**-f)`` like every grid in ``digits.to_planes`` so
    the quantizer never clips.  A sound upper bound on the observed scale
    (the grid is coarser, never finer — the planner's ``recode_bound``
    prices the difference); budget-independent, which is what keeps the
    adaptive cascade's prefix-vs-full comparison on one grid
    (`repro.adaptive`).  ``scale`` may be per-sample ``(B,)``.
    """
    row_l1 = jnp.max(jnp.sum(jnp.abs(w_flat.astype(jnp.float32)), axis=0))
    bmax = 0.0 if bias is None else jnp.max(jnp.abs(bias.astype(jnp.float32)))
    return (row_l1 * scale + bmax) * (1.0 + 2.0**-frac_bits)


@functools.partial(jax.jit, static_argnames=("n_digits", "recoding"))
def dslr_linear(
    x: jax.Array, w: jax.Array, b: jax.Array | None = None,
    n_digits: int = 8, recoding: str = "csd",
) -> jax.Array:
    """Drop-in linear layer in DSLR execution mode (scan-serial reference;
    the production LM projection path is ``repro.lm`` over the packed Pallas
    kernel)."""
    y = dslr_matmul(x, w, n_digits=n_digits, recoding=recoding)
    if b is not None:
        y = y + b
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# convolution digit planes (the DSLR-CNN workload proper)
# ---------------------------------------------------------------------------


def quantize_conv_planes(
    x: jax.Array, n_digits: int = 8, recoding: str = "csd", per_sample: bool = False
) -> DslrQuant:
    """CSD digit-plane quantization of a conv activation map.

    ``x``: (B, H, W, Cin) float.  Returns ``DslrQuant`` with planes of shape
    (D+1, B, H, W, Cin) int8 in MSDF order — plane j is what every PE's
    serial activation wire carries at digit cycle j, for the *whole* feature
    map at once.  Identical digit frame to ``quantize_msdf`` (shared scale),
    so partial-plane sums inherit the anytime property.

    ``per_sample=True`` quantizes each batch row against its own amax
    (``scale`` shape ``(B,)``): one outlier image no longer coarsens every
    batchmate's digit grid, and an all-zero padding row quantizes to exactly
    zero planes — request-level serving composes batches from independent
    requests, so this is its default.
    """
    return quantize_msdf(x, n_digits, recoding, per_sample=per_sample)


def im2col_planes(
    planes: jax.Array,
    kernel_size: int,
    stride: int = 1,
    padding: int = 0,
) -> jax.Array:
    """Per-digit-plane im2col patch extraction.

    ``planes``: (D, B, H, W, Cin) int8 digit planes of the activation.
    Returns (D, B, Ho, Wo, K*K*Cin) int8 — digit planes of the im2col
    patches.  Exact because patch extraction is a gather and the implicit
    padding is zero, so it commutes with the signed-digit decomposition:
    im2col(planes(x)) == planes(im2col(x)) digit for digit.

    Feature order of the last axis is Cin-major (Cin, K, K) flattened — the
    XLA ``conv_general_dilated_patches`` convention; weights must be
    transposed to match (see ``flatten_conv_weights``).
    """
    D, B, H, W, C = planes.shape
    K = kernel_size
    Ho = (H + 2 * padding - K) // stride + 1
    Wo = (W + 2 * padding - K) // stride + 1
    p = jnp.pad(planes, ((0, 0), (0, 0), (padding, padding), (padding, padding), (0, 0)))
    # one strided window slice per filter tap, stacked tap-minor so the
    # flattened feature index is c*K*K + i*K + j.  A pure int8 gather: the
    # equivalent identity-filter f32 convolution made the TPU compile of
    # ResNet-18's conv1 wrapper take two minutes instead of six seconds
    taps = [
        p[:, :, i : i + stride * (Ho - 1) + 1 : stride, j : j + stride * (Wo - 1) + 1 : stride]
        for i in range(K)
        for j in range(K)
    ]
    return jnp.stack(taps, axis=-1).reshape(D, B, Ho, Wo, C * K * K)


def flatten_conv_weights(w: jax.Array) -> jax.Array:
    """(K, K, Cin, Cout) -> (K*K*Cin, Cout) in the im2col feature order
    (Cin-major, matching ``im2col_planes``)."""
    K, K2, Cin, Cout = w.shape
    assert K == K2, w.shape
    return jnp.transpose(w, (2, 0, 1, 3)).reshape(K * K * Cin, Cout)


def expected_digit_activity(x: jax.Array, n_digits: int = 8, recoding: str = "csd") -> jax.Array:
    """Fraction of non-zero digit-plane entries — drives the energy model and
    the kernel's zero-tile skipping."""
    q = quantize_msdf(x, n_digits, recoding)
    return dig.nonzero_digit_fraction(q.planes)
