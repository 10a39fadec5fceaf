"""Signed-digit (SD) fixed-point machinery for left-to-right (online) arithmetic.

The paper (DSLR-CNN, arXiv:2501.01737) computes with radix-2 signed digits
drawn from {-1, 0, 1} in most-significant-digit-first (MSDF) order.  This
module provides exact, integer-domain conversions between ordinary
fixed-point values and MSDF digit vectors, plus the tensor-level
"digit-plane" decomposition used by the TPU adaptation (a digit *plane* is
the whole tensor's j-th digit, so the hardware's serial-in-time dimension
becomes a leading array axis).

Digit frame convention (used consistently across core/ and kernels/):
    a digit vector d[..., 0:n+1] represents  value = sum_j d[..., j] * 2**-j
i.e. slot j carries weight 2**-j, slot 0 is the integer (2**0) digit that the
paper's Eq. (2) writes as ``-y_0``.  Values handled by the online units are
in (-1, 1), so slot 0 is zero for operands but may be non-zero for
intermediate sums (the online adder emits a carry there).

Everything here is exact: values are int32 fixed point with ``frac_bits``
fractional bits and all digit expansions recover the value with zero error.
"""
from __future__ import annotations

import functools
from typing import Literal, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Recoding = Literal["greedy", "csd", "binary"]

# ---------------------------------------------------------------------------
# fixed-point helpers
# ---------------------------------------------------------------------------


def quantize(x: jax.Array, frac_bits: int) -> jax.Array:
    """Quantize real ``x`` in (-1, 1) to int32 fixed point (round-to-nearest).

    Values outside (-1, 1) are clipped to +/-(1 - 2**-frac_bits); the online
    operators require operands strictly inside the unit interval.
    """
    scale = float(2**frac_bits)
    lim = 2**frac_bits - 1
    xi = jnp.clip(jnp.round(x * scale), -lim, lim)
    return xi.astype(jnp.int32)


def dequantize(xi: jax.Array, frac_bits: int) -> jax.Array:
    return xi.astype(jnp.float32) * float(2.0 ** (-frac_bits))


# ---------------------------------------------------------------------------
# MSDF signed-digit expansions (exact, integer domain)
# ---------------------------------------------------------------------------


def sd_from_fixed(xi: jax.Array, frac_bits: int, n_digits: int | None = None) -> jax.Array:
    """Greedy MSDF signed-digit expansion of fixed-point ``xi``.

    Returns int8 digits of shape ``xi.shape + (n_digits + 1,)`` in the
    standard frame (slot 0 = weight 2**0, always zero here since |x| < 1).
    Exact whenever ``n_digits >= frac_bits``.

    The greedy rule at weight 2**-j keeps the running remainder W bounded by
    the remaining representable mass:  emit +1 when 2*W >= 2**(f-j), -1 when
    2*W <= -2**(f-j), else 0, then subtract.  (Proof of exactness: |W| halves
    its bound every step and the final step clears it -- see tests.)
    """
    if n_digits is None:
        n_digits = frac_bits
    if n_digits < frac_bits:
        raise ValueError(f"n_digits={n_digits} < frac_bits={frac_bits} would truncate")
    w = xi.astype(jnp.int32)
    digits = [jnp.zeros_like(w, dtype=jnp.int8)]  # slot 0 (weight 2**0)
    for j in range(1, n_digits + 1):
        weight = 1 << max(frac_bits - j, 0)
        if j <= frac_bits:
            two_w = 2 * w
            d = jnp.where(two_w >= weight, 1, jnp.where(two_w <= -weight, -1, 0)).astype(jnp.int8)
            w = w - d.astype(jnp.int32) * weight
        else:  # exhausted precision: remaining digits are zero
            d = jnp.zeros_like(w, dtype=jnp.int8)
        digits.append(d)
    return jnp.stack(digits, axis=-1)


def csd_from_fixed(xi: jax.Array, frac_bits: int, n_digits: int | None = None) -> jax.Array:
    """Canonical signed-digit (NAF) expansion: minimal number of non-zeros.

    Non-adjacent form guarantees no two consecutive non-zero digits, giving
    an expected non-zero density of ~1/3 -- this is the digit-sparsity the
    cycle/energy model and the plane-skipping kernel exploit.

    NAF of a value in (-1,1) can spill one position into weight 2**0
    (e.g. 0.75 = 1 - 0.25), which is why the frame has slot 0.
    """
    if n_digits is None:
        n_digits = frac_bits
    if n_digits < frac_bits:
        raise ValueError(f"n_digits={n_digits} < frac_bits={frac_bits} would truncate")
    v = xi.astype(jnp.int32)
    lsb_digits = []
    # classic LSB-first NAF: d = 2 - (v mod 4) if v odd else 0; v = (v - d) / 2
    for _ in range(frac_bits + 1):
        odd = (v & 1) != 0
        vmod4 = v & 3
        d = jnp.where(odd, jnp.where(vmod4 == 1, 1, -1), 0).astype(jnp.int8)
        v = (v - d.astype(jnp.int32)) >> 1
        lsb_digits.append(d)
    # lsb_digits[i] has weight 2**(i - frac_bits); map into frame slot j = frac_bits - i
    out = [jnp.zeros_like(xi, dtype=jnp.int8)] * (n_digits + 1)
    for i, d in enumerate(lsb_digits):
        j = frac_bits - i
        if 0 <= j <= n_digits:
            out[j] = d
    return jnp.stack(out, axis=-1)


def binary_from_fixed(xi: jax.Array, frac_bits: int, n_digits: int | None = None) -> jax.Array:
    """Two's-complement digit planes (the *conventional bit-serial baseline*).

    value = -b_0 + sum_{j>=1} b_j 2**-j with b in {0,1}; we store b_0's
    contribution as a digit in {0,-1} so the same frame/evaluator applies.
    """
    if n_digits is None:
        n_digits = frac_bits
    if n_digits < frac_bits:
        raise ValueError(f"n_digits={n_digits} < frac_bits={frac_bits} would truncate")
    # two's complement over frac_bits+1 bits
    mod = 1 << (frac_bits + 1)
    u = jnp.where(xi < 0, xi + mod, xi).astype(jnp.int32)
    out = []
    for j in range(n_digits + 1):
        if j > frac_bits:
            out.append(jnp.zeros_like(xi, dtype=jnp.int8))
            continue
        bit = (u >> (frac_bits - j)) & 1
        if j == 0:
            out.append((-bit).astype(jnp.int8))  # sign bit has weight -2**0
        else:
            out.append(bit.astype(jnp.int8))
    return jnp.stack(out, axis=-1)


_RECODERS = {"greedy": sd_from_fixed, "csd": csd_from_fixed, "binary": binary_from_fixed}


def digits_to_fixed(d: jax.Array, frac_bits: int) -> jax.Array:
    """Exact inverse: digit frame -> int fixed point."""
    n = d.shape[-1] - 1
    weights = np.array([2.0**frac_bits * 2.0**-j for j in range(n + 1)])
    if np.any(weights != np.round(weights)):
        # digits below 2**-frac_bits: scale everything up so it stays exact
        raise ValueError("digit frame extends below frac_bits; use digits_to_float")
    w = jnp.asarray(weights.astype(np.int32))
    return jnp.sum(d.astype(jnp.int32) * w, axis=-1)


def digits_to_float(d: jax.Array, dtype=jnp.float32) -> jax.Array:
    n = d.shape[-1] - 1
    w = jnp.asarray([2.0**-j for j in range(n + 1)], dtype=dtype)
    return jnp.sum(d.astype(dtype) * w, axis=-1)


# ---------------------------------------------------------------------------
# tensor-level digit planes (TPU adaptation: serial-in-time -> leading axis)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("frac_bits", "n_digits", "recoding", "per_sample")
)
def to_planes(
    x: jax.Array,
    frac_bits: int,
    n_digits: int | None = None,
    recoding: Recoding = "greedy",
    per_sample: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Decompose a real tensor into MSDF digit planes.

    Returns ``(planes, scale)`` with ``planes`` int8 of shape
    ``(n_digits + 1,) + x.shape`` (axis 0 is MSDF digit index, slot 0 =
    weight 2**0) and ``scale`` such that

        x ~= scale * sum_j planes[j] * 2**-j        (exact after quantize)

    ``per_sample=False`` (default) uses one per-tensor scale (scalar amax).
    ``per_sample=True`` treats axis 0 of ``x`` as a batch of independent
    samples and computes one scale per row (``scale`` has shape
    ``(x.shape[0],)``): sample i's digits depend only on sample i, so an
    outlier batchmate cannot degrade anyone else's digit resolution and
    zero-padded rows are exactly zero planes — the decoupling the serving
    path needs.

    This is the bridge from the paper's digit-serial streams to whole-tensor
    MXU work: plane j is what every PE's serial input wire carries at cycle j.
    """
    if n_digits is None:
        n_digits = frac_bits
    if per_sample:
        axes = tuple(range(1, x.ndim))
        amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axes), 1e-30)  # (B,)
        scale = amax * (1.0 + 2.0**-frac_bits)
        xi = quantize(x / scale.reshape((-1,) + (1,) * (x.ndim - 1)), frac_bits)
    else:
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = amax * (1.0 + 2.0**-frac_bits)  # keep strictly inside (-1, 1)
        xi = quantize(x / scale, frac_bits)
    d = _RECODERS[recoding](xi, frac_bits, n_digits)
    return jnp.moveaxis(d, -1, 0), scale.astype(x.dtype)


def planes_to_value(planes: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Inverse of ``to_planes``.  ``scale`` is the per-tensor scalar or the
    per-sample ``(B,)`` vector (broadcast over the sample's trailing axes)."""
    n = planes.shape[0] - 1
    w = jnp.asarray([2.0**-j for j in range(n + 1)], dtype=dtype)
    val = jnp.tensordot(w, planes.astype(dtype), axes=1)
    s = scale.astype(dtype)
    if s.ndim:
        s = s.reshape(s.shape + (1,) * (val.ndim - s.ndim))
    return val * s


def nonzero_digit_fraction(planes: jax.Array) -> jax.Array:
    """Fraction of non-zero digits — the activity factor the paper's energy
    argument rests on (CSD -> ~1/3)."""
    return jnp.mean((planes != 0).astype(jnp.float32))


# ---------------------------------------------------------------------------
# packed digit planes (2-bit signed digits, 4 MSDF digits per int8 byte)
# ---------------------------------------------------------------------------
#
# A digit in {-1, 0, 1} carries 2 bits of information; storing it in a whole
# int8 wastes 4x the HBM traffic the conv path's dominant operand (the im2col
# patch planes) pays.  The packed interchange format keeps the digit stream
# narrow across the HBM boundary — the TPU image of L2R-CIPU/DSLOT-NN keeping
# serial digit wires narrow between units — and only widens inside VMEM:
#
#     byte b of packed[g] holds digits 4g .. 4g+3 (MSDF order), digit j in
#     bits 2*(j%4) .. 2*(j%4)+1 as its 2-bit two's complement
#     (0 -> 0b00, +1 -> 0b01, -1 -> 0b11; 0b10 never occurs).
#
# Properties the pipeline relies on:
#   * the zero digit encodes as 0b00, so an all-zero byte is the zero digit
#     group — zero padding (im2col halos, tile padding) commutes with packing
#     byte-for-byte, and ``packed == 0`` witnesses a dead digit group;
#   * packing is a bijection on digit tensors (unpack . pack == id), so every
#     numerical statement about planes applies verbatim to packed planes;
#   * the digit axis packs leading-major: truncating to a digit budget k is
#     the leading-axis slice ``packed[: (k + 3) // 4]`` (nibble granularity) —
#     residual digits in the last byte are simply never unpacked.

PACK_DIGITS_PER_BYTE = 4


def packed_group_count(n_digits: int) -> int:
    """Number of int8 bytes per element for ``n_digits`` packed digits."""
    return -(-n_digits // PACK_DIGITS_PER_BYTE)


def pack_planes(planes: jax.Array) -> jax.Array:
    """Pack signed-digit planes (D, ...) int8 in {-1, 0, 1} into
    (ceil(D/4), ...) int8 bytes, 4 MSDF digits per byte (digit-axis packing).

    The tail group of a D not divisible by 4 is padded with zero digits, so
    ``pack_planes(planes[:k])`` and ``pack_planes(planes)[: ceil(k/4)]``
    agree on every digit < k (see ``unpack_planes``).
    """
    D = planes.shape[0]
    G = packed_group_count(D)
    if D != 4 * G:
        planes = jnp.concatenate(
            [planes, jnp.zeros((4 * G - D,) + planes.shape[1:], planes.dtype)]
        )
    codes = (planes.astype(jnp.int32) & 3).reshape((G, 4) + planes.shape[1:])
    val = (
        codes[:, 0]
        | (codes[:, 1] << 2)
        | (codes[:, 2] << 4)
        | (codes[:, 3] << 6)
    )
    # bytes >= 128 are negative int8; wrap explicitly (portable, no bitcast)
    return jnp.where(val >= 128, val - 256, val).astype(jnp.int8)


def unpack_planes(packed: jax.Array, n_digits: int) -> jax.Array:
    """Exact inverse of ``pack_planes``: (G, ...) int8 bytes -> (n_digits, ...)
    int8 digits in {-1, 0, 1}.  ``n_digits`` may be any count <= 4*G —
    residual bits of the last byte beyond ``n_digits`` are ignored, which is
    what makes digit-budget truncation commute with packing."""
    G = packed.shape[0]
    if not 1 <= n_digits <= 4 * G:
        raise ValueError(f"n_digits={n_digits} outside [1, {4 * G}]")
    j = np.arange(n_digits)
    grp = jnp.asarray(j // 4)
    shift = jnp.asarray(2 * (j % 4)).reshape((-1,) + (1,) * (packed.ndim - 1))
    v = (packed[grp].astype(jnp.int32) >> shift) & 3
    return (v - ((v & 2) << 1)).astype(jnp.int8)  # 2-bit sign extension


def packed_plane_activity(packed: jax.Array, n_digits: int, tile_rows: int) -> jax.Array:
    """Per-(row tile, digit) nonzero-activity bitmap of a packed plane matrix.

    ``packed``: (G, M, T) packed digit planes with M divisible by
    ``tile_rows``.  Returns (M // tile_rows, n_digits) int32, entry 1 iff
    digit plane d of row tile m has any non-zero digit — exactly the
    ``jnp.any(plane != 0)`` predicate of the zero-plane-skipping kernel,
    hoisted out of the kernel so a dead (tile, digit) is known *before* its
    bytes would be DMA'd into VMEM.

    One bitwise-OR reduction over each byte group's row tile reads the
    packed operand once and writes G bytes a tile; the bitmap is then split
    out of those bytes.  Exact because the zero digit is the field 0b00 and
    both nonzero digits (0b01, 0b11) are nonzero fields: field j % 4 of the
    OR of a tile's bytes is nonzero iff digit j is nonzero somewhere in the
    tile.  Nothing is indexed by digit, so no (n_digits, M, T) copy of the
    operand is ever materialised.
    """
    _, M, T = packed.shape
    if M % tile_rows:
        raise ValueError(f"M={M} not a multiple of tile_rows={tile_rows}")
    G = packed_group_count(n_digits)
    if not 1 <= G <= packed.shape[0]:
        raise ValueError(f"n_digits={n_digits} outside [1, {4 * packed.shape[0]}]")
    Mt = M // tile_rows
    # the barrier keeps XLA from pushing the reduction back through the ops
    # that built ``packed`` (an im2col's slices of the image), where it
    # reduces each slice over its channels, several times slower than one
    # read of the matrix the kernel reads anyway
    tiles = jax.lax.optimization_barrier(packed[:G]).reshape(G, Mt, tile_rows, T)
    orr = jax.lax.reduce(tiles, jnp.int8(0), jax.lax.bitwise_or, (2, 3))  # (G, Mt)
    # widen before shifting: a -1 in field 3 makes the byte negative, and
    # & 3 drops the sign-extension bits
    shift = 2 * jnp.arange(PACK_DIGITS_PER_BYTE, dtype=jnp.int32)
    fields = (orr.astype(jnp.int32)[..., None] >> shift) & 3  # (G, Mt, 4)
    live = fields.transpose(1, 0, 2).reshape(Mt, PACK_DIGITS_PER_BYTE * G)
    return (live[:, :n_digits] != 0).astype(jnp.int32)
