"""Pallas TPU kernel: MSDF digit-plane convolution — DSLR-CNN's workload on the MXU.

The paper's accelerator computes conv layers as digit-serial sums of products:
weights sit bit-parallel in the PEs while activation digits stream MSDF
through LR-SPMs and an online adder tree (Fig. 5).  The TPU-native analogue
lowers the convolution to an im2col digit-plane matmul:

    patches(x) quantized to D MSDF planes  ->  planes[d] in {-1,0,1}
    y[m, n] = scale * sum_d 2**-d * (planes[d][m, :] @ W_flat[:, n])

with the (m, n, d) grid of ``dslr_matmul`` reused: d is the innermost grid
axis so the f32 accumulator for an (m, n) output tile lives in VMEM across
all digits and never round-trips to HBM — the memory-system image of the
paper's digit-level pipelining (partial products never leave the PE).

Two interchange formats feed the kernel:

  * **unpacked** (``dslr_conv2d_planes_mxu``): one int8 per digit — simple,
    but the dominant operand (the im2col patch planes) pays 8 bits of HBM
    traffic for 2 bits of information, and the zero-plane skip must DMA a
    tile in to discover it was dead;
  * **packed** (``dslr_conv2d_planes_packed_mxu``): 4 MSDF digits per int8
    byte (core/digits.pack_planes), ~4x less HBM traffic on the dominant
    operand.  The BlockSpec carries packed bytes into VMEM; the kernel
    widens the current digit with shift/mask VPU ops right before the MXU
    dot.  A scalar-prefetched per-(tile, digit) activity bitmap replaces the
    in-kernel ``jnp.any(plane != 0)``: the *index map* consults it, so a
    dead digit group issues **no tile load at all** (the grid-revisiting
    rule: an unchanged block index between consecutive steps is not
    re-fetched), and the kernel skips the MXU pass without ever touching
    the bytes.  Both variants are bitwise identical — packing is a
    bijection and the f32 accumulation sequence is unchanged.

Conv-specific features on top of the matmul kernel:
  * the contraction axis is the im2col window T = K*K*Cin, kept whole inside
    the block (single-pass accumulation over the receptive field, like the
    PE's adder tree over the window);
  * M = B*Ho*Wo output pixels is padded internally to the tile size with
    zero digit rows (they contribute exactly 0 and are sliced off), so any
    image/stride geometry is accepted;
  * the MSDF digit budget is the leading ``planes`` extent: truncating it is
    the paper's runtime precision scaling — fewer planes, proportionally
    fewer MXU passes, 2**-k bounded output error (anytime inference); on the
    packed path the truncation is a nibble-granularity leading-axis slice;
  * the stationary weight tile's index map depends only on the n grid axis,
    so it is never re-fetched across the digit axis (asserted by the traffic
    model in kernels/traffic.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import digits as dig
from repro.core import dslr as core_dslr

from . import tuning

# The digit scales enter whole, in SMEM, and the kernel reads the current
# plane's weight as a scalar.  A per-step (1, 1) VMEM block of the (D,) array
# would be refused by Mosaic: a block's second-to-last dimension must be a
# multiple of 8 or the whole array's.
SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)

# Every digit-plane dot runs as three bf16 MXU passes.  A plane holds only -1,
# 0 and +1, exact in bf16, and an f32 weight is exactly the sum of three bf16
# limbs, so every plane x limb product is exact and the f32 accumulation sees
# the same values as an f32 x f32 dot at Precision.HIGHEST, whose six passes
# would spend three on the plane's zero mid and lo limbs.  The split is exact
# for |w| >= about 2**-102; below that the TPU flushes the lo limb's
# denormals, as HIGHEST's own split does.  kernels/ref.py shares both helpers,
# which keeps the oracle bitwise-coupled to the kernels.
_BF16_HEAD = -(1 << 16)  # int32 0xFFFF0000: sign, exponent, 7 fraction bits


def _bf16_head(x: jax.Array) -> jax.Array:
    """f32 ``x`` with the low 16 bits of its pattern cleared: a bf16 value
    held in f32, so the cast to bf16 that follows is exact.  Truncating by
    bits leaves no f32 -> bf16 -> f32 round trip for a compiler to fold."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & _BF16_HEAD, jnp.float32)


def weight_limbs(w: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The three bf16 limbs (hi, mid, lo) of f32 ``w``, each of the shape of
    ``w``: ``f32(hi) + f32(mid) + f32(lo) == w`` exactly.  hi keeps w's top 8
    significand bits, mid the next 8, lo the last 8; each subtraction is
    exact (the subtrahend is a truncation of the minuend)."""
    w = w.astype(jnp.float32)
    hi = _bf16_head(w)
    rest = w - hi
    mid = _bf16_head(rest)
    lo = rest - mid
    return tuple(limb.astype(jnp.bfloat16) for limb in (hi, mid, lo))


def plane_dot(plane: jax.Array, limbs) -> jax.Array:
    """``plane @ w`` in f32 as three bf16 MXU passes over ``w``'s limbs.

    ``plane``: (..., T) digits in {-1, 0, +1} of any integer or float dtype;
    ``limbs``: the three (T, N) bf16 limbs of ``weight_limbs``, as a tuple or
    a (3, T, N) ref.  The kernels and the oracle add the passes in this one
    order, ``(hi + mid) + lo``."""
    p = plane.astype(jnp.bfloat16)
    dims = (((p.ndim - 1,), (0,)), ((), ()))

    def one_pass(i):
        return jax.lax.dot_general(p, limbs[i], dims, preferred_element_type=jnp.float32)

    return (one_pass(0) + one_pass(1)) + one_pass(2)


def unpack_digit(packed: jax.Array, d) -> jax.Array:
    """Digit ``d`` of a packed int8 block as int32 in {-1, 0, +1}: shift and
    mask its 2-bit field on the VPU, then the sign extension pack_planes
    inverts — bit-for-bit the unpacked kernel's plane."""
    v = (packed.astype(jnp.int32) >> (2 * (d % 4))) & 3
    return v - ((v & 2) << 1)


def plane_dot_scratch(bm: int, bn: int, T: int) -> list:
    """VMEM scratch of a digit-plane kernel: the (bm, bn) f32 accumulator and
    the stationary weight tile's (3, T, bn) bf16 limbs."""
    return [pltpu.VMEM((bm, bn), jnp.float32), pltpu.VMEM((3, T, bn), jnp.bfloat16)]


def store_weight_limbs(w_ref, limbs_ref):
    """Write the stationary weight tile's limbs into the (3, T, bn) bf16
    scratch, once per (m, n) output tile."""
    for i, limb in enumerate(weight_limbs(w_ref[...])):
        limbs_ref[i] = limb


def _epilogue(acc, bias_ref, apply_relu: bool):
    """Fused flush epilogue: bias add + ReLU ride the last digit step, so a
    conv+activation layer is one kernel launch and the pre-activation tile
    never round-trips to HBM."""
    res = acc
    if bias_ref is not None:
        res = res + bias_ref[0]
    if apply_relu:
        res = jnp.maximum(res, 0.0)
    return res


def _emit_packed_planes(res, inv_ref, out_ref, frac_bits: int, n_digits: int):
    """Digit-emitting flush epilogue (``emit_planes=True``): quantize the
    finished accumulator tile onto the grid ``1/inv`` and write its packed
    2-bit MSDF planes instead of f32 — the next conv layer's input is born
    in the interchange format and the f32 activation never exists in HBM.

    The math line-for-line mirrors ``msdf_quantize._quantize_packed_kernel``
    (same reciprocal multiply, same round/clip, same greedy recurrence and
    byte layout), so the emitted planes are bitwise identical to routing the
    f32 output through ``ops.msdf_quantize(..., packed=True)`` on the same
    grid — the property tests/test_pipeline_diff.py pins."""
    scaled = res * inv_ref[...] * float(2**frac_bits)
    lim = float(2**frac_bits - 1)
    w = jnp.clip(jnp.round(scaled), -lim, lim).astype(jnp.int32)
    for g in range(dig.packed_group_count(n_digits)):
        byte = jnp.zeros_like(w)
        for s in range(4):
            j = 4 * g + s
            # slot 0 and out-of-budget digits encode as 0b00
            if j == 0 or j >= n_digits:
                continue
            weight = 1 << (frac_bits - j)
            two_w = 2 * w
            dgt = jnp.where(two_w >= weight, 1, jnp.where(two_w <= -weight, -1, 0))
            w = w - dgt * weight
            byte = byte | ((dgt & 3) << (2 * s))
        out_ref[g] = jnp.where(byte >= 128, byte - 256, byte).astype(jnp.int8)


def _dslr_conv2d_kernel(
    planes_ref,  # (1, bm, T) int8 — digit plane d of the im2col patches
    w_ref,  # (T, bn) f32 — stationary flattened filter tile
    scale_ref,  # SMEM (D,) f32 — digit weights, plane d reads scale_ref[d]
    *refs,  # [row_scale_ref (bm, 1) if has_row_scale,] [bias_ref (1, bn) if
    #        has_bias,] out_ref (bm, bn), acc_ref and limbs_ref scratch
    n_digits: int,
    skip_zero_planes: bool,
    has_row_scale: bool,
    has_bias: bool,
    apply_relu: bool,
):
    row_scale_ref = refs[0] if has_row_scale else None
    bias_ref = refs[1] if (has_row_scale and has_bias) else refs[0] if has_bias else None
    out_ref, acc_ref, limbs_ref = refs[-3], refs[-2], refs[-1]
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        store_weight_limbs(w_ref, limbs_ref)

    plane = planes_ref[0]
    # the activation quantization scale reaches the accumulator inside the
    # per-plane step — folded into ``digit_scales`` (per-tensor: one scalar)
    # or via ``row_scale`` (per-sample: each output row carries its own
    # sample's scale, broadcast (bm, 1) x (bm, bn)) — so the flush step is a
    # pure add/max epilogue in both cases and holds real conv values when
    # the bias lands
    scale = scale_ref[d]
    if has_row_scale:
        scale = scale * row_scale_ref[...]

    def _accumulate():
        acc_ref[...] += scale * plane_dot(plane, limbs_ref)

    if skip_zero_planes:
        # widened first: Mosaic cannot relayout an int8-shaped i1 mask
        live = jnp.any(plane.astype(jnp.int32) != 0)
        jax.lax.cond(live, _accumulate, lambda: None)
    else:
        _accumulate()

    @pl.when(d == n_digits - 1)
    def _flush():
        out_ref[...] = _epilogue(acc_ref[...], bias_ref, apply_relu)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "skip_zero_planes", "apply_relu", "interpret"),
)
def dslr_conv2d_planes_mxu(
    planes: jax.Array,  # (D, M, T) int8 MSDF digit planes of im2col patches
    w_flat: jax.Array,  # (T, N) float — flattened (K*K*Cin, Cout) filters
    digit_scales: jax.Array,  # (D,) f32, typically 2**-arange(D)
    bias: jax.Array | None = None,  # (N,) f32 — fused into the flush step
    row_scale: jax.Array | None = None,  # (M,) f32 — per-row flush scale
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    apply_relu: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Digit-plane patch matmul ``sum_d digit_scales[d] * (planes[d] @ w_flat)``
    with an optional fused ``(+ bias, ReLU)`` epilogue in the flush step.

    Accepts any (M, N); tiles are padded internally with zero rows/columns
    (zero digit rows contribute nothing) and the (M, N) result is sliced
    back out.  MSDF accumulation order (d = 0 first) gives the anytime
    semantics; pass truncated ``planes``/``digit_scales`` for a reduced
    digit budget.  When fusing the epilogue, the activation quantization
    scale must reach the accumulator before the bias: fold a per-tensor
    scalar into ``digit_scales``, or pass per-sample scales as ``row_scale``
    (one value per output row, multiplied in at the flush step).
    """
    D, M, T = planes.shape
    T2, N = w_flat.shape
    assert T == T2, (planes.shape, w_flat.shape)
    bm, bn, Mp, Np = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    if Mp != M:
        planes = jnp.pad(planes, ((0, 0), (0, Mp - M), (0, 0)))
    wf = w_flat.astype(jnp.float32)
    if Np != N:
        wf = jnp.pad(wf, ((0, 0), (0, Np - N)))

    has_row_scale = row_scale is not None
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((1, bm, T), lambda m, n, d: (d, m, 0)),
        pl.BlockSpec((T, bn), lambda m, n, d: (0, n)),
        SMEM_WHOLE,
    ]
    operands = [planes, wf, digit_scales.astype(jnp.float32)]
    if has_row_scale:
        rs = row_scale.astype(jnp.float32).reshape(M, 1)
        if Mp != M:
            rs = jnp.pad(rs, ((0, Mp - M), (0, 0)))
        in_specs.append(pl.BlockSpec((bm, 1), lambda m, n, d: (m, 0)))
        operands.append(rs)
    if has_bias:
        b = bias.astype(jnp.float32).reshape(1, N)
        if Np != N:
            b = jnp.pad(b, ((0, 0), (0, Np - N)))
        in_specs.append(pl.BlockSpec((1, bn), lambda m, n, d: (0, n)))
        operands.append(b)

    out = pl.pallas_call(
        functools.partial(
            _dslr_conv2d_kernel,
            n_digits=D,
            skip_zero_planes=skip_zero_planes,
            has_row_scale=has_row_scale,
            has_bias=has_bias,
            apply_relu=apply_relu,
        ),
        grid=(Mp // bm, Np // bn, D),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, d: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=plane_dot_scratch(bm, bn, T),
        interpret=interpret,
    )(*operands)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# packed variant: 2-bit digits across the HBM boundary, bitmap-driven skip
# ---------------------------------------------------------------------------


def plane_fetch_indices(activity: jax.Array, n_digits: int) -> jax.Array:
    """Byte-group block index the packed plane BlockSpec should have resident
    at each (row tile, digit) grid step.

    ``activity``: (Mt, D) per-(tile, digit) nonzero bitmap
    (``digits.packed_plane_activity``).  Digit d lives in byte group d // 4;
    a group that is dead (all four digits zero) for a tile maps to the *most
    recent live* group instead of its own, so consecutive grid steps keep an
    unchanged block index and Pallas's grid-revisiting rule issues no DMA for
    it.  A dead prefix clamps to group 0 (the first step of a tile always
    loads one block; the kernel's activity guard never reads it).  Shared
    with kernels/traffic.py so the traffic model counts exactly the fetches
    the kernel performs.
    """
    Mt, D = activity.shape
    assert D == n_digits, (activity.shape, n_digits)
    G = dig.packed_group_count(n_digits)
    pad = 4 * G - n_digits
    act = jnp.pad(activity, ((0, 0), (0, pad))) if pad else activity
    group_live = act.reshape(Mt, G, 4).any(axis=2)
    live_idx = jnp.where(group_live, jnp.arange(G)[None, :], -1)
    fetch_g = jax.lax.cummax(live_idx, axis=1)
    fetch = jnp.maximum(fetch_g, 0)[:, jnp.arange(n_digits) // 4]
    return fetch.astype(jnp.int32)


def table_slot(m, d, n_digits: int):
    """Slot of (row tile ``m``, digit ``d``) in a flat scalar-prefetch table."""
    return m * n_digits + d


def plane_tables(activity: jax.Array, n_digits: int) -> tuple[jax.Array, jax.Array]:
    """The packed kernels' two scalar-prefetch tables from an (Mt, D)
    activity bitmap: the bitmap and ``plane_fetch_indices``, each flat
    (Mt * D,) int32 and read at ``table_slot(m, d, D)``.

    Flat because Mosaic pads the last dimension of an SMEM array to 128
    words: an (Mt, D) table would take Mt * 512 bytes whatever D is, and at
    VGG-16's 224x224 convs (Mt = 3136 at bucket 8) the pair would need 3.1
    MiB of the 1 MiB SMEM.  Flat, the pair takes 8 * Mt * D bytes (220 KiB
    there).  Shared with kernels/traffic.py, which replays the same reads.
    """
    act = activity.astype(jnp.int32).reshape(-1)
    return act, plane_fetch_indices(activity, n_digits).reshape(-1)


def packed_plane_tables(
    packed: jax.Array, n_digits: int, tile_rows: int, skip_zero_planes: bool
) -> tuple[jax.Array, jax.Array]:
    """Flat (act, fetch) tables of a packed kernel launch over ``packed``
    (G, M, T) at row tile ``tile_rows`` (``plane_tables``).  Without
    skipping every digit's own group is resident (fetched once per 4 digits
    either way, since consecutive digits share a group) and the kernel never
    reads the bitmap, so none is computed."""
    if skip_zero_planes:
        return plane_tables(dig.packed_plane_activity(packed, n_digits, tile_rows), n_digits)
    Mt = packed.shape[1] // tile_rows
    act = jnp.zeros((Mt * n_digits,), jnp.int32)
    fetch = jnp.tile(jnp.arange(n_digits, dtype=jnp.int32) // 4, Mt)
    return act, fetch


def packed_plane_index_map(n_digits: int):
    """Index map of the packed plane operand's (1, bm, T) block: byte group
    ``fetch[m, d]`` of row tile m, read from the flat tables."""

    def index_map(m, n, d, act, fetch):
        return fetch[table_slot(m, d, n_digits)], m, 0

    return index_map


def _dslr_conv2d_packed_kernel(
    act_ref,  # SMEM (Mt * D,) int32 — per-(tile, digit) nonzero bitmap
    fetch_ref,  # SMEM (Mt * D,) int32 — resident byte group per step (index map)
    packed_ref,  # (1, bm, T) int8 — byte group fetch[m, d] of the patches
    w_ref,  # (T, bn) f32 — stationary flattened filter tile
    scale_ref,  # SMEM (D,) f32 — digit weights, plane d reads scale_ref[d]
    *refs,  # [row_scale_ref,] [bias_ref,] [inv_ref if emit,] out_ref, acc_ref,
    #        limbs_ref
    n_digits: int,
    skip_zero_planes: bool,
    has_row_scale: bool,
    has_bias: bool,
    apply_relu: bool,
    emit: tuple | None = None,  # (frac_bits, n_digits) of the emitted planes
):
    del fetch_ref  # consumed by the index map, not the body
    row_scale_ref = refs[0] if has_row_scale else None
    bias_ref = refs[1] if (has_row_scale and has_bias) else refs[0] if has_bias else None
    inv_ref = refs[-4] if emit is not None else None
    out_ref, acc_ref, limbs_ref = refs[-3], refs[-2], refs[-1]
    m, d = pl.program_id(0), pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        store_weight_limbs(w_ref, limbs_ref)

    scale = scale_ref[d]
    if has_row_scale:
        scale = scale * row_scale_ref[...]

    def _accumulate():
        acc_ref[...] += scale * plane_dot(unpack_digit(packed_ref[0], d), limbs_ref)

    if skip_zero_planes:
        # the SMEM bitmap already knows a dead (tile, digit) — no byte was
        # DMA'd in to find out (cf. the unpacked kernel's jnp.any probe)
        jax.lax.cond(act_ref[table_slot(m, d, n_digits)] != 0, _accumulate, lambda: None)
    else:
        _accumulate()

    @pl.when(d == n_digits - 1)
    def _flush():
        res = _epilogue(acc_ref[...], bias_ref, apply_relu)
        if emit is None:
            out_ref[...] = res
        else:
            _emit_packed_planes(res, inv_ref, out_ref, emit[0], emit[1])


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m",
        "block_n",
        "skip_zero_planes",
        "apply_relu",
        "interpret",
        "emit_planes",
        "emit_frac_bits",
        "emit_n_digits",
    ),
)
def dslr_conv2d_planes_packed_mxu(
    packed: jax.Array,  # (ceil(D/4), M, T) int8 — packed im2col digit planes
    w_flat: jax.Array,  # (T, N) float — flattened (K*K*Cin, Cout) filters
    digit_scales: jax.Array,  # (D,) f32, typically 2**-arange(D)
    bias: jax.Array | None = None,
    row_scale: jax.Array | None = None,
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    apply_relu: bool = False,
    interpret: bool = False,
    emit_planes: bool = False,
    emit_scale: jax.Array | None = None,  # scalar or (M,) — the mid grid
    emit_frac_bits: int = 8,
    emit_n_digits: int | None = None,
) -> jax.Array:
    """Packed-interchange twin of ``dslr_conv2d_planes_mxu`` — same contract,
    bitwise-identical result, ~4x less HBM traffic on the patch operand.

    ``packed`` carries 4 MSDF digits per int8 byte (``digits.pack_planes``
    of the im2col patch planes); the digit budget D is ``len(digit_scales)``
    and ``packed`` must hold exactly ``ceil(D/4)`` byte groups (a digit
    budget truncates the packed operand at nibble granularity — residual
    digits in the last byte are never unpacked).  Zero-plane skipping is
    driven by a scalar-prefetched activity bitmap: dead digits skip the MXU
    pass *and* dead byte groups are never DMA'd into VMEM, because the plane
    index map points them at the already-resident block.

    ``emit_planes=True`` switches the flush epilogue from f32 to the digit
    emitter: the post-bias/ReLU tile is quantized onto the grid
    ``emit_scale`` (scalar, or (M,) per output row) and written as packed
    2-bit MSDF planes — ``(ceil(emit_n_digits/4), M, N) int8`` instead of
    ``(M, N) f32`` — bitwise identical to quantizing the f32 output through
    ``ops.msdf_quantize(..., packed=True)`` on the same grid.  This is the
    producer half of the cross-layer digit pipeline: the fused conv→conv
    chain exchanges these planes directly and the intermediate activation
    never exists as f32 in HBM.
    """
    G, M, T = packed.shape
    D = digit_scales.shape[0]
    T2, N = w_flat.shape
    assert T == T2, (packed.shape, w_flat.shape)
    assert G == dig.packed_group_count(D), (packed.shape, D)
    emit = None
    if emit_planes:
        if emit_scale is None:
            raise ValueError("emit_planes=True requires emit_scale")
        if emit_n_digits is None:
            emit_n_digits = emit_frac_bits + 1
        if emit_n_digits > emit_frac_bits + 1:
            raise ValueError("emit_n_digits must be <= emit_frac_bits + 1")
        emit = (emit_frac_bits, emit_n_digits)
    bm, bn, Mp, Np = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    if Mp != M:
        packed = jnp.pad(packed, ((0, 0), (0, Mp - M), (0, 0)))
    wf = w_flat.astype(jnp.float32)
    if Np != N:
        wf = jnp.pad(wf, ((0, 0), (0, Np - N)))

    activity, fetch = packed_plane_tables(packed, D, bm, skip_zero_planes)

    has_row_scale = row_scale is not None
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((1, bm, T), packed_plane_index_map(D)),
        pl.BlockSpec((T, bn), lambda m, n, d, act, fetch: (0, n)),
        SMEM_WHOLE,
    ]
    operands = [packed, wf, digit_scales.astype(jnp.float32)]
    if has_row_scale:
        rs = row_scale.astype(jnp.float32).reshape(M, 1)
        if Mp != M:
            rs = jnp.pad(rs, ((0, Mp - M), (0, 0)))
        in_specs.append(pl.BlockSpec((bm, 1), lambda m, n, d, act, fetch: (m, 0)))
        operands.append(rs)
    if has_bias:
        b = bias.astype(jnp.float32).reshape(1, N)
        if Np != N:
            b = jnp.pad(b, ((0, 0), (0, Np - N)))
        in_specs.append(pl.BlockSpec((1, bn), lambda m, n, d, act, fetch: (0, n)))
        operands.append(b)
    if emit is not None:
        # same reciprocal multiply as ops.msdf_quantize computes outside its
        # kernel — identical f32 rounding ties, hence bitwise-equal digits
        if jnp.ndim(emit_scale) == 1:
            assert emit_scale.shape[0] == M, (emit_scale.shape, M)
            inv = (1.0 / emit_scale).reshape(M, 1).astype(jnp.float32)
            if Mp != M:  # pad rows carry inv 1 (they are sliced off below)
                inv = jnp.pad(inv, ((0, Mp - M), (0, 0)), constant_values=1.0)
            in_specs.append(pl.BlockSpec((bm, 1), lambda m, n, d, act, fetch: (m, 0)))
        else:
            inv = (1.0 / emit_scale).reshape(1, 1).astype(jnp.float32)
            in_specs.append(pl.BlockSpec((1, 1), lambda m, n, d, act, fetch: (0, 0)))
        operands.append(inv)

    if emit is None:
        out_shape = jax.ShapeDtypeStruct((Mp, Np), jnp.float32)
        out_spec = pl.BlockSpec((bm, bn), lambda m, n, d, act, fetch: (m, n))
    else:
        G_out = dig.packed_group_count(emit[1])
        out_shape = jax.ShapeDtypeStruct((G_out, Mp, Np), jnp.int8)
        out_spec = pl.BlockSpec((G_out, bm, bn), lambda m, n, d, act, fetch: (0, m, n))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Mp // bm, Np // bn, D),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=plane_dot_scratch(bm, bn, T),
    )
    out = pl.pallas_call(
        functools.partial(
            _dslr_conv2d_packed_kernel,
            n_digits=D,
            skip_zero_planes=skip_zero_planes,
            has_row_scale=has_row_scale,
            has_bias=has_bias,
            apply_relu=apply_relu,
            emit=emit,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(activity, fetch, *operands)
    if emit is not None:
        return out[:, :M, :N]
    return out[:M, :N]


# ---------------------------------------------------------------------------
# cross-layer digit pipelining: two convs over a shared packed digit grid
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "mid_spatial",
        "mid_frac_bits",
        "mid_n_digits",
        "mid_budget",
        "kernel_size2",
        "stride2",
        "padding2",
        "relu1",
        "relu2",
        "block_m",
        "block_n",
        "skip_zero_planes",
        "interpret",
    ),
)
def dslr_conv2d_pipelined(
    patches1: jax.Array,  # (G1, M1, T1) int8 — layer-1 packed im2col planes
    w1_flat: jax.Array,  # (T1, N1)
    digit_scales1: jax.Array,  # (D1,) — layer-1 scale-folded digit weights
    w2_flat: jax.Array,  # (T2, N2), T2 = K2*K2*N1
    digit_scales2: jax.Array,  # (D2,) — layer-2 digit weights (mid scale folded
    #                             in by the caller, or carried by row_scale2)
    mid_scale: jax.Array,  # scalar or (M1,) f32 — the interchange grid s_mid
    mid_spatial: tuple,  # static (B, Ho1, Wo1) with B*Ho1*Wo1 == M1
    mid_frac_bits: int,
    mid_n_digits: int,
    mid_budget: int,
    kernel_size2: int,
    bias1: jax.Array | None = None,
    row_scale1: jax.Array | None = None,
    relu1: bool = False,
    bias2: jax.Array | None = None,
    row_scale2: jax.Array | None = None,
    relu2: bool = False,
    stride2: int = 1,
    padding2: int = 0,
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Fused conv→conv pair over a shared packed digit grid.

    Two ``(m, n, d)`` digit-grid launches chained through the 2-bit packed
    interchange: launch 1 runs layer 1 with the ``emit_planes`` epilogue
    (bias/ReLU fused, output quantized onto ``mid_scale`` and written as
    packed MSDF planes), the packed mid planes are im2col-gathered *as
    bytes* (exact — the zero digit is the zero byte), truncated to
    ``mid_budget`` digits at nibble granularity, and launch 2 consumes them
    like any packed conv.  The intermediate activation never exists as f32
    in HBM: inter-layer traffic drops from ``8 + 2·ceil(D/4)`` bytes per
    element (f32 write + f32 read + packed write + packed read) to
    ``2·ceil(D/4)`` (``kernels/traffic.py::interlayer_traffic``).

    Returns f32 ``(M2, N2)`` with ``M2 = B*Ho2*Wo2``; the caller folds
    ``mid_scale`` into ``digit_scales2``/``row_scale2`` (fused epilogue) or
    multiplies it in afterwards, exactly as for the serial kernel.
    """
    B, Ho1, Wo1 = mid_spatial
    G1, M1, T1 = patches1.shape
    assert M1 == B * Ho1 * Wo1, (patches1.shape, mid_spatial)
    N1 = w1_flat.shape[1]
    mid_packed = dslr_conv2d_planes_packed_mxu(
        patches1,
        w1_flat,
        digit_scales1,
        bias=bias1,
        row_scale=row_scale1,
        block_m=block_m,
        block_n=block_n,
        skip_zero_planes=skip_zero_planes,
        apply_relu=relu1,
        interpret=interpret,
        emit_planes=True,
        emit_scale=mid_scale,
        emit_frac_bits=mid_frac_bits,
        emit_n_digits=mid_n_digits,
    )  # (ceil(mid_n_digits/4), M1, N1) int8
    image = mid_packed.reshape(mid_packed.shape[0], B, Ho1, Wo1, N1)
    patches2 = core_dslr.im2col_planes(image, kernel_size2, stride2, padding2)
    patches2 = patches2[: dig.packed_group_count(mid_budget)]
    _, _, Ho2, Wo2, T2 = patches2.shape
    planes2 = patches2.reshape(patches2.shape[0], B * Ho2 * Wo2, T2)
    assert digit_scales2.shape[0] == mid_budget, (digit_scales2.shape, mid_budget)
    return dslr_conv2d_planes_packed_mxu(
        planes2,
        w2_flat,
        digit_scales2,
        bias=bias2,
        row_scale=row_scale2,
        block_m=block_m,
        block_n=block_n,
        skip_zero_planes=skip_zero_planes,
        apply_relu=relu2,
        interpret=interpret,
    )
