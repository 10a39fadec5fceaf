"""Pallas TPU kernel: MSDF digit-plane matmul — the DSLR SoP unit on the MXU.

The ASIC's PE streams activation digits into LR-SPMs with weights stationary;
the TPU-native equivalent keeps the weight tile stationary in VMEM and loops
MSDF over int8 digit *planes*, accumulating

    acc += 2**-j * (plane_j_tile @ w_tile)

into a VMEM accumulator that never round-trips to HBM until all digits of an
(m, n) tile are consumed — the memory-system analogue of the paper's
digit-level pipelining (partial products never leave the PE).

Performance features mirroring the paper's arguments:
  * MSDF digit budget: the plane count is a static compile-time knob (the
    paper's runtime-precision benefit); fewer planes = proportionally fewer
    MXU passes with a 2**-k bounded error (anytime inference).
  * Zero-plane skipping: CSD recoding leaves ~2/3 of digits zero; tiles whose
    digit-plane block is entirely zero skip the MXU dot (the signal-activity
    / sparsity benefit, §V-A item 5).

Grid layout: (m, n, d) with d innermost, so the accumulator for an (m, n)
tile is zeroed at d == 0 and flushed to HBM at d == D-1.  The contraction
(K) dimension stays whole inside the block for single-pass accumulation.

BlockSpec tiling (v5e): MXU is 128x128; default tiles are (128, K) x (K, 128)
with VMEM footprint  128*K (int8 plane) + K*128*4 (f32 weights) +
K*128*6 (the weights' three bf16 limbs) + 2 * 128*128*4 (acc + out)  =
K*1408 B + 128 KiB, before double buffering — qwen2-0.5b's d_ff of 4864
and VGG-16's T = 4608 compile for a v5e (tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import digits as dig

from . import tuning
from .dslr_conv2d import (
    SMEM_WHOLE,
    packed_plane_index_map,
    packed_plane_tables,
    plane_dot,
    plane_dot_scratch,
    store_weight_limbs,
    table_slot,
    unpack_digit,
)


def _dslr_matmul_kernel(
    planes_ref,  # (1, bm, K) int8 — digit plane d for this m-tile
    w_ref,  # (K, bn) f32 — stationary weight tile
    scale_ref,  # SMEM (D,) f32 — digit weights, plane d reads scale_ref[d]
    out_ref,  # (bm, bn) f32
    acc_ref,  # VMEM scratch (bm, bn) f32
    limbs_ref,  # VMEM scratch (3, K, bn) bf16 — the weight tile's limbs
    *,
    n_digits: int,
    skip_zero_planes: bool,
):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        store_weight_limbs(w_ref, limbs_ref)

    plane = planes_ref[0]
    scale = scale_ref[d]

    def _accumulate():
        acc_ref[...] += scale * plane_dot(plane, limbs_ref)

    if skip_zero_planes:
        # CSD leaves ~2/3 of digits zero — skip the MXU pass for all-zero
        # plane tiles (the paper's reduced-activity argument, in tile form).
        # widened first: Mosaic cannot relayout an int8-shaped i1 mask
        live = jnp.any(plane.astype(jnp.int32) != 0)
        jax.lax.cond(live, _accumulate, lambda: None)
    else:
        _accumulate()

    @pl.when(d == n_digits - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "skip_zero_planes", "interpret"),
)
def dslr_matmul_planes(
    planes: jax.Array,  # (D, M, K) int8 MSDF digit planes of the activation
    w: jax.Array,  # (K, N) float
    digit_scales: jax.Array,  # (D,) f32, typically 2**-arange(D)
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Digit-plane matmul: ``sum_d digit_scales[d] * (planes[d] @ w)``.

    MSDF accumulation order (d = 0 first) gives anytime semantics: compiling
    with a truncated ``planes``/``digit_scales`` is the paper's runtime
    precision scaling.
    """
    D, M, K = planes.shape
    K2, N = w.shape
    assert K == K2, (planes.shape, w.shape)
    bm = min(block_m, M)
    bn = min(block_n, N)
    assert M % bm == 0 and N % bn == 0, "pad M/N to tile multiples"

    return pl.pallas_call(
        functools.partial(
            _dslr_matmul_kernel, n_digits=D, skip_zero_planes=skip_zero_planes
        ),
        grid=(M // bm, N // bn, D),
        in_specs=[
            pl.BlockSpec((1, bm, K), lambda m, n, d: (d, m, 0)),
            pl.BlockSpec((K, bn), lambda m, n, d: (0, n)),
            SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, d: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=plane_dot_scratch(bm, bn, K),
        interpret=interpret,
    )(planes, w.astype(jnp.float32), digit_scales.astype(jnp.float32))


# ---------------------------------------------------------------------------
# packed variant: 2-bit digits across the HBM boundary, bitmap-driven skip
# (the matmul twin of kernels/dslr_conv2d.py's packed conv path — transformer
# projections are plain (M, K) x (K, N) products, so there is no im2col stage
# and no emit epilogue, but the interchange format, the scalar-prefetched
# activity bitmap, and the per-row sample scales carry over unchanged)
# ---------------------------------------------------------------------------


def _dslr_matmul_packed_kernel(
    act_ref,  # SMEM (Mt * D,) int32 — per-(tile, digit) nonzero bitmap
    fetch_ref,  # SMEM (Mt * D,) int32 — resident byte group per step (index map)
    packed_ref,  # (1, bm, K) int8 — byte group fetch[m, d] of the activations
    w_ref,  # (K, bn) f32 — stationary projection weight tile
    scale_ref,  # SMEM (D,) f32 — digit weights (scale-folded), read at d
    *refs,  # [row_scale_ref (bm, 1),] [bias_ref (1, bn),] out_ref, acc_ref,
    #        limbs_ref
    n_digits: int,
    skip_zero_planes: bool,
    has_row_scale: bool,
    has_bias: bool,
):
    del fetch_ref  # consumed by the index map, not the body
    row_scale_ref = refs[0] if has_row_scale else None
    bias_ref = refs[1] if (has_row_scale and has_bias) else refs[0] if has_bias else None
    out_ref, acc_ref, limbs_ref = refs[-3], refs[-2], refs[-1]
    m, d = pl.program_id(0), pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        store_weight_limbs(w_ref, limbs_ref)

    # the activation quantization scale reaches the accumulator inside the
    # per-plane step — folded into ``digit_scales`` (per-tensor) or via
    # ``row_scale`` (per-token: each output row carries its own token's
    # scale) — so the flush step is a pure bias add on real projection values
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
        res = acc_ref[...]
        if bias_ref is not None:
            res = res + bias_ref[0]
        out_ref[...] = res


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "skip_zero_planes", "interpret"),
)
def dslr_matmul_planes_packed(
    packed: jax.Array,  # (ceil(D/4), M, K) int8 — packed activation planes
    w: jax.Array,  # (K, N) float — stationary projection weights
    digit_scales: jax.Array,  # (D,) f32 — 2**-arange(D), scale-folded or not
    bias: jax.Array | None = None,  # (N,) f32 — fused into the flush step
    row_scale: jax.Array | None = None,  # (M,) f32 — per-token flush scale
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Packed-interchange twin of ``dslr_matmul_planes`` — same contract,
    bitwise-identical result, ~4x less HBM traffic on the activation operand.

    ``packed`` carries 4 MSDF digits per int8 byte (``digits.pack_planes`` of
    the activation planes); the digit budget D is ``len(digit_scales)`` and
    ``packed`` must hold exactly ``ceil(D/4)`` byte groups (budget truncation
    is a nibble-granularity leading-axis slice — residual digits in the last
    byte are never unpacked).  Zero-plane skipping is driven by a
    scalar-prefetched activity bitmap: dead digits skip the MXU pass *and*
    dead byte groups are never DMA'd into VMEM, because the plane index map
    points them at the already-resident block.

    Accepts any (M, N); tiles are padded internally with zero rows/columns
    (zero digit rows are zero bytes and contribute nothing) and the (M, N)
    result is sliced back out.  When fusing ``bias``, the activation
    quantization scale must reach the accumulator first: fold a per-tensor
    scalar into ``digit_scales``, or pass per-token scales as ``row_scale``
    (one value per activation row, multiplied in at every accumulation step —
    row i's output then depends on row i alone, the serving decoupling
    contract).
    """
    G, M, K = packed.shape
    D = digit_scales.shape[0]
    K2, N = w.shape
    assert K == K2, (packed.shape, w.shape)
    assert G == dig.packed_group_count(D), (packed.shape, D)
    bm, bn, Mp, Np = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    if Mp != M:
        packed = jnp.pad(packed, ((0, 0), (0, Mp - M), (0, 0)))
    wf = w.astype(jnp.float32)
    if Np != N:
        wf = jnp.pad(wf, ((0, 0), (0, Np - N)))

    activity, fetch = packed_plane_tables(packed, D, bm, skip_zero_planes)

    has_row_scale = row_scale is not None
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((1, bm, K), packed_plane_index_map(D)),
        pl.BlockSpec((K, bn), lambda m, n, d, act, fetch: (0, n)),
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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Mp // bm, Np // bn, D),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, d, act, fetch: (m, n)),
        scratch_shapes=plane_dot_scratch(bm, bn, K),
    )
    out = pl.pallas_call(
        functools.partial(
            _dslr_matmul_packed_kernel,
            n_digits=D,
            skip_zero_planes=skip_zero_planes,
            has_row_scale=has_row_scale,
            has_bias=has_bias,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        interpret=interpret,
    )(activity, fetch, *operands)
    return out[:M, :N]
