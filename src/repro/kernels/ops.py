"""Public jit'd wrappers for the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True`` — the
kernel body runs per grid step in Python/XLA exactly as written, which is
how we validate them against ``ref.py``.  On TPU backends the same calls
compile to Mosaic.

Block sizing: odd/prime dims are handled by *padding* the tiled dimension up
to a block multiple and slicing the result back out (zero rows/digit planes
contribute exactly nothing), never by shrinking the block — a prime M must
not degrade the MXU tile to 1.  The tile/pad math lives in
``kernels/tuning.py`` (one shared copy), which also holds the measured
(block_m, block_n) autotuner the conv path consults when blocks are left
unspecified (``block_m=None``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import digits as dig
from repro.core import dslr as core_dslr

from . import dslr_conv2d as _dc
from . import dslr_matmul as _dm
from . import ref as _ref
from . import msdf_quantize as _mq
from . import online_sop as _os
from . import tuning


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_axis(a: jax.Array, size: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` of ``a`` up to ``size``."""
    if a.shape[axis] == size:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, widths)


def dslr_matmul(
    x: jax.Array,
    w: jax.Array,
    n_digits: int = 8,
    recoding: str = "csd",
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """x @ w with MSDF digit-plane execution (2-D x (M, K), w (K, N))."""
    if interpret is None:
        interpret = _on_cpu()
    q = core_dslr.quantize_msdf(x, n_digits, recoding)
    scales = core_dslr.digit_scales(q.planes.shape[0])
    M, N = x.shape[0], w.shape[1]
    bm, bn, Mp, Np = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    planes = _pad_axis(q.planes, Mp, 1)
    wf = _pad_axis(w.astype(jnp.float32), Np, 1)
    out = _dm.dslr_matmul_planes(
        planes,
        wf,
        scales,
        block_m=bm,
        block_n=bn,
        skip_zero_planes=skip_zero_planes,
        interpret=interpret,
    )
    return out[:M, :N] * q.scale


def dslr_matmul_packed(
    x: jax.Array,
    w: jax.Array,
    n_digits: int = 8,
    recoding: str = "csd",
    digit_budget: int | None = None,
    bias: jax.Array | None = None,
    per_sample: bool = False,
    block_m: int | None = None,
    block_n: int | None = None,
    skip_zero_planes: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ w`` through the packed 2-bit digit-plane interchange — the one
    spelling of digit-serial projection the LM engine routes everything
    through (``repro.lm``).

    ``x``: (M, K) float activations (for a transformer projection, M = B*S
    token rows); ``w``: (K, N) float stationary weights.  Returns (M, N) f32.

    ``digit_budget`` (<= n_digits + 1) truncates the MSDF plane stream — the
    anytime knob; the packed operand is sliced at nibble granularity.  The
    activation quantization scale is always folded into the accumulation
    (per-tensor: into the digit scales; ``per_sample=True``: one scale per
    *token row* via the kernel's ``row_scale`` path), so ``bias`` fuses into
    the flush step and row i's output is a function of row i alone — an
    outlier batchmate or a zero padding row cannot perturb it (bitwise).
    Validated bit-for-bit against ``ref.dslr_matmul_packed_ref``.
    """
    if interpret is None:
        interpret = _on_cpu()
    q = core_dslr.quantize_msdf(x, n_digits, recoding, per_sample=per_sample)
    n_planes = q.planes.shape[0]
    if digit_budget is not None and not 1 <= digit_budget <= n_planes:
        raise ValueError(f"digit_budget={digit_budget} outside [1, {n_planes}]")
    D = digit_budget if digit_budget is not None else n_planes
    packed = dig.pack_planes(q.planes)[: dig.packed_group_count(D)]
    scales = core_dslr.digit_scales(D)
    row_scale = None
    if per_sample:
        row_scale = q.scale.astype(jnp.float32)
    else:
        scales = q.scale * scales
    if block_m is None or block_n is None:
        tuned_m, tuned_n = tuning.autotune_conv_blocks(
            x.shape[0], w.shape[1], x.shape[1], D, packed=True, interpret=interpret
        )
        block_m = block_m if block_m is not None else tuned_m
        block_n = block_n if block_n is not None else tuned_n
    return _dm.dslr_matmul_planes_packed(
        packed,
        w,
        scales,
        bias=bias,
        row_scale=row_scale,
        block_m=block_m,
        block_n=block_n,
        skip_zero_planes=skip_zero_planes,
        interpret=interpret,
    )


def dslr_conv2d_planes(
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
    packed: bool = True,
    block_m: int | None = None,
    block_n: int | None = None,
    skip_zero_planes: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """2-D conv on the MXU as an MSDF digit-plane im2col matmul.

    ``x``: (B, H, W, Cin) float; ``w``: (K, K, Cin, Cout) float (stationary,
    bit-parallel).  Returns float32 (B, Ho, Wo, Cout).

    ``digit_budget`` (<= n_digits + 1) truncates the MSDF plane stream — the
    paper's runtime precision knob: the result is a k-MSB approximation with
    error <= scale * 2**-(k-1) * max ||W_col||_1 (``conv_anytime_error_bound``)
    at proportionally fewer MXU passes.  Validated bit-for-bit against
    ``ref.dslr_conv2d_planes_ref`` and within the anytime bound against
    ``core.online.conv2d_ref``.

    ``bias``/``relu`` fuse the layer epilogue into the kernel's flush step
    (one launch for conv + bias + activation; the quantization scale reaches
    the accumulator before the bias — folded into the per-plane digit scales,
    or per output row when ``per_sample`` — so the bias lands on real conv
    values).

    ``per_sample`` quantizes every batch row against its own amax: sample
    i's output is a function of sample i alone, so batch composition (and
    zero padding) cannot perturb it — the request-level serving contract.

    ``packed`` (default) keeps the digit planes in the 2-bit packed
    interchange format across the HBM boundary: the materialized im2col
    patch tensor shrinks ~4x in the digit axis and dead digit groups are
    never DMA'd (bitmap-driven skip).  Bitwise identical to ``packed=False``
    — packing is a bijection and the kernel's f32 accumulation sequence is
    unchanged.

    ``block_m``/``block_n`` default to the autotuner's table entry for this
    geometry (``kernels/tuning.py``); pass explicit ints to pin them.
    """
    return dslr_conv2d_planes_flat(
        x,
        core_dslr.flatten_conv_weights(w),
        kernel_size=w.shape[0],
        n_digits=n_digits,
        stride=stride,
        padding=padding,
        recoding=recoding,
        digit_budget=digit_budget,
        bias=bias,
        relu=relu,
        per_sample=per_sample,
        packed=packed,
        block_m=block_m,
        block_n=block_n,
        skip_zero_planes=skip_zero_planes,
        interpret=interpret,
    )


def dslr_conv2d_planes_flat(
    x: jax.Array,
    w_flat: jax.Array,
    kernel_size: int,
    n_digits: int = 8,
    stride: int = 1,
    padding: int = 0,
    recoding: str = "csd",
    digit_budget: int | None = None,
    bias: jax.Array | None = None,
    relu: bool = False,
    per_sample: bool = False,
    packed: bool = True,
    block_m: int | None = None,
    block_n: int | None = None,
    skip_zero_planes: bool = True,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """``dslr_conv2d_planes`` with pre-flattened stationary weights
    ``w_flat``: (K*K*Cin, Cout) — what a compiled engine calls so weight
    flattening happens once at build time, not per forward pass.

    ``use_ref=True`` routes the accumulation through the pure-jnp oracle
    scan (``ref.planes_scan_flat_ref``) instead of the Pallas kernel — the
    serving guardrails' trusted fallback, bitwise-identical to a healthy
    kernel (quantize / pack / im2col / scale folding are shared; only the
    plane-accumulation launch differs).

    Each step runs in its own name scope (``quantize``, ``pack``,
    ``im2col``, ``kernel``), so a device operation's ``op_name`` says which
    step of the conv it belongs to."""
    if interpret is None:
        interpret = _on_cpu()
    with jax.named_scope("quantize"):
        q = core_dslr.quantize_conv_planes(x, n_digits, recoding, per_sample=per_sample)
    n_planes = q.planes.shape[0]
    if digit_budget is not None and not 1 <= digit_budget <= n_planes:
        raise ValueError(f"digit_budget={digit_budget} outside [1, {n_planes}]")
    D = digit_budget if digit_budget is not None else n_planes
    if packed:
        # pack the *image* planes (a bijection, commutes with the im2col
        # gather because the zero digit encodes as a zero byte), so the big
        # materialized patch tensor is born packed: ceil(D/4) bytes per
        # patch element instead of D
        with jax.named_scope("pack"):
            image = dig.pack_planes(q.planes)
    else:
        image = q.planes
    with jax.named_scope("im2col"):
        patches = core_dslr.im2col_planes(image, kernel_size, stride, padding)
        # digit-budget truncation: a leading-axis slice either way (nibble
        # granularity when packed — residual digits in the last byte are
        # simply never unpacked by the kernel)
        patches = patches[: dig.packed_group_count(D) if packed else D]
        _, B, Ho, Wo, T = patches.shape
        planes = patches.reshape(patches.shape[0], B * Ho * Wo, T)
    fused = bias is not None or relu
    scales = core_dslr.digit_scales(D)
    row_scale = None
    if fused and not per_sample:
        # fold the activation scale into the digit scales: the accumulator
        # then holds real conv values, so bias+ReLU fuse into the flush
        scales = q.scale * scales
    elif fused:
        # per-sample: one scale per output row (every row of a sample's
        # Ho*Wo pixel block shares its sample's scale), multiplied into the
        # accumulator at the flush step before the bias lands
        row_scale = jnp.repeat(q.scale.astype(jnp.float32), Ho * Wo)
    with jax.named_scope("kernel"):
        if use_ref:
            out = _ref.planes_scan_flat_ref(
                planes,
                w_flat,
                scales,
                n_planes=D,
                packed=packed,
                bias=bias,
                row_scale=row_scale,
                apply_relu=relu,
            )
        else:
            if block_m is None or block_n is None:
                tuned_m, tuned_n = tuning.autotune_conv_blocks(
                    B * Ho * Wo, w_flat.shape[1], T, D, packed=packed, interpret=interpret
                )
                block_m = block_m if block_m is not None else tuned_m
                block_n = block_n if block_n is not None else tuned_n
            kernel = (
                _dc.dslr_conv2d_planes_packed_mxu
                if packed
                else _dc.dslr_conv2d_planes_mxu
            )
            out = kernel(
                planes,
                w_flat,
                scales,
                bias=bias,
                row_scale=row_scale,
                block_m=block_m,
                block_n=block_n,
                skip_zero_planes=skip_zero_planes,
                apply_relu=relu,
                interpret=interpret,
            )
    out = out.reshape(B, Ho, Wo, w_flat.shape[1])
    if not fused:
        s = q.scale.reshape(-1, 1, 1, 1) if per_sample else q.scale
        out = out * s
    return out


def dslr_conv2d_pipelined(
    x: jax.Array,
    w1_flat: jax.Array,
    w2_flat: jax.Array,
    kernel_size1: int,
    kernel_size2: int,
    n_digits: int = 8,
    stride1: int = 1,
    padding1: int = 0,
    stride2: int = 1,
    padding2: int = 0,
    recoding: str = "csd",
    budget1: int | None = None,
    budget2: int | None = None,
    bias1: jax.Array | None = None,
    relu1: bool = False,
    bias2: jax.Array | None = None,
    relu2: bool = False,
    per_sample: bool = False,
    mid_scale: jax.Array | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    skip_zero_planes: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused conv→conv pair exchanging packed MSDF digit planes directly.

    Layer 1 runs with the digit-emitting epilogue: its post-bias/ReLU output
    is quantized in-kernel onto the interchange grid ``mid_scale`` and
    written as packed 2-bit planes, which layer 2 consumes like any packed
    conv — the intermediate activation never exists as f32 in HBM
    (``kernels/dslr_conv2d.py::dslr_conv2d_pipelined``).

    ``mid_scale`` defaults to the analytic a-priori grid
    ``core.dslr.pipeline_mid_scale(w1_flat, bias1, q.scale, n_digits)`` — a
    sound, budget-independent upper bound on the observed output scale, so
    anytime prefix runs and the full-budget run share one mid grid (the
    adaptive cascade's soundness hinges on this).  Against the serial
    composition (layer-1 conv → ``msdf_quantize`` on the *same* grid →
    layer-2 conv) the result is bitwise identical at equal digit budgets;
    truncating ``budget1``/``budget2`` below full stays within the recoding
    bound (``core.planner.recode_bound``, tests/test_pipeline_diff.py).

    ``budget1`` truncates layer 1's input digit stream, ``budget2`` the mid
    interchange stream feeding layer 2.  Returns
    ``(out (B, Ho2, Wo2, Cout2) f32, mid_scale)`` — the grid is handed back
    so engines can report the scale the pair actually used.
    """
    if interpret is None:
        interpret = _on_cpu()
    q = core_dslr.quantize_conv_planes(x, n_digits, recoding, per_sample=per_sample)
    n_planes = q.planes.shape[0]
    for name, k in (("budget1", budget1), ("budget2", budget2)):
        if k is not None and not 1 <= k <= n_planes:
            raise ValueError(f"{name}={k} outside [1, {n_planes}]")
    D1 = budget1 if budget1 is not None else n_planes
    D2 = budget2 if budget2 is not None else n_planes
    image = dig.pack_planes(q.planes)
    patches = core_dslr.im2col_planes(image, kernel_size1, stride1, padding1)
    patches = patches[: dig.packed_group_count(D1)]
    _, B, Ho1, Wo1, T1 = patches.shape
    M1 = B * Ho1 * Wo1
    planes1 = patches.reshape(patches.shape[0], M1, T1)
    # the emit epilogue quantizes the accumulator, so it must hold real conv
    # values: the activation scale always folds in (digit scales per-tensor,
    # per-row otherwise) — same folding as the serial fused path
    scales1 = core_dslr.digit_scales(D1)
    row_scale1 = None
    if per_sample:
        row_scale1 = jnp.repeat(q.scale.astype(jnp.float32), Ho1 * Wo1)
    else:
        scales1 = q.scale * scales1
    if mid_scale is None:
        mid_scale = core_dslr.pipeline_mid_scale(w1_flat, bias1, q.scale, n_digits)
    mid_scale = jnp.asarray(mid_scale, jnp.float32)
    emit_scale = jnp.repeat(mid_scale, Ho1 * Wo1) if per_sample else mid_scale
    Ho2 = (Ho1 + 2 * padding2 - kernel_size2) // stride2 + 1
    Wo2 = (Wo1 + 2 * padding2 - kernel_size2) // stride2 + 1
    fused2 = bias2 is not None or relu2
    scales2 = core_dslr.digit_scales(D2)
    row_scale2 = None
    if fused2 and per_sample:
        row_scale2 = jnp.repeat(mid_scale, Ho2 * Wo2)
    elif fused2:
        scales2 = mid_scale * scales2
    if block_m is None or block_n is None:
        tuned_m, tuned_n = tuning.autotune_conv_blocks(
            M1, w1_flat.shape[1], T1, D1, packed=True, interpret=interpret
        )
        block_m = block_m if block_m is not None else tuned_m
        block_n = block_n if block_n is not None else tuned_n
    out = _dc.dslr_conv2d_pipelined(
        planes1,
        w1_flat,
        scales1,
        w2_flat,
        scales2,
        emit_scale,
        mid_spatial=(B, Ho1, Wo1),
        mid_frac_bits=n_digits,
        mid_n_digits=n_planes,
        mid_budget=D2,
        kernel_size2=kernel_size2,
        bias1=bias1,
        row_scale1=row_scale1,
        relu1=relu1,
        bias2=bias2,
        row_scale2=row_scale2,
        relu2=relu2,
        stride2=stride2,
        padding2=padding2,
        block_m=block_m,
        block_n=block_n,
        skip_zero_planes=skip_zero_planes,
        interpret=interpret,
    )
    out = out.reshape(B, Ho2, Wo2, w2_flat.shape[1])
    if not fused2:
        s = mid_scale.reshape(-1, 1, 1, 1) if per_sample else mid_scale
        out = out * s
    return out, mid_scale


def conv_anytime_error_bound(
    w: jax.Array, scale: jax.Array, digits_used: int
) -> jax.Array:
    """|exact_quantized_conv - partial_k| elementwise bound after k planes:
    tail mass sum_{j>=k} 2**-j < 2**-(k-1), worst case every tail digit
    is +/-1 in every patch position."""
    w_flat = core_dslr.flatten_conv_weights(w)
    return core_dslr.anytime_error_bound(w_flat, scale, digits_used)


def msdf_quantize(
    x: jax.Array,
    scale: jax.Array,
    frac_bits: int = 8,
    n_digits: int | None = None,
    block_rows: int = 256,
    packed: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """``scale`` is a scalar (per-tensor grid) or an (M,) per-row vector —
    the per-request quantization grids the serving path uses.  ``packed``
    emits the 2-bit packed interchange format (``digits.pack_planes`` of the
    unpacked output, computed in-kernel: 4 digits per byte, one HBM write
    per byte group)."""
    if interpret is None:
        interpret = _on_cpu()
    M = x.shape[0]
    br, Mp = tuning.row_tile_dims(M, block_rows)
    if jnp.ndim(scale) == 1 and Mp != M:
        # pad rows carry scale 1 (not 0: 1/0 would turn the padded zero rows
        # into NaNs); they are sliced off below either way
        scale = jnp.concatenate([scale, jnp.ones((Mp - M,), scale.dtype)])
    planes = _mq.msdf_quantize(
        _pad_axis(x, Mp, 0),
        scale,
        frac_bits=frac_bits,
        n_digits=n_digits,
        block_rows=br,
        packed=packed,
        interpret=interpret,
    )
    return planes[:, :M]


def online_sop_exact(
    x_fixed: jax.Array,
    y_digits: jax.Array,
    frac_bits: int = 8,
    n_out: int | None = None,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = _on_cpu()
    M = x_fixed.shape[0]
    br, Mp = tuning.row_tile_dims(M, block_rows)
    out = _os.online_sop_exact(
        _pad_axis(x_fixed, Mp, 0),
        _pad_axis(y_digits, Mp, 0),
        frac_bits=frac_bits,
        n_out=n_out,
        block_rows=br,
        interpret=interpret,
    )
    return out[:M]


def slstm_sweep(
    wx: jax.Array,
    r_w: jax.Array,
    n_heads: int,
    chunk: int = 16,
    block_batch: int = 8,
    interpret: bool | None = None,
):
    """Weight-stationary sLSTM sequence sweep (see kernels/slstm_cell.py)."""
    from . import slstm_cell as _sc

    if interpret is None:
        interpret = _on_cpu()
    return _sc.slstm_sweep(
        wx,
        r_w,
        n_heads=n_heads,
        chunk=_pick_block(wx.shape[1], chunk),
        block_batch=_pick_block(wx.shape[0], block_batch),
        interpret=interpret,
    )


def _pick_block(dim: int, preferred: int) -> int:
    """Largest divisor of ``dim`` not exceeding ``preferred``.

    Only for kernels where zero-padding would corrupt state (the sLSTM sweep
    carries a recurrence across chunks, so padded timesteps would pollute the
    returned final state).  Everything else pads + slices instead.
    """
    b = min(preferred, dim)
    while dim % b:
        b -= 1
    return b
