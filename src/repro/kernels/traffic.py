"""Operand-traffic model for the digit-plane conv kernels (bytes over HBM).

Pallas's pipelining machinery issues a block copy only when an operand's
block index *changes* between consecutive grid steps (the grid-revisiting
rule).  This module replays the exact grid iteration order and index maps of
``kernels/dslr_conv2d.py`` — including the packed path's bitmap-driven fetch
indices, via the very ``plane_tables`` and ``packed_plane_index_map`` the
kernel wrappers use — and counts the copies each operand performs.  That
makes two of the paper's roofline quantities measurable in-repo without a
hardware profiler:

  * bytes moved per conv (the Fig. 12 denominator), split per operand, and
  * the structural claims the packed rework makes: the stationary weight
    tile is never re-fetched across the digit axis, and a dead digit group
    issues no tile load at all.

The model is exact for the interpret-mode kernels (one buffer per block, no
double buffering) and an upper bound for Mosaic (which may add prefetch
overlap but never *more* copies of the same blocks).  The digit scales are
not a blocked operand: they enter whole in SMEM once per launch
(``D * 4`` bytes), so no index map of theirs is replayed.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core import digits as dig

from . import dslr_conv2d as _dc
from . import tuning


class OperandTraffic(NamedTuple):
    fetches: int  # block copies issued over the whole grid
    block_bytes: int  # bytes per copy
    bytes: int  # fetches * block_bytes


class ConvTraffic(NamedTuple):
    """Per-operand HBM traffic of one digit-plane conv kernel launch."""

    patches: OperandTraffic  # the dominant operand (packed or unpacked)
    weights: OperandTraffic
    out: OperandTraffic
    grid: Tuple[int, int, int]  # (Mt, Nt, D)

    @property
    def total_bytes(self) -> int:
        return self.patches.bytes + self.weights.bytes + self.out.bytes


def count_fetches(
    grid: Sequence[int],
    index_map: Callable[..., Tuple[int, ...]],
) -> int:
    """Copies issued for one operand: walk the grid row-major (last axis
    innermost, exactly Pallas's order) and count block-index changes; the
    first step always copies."""
    fetches, last = 0, None
    for step in np.ndindex(*grid):
        idx = tuple(int(v) for v in index_map(*step))
        if idx != last:
            fetches += 1
            last = idx
    return fetches


def packed_dead_group_fetches(
    M: int,
    N: int,
    T: int,
    n_digits: int,
    activity: np.ndarray,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = True,
) -> int:
    """Count the packed plane operand's fetch events that load a *dead* byte
    group (all four digits zero for that row tile) — the loads the bitmap
    skip exists to eliminate.

    By construction of ``plane_fetch_indices`` the fetch index only ever
    *changes to* a live group, so a dead-group load can arise solely from
    the dead-prefix clamp at a tile boundary (the first grid step of a row
    tile must have some block resident; if byte group 0 is dead it is
    fetched once and never read).  Zero on typical data, where group 0
    (digits 0..3) is live for every tile.
    """
    activity = np.asarray(activity)
    bm, bn, Mp, Np = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    grid = (Mp // bm, Np // bn, n_digits)
    fetch = np.asarray(_dc.plane_tables(activity, n_digits)[1])
    G = dig.packed_group_count(n_digits)
    pad = np.zeros((activity.shape[0], 4 * G - n_digits), activity.dtype)
    group_live = np.concatenate([activity, pad], axis=1).reshape(-1, G, 4).any(axis=2)
    dead, last = 0, None
    for m, n, d in np.ndindex(*grid):
        idx = (int(fetch[_dc.table_slot(m, d, n_digits)]), m, 0)
        if idx != last:
            if not group_live[m, idx[0]]:
                dead += 1
            last = idx
    return dead


def conv_planes_traffic(
    M: int,
    N: int,
    T: int,
    n_digits: int,
    packed: bool,
    activity: Optional[np.ndarray] = None,
    block_m: int = 128,
    block_n: int = 128,
    skip_zero_planes: bool = True,
    interpret: bool = True,
) -> ConvTraffic:
    """Traffic of one ``dslr_conv2d_planes[_packed]_mxu`` launch at geometry
    ``planes (D, M, T) @ w (T, N)``.

    ``activity`` is the per-(row tile, digit) nonzero bitmap
    (``digits.packed_plane_activity`` at this call's ``bm``); required for
    the packed path with skipping, ignored otherwise.  The index maps below
    are line-for-line the kernel wrappers' BlockSpecs.
    """
    bm, bn, Mp, Np = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    Mt, Nt, D = Mp // bm, Np // bn, n_digits
    grid = (Mt, Nt, D)

    if packed and skip_zero_planes:
        if activity is None:
            raise ValueError("packed traffic with skipping needs the activity bitmap")
        tables = [np.asarray(t) for t in _dc.plane_tables(np.asarray(activity), D)]
        plane_map = _dc.packed_plane_index_map(D)
        patches_map = lambda m, n, d: plane_map(m, n, d, *tables)
    elif packed:
        patches_map = lambda m, n, d: (d // 4, m, 0)
    else:
        patches_map = lambda m, n, d: (d, m, 0)

    patch_block = bm * T  # int8 bytes, packed or not — packing shrinks D, not T
    specs: Dict[str, Tuple[Callable, int]] = {
        "patches": (patches_map, patch_block),
        "weights": (lambda m, n, d: (0, n), T * bn * 4),
        "out": (lambda m, n, d: (m, n), bm * bn * 4),
    }
    counted = {
        name: OperandTraffic(f := count_fetches(grid, imap), blk, f * blk)
        for name, (imap, blk) in specs.items()
    }
    return ConvTraffic(counted["patches"], counted["weights"], counted["out"], grid)


class InterlayerTraffic(NamedTuple):
    """HBM bytes the intermediate activation of one conv→conv pair moves
    across the layer boundary, serial vs pipelined."""

    elements: int  # mid activation elements (B * Ho * Wo * Cout)
    serial_bytes: int  # f32 write + f32 read + packed write + packed read
    pipelined_bytes: int  # packed write + packed read only
    ratio: float  # serial / pipelined (>= 1)


def interlayer_traffic(
    elements: int, n_planes: int, digit_budget: Optional[int] = None
) -> InterlayerTraffic:
    """Inter-layer activation traffic of one conv→conv pair.

    Serial path, per mid element: the producer's kernel writes the f32
    activation (4 B), ``ops.msdf_quantize`` reads it back (4 B) and writes
    ``ceil(n_planes/4)`` packed bytes, and the consumer's im2col/kernel
    reads ``ceil(budget/4)`` of them — ``8 + G_full + G_used`` bytes.  The
    pipelined path emits the packed planes straight from the producer's
    flush epilogue: the f32 round-trip vanishes and only
    ``G_full + G_used`` bytes cross HBM.  (Patch duplication from the
    consumer's im2col gather multiplies *both* paths' read terms equally,
    so it is left out of this per-element model; weights and the pair's
    outer operands are identical between paths and excluded.)

    At the paper's D=9 grid (``n_planes=9``, full budget) this is
    ``(8 + 3 + 3) / (3 + 3) = 2.33x`` — the >= 2x floor BENCH_pipeline.json
    guards.
    """
    if digit_budget is None:
        digit_budget = n_planes
    if not 1 <= digit_budget <= n_planes:
        raise ValueError(f"digit_budget={digit_budget} outside [1, {n_planes}]")
    g_full = dig.packed_group_count(n_planes)
    g_used = dig.packed_group_count(digit_budget)
    serial = elements * (4 + 4 + g_full + g_used)
    pipelined = elements * (g_full + g_used)
    return InterlayerTraffic(
        elements=elements,
        serial_bytes=serial,
        pipelined_bytes=pipelined,
        ratio=serial / pipelined,
    )


def conv_traffic_for_input(
    x,
    w,
    n_digits: int = 8,
    stride: int = 1,
    padding: int = 0,
    recoding: str = "csd",
    digit_budget: Optional[int] = None,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = True,
) -> Dict[str, object]:
    """Packed vs unpacked traffic for a real conv call: quantizes + im2cols
    exactly like ``ops.dslr_conv2d_planes`` and measures both paths' operand
    bytes on the *actual* digit data (so the packed path's dead-group skips
    reflect this input's digit sparsity, not a model).

    Returns ``{"unpacked": ConvTraffic, "packed": ConvTraffic,
    "activity": (Mt, D) np.ndarray, "geometry": (M, N, T, D)}`` — the
    activity bitmap and geometry are exposed so callers (benchmarks, tests)
    reuse this one quantize/pack/im2col pipeline instead of re-deriving it.
    """
    import jax.numpy as jnp

    from repro.core import dslr as core_dslr

    q = core_dslr.quantize_conv_planes(x, n_digits, recoding)
    D = digit_budget if digit_budget is not None else q.planes.shape[0]
    packed_img = dig.pack_planes(q.planes)
    patches = core_dslr.im2col_planes(packed_img, w.shape[0], stride, padding)
    G = dig.packed_group_count(D)
    _, B, Ho, Wo, T = patches.shape
    M, N = B * Ho * Wo, w.shape[3]
    pk = patches[:G].reshape(G, M, T)
    bm, _, Mp, _ = tuning.conv_tile_dims(M, N, block_m, block_n, interpret)
    if Mp != M:
        pk = jnp.pad(pk, ((0, 0), (0, Mp - M), (0, 0)))
    activity = np.asarray(dig.packed_plane_activity(pk, D, bm))
    common = dict(
        M=M, N=N, T=T, n_digits=D,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return {
        "unpacked": conv_planes_traffic(packed=False, **common),
        "packed": conv_planes_traffic(packed=True, activity=activity, **common),
        "activity": activity,
        "geometry": (M, N, T, D),
    }
