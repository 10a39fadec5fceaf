"""Block-shape selection for the Pallas kernels: shared tile/pad math + a
small measured autotuner.

Tile/pad math (the one copy)
----------------------------
Every matmul-shaped kernel wrapper used to carry its own ``_round_up`` +
pad-and-slice block sizing; ``conv_tile_dims`` / ``row_tile_dims`` are now the
single source of truth.  The policy is always *pad, never shrink*: an odd or
prime dimension pads up to a block multiple (zero rows / zero digit planes
contribute exactly nothing and are sliced off), so a prime M cannot degrade
the MXU tile to 1.  Alignment follows the TPU layout rules: sublane (second-
to-last dim) multiples of 8, lane (last dim) multiples of 128 on hardware —
relaxed to 8 in interpret mode, where tiny test shapes would otherwise pad
16x.

Autotuner
---------
``autotune_conv_blocks`` gives the conv and matmul paths their
``(block_m, block_n)`` pair from a cached per-(geometry, backend) table.  A
cache miss records the MXU-aligned 128/128 heuristic.  ``measure=True`` runs
a measured sweep instead: each candidate block shape executes the actual
packed conv kernel on synthetic CSD-sparse planes and the fastest wins.  The
sweep is never the default because the engines consult the table while
``jax.jit`` traces them: there the candidate calls return tracers,
``block_until_ready`` returns at once, and the sweep would time tracing, not
kernels.  The table is process-global.
"""
from __future__ import annotations

import time
from typing import Dict, Iterable, NamedTuple, Tuple

import jax

# f32 sublane multiple.  An int8 plane block is then either the whole padded
# row extent (M <= block_m) or a multiple of 32 rows, both of which Mosaic
# accepts (tests/test_tpu_compile.py compiles M = 8, 49 and 12544).
SUBLANE = 8
LANE = 128  # MXU/VPU lane width


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= ``x``."""
    return -(-x // mult) * mult


class TileDims(NamedTuple):
    """Resolved tile shape + padded extents for a pad-and-slice kernel."""

    bm: int
    bn: int
    m_pad: int
    n_pad: int


def conv_tile_dims(
    M: int, N: int, block_m: int, block_n: int, interpret: bool
) -> TileDims:
    """(M, N) output tiling: clamp the preferred blocks to the (aligned)
    problem size, then pad M/N up to block multiples."""
    bm = min(block_m, round_up(M, SUBLANE))
    bn = min(block_n, round_up(N, SUBLANE if interpret else LANE))
    return TileDims(bm, bn, round_up(M, bm), round_up(N, bn))


def row_tile_dims(M: int, block_rows: int) -> Tuple[int, int]:
    """1-D row tiling (quantize / SoP kernels): (rows per block, padded M)."""
    br = min(block_rows, round_up(M, SUBLANE))
    return br, round_up(M, br)


# ---------------------------------------------------------------------------
# measured (block_m, block_n) autotuner with a per-(geometry, backend) table
# ---------------------------------------------------------------------------

# candidate preferred blocks; conv_tile_dims clamps them to the geometry, so
# duplicates after clamping collapse before any timing happens
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (64, 128),
    (128, 128),
    (128, 256),
    (256, 128),
    (256, 256),
)

_BLOCK_TABLE: Dict[tuple, Tuple[int, int]] = {}


def block_table() -> Dict[tuple, Tuple[int, int]]:
    """Snapshot of the cached (geometry, backend) -> (block_m, block_n) table."""
    return dict(_BLOCK_TABLE)


def clear_block_table() -> None:
    _BLOCK_TABLE.clear()


def _time_best(fn, samples: int = 3) -> float:
    """Min-of-N wall clock after one warmup — one transient hiccup must not
    crown a slow candidate that then sticks in the process-global table."""
    out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(samples):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_conv_blocks(
    M: int,
    N: int,
    T: int,
    n_digits: int,
    packed: bool = True,
    interpret: bool = False,
    measure: bool = False,
    candidates: Iterable[Tuple[int, int]] = DEFAULT_CANDIDATES,
) -> Tuple[int, int]:
    """Preferred (block_m, block_n) for a digit-plane conv matmul of geometry
    ``planes (D, M, T) @ w (T, N)``.

    Consults the process-global table first; on a miss records the 128/128
    MXU heuristic, or with ``measure=True`` times the candidates (call it
    outside any trace).  The returned pair is a *preferred* shape —
    ``conv_tile_dims`` still clamps it to the padded problem size at the
    kernel call.
    """
    backend = jax.default_backend()
    key = ("conv_planes", M, N, T, n_digits, bool(packed), backend, bool(interpret))
    hit = _BLOCK_TABLE.get(key)
    if hit is not None:
        return hit
    if not measure:
        best = (128, 128)
        _BLOCK_TABLE[key] = best
        return best

    import numpy as np

    from repro.core import digits as dig

    from . import dslr_conv2d as _dc

    rng = np.random.default_rng(0)
    # ranking block shapes needs only a few row tiles, not the full problem:
    # cap the synthetic operand's M so a VGG-scale first call does not
    # allocate hundreds of MB just to time candidates
    M_bench = min(M, 4 * max(max(c[0] for c in candidates), 128))
    # CSD-like sparsity (~1/3 non-zero) so zero-group skipping behaves as in
    # production, not as in a dense worst case
    planes = rng.choice(
        np.array([-1, 0, 1], np.int8),
        size=(n_digits, M_bench, T),
        p=[1 / 6, 2 / 3, 1 / 6],
    )
    planes = jax.numpy.asarray(planes)
    w = jax.numpy.asarray(rng.standard_normal((T, N)).astype(np.float32))
    scales = jax.numpy.exp2(-jax.numpy.arange(n_digits, dtype=jax.numpy.float32))
    operand = dig.pack_planes(planes) if packed else planes
    kernel = (
        _dc.dslr_conv2d_planes_packed_mxu if packed else _dc.dslr_conv2d_planes_mxu
    )

    seen = set()
    best, best_t = (128, 128), float("inf")
    for cand_m, cand_n in candidates:
        td = conv_tile_dims(M, N, cand_m, cand_n, interpret)
        if (td.bm, td.bn) in seen:
            continue
        seen.add((td.bm, td.bn))
        t = _time_best(
            lambda bm=td.bm, bn=td.bn: kernel(
                operand, w, scales, block_m=bm, block_n=bn, interpret=interpret
            )
        )
        if t < best_t:
            best, best_t = (td.bm, td.bn), t
    _BLOCK_TABLE[key] = best
    return best
