"""The 2-bit packed digit-plane interchange format (core/digits.py).

Property tests (hypothesis) for the pipeline-enabling invariants:
  * ``pack_planes``/``unpack_planes`` roundtrip is exact for all three
    recoders (greedy/csd/binary) at every digit count 1..12,
  * digit-budget truncation commutes with packing (a budget is a
    nibble-granularity leading-axis slice of the packed tensor),
  * the zero digit is the zero byte (packing commutes with zero padding,
    hence with the im2col gather),
  * the per-(tile, digit) activity bitmap equals the kernel's
    ``jnp.any(plane != 0)`` predicate,
plus the packed output mode of the fused Pallas quantizer
(kernels/msdf_quantize.py) against ``pack_planes`` of its unpacked output.
"""
import numpy as np
import pytest
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import digits as dig
from repro.kernels import ops


@given(st.sampled_from(["greedy", "csd", "binary"]),
       st.integers(min_value=0, max_value=10**6))
@settings(max_examples=12, deadline=None)
def test_pack_unpack_roundtrip_all_recoders(recoding, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-1, 1, size=(3, 5)).astype(np.float32))
    for n_digits in range(1, 13):  # every digit count 1..12, exhaustively
        planes, _ = dig.to_planes(x, frac_bits=n_digits, n_digits=n_digits,
                                  recoding=recoding)
        D = planes.shape[0]  # n_digits + 1 (slot 0)
        packed = dig.pack_planes(planes)
        assert packed.shape == (dig.packed_group_count(D),) + planes.shape[1:]
        assert packed.dtype == jnp.int8
        np.testing.assert_array_equal(
            np.asarray(dig.unpack_planes(packed, D)), np.asarray(planes)
        )


@given(st.sampled_from(["greedy", "csd", "binary"]),
       st.integers(min_value=1, max_value=12),
       st.integers(min_value=0, max_value=10**6))
@settings(max_examples=25, deadline=None)
def test_budget_truncation_commutes_with_packing(recoding, n_digits, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-1, 1, size=(3, 5)).astype(np.float32))
    planes, _ = dig.to_planes(x, frac_bits=n_digits, n_digits=n_digits,
                              recoding=recoding)
    packed = dig.pack_planes(planes)
    for k in range(1, planes.shape[0] + 1):
        # slice the packed tensor at nibble granularity, unpack k digits:
        # must equal packing after truncating (residual bits never read)
        sliced = packed[: dig.packed_group_count(k)]
        np.testing.assert_array_equal(
            np.asarray(dig.unpack_planes(sliced, k)), np.asarray(planes[:k])
        )
        np.testing.assert_array_equal(
            np.asarray(dig.unpack_planes(dig.pack_planes(planes[:k]), k)),
            np.asarray(planes[:k]),
        )


def test_byte_encoding_spec():
    """0 -> 0b00, +1 -> 0b01, -1 -> 0b11, digit j in bits 2*(j%4)."""
    planes = jnp.asarray([[0], [1], [-1], [1]], jnp.int8)  # digits 0..3
    packed = dig.pack_planes(planes)
    assert packed.shape == (1, 1)
    # 0b01_11_01_00 = 0x74 = 116
    assert int(packed[0, 0]) == 0x74
    # zero digits pack to the zero byte (zero padding commutes with packing)
    assert int(dig.pack_planes(jnp.zeros((4, 1), jnp.int8))[0, 0]) == 0


def test_unpack_validates_digit_count():
    packed = dig.pack_planes(jnp.zeros((5, 2), jnp.int8))  # 2 groups
    with pytest.raises(ValueError):
        dig.unpack_planes(packed, 9)
    with pytest.raises(ValueError):
        dig.unpack_planes(packed, 0)


# (M, T, tile_rows): a small matrix of two 8-row tiles, then ResNet-18's patch
# widths (C1's 7x7x3, a 3x3x64 and a 3x3x512 conv) at three 128-row tiles
ACTIVITY_SHAPES = [(16, 5, 8), (384, 147, 128), (384, 576, 128), (384, 4608, 128)]


def _activity_planes(M, T, bm, D, rng):
    """Digit planes whose (tile, digit) pairs are each dead, hold one lone
    -1 or +1, or are dense at random; tile 0 holds a lone -1 in every digit
    (each field reads 0b11 in some byte, and a -1 in digit 3 makes its byte
    negative), and tile 1 has the last byte group dead while tile 0 has it
    live."""
    planes = np.zeros((D, M, T), np.int8)
    for m in range(M // bm):
        for d in range(D):
            kind = "lone-1" if m == 0 else rng.choice(["dead", "lone-1", "lone+1", "dense"])
            if m == 1 and d >= 4 * (dig.packed_group_count(D) - 1):
                kind = "dead"
            rows = slice(m * bm, (m + 1) * bm)
            if kind == "dense":
                planes[d, rows] = rng.choice(np.array([-1, 0, 1], np.int8), size=(bm, T),
                                             p=[1 / 6, 2 / 3, 1 / 6])
            elif kind != "dead":
                r, c = rng.integers(bm), rng.integers(T)
                planes[d, m * bm + r, c] = -1 if kind == "lone-1" else 1
    return planes


@pytest.mark.parametrize("n_digits", range(1, 13))
@pytest.mark.parametrize("shape", ACTIVITY_SHAPES, ids=lambda s: "M{}-T{}-bm{}".format(*s))
def test_packed_plane_activity_matches_any_nonzero(shape, n_digits):
    M, T, bm = shape
    D = n_digits
    planes = _activity_planes(M, T, bm, D, np.random.default_rng([M, T, D]))
    packed = dig.pack_planes(jnp.asarray(planes))
    if D >= 4:  # tile 0's -1 in digit 3 makes a negative byte
        assert np.any(np.asarray(packed) < 0)
    act = dig.packed_plane_activity(packed, D, bm)
    want = np.stack([
        [int(np.any(planes[d, mt * bm:(mt + 1) * bm] != 0)) for d in range(D)]
        for mt in range(M // bm)
    ])
    assert want[0].all() and not want[1, -1]  # a group live in one tile, dead in another
    np.testing.assert_array_equal(np.asarray(act), want)


def test_packed_plane_activity_rejects_ragged_tiles():
    packed = dig.pack_planes(jnp.zeros((4, 10, 3), jnp.int8))
    with pytest.raises(ValueError):
        dig.packed_plane_activity(packed, 4, 8)


def test_packed_plane_activity_rejects_digits_beyond_its_groups():
    packed = dig.pack_planes(jnp.zeros((5, 8, 3), jnp.int8))  # 2 groups
    with pytest.raises(ValueError):
        dig.packed_plane_activity(packed, 9, 8)
    with pytest.raises(ValueError):
        dig.packed_plane_activity(packed, 0, 8)


# ---------------------------------------------------------------------------
# fused Pallas quantizer, packed output mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_digits", [3, 8, 9])
def test_msdf_quantize_packed_mode_matches_pack_of_unpacked(n_digits):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((7, 5)).astype(np.float32))
    scale = jnp.float32(4.0)
    up = ops.msdf_quantize(x, scale, frac_bits=8, n_digits=n_digits)
    pk = ops.msdf_quantize(x, scale, frac_bits=8, n_digits=n_digits, packed=True)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(dig.pack_planes(up)))


def test_msdf_quantize_digit_capacity_validated_in_both_modes():
    x = jnp.zeros((8, 4), jnp.float32)
    for packed in (False, True):
        with pytest.raises(ValueError):
            ops.msdf_quantize(x, jnp.float32(1.0), frac_bits=4, n_digits=6,
                              packed=packed)


def test_msdf_quantize_packed_per_row_scales():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((6, 4)).astype(np.float32))
    rs = jnp.asarray(rng.uniform(1, 5, size=(6,)).astype(np.float32))
    up = ops.msdf_quantize(x, rs, frac_bits=8)
    pk = ops.msdf_quantize(x, rs, frac_bits=8, packed=True)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(dig.pack_planes(up)))
