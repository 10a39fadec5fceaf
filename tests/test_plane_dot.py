"""The digit-plane dot as three exact bf16 passes (kernels/dslr_conv2d.py).

* ``weight_limbs`` splits f32 weights into three bf16 limbs that add back to
  the weights bit for bit, over the whole range where the split is exact;
* ``plane_dot`` of a signed one-hot plane returns +-w exactly: every
  plane x limb product is exact and the three passes add back to w;
* every digit-plane Pallas kernel (conv unpacked and packed, matmul unpacked
  and packed) runs exactly three bf16 x bf16 dots per digit step and no
  f32 x f32 dot, read from the kernel bodies' jaxprs.
"""
from __future__ import annotations

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import digits as dig
from repro.kernels import dslr_conv2d as dc
from repro.kernels import dslr_matmul as dm


def _weights() -> np.ndarray:
    """Random f32 over exponents 2**-100 to 2**20 with both signs, zeros,
    and the neighbours just below and above every power of two there."""
    rng = np.random.default_rng(0)
    exps = rng.uniform(-100.0, 20.0, 50_000)
    signs = np.where(rng.random(exps.size) < 0.5, -1.0, 1.0)
    mant = rng.uniform(1.0, 2.0, exps.size)
    rand = (signs * mant * 2.0**np.floor(exps)).astype(np.float32)
    pows = (2.0 ** np.arange(-100, 21)).astype(np.float32)
    below = np.nextafter(pows, np.float32(0))
    above = np.nextafter(pows, np.float32(np.inf))
    edges = np.concatenate([pows, below, above])
    return np.concatenate([rand, edges, -edges, [0.0, -0.0]]).astype(np.float32)


def test_weight_limbs_reconstruct_weights_bitwise():
    w = _weights()
    limbs = jax.jit(dc.weight_limbs)(jnp.asarray(w))
    assert all(limb.dtype == jnp.bfloat16 for limb in limbs)
    hi, mid, lo = (np.asarray(limb).astype(np.float32) for limb in limbs)
    rec = (hi + mid) + lo
    nz = w != 0
    np.testing.assert_array_equal(rec[nz].view(np.int32), w[nz].view(np.int32))
    # a zero weight's limbs are zeros (the sign of a zero sum is +)
    np.testing.assert_array_equal(rec[~nz], 0.0)
    # each limb carries w's sign or is zero: no limb cancels another
    for limb in (hi, mid, lo):
        assert np.all((limb == 0) | (np.sign(limb) == np.sign(w)))


def test_plane_dot_of_signed_one_hot_plane_is_exact():
    """Row i of the plane picks weight row i with a sign: the result is that
    row negated or not, bit for bit, at the full 24-bit significand."""
    w = _weights()[: 64 * 96].reshape(64, 96)
    sign = np.where(np.arange(64) % 3 == 0, -1, 1).astype(np.int8)
    plane = jnp.asarray(np.diag(sign))
    got = np.asarray(jax.jit(dc.plane_dot)(plane, dc.weight_limbs(jnp.asarray(w))))
    want = sign[:, None].astype(np.float32) * w
    nz = want != 0
    np.testing.assert_array_equal(got[nz].view(np.int32), want[nz].view(np.int32))
    np.testing.assert_array_equal(got[~nz], 0.0)


# ---------------------------------------------------------------------------
# engagement: what the kernel bodies' MXU dots take
# ---------------------------------------------------------------------------


def _sub_jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _eqns(sub)


def _kernel_dots(fn, *args):
    """Operand dtypes of every dot_general in each pallas_call body of
    ``fn(*args)``, one list per kernel."""
    outer = jax.make_jaxpr(fn)(*args).jaxpr
    kernels = [e for e in _eqns(outer) if e.primitive.name == "pallas_call"]
    assert kernels
    return [
        [
            tuple(v.aval.dtype for v in e.invars)
            for e in _eqns(k.params["jaxpr"])
            if e.primitive.name == "dot_general"
        ]
        for k in kernels
    ]


def _operands(packed: bool):
    D, M, T, N = 9, 16, 24, 16
    rng = np.random.default_rng(1)
    planes = jnp.asarray(rng.integers(-1, 2, (D, M, T)).astype(np.int8))
    w = jnp.asarray(rng.standard_normal((T, N)).astype(np.float32))
    scales = jnp.exp2(-jnp.arange(D, dtype=jnp.float32))
    return (dig.pack_planes(planes) if packed else planes), w, scales


# (kernel, takes packed planes)
KERNELS = {
    "conv_unpacked": (dc.dslr_conv2d_planes_mxu, False),
    "conv_packed": (dc.dslr_conv2d_planes_packed_mxu, True),
    "matmul_unpacked": (dm.dslr_matmul_planes, False),
    "matmul_packed": (dm.dslr_matmul_planes_packed, True),
}


@pytest.mark.parametrize("skip", [True, False], ids=["skip", "dense"])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_kernel_runs_three_bf16_passes_per_digit_step(kernel, skip):
    fn, packed = KERNELS[kernel]
    kw = dict(block_m=8, block_n=8, skip_zero_planes=skip, interpret=True)
    (dots,) = _kernel_dots(lambda *a: fn(*a, **kw), *_operands(packed))
    assert len(dots) == 3, dots
    assert all(dt == (jnp.bfloat16, jnp.bfloat16) for dt in dots), dots
    assert not any(jnp.float32 in dt for dt in dots), dots
