"""The packed-interchange conv path: kernel, engine, and traffic guarantees.

Checks, in interpret mode on CPU:
  * the packed Pallas kernel (``dslr_conv2d_planes_packed_mxu``) is bitwise
    identical to the unpacked kernel and to both ref oracles across kernel
    size / stride / padding / recoding / block shapes / digit budgets
    (including budgets that are not nibble-aligned),
  * the fused bias+ReLU epilogue and per-sample row scales survive packing
    unchanged (bitwise),
  * engine-level: packed vs unpacked logits are bitwise identical on the
    AlexNet / VGG-16 / ResNet-18 topologies, per-tensor and per-sample
    scales, with and without the fused epilogue,
  * the roofline claims, via the kernel traffic model (kernels/traffic.py,
    which replays the kernels' own index maps): the stationary weight tile
    is never re-fetched across the digit axis, and dead digit groups issue
    no tile load,
  * the packed path still compiles to one Pallas launch per conv layer
    (jaxpr inspection — the epilogue fusion survives the rework).
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import digits as dig
from repro.core import dslr as core_dslr
from repro.kernels import dslr_conv2d as dc
from repro.kernels import ops, ref, traffic, tuning
from repro.models import common as cm
from repro.models.engine import compile_cnn, execute_graph
from repro.models.graph import CnnConfig, ExecutionPolicy, graph_spec


def rand_conv(seed, B=1, H=8, W=8, Cin=3, Cout=4, K=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, H, W, Cin)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, K, Cin, Cout)).astype(np.float32))
    return x, w


# ---------------------------------------------------------------------------
# packed kernel vs oracles (bitwise)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", [0, 1])
def test_packed_matches_unpacked_bitwise(K, stride, padding):
    x, w = rand_conv(K * 10 + stride, B=2, H=9, W=7, Cin=3, Cout=5, K=K)
    pk = ops.dslr_conv2d_planes(x, w, n_digits=8, stride=stride, padding=padding,
                                packed=True)
    up = ops.dslr_conv2d_planes(x, w, n_digits=8, stride=stride, padding=padding,
                                packed=False)
    want = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, stride=stride,
                                      padding=padding)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(up))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(want))


@pytest.mark.parametrize("recoding", ["greedy", "csd", "binary"])
def test_packed_all_recodings_bitwise(recoding):
    x, w = rand_conv(7)
    pk = ops.dslr_conv2d_planes(x, w, n_digits=8, padding=1, recoding=recoding)
    want = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1,
                                      recoding=recoding, packed=True)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(want))


def test_packed_ref_equals_unpacked_ref():
    """Packing is a bijection: the packed oracle IS the unpacked oracle."""
    x, w = rand_conv(3, B=2, H=10, W=10, Cin=4, Cout=6)
    for budget in (None, 3, 5):
        a = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1,
                                       digit_budget=budget, packed=True)
        b = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1,
                                       digit_budget=budget, packed=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("bm,bn", [(8, 8), (16, 128), (128, 16)])
def test_packed_block_shapes_bitwise(bm, bn):
    x, w = rand_conv(3, B=2, H=10, W=10, Cin=4, Cout=6)
    want = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1)
    got = ops.dslr_conv2d_planes(x, w, n_digits=8, padding=1,
                                 block_m=bm, block_n=bn, packed=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8, 9])
def test_packed_budgets_nibble_truncation_bitwise(k):
    """Budgets that are NOT multiples of 4 exercise the residual bits of the
    last byte group — the kernel must never unpack digits beyond the budget."""
    x, w = rand_conv(13)
    got = ops.dslr_conv2d_planes(x, w, n_digits=8, padding=1, digit_budget=k,
                                 packed=True)
    want = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1, digit_budget=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("per_sample", [False, True])
@pytest.mark.parametrize("relu", [False, True])
def test_packed_fused_epilogue_and_row_scales_bitwise(per_sample, relu):
    x, w = rand_conv(21, B=3, H=8, W=8, Cin=4, Cout=4)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(4), jnp.float32)
    got = ops.dslr_conv2d_planes(x, w, n_digits=8, padding=1, bias=b, relu=relu,
                                 per_sample=per_sample, packed=True)
    want = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1, bias=b,
                                      relu=relu, per_sample=per_sample)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packed_tables_longer_than_one_smem_row_bitwise():
    """The flat (Mt * D,) scalar-prefetch tables past one 128-word row: 36
    row tiles of 8 at 9 digits, 324 slots, with the lower half of each image
    zero, so dead tiles (every group dead, the fetch clamped to group 0)
    sit beside live ones."""
    x, w = rand_conv(29, B=2, H=12, W=12, Cin=3, Cout=5)
    x = x.at[:, 6:].set(0.0)
    b = jnp.asarray(np.random.default_rng(4).standard_normal(5), jnp.float32)
    bm, D = 8, 9
    _, act, _, M, _ = _packed_patches_and_activity(x, w, 8, 1, bm)
    assert act.size == (M // bm) * D > 128
    assert act.any(axis=1).sum() < act.shape[0] - 4  # dead tiles past slot 128 too
    got = ops.dslr_conv2d_planes(x, w, n_digits=8, padding=1, bias=b, relu=True,
                                 per_sample=True, block_m=bm, packed=True)
    want = ref.dslr_conv2d_planes_ref(x, w, n_digits=8, padding=1, bias=b,
                                      relu=True, per_sample=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packed_skip_toggle_identical():
    x, w = rand_conv(5)
    a = ops.dslr_conv2d_planes(x, w, padding=1, packed=True, skip_zero_planes=True)
    b = ops.dslr_conv2d_planes(x, w, padding=1, packed=True, skip_zero_planes=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine-level bitwise identity (AlexNet / VGG-16 / ResNet-18)
# ---------------------------------------------------------------------------


def _engine_pair(net, **policy_kw):
    cfg = CnnConfig(name=net, width=0.02, num_classes=3)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(0))
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 12, 12, 3)), jnp.float32
    )
    pol = ExecutionPolicy(**policy_kw)
    e_pk = compile_cnn(cfg, params, pol)
    e_up = e_pk.with_policy(dataclasses.replace(pol, packed=False))
    return e_pk, e_up, x


@pytest.mark.parametrize("net", ["alexnet", "vgg16", "resnet18"])
@pytest.mark.parametrize("per_sample", [False, True])
def test_engine_packed_bitwise_identical_logits(net, per_sample):
    e_pk, e_up, x = _engine_pair(net, per_sample_scales=per_sample)
    np.testing.assert_array_equal(np.asarray(e_pk(x)), np.asarray(e_up(x)))


@pytest.mark.parametrize("per_sample", [False, True])
def test_engine_packed_bitwise_unfused_epilogue(per_sample):
    e_pk, e_up, x = _engine_pair(
        "alexnet", per_sample_scales=per_sample, fuse_epilogue=False
    )
    np.testing.assert_array_equal(np.asarray(e_pk(x)), np.asarray(e_up(x)))


def test_engine_packed_per_layer_budgets_bitwise():
    e_pk, e_up, x = _engine_pair("resnet18", digit_budget=5)
    np.testing.assert_array_equal(np.asarray(e_pk(x)), np.asarray(e_up(x)))


def test_packed_path_still_one_launch_per_conv():
    """The epilogue fusion survives the packed rework (jaxpr inspection)."""
    from tests.test_engine import _find_eqns

    cfg = CnnConfig(name="alexnet", width=0.02, num_classes=3)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(0))
    x = jnp.zeros((1, 12, 12, 3), jnp.float32)
    engine = compile_cnn(cfg, params, ExecutionPolicy(packed=True))
    jaxpr = jax.make_jaxpr(
        lambda xx: execute_graph(engine.graph, params, xx, engine.policy,
                                 engine._weights)
    )(x)
    launches = _find_eqns(jaxpr.jaxpr, "pallas_call", [])
    assert len(launches) == len(engine.graph.conv_nodes)


# ---------------------------------------------------------------------------
# traffic guarantees (grid/index-map inspection via the traffic model)
# ---------------------------------------------------------------------------


def _packed_patches_and_activity(x, w, n_digits, padding, bm):
    q = core_dslr.quantize_conv_planes(x, n_digits)
    patches = core_dslr.im2col_planes(dig.pack_planes(q.planes), w.shape[0], 1,
                                      padding)
    G, B, Ho, Wo, T = patches.shape
    flat = patches.reshape(G, B * Ho * Wo, T)
    D = q.planes.shape[0]
    M = flat.shape[1]
    Mp = tuning.round_up(M, bm)
    if Mp != M:
        flat = jnp.pad(flat, ((0, 0), (0, Mp - M), (0, 0)))
    return flat, np.asarray(dig.packed_plane_activity(flat, D, bm)), D, M, T


def test_weight_tile_not_refetched_across_digit_axis():
    """The stationary weight fetch count depends only on the (m, n) tiling —
    doubling the digit budget must not add a single weight fetch."""
    M, N, T = 300, 260, 27
    counts = {}
    for D in (5, 9):
        tr = traffic.conv_planes_traffic(M, N, T, D, packed=True,
                                         activity=np.ones((3, D), np.int32),
                                         block_m=128, block_n=128)
        Mt, Nt, _ = tr.grid
        assert tr.weights.fetches == Mt * Nt
        counts[D] = tr.weights.fetches
    assert counts[5] == counts[9]
    # the unpacked path obeys the same stationarity (grid order unchanged)
    up = traffic.conv_planes_traffic(M, N, T, 9, packed=False,
                                     block_m=128, block_n=128)
    assert up.weights.fetches == counts[9]


def test_dead_digit_groups_issue_no_tile_load():
    """Digit planes 4.. forced to zero: byte groups 1 and 2 are dead for
    every tile, so the packed plane operand is fetched exactly once per
    (m, n) tile — and the kernel result is still bitwise exact."""
    rng = np.random.default_rng(0)
    D, M, T, N = 9, 48, 18, 8
    planes = rng.choice(np.array([-1, 0, 1], np.int8), size=(D, M, T))
    planes[4:] = 0  # only group 0 (digits 0..3) is live
    planes = jnp.asarray(planes)
    packed = dig.pack_planes(planes)
    scales = core_dslr.digit_scales(D)
    w = jnp.asarray(rng.standard_normal((T, N)).astype(np.float32))

    bm = 16
    act = np.asarray(dig.packed_plane_activity(packed, D, bm))
    tr = traffic.conv_planes_traffic(M, N, T, D, packed=True, activity=act,
                                     block_m=bm, block_n=128)
    Mt, Nt, _ = tr.grid
    assert tr.patches.fetches == Mt * Nt  # one live group, one load per tile
    # vs. the unpacked kernel, which pays a fetch per digit to discover death
    up = traffic.conv_planes_traffic(M, N, T, D, packed=False,
                                     block_m=bm, block_n=128)
    assert up.patches.fetches == Mt * Nt * D
    # and the skipped loads change nothing numerically
    got = dc.dslr_conv2d_planes_packed_mxu(packed, w, scales, block_m=bm,
                                           interpret=True)
    want = dc.dslr_conv2d_planes_mxu(planes, w, scales, block_m=bm,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fetch_indices_point_dead_groups_at_resident_block():
    act = np.zeros((2, 9), np.int32)
    act[0, [0, 8]] = 1  # tile 0: groups 0 and 2 live, group 1 dead
    act[1, 5] = 1  # tile 1: dead prefix (group 0), live group 1
    fetch = np.asarray(dc.plane_fetch_indices(jnp.asarray(act), 9))
    # tile 0: digits 4..7 (dead group 1) keep group 0 resident
    assert fetch[0].tolist() == [0, 0, 0, 0, 0, 0, 0, 0, 2]
    # tile 1: dead prefix clamps to 0; digit 8 (dead group 2) keeps group 1
    assert fetch[1].tolist() == [0, 0, 0, 0, 1, 1, 1, 1, 1]


def test_dead_group_fetch_classifier():
    """The only dead-group load is the tile-boundary dead-prefix clamp."""
    act = np.zeros((2, 9), np.int32)
    act[0, 0] = 1  # tile 0: group 0 live
    act[1, 5] = 1  # tile 1: group 0 dead (clamp load), group 1 live
    dead = traffic.packed_dead_group_fetches(16, 8, 4, 9, act,
                                             block_m=8, block_n=128)
    assert dead == 1
    act[1, 0] = 1  # make tile 1's group 0 live: no dead loads remain
    assert traffic.packed_dead_group_fetches(16, 8, 4, 9, act,
                                             block_m=8, block_n=128) == 0


def test_traffic_ratio_at_d9_at_least_3x():
    """The acceptance ratio on real digit data: >= 3x fewer patch-operand
    bytes at the full 9-plane budget (ceil(9/4) = 3 byte groups)."""
    x, w = rand_conv(11, B=1, H=12, W=12, Cin=4, Cout=8)
    tr = traffic.conv_traffic_for_input(x, w, n_digits=8, padding=1)
    ratio = tr["unpacked"].patches.bytes / tr["packed"].patches.bytes
    assert ratio >= 3.0, ratio
