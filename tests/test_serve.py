"""Request-level serving: per-sample quantization scales + DslrServer.

The contracts under test, in interpret mode on CPU:

  * **Per-sample scales decouple batchmates** — with
    ``ExecutionPolicy(per_sample_scales=True)`` a batch containing one
    large-magnitude outlier image leaves every other sample's logits
    *bitwise identical* to serving it alone; the per-tensor path
    demonstrably fails the same assertion (the outlier raises the shared
    amax and coarsens everyone's digit grid).
  * The per-sample kernel paths (fused and unfused epilogue, truncated
    budgets, per-row quantize scales) match the pure-jnp ref oracles
    bit-for-bit.
  * **Ragged serving is exact** — ``engine.serve`` batches not divisible by
    the padding multiple produce bitwise the unpadded results, with and
    without per-sample scales.
  * **One compiled program per (bucket, policy)** — a mixed-bucket
    ``DslrServer`` run traces each (bucket, policy) program exactly once
    (asserted by counting ``execute_graph`` trace entries), and re-running
    the same traffic compiles nothing new.
  * **Anytime partials are sound** — each k-digit partial's reported error
    bound dominates the measured deviation from the full-budget result.
"""
import logging

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import common as cm
from repro.models import engine as engine_mod
from repro.models.engine import compile_cnn
from repro.models.graph import CnnConfig, ExecutionPolicy, graph_spec
from repro.serve import DEFAULT_SLOS, DslrServer, SloClass, slo_table


def setup(name="alexnet", width=0.05, classes=4, seed=0, B=3, img=16, outlier=None):
    cfg = CnnConfig(name=name, width=width, num_classes=classes)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(seed))
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal((B, img, img, 3)), jnp.float32
    )
    if outlier is not None:
        x = x.at[0].multiply(outlier)
    return cfg, params, x


# ---------------------------------------------------------------------------
# per-sample quantization scales
# ---------------------------------------------------------------------------


def test_outlier_batchmate_decoupling_per_sample_vs_per_tensor():
    """The acceptance contract: one outlier image must not perturb its
    batchmates under per-sample scales (bitwise), and must perturb them
    under per-tensor scales (the coupling the redesign removes)."""
    cfg, params, x = setup(outlier=1000.0)
    eng_ps = compile_cnn(cfg, params, ExecutionPolicy(per_sample_scales=True))
    batch = eng_ps(x)
    alone = jnp.concatenate([eng_ps(x[i : i + 1]) for i in range(x.shape[0])])
    np.testing.assert_array_equal(np.asarray(batch), np.asarray(alone))

    eng_pt = compile_cnn(cfg, params, ExecutionPolicy(per_sample_scales=False))
    batch_pt = eng_pt(x)
    alone_pt = jnp.concatenate([eng_pt(x[i : i + 1]) for i in range(x.shape[0])])
    # rows 1.. (non-outliers) must differ: the shared amax coarsened them
    assert bool(jnp.any(batch_pt[1:] != alone_pt[1:]))


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("budget", [None, 4])
def test_per_sample_conv_matches_ref_bitwise(fused, budget):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 8, 8, 3)), jnp.float32)
    x = x.at[0].multiply(1000.0)
    w = jnp.asarray(rng.standard_normal((3, 3, 3, 5)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal(5), jnp.float32)
    kw = dict(
        padding=1, digit_budget=budget, per_sample=True,
        bias=b if fused else None, relu=fused,
    )
    got = kops.dslr_conv2d_planes(x, w, **kw)
    want = kref.dslr_conv2d_planes_ref(x, w, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_msdf_quantize_per_row_scale_matches_ref():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((13, 7)), jnp.float32)
    scale = jnp.asarray(np.abs(rng.standard_normal(13)) + 0.5, jnp.float32)
    got = kops.msdf_quantize(x, scale, frac_bits=8)
    want = kref.msdf_quantize_ref(x, scale, frac_bits=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # per-row scales really differ from the shared-amax planes
    shared = kops.msdf_quantize(x, jnp.max(jnp.abs(x)), frac_bits=8)
    assert bool(jnp.any(got != shared))


def test_per_sample_policy_validation():
    with pytest.raises(ValueError):
        ExecutionPolicy(mode="float", per_sample_scales=True)
    with pytest.raises(ValueError):
        ExecutionPolicy(mode="dslr", per_sample_scales=True)


# ---------------------------------------------------------------------------
# ragged-batch serving (engine.serve shim)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("per_sample", [False, True])
@pytest.mark.parametrize("B", [3, 5])
def test_ragged_serve_bitwise_identical_to_unpadded(per_sample, B):
    """Batch sizes not divisible by the padding multiple: the zero-padded,
    sliced `serve` result equals the direct unpadded call bitwise — zero
    rows cannot raise the per-tensor amax, and per-sample rows quantize
    independently by construction."""
    cfg, params, x = setup(B=B, outlier=100.0 if per_sample else None)
    engine = compile_cnn(
        cfg, params,
        ExecutionPolicy(per_sample_scales=per_sample, serve_pad_to=4),
    )
    served = engine.serve(x)  # 3 -> 4, 5 -> 8: real padding
    np.testing.assert_array_equal(np.asarray(served), np.asarray(engine(x)))


# ---------------------------------------------------------------------------
# DslrServer: buckets, program cache, SLO classes
# ---------------------------------------------------------------------------


def _counting_execute_graph(monkeypatch):
    """Count jit traces: ``_jit_execute`` re-enters ``execute_graph`` once
    per trace; cached program executions never do."""
    calls = {"n": 0}
    real = engine_mod.execute_graph

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "execute_graph", counting)
    return calls


def test_one_program_per_bucket_policy_by_trace_counting(monkeypatch):
    # unique shapes/classes so this test owns its jit cache entries
    cfg, params, _ = setup(width=0.04, classes=5, img=10)
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    server = DslrServer(
        engine,
        slos=(),
        buckets=(1, 2),
        policies={
            "lo": ExecutionPolicy(digit_budget=3),
            "hi": ExecutionPolicy(digit_budget=6),
        },
    )
    calls = _counting_execute_graph(monkeypatch)
    rng = np.random.default_rng(0)

    def traffic():
        handles = []
        for tier in ("lo", "hi"):
            for _ in range(3):  # 3 requests -> chunks of 2 + 1 -> buckets 2, 1
                img = jnp.asarray(rng.standard_normal((10, 10, 3)), jnp.float32)
                handles.append(server.submit(img, slo=tier))
        server.flush()
        return handles

    traffic()
    # 2 buckets x 2 policies = 4 programs, each traced exactly once
    assert calls["n"] == 4, calls
    assert len(server.program_keys) == 4
    assert server.stats["dispatches"] == 4
    # the same mixed traffic again: every program comes from the jit cache
    handles = traffic()
    assert calls["n"] == 4, calls
    assert len(server.program_keys) == 4
    assert all(h.done() for h in handles)


def test_server_result_bitwise_matches_solo_engine_call():
    """Bucket padding + batch composition are invisible to a request: its
    served logits equal a solo engine call under the same policy, bitwise
    (per-sample scales on by default)."""
    cfg, params, x = setup(B=3, outlier=1000.0)
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    server = DslrServer(engine, buckets=(4,))  # forces one padded row
    handles = [server.submit(x[i], slo="exact") for i in range(3)]
    solo = server._engine_for(server.policy_for("exact"))
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(
            np.asarray(h.result()), np.asarray(solo(x[i : i + 1])[0])
        )
    assert server.stats["padded_rows"] == 1


def test_anytime_partial_bounds_dominate_measured_error():
    cfg, params, x = setup()
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    server = DslrServer(engine, buckets=(1, 2))
    h = server.submit(x[1], slo="exact", anytime=(1, 2, 4, 9))
    full = h.result()
    assert len(h.partials) == 4
    for p in h.partials:
        err = float(jnp.max(jnp.abs(p.logits - full)))
        assert err <= p.bound, (p.budget, err, p.bound)
        assert isinstance(p.top1, int)
    # the full-budget "partial" is the full result itself, bound exactly 0
    last = h.partials[-1]
    assert last.budget == 9 and last.bound == 0.0
    np.testing.assert_array_equal(np.asarray(last.logits), np.asarray(full))
    # bounds shrink as the prefix grows
    bounds = [p.bound for p in h.partials]
    assert bounds == sorted(bounds, reverse=True)


def test_slo_classes_resolve_via_planner():
    cfg, params, _ = setup()
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    server = DslrServer(engine)
    exact = server.policy_for("exact")
    assert exact.digit_budget is None and exact.layer_budgets is None
    fast, bal = server.policy_for("fast"), server.policy_for("balanced")
    assert fast.layer_budgets is not None and bal.layer_budgets is not None
    # a tighter cycle fraction never gets more digits anywhere
    for (_, kf), (_, kb) in zip(fast.layer_budgets, bal.layer_budgets):
        assert kf <= kb
    # every served tier carries per-sample scales by default
    assert fast.per_sample_scales and exact.per_sample_scales
    with pytest.raises(ValueError):
        server.policy_for("no_such_tier")
    with pytest.raises(ValueError):
        SloClass("bad", 1.5)
    with pytest.raises(ValueError):
        slo_table(DEFAULT_SLOS + (SloClass("fast", 0.1),))  # duplicate name


def test_server_validation_and_handle_api():
    cfg, params, x = setup()
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    with pytest.raises(ValueError):
        DslrServer(engine, buckets=())
    with pytest.raises(ValueError):
        DslrServer(engine, buckets=(4, 2))
    with pytest.raises(ValueError):
        DslrServer(compile_cnn(cfg, params, ExecutionPolicy(mode="float")))
    with pytest.raises(ValueError):
        DslrServer(engine, policies={"exact": ExecutionPolicy()})  # shadows SLO
    server = DslrServer(engine, buckets=(1, 2))
    with pytest.raises(ValueError):
        server.submit(x, slo="exact")  # batch, not a single image
    with pytest.raises(ValueError):
        server.submit(x[0], slo="exact", anytime=(99,))
    h = server.submit(x[0], slo="exact")
    assert not h.done()
    h.result()
    assert h.done() and isinstance(h.top1, int)
    assert h.partials == ()  # none requested


def test_warmup_precompiles_every_bucket_program(monkeypatch):
    cfg, params, _ = setup(width=0.04, classes=6, img=10)
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    server = DslrServer(
        engine, slos=(), buckets=(1, 2), policies={"only": ExecutionPolicy()}
    )
    calls = _counting_execute_graph(monkeypatch)
    assert server.warmup((10, 10, 3)) == 2  # 1 tier x 2 buckets
    assert calls["n"] == 2
    rng = np.random.default_rng(0)
    for _ in range(3):
        server.submit(jnp.asarray(rng.standard_normal((10, 10, 3)), jnp.float32),
                      slo="only")
    server.flush()
    assert calls["n"] == 2  # steady-state traffic compiles nothing new


def test_warmup_compiles_the_anytime_bounds_forward(caplog):
    # unique shapes/classes so this test owns its compile cache entries.  The
    # anytime bounds' calibration forward runs op by op; warmup must have
    # compiled its conv kernels, or the first served wave compiles them
    cfg, params, _ = setup(width=0.04, classes=7, img=11)
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    server = DslrServer(
        engine, slos=(), buckets=(2,), policies={"only": ExecutionPolicy()}
    )
    server.warmup((11, 11, 3), anytime=(2,))
    img = jnp.asarray(np.random.default_rng(0).standard_normal((11, 11, 3)), jnp.float32)
    with caplog.at_level(logging.WARNING, logger="jax"), jax.log_compiles(True):
        h = server.submit(img, slo="only", anytime=(2,))
        server.flush()
    assert len(h.partials) == 1 and h.partials[0].bound > 0.0
    kernels = [
        r.getMessage() for r in caplog.records
        if "Finished XLA compilation" in r.getMessage() and "dslr_conv2d" in r.getMessage()
    ]
    assert kernels == []
