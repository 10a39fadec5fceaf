"""The benchmark's plain float32 reference agrees with the program's own float
path (``ExecutionPolicy(mode="float")``) at a small width on the CPU, on the
weights the benchmark makes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import tiny

from bench import reference, weights
from repro.models.engine import compile_cnn
from repro.models.graph import CnnConfig, ExecutionPolicy


@pytest.mark.parametrize("name,image", [("resnet18", 16), ("vgg16", 32)])
def test_reference_matches_the_programs_float_path(name, image):
    cfg = tiny(name, image=image)
    params = weights.make(cfg, 2**33 + 3)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, image, image, 3))
    want = reference.forward(cfg, params, x)
    engine = compile_cnn(CnnConfig(name, cfg["width"], 10), params, ExecutionPolicy(mode="float"))
    with jax.default_matmul_precision("highest"):
        got = engine(x)
    assert got.shape == want.shape == (2, 10)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_reference_sees_the_biases():
    cfg = tiny("resnet18")
    params = weights.make(cfg, 1)
    x = jnp.ones((1, 16, 16, 3))
    zeroed = jax.tree_util.tree_map_with_path(
        lambda path, v: jnp.zeros_like(v) if path[-1].key in ("b", "bias") else v, params)
    assert not np.allclose(reference.forward(cfg, params, x), reference.forward(cfg, zeroed, x))


def test_reference_gap_reads_the_reference_against_itself():
    from bench import calibrate

    cfg = dict(tiny("resnet18"), image=[16, 16, 3])
    traffic = {"image_pool": 10}
    assert calibrate.reference_gap(cfg, traffic, 2**33 + 5, "HIGHEST") == 0.0
    gap = calibrate.reference_gap(cfg, traffic, 2**33 + 5, "DEFAULT")
    assert 0.0 <= gap < cfg["limits"]["max_rel_logit_err"]
