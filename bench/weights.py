"""Seeded weights for a configuration, made on the device in one jitted call.

Convs are He-normal with fan-in ``K*K*Cin`` (the scale a trained network's
folded weights keep activations at); biases are small and nonzero so that a
dropped bias shows in the comparison with the reference; the head is
LeCun-normal.  The tree is keyed the way the program's parameter tree is:
``{"C1": {"w", "b"}, ..., "C6.ds": {...}, "head": {"kernel", "bias"}}``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

BIAS_STD = 0.1


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words from any whole-number seed: JAX's own ``PRNGKey``
    keeps only the low 32 bits, so seeds 5 and 2**33 + 5 would collide."""
    return np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)


def layout(config: dict):
    """``((name, kernel shape, bias shape), ...)`` for every weighted layer."""
    out = [(c.name, (c.k, c.k, c.cin, c.cout), (c.cout,)) for c in arch.convs(config)]
    _, head = arch.walk(config)
    out.append(("head", (head.cin, head.num_classes), (head.num_classes,)))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=0)
def _make(layout, data):
    keys = jax.random.split(jax.random.wrap_key_data(data), 2 * len(layout))
    out = {}
    for i, (name, wshape, bshape) in enumerate(layout):
        fan_in = math.prod(wshape[:-1])
        w = jax.random.normal(keys[2 * i], wshape) / math.sqrt(fan_in)
        b = BIAS_STD * jax.random.normal(keys[2 * i + 1], bshape)
        if name == "head":
            out[name] = {"kernel": w, "bias": b}
        else:  # He-normal: ReLU halves the variance a conv passes on
            out[name] = {"w": math.sqrt(2.0) * w, "b": b}
    return out


def make(config: dict, seed: int):
    """The float32 weight tree of ``config`` for ``seed``, on the default device."""
    return _make(layout(config), jnp.asarray(key_data(seed)))
