"""The plain float32 forward pass that decides ``correct``.

Written from the published architectures (``bench/arch.py`` walks the
configuration file), in straightforward ``jax.numpy`` / ``lax`` at the
highest matmul precision: no digit planes, no quantization, no kernels, no
batching logic, and nothing imported from the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench import arch


def _conv(x, params, c: arch.Conv, precision):
    y = lax.conv_general_dilated(
        x,
        params[c.name]["w"],
        window_strides=(c.stride, c.stride),
        padding=[(c.pad, c.pad), (c.pad, c.pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision,
    )
    return y + params[c.name]["b"]


def _maxpool(x, p: arch.Pool):
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        (1, p.window, p.window, 1), (1, p.stride, p.stride, 1),
        [(0, 0), (p.pad, p.pad), (p.pad, p.pad), (0, 0)],
    )


def forward(config: dict, params, x, precision=lax.Precision.HIGHEST):
    """Logits ``(B, num_classes)`` of images ``x`` ``(B, H, W, C)``.

    ``precision`` is for the control alone (``bench/calibrate.py``): the
    reference computed one step below the float32 it decides with."""
    steps, _ = arch.walk(config)
    conv = functools.partial(_conv, precision=precision)
    for s in steps:
        if isinstance(s, arch.Pool):
            x = _maxpool(x, s)
        elif isinstance(s, arch.Block):
            y = conv(jax.nn.relu(conv(x, params, s.a)), params, s.b)
            skip = x if s.ds is None else conv(x, params, s.ds)
            x = jax.nn.relu(y + skip)
        else:
            layer, relu = s
            x = conv(x, params, layer)
            x = jax.nn.relu(x) if relu else x
    x = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    return jnp.dot(x, head["kernel"], precision=precision) + head["bias"]

