"""The layers of a configuration's network, walked from its JSON description.

A configuration file describes its network the way the paper that defines it
does, independent of how the program constructs its graph:

* ``"kind": "sequential"`` — ``layers`` is a list of ``{"conv": [k, cout,
  stride, pad]}`` and ``{"maxpool": [window, stride, pad]}`` in order
  (VGG-16, AlexNet), every conv followed by bias and ReLU;
* ``"kind": "resnet_basic"`` — a ``stem`` conv and max-pool, then ``stages``
  of ``[channels, blocks, first_stride]`` basic blocks (two 3x3 convs, a
  residual add and a ReLU), with a 1x1 projection shortcut where a block
  changes stride or width (ResNet-18, option B).

Both end in global average pooling and a dense head of ``num_classes``.
Convs are named ``C1, C2, ...`` in execution order and a projection shortcut
after the first conv of its block (``C6.ds``): the names the program's
parameter tree uses.  The weights, the plain reference and the work counts
all walk the same list.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple, Union


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    k: int
    cin: int
    cout: int
    stride: int
    pad: int
    h_in: int
    w_in: int

    @property
    def h_out(self) -> int:
        return (self.h_in + 2 * self.pad - self.k) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w_in + 2 * self.pad - self.k) // self.stride + 1

    @property
    def macs(self) -> int:
        """Multiply-accumulates per image."""
        return self.h_out * self.w_out * self.cout * self.k * self.k * self.cin


@dataclasses.dataclass(frozen=True)
class Pool:
    window: int
    stride: int
    pad: int


@dataclasses.dataclass(frozen=True)
class Block:
    """One residual basic block: ``relu(b(relu(a(x))) + shortcut(x))``."""

    a: Conv
    b: Conv
    ds: Union[Conv, None]


@dataclasses.dataclass(frozen=True)
class Head:
    cin: int
    num_classes: int


Step = Union[Tuple[Conv, bool], Pool, Block]  # (conv, relu) | pool | block


def _pool_out(h: int, p: Pool) -> int:
    return (h + 2 * p.pad - p.window) // p.stride + 1


def walk(config: dict) -> Tuple[List[Step], Head]:
    """The network of ``config`` as an ordered list of steps, and its head."""
    arch = config["architecture"]
    h, w, c = config["image"]
    steps: List[Step] = []
    n = 0

    def conv(k, cout, stride, pad, name=None):
        nonlocal n, h, w, c
        if name is None:
            n += 1
            name = f"C{n}"
        layer = Conv(name, k, c, cout, stride, pad, h, w)
        h, w, c = layer.h_out, layer.w_out, cout
        return layer

    def pool(window, stride, pad):
        nonlocal h, w
        p = Pool(window, stride, pad)
        h, w = _pool_out(h, p), _pool_out(w, p)
        return p

    if arch["kind"] == "sequential":
        for item in arch["layers"]:
            (op, args), = item.items()
            if op == "conv":
                steps.append((conv(*args), True))
            elif op == "maxpool":
                steps.append(pool(*args))
            else:
                raise ValueError(f"unknown layer {op!r} in a sequential network")
    elif arch["kind"] == "resnet_basic":
        stem = arch["stem"]
        steps.append((conv(*stem["conv"]), True))
        steps.append(pool(*stem["maxpool"]))
        for channels, blocks, first_stride in arch["stages"]:
            for i in range(blocks):
                stride = first_stride if i == 0 else 1
                h0, w0, c0 = h, w, c
                a = conv(3, channels, stride, 1)
                b = conv(3, channels, 1, 1)
                ds = None
                if stride != 1 or c0 != channels:
                    ds = Conv(f"{a.name}.ds", 1, c0, channels, stride, 0, h0, w0)
                steps.append(Block(a, b, ds))
    else:
        raise ValueError(f"unknown architecture kind {arch['kind']!r}")
    return steps, Head(c, int(config["num_classes"]))


def convs(config: dict) -> List[Conv]:
    """Every weight-carrying conv in execution order, shortcuts included."""
    steps, _ = walk(config)
    out: List[Conv] = []
    for s in steps:
        if isinstance(s, Block):
            out += [s.a, s.b] + ([s.ds] if s.ds is not None else [])
        elif isinstance(s, tuple):
            out.append(s[0])
    return out
