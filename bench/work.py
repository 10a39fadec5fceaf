"""Operations and bytes of a configuration's forward pass, from its shapes.

The counts depend only on the network, never on how the program implements a
layer or how many digit planes it runs: an operation is one multiply or one
add of a multiply-accumulate (2 per MAC), activations and weights count one
byte each (the int8-class operands a digit plane carries) and conv outputs
four (float32).  The count stays the same whatever a later change does to
the kernels, so a roofline share built on it can only rise by doing the same
work faster.
"""
from __future__ import annotations

import dataclasses
from typing import List

from bench import arch


@dataclasses.dataclass(frozen=True)
class LayerWork:
    name: str
    ops: int  # per image
    act_bytes: int  # per image: input activations (1 B) + outputs (4 B)
    weight_bytes: int  # per call, whatever its batch


def conv_work(config: dict) -> List[LayerWork]:
    out = []
    for c in arch.convs(config):
        out.append(LayerWork(
            c.name,
            2 * c.macs,
            c.h_in * c.w_in * c.cin + 4 * c.h_out * c.w_out * c.cout,
            c.k * c.k * c.cin * c.cout,
        ))
    return out


def head_ops(config: dict) -> int:
    _, head = arch.walk(config)
    return 2 * head.cin * head.num_classes


def model_ops(config: dict) -> int:
    """Operations of one image's whole forward pass: convs and head."""
    return sum(l.ops for l in conv_work(config)) + head_ops(config)


def conv_min_seconds(config: dict, images: int, calls: int, peaks: dict) -> float:
    """The least time the chip could take for the conv layers of ``images``
    images run in ``calls`` waves: per layer, the larger of its operations
    over the int8 peak and its bytes over HBM bandwidth, summed."""
    total = 0.0
    for l in conv_work(config):
        t_ops = images * l.ops / peaks["int8_ops_per_s"]
        t_bytes = (images * l.act_bytes + calls * l.weight_bytes) / peaks["hbm_bytes_per_s"]
        total += max(t_ops, t_bytes)
    return total
