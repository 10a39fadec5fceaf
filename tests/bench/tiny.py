"""Small configurations of the benchmark's networks, for CPU tests: the
published architectures with every width scaled as ``CnnConfig(width=...)``
scales it, on small images.

With 4 to 25 channels a layer averages away less of the 8-digit quantization
error than at full width, so a small configuration states its own limit on
the widest relative logit gap, set as the full-size one was: between what
sound runs read on the CPU (0.008 to 0.037 over 11 seeds at widths 0.05 and
0.1) and what the 4-digit control reads (0.14 to 0.32)."""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TINY_LIMIT = 0.05


def scaled(c: int, width: float) -> int:
    return max(4, int(c * width))


def tiny(name: str, width: float = 0.05, image: int = 16, buckets=(1, 2)) -> dict:
    cfg = copy.deepcopy(json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text()))
    arch = cfg["architecture"]
    if arch["kind"] == "resnet_basic":
        arch["stem"]["conv"][1] = scaled(arch["stem"]["conv"][1], width)
        arch["stages"] = [[scaled(c, width), n, s] for c, n, s in arch["stages"]]
    else:
        for layer in arch["layers"]:
            if "conv" in layer:
                layer["conv"][1] = scaled(layer["conv"][1], width)
    cfg.update(width=width, num_classes=10, image=[image, image, 3], buckets=list(buckets),
               limits={"max_rel_logit_err": TINY_LIMIT})
    return cfg
