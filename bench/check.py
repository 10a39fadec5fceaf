"""The comparison that decides ``correct``.

Every answer the timed path returned for the configuration's tier — wave
assembly, bucket padding and per-row slicing included — is compared with the
plain float32 reference (``bench/reference.py``) run on the same image once
the window has closed.  The number compared is the widest relative logit
gap over all of them: ``max|served - reference| / max|reference|`` per
request, the worst request.  Requests that never answered, or answered with
an error, are counted against a limit of 0.  A request the server itself
flagged as degraded (a brown-out digit prefix) is a failure of service, not
a wrong answer, and is left out of the comparison.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

BLOCK = 8  # reference images per call: one compiled shape, bounded memory
_FORWARD: Dict[str, object] = {}  # one jitted reference per configuration


@dataclasses.dataclass
class Check:
    max_rel_logit_err: float
    compared: int
    missing: int
    errored: int
    limit: Optional[float]

    @property
    def correct(self) -> bool:
        return (
            self.limit is not None
            and self.compared > 0
            and self.missing == 0
            and self.errored == 0
            and self.max_rel_logit_err <= self.limit
        )

    def numbers(self) -> Dict[str, Dict[str, float]]:
        """Each number compared, beside its limit."""
        return {
            "max_rel_logit_err": {"value": self.max_rel_logit_err, "limit": self.limit},
            "missing": {"value": self.missing, "limit": 0},
            "errored": {"value": self.errored, "limit": 0},
        }


def reference_logits(config: dict, params, images: np.ndarray,
                     indices: List[int]) -> Dict[int, np.ndarray]:
    """Reference logits of the pool images ``indices``, in blocks."""
    key = json.dumps(config, sort_keys=True)
    if key not in _FORWARD:
        _FORWARD[key] = jax.jit(functools.partial(reference.forward, config))
    fwd = _FORWARD[key]
    out: Dict[int, np.ndarray] = {}
    for i in range(0, len(indices), BLOCK):
        idx = indices[i:i + BLOCK]
        block = images[idx]
        if len(idx) < BLOCK:
            pad = np.zeros((BLOCK - len(idx),) + block.shape[1:], block.dtype)
            block = np.concatenate([block, pad])
        logits = np.asarray(fwd(params, jnp.asarray(block)))
        out.update(zip(idx, logits[: len(idx)]))
    return out


def compare(config: dict, params, images: np.ndarray, requests, limit: Optional[float]) -> Check:
    answered = [r for r in requests if r.outcome == "ok"]
    ref = reference_logits(config, params, images, sorted({r.image for r in answered}))
    worst = 0.0
    for r in answered:
        want = ref[r.image]
        err = float(np.max(np.abs(r.logits - want)) / np.max(np.abs(want)))
        worst = max(worst, err if np.isfinite(err) else float("inf"))
    return Check(
        max_rel_logit_err=worst,
        compared=len(answered),
        missing=sum(r.outcome == "missing" for r in requests),
        errored=sum(r.outcome == "error" for r in requests),
        limit=limit,
    )
