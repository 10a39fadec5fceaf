"""``correct`` comes out true for the sound program and false for its control
and for each fault a serving cell can have, with the rest of a run driven as
on the chip (the look for a chip skipped), at a size a CPU test can hold.

The control is the program's own lower-precision path: activations at 4
MSDF digits (int4-class) where the configuration states 8 (int8-class).
The faults are planted under the timed path, in the engine's output: one
answer altered where it is produced, and half of each wave left uncomputed
(its rows answered with the other half's logits)."""
import json
import time

import jax.numpy as jnp
import pytest
from tiny import ROOT, tiny

from bench import harness
from repro.models.engine import DslrEngine


def traffic():
    t = json.loads((ROOT / "bench" / "traffic" / "exact.closed16.json").read_text())
    t.update(clients=4, image_pool=12)
    return t


def run(config, seed=2**33 + 11):
    return harness.run_cell({"name": "tiny", "chips": 1}, config, traffic(), seed, 1.0, False,
                            time.perf_counter(), require_chip=False, log=lambda line: None)


def test_sound_program_is_correct():
    res = run(tiny("resnet18"))
    assert res["check"]["max_rel_logit_err"]["value"] <= res["check"]["max_rel_logit_err"]["limit"]
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0


def test_four_digit_control_is_not_correct():
    res = run(dict(tiny("resnet18"), n_digits=4))
    assert not res["correct"] and res["failed"] == 0


def _one_answer_altered(logits):
    return logits.at[0, 0].add(0.5 * jnp.max(jnp.abs(logits[0])))


def _half_the_wave_left_out(logits):
    half = (logits.shape[0] + 1) // 2
    return jnp.concatenate([logits[:half], logits[: logits.shape[0] - half]])


@pytest.mark.parametrize("fault", [_one_answer_altered, _half_the_wave_left_out])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    call = DslrEngine.__call__
    monkeypatch.setattr(DslrEngine, "__call__", lambda self, x: fault(call(self, x)))
    res = run(tiny("resnet18"))
    assert not res["correct"]
    assert res["failed"] == 0  # every answer came: they are wrong, not missing


def test_open_loop_answers_every_arrival_and_flushes_the_last_wave():
    """The open-loop mix on the tiny server: every arrival is answered and
    correct, none shed, and the last wave, partial at an odd count, is
    flushed when the arrivals end instead of waiting out its 60 s dwell."""
    t = json.loads((ROOT / "bench" / "traffic" / "exact.poisson80.json").read_text())
    t.update(rate_per_s=7, image_pool=12)
    t0 = time.perf_counter()
    res = harness.run_cell({"name": "tiny", "chips": 1}, tiny("resnet18"), t, 2**33 + 5, 1.0,
                           False, t0, require_chip=False, log=lambda line: None)
    assert res["correct"] and res["attempted"] == 7 and res["failed"] == 0
    assert time.perf_counter() - t0 < 50.0
