"""The ``vgg16`` configuration's ``correct`` comes out true for the sound
program and false for its 4-digit control, as ``test_bench_correct.py`` checks
for ResNet-18: ``harness.run_cell`` on a small VGG-16 (config D's 13 convs and
5 pools at 0.05 of their widths, 32x32 images) served through ``DslrServer``
at the exact tier, with the benchmark's seeded weights, the look for a chip
skipped."""
import json
import time

from tiny import ROOT, tiny

from bench import harness


def run(config, seed=2**33 + 17):
    t = json.loads((ROOT / "bench" / "traffic" / "exact.closed16.json").read_text())
    t.update(clients=4, image_pool=12)
    return harness.run_cell({"name": "tiny", "chips": 1}, config, t, seed, 1.0, False,
                            time.perf_counter(), require_chip=False, log=lambda line: None)


def test_sound_program_is_correct():
    res = run(tiny("vgg16_d", image=32))
    assert res["check"]["max_rel_logit_err"]["value"] <= res["check"]["max_rel_logit_err"]["limit"]
    assert res["correct"] and res["attempted"] > 0 and res["failed"] == 0


def test_four_digit_control_is_not_correct():
    res = run(dict(tiny("vgg16_d", image=32), n_digits=4))
    assert not res["correct"] and res["attempted"] > 0 and res["failed"] == 0
