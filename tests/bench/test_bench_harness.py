"""The harness finds every configuration, traffic mix and metric by name, in
files of their own, so a new cell is new files plus entries in
``BENCHMARK.json``; and ``bench/run.py`` prints no result off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import generator, harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def spec():
    return harness.load_benchmark(ROOT)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "bench" / "configs" / "alexnet.json").write_text(json.dumps({"network": "alexnet"}))
    (tmp_path / "bench" / "traffic" / "exact.bursty.json").write_text(json.dumps({"loop": "open"}))
    (tmp_path / "bench" / "metrics" / "queue_wait_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    cell = "alexnet.exact.bursty"
    s = {"configs": [{"name": "alexnet", "file": "bench/configs/alexnet.json"}],
         "end_to_end": [], "per_layer": [{"name": "queue_wait_ms", "workloads": [cell]}]}
    assert harness.load_config(s, "alexnet", tmp_path) == {"network": "alexnet"}
    assert harness.load_traffic("exact.bursty", tmp_path) == {"loop": "open"}
    assert harness.metric_reader("queue_wait_ms", tmp_path)(None) == 1.5
    assert [m["name"] for m in harness.cell_metrics(s, cell, True)] == ["queue_wait_ms"]
    assert harness.cell_metrics(s, "other", True) == []


def test_unknown_names_are_refused(tmp_path):
    s = spec()
    with pytest.raises(SystemExit, match="unknown configuration"):
        harness.load_config(s, "alexnet")
    with pytest.raises(SystemExit, match="unknown workload"):
        harness.find(s["workloads"], "resnet18.exact.nothing", "workload")
    with pytest.raises(SystemExit, match="unknown traffic mix"):
        harness.load_traffic("exact.nothing")
    with pytest.raises(SystemExit, match="unknown metric"):
        harness.metric_reader("nothing_ms")


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(ValueError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_every_entry_has_its_files():
    s = spec()
    assert s["command"] == ["python3", "bench/run.py"]
    for c in s["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["limits"]["max_rel_logit_err"] is not None
    names = {c["name"] for c in s["configs"]}
    for w in s["workloads"]:
        assert w["config"] in names
        harness.load_traffic(w["traffic"])
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        harness.metric_reader(m["name"])
    for w in s["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(s, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(s, w["name"], True)
        assert layer and all(m["moves"] in e2e for m in layer), w["name"]


def _run(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ("--workload", "resnet18.exact.closed16", "--seed", "3", "--seconds", "1", "--trace", "0")


def test_run_refuses_the_cpu():
    proc = _run(ROOT, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr, proc.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec()["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, *ARGS)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    probe = subprocess.run(
        [sys.executable, "-c", "import sys; from pathlib import Path; sys.path[0] = '.'; "
         "from bench import harness; harness._program(Path('.'))"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert probe.returncode != 0 and "ModuleNotFoundError" in probe.stderr, probe.stderr


def test_open_loop_seeds_offer_the_same_work():
    t = {"rate_per_s": 50}
    a, b = generator.arrivals(t, 10.0, 1), generator.arrivals(t, 10.0, 2**40 + 1)
    assert len(a) == len(b) == 500 and a[0] == b[0] == 0.0
    assert not np.array_equal(a, b)
    assert a.max() < 10.0 and b.max() < 10.0 and np.all(np.diff(a) >= 0)
    # the same gaps in another order: the gap left after the last arrival
    # and the sorted inner gaps together are one multiset
    ga = np.sort(np.append(np.diff(a), 10.0 - a[-1]))
    gb = np.sort(np.append(np.diff(b), 10.0 - b[-1]))
    np.testing.assert_allclose(ga, gb, rtol=1e-9, atol=1e-12)


def test_nearest_rank_percentile_reads_an_observed_value():
    v = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert generator.percentile(v, 50) == 3.0
    assert generator.percentile(v, 95) == 5.0
    assert generator.percentile(v, 20) == 1.0


def test_longest_gap_between_answers_spans_the_window_edges():
    reqs = [generator.Request(i, 0, 0.0, done=t, outcome="ok") for i, t in enumerate([0.5, 0.75])]
    reqs.append(generator.Request(2, 0, 0.0, done=3.0, outcome="ok"))  # after the close
    reqs.append(generator.Request(3, 0, 0.0, done=0.1, outcome="shed"))
    assert generator.Drive(reqs, 2.0, 0.0).longest_gap_s() == 1.25
    assert generator.Drive([], 2.0, 0.0).longest_gap_s() == 2.0


def test_gc_pauses_are_recorded_only_while_entered():
    import gc

    with harness.GcPauses() as pauses:
        gc.collect()
    gc.collect()
    assert pauses.longest()[0] == 2 and len(pauses.pauses) == 1
    assert harness.GcPauses().longest() == (None, 0.0)
