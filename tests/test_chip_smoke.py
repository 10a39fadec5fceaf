"""``chip_smoke.py`` refuses to pass anywhere but on a TPU: under
``JAX_PLATFORMS=cpu`` it exits nonzero before building anything, and copied
alone into an empty directory it cannot import the program.  Neither run
prints the ``"ok": true`` line."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(script: pathlib.Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(script)],
        cwd=script.parent,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_chip_smoke_refuses_the_cpu():
    proc = _run_smoke(ROOT / "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "not a TPU" in proc.stderr, proc.stderr


def test_chip_smoke_fails_without_the_program(tmp_path):
    script = tmp_path / "chip_smoke.py"
    shutil.copy(ROOT / "chip_smoke.py", script)
    proc = _run_smoke(script)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "ModuleNotFoundError" in proc.stderr, proc.stderr
