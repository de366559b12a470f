"""``bench/run.py`` refuses to measure where it cannot: no TPU, or no
program in the checkout. Either way it exits non-zero and prints no
result."""
import os
import shutil
import subprocess
import sys

from bench.cells import ROOT, load_benchmark

CELL = load_benchmark()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", str(2 ** 31 + 7), "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_device_that_is_not_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
