"""The command refuses to run, and prints no result, where it cannot
measure: no TPU, or no system under test beside the benchmark."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from chipbench import harness

REPO = harness.ROOT
ARGS = ["--workload", "vgg19_96.closed32", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_no_tpu_means_no_result(tmp_path):
    r = subprocess.run([sys.executable, str(REPO / "chipbench/run.py"), *ARGS],
                       capture_output=True, text=True, timeout=300,
                       env=_env(), cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths has
    no system to serve: the run fails past the chip check, with no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(tmp_path)!r})\n"
        "from chipbench import harness, run\n"
        "harness.check_devices = lambda chips: jax.devices()\n"
        f"sys.exit(run.main({ARGS!r}))\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=_env(), cwd=tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "repro" in r.stderr  # the system under test is what is missing
