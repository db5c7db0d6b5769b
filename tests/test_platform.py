"""CPU-side plumbing of the chip bring-up: the interpret-vs-Mosaic choice,
the chip smoke script's refusal to run off the TPU, the compile-cache
placement, and the peaks table keyed by device kind."""
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import synth_feature_map
from repro.kernels.ecr_conv.ops import ecr_conv
from repro.kernels.ecr_conv.ref import ecr_conv_ref
from repro.launch import compile_cache
from repro.obs.constants import (
    CPU_TEST_PRIOR,
    DEVICE_PEAKS,
    device_peaks,
    peaks_for,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_kernels_run_interpreted_on_cpu():
    x = synth_feature_map(jax.random.PRNGKey(0), (16, 10, 10), 0.5)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 3, 3))
    text = jax.jit(ecr_conv).lower(x, w).compile().as_text()
    assert "tpu_custom_call" not in text  # the interpreter's plain HLO
    np.testing.assert_allclose(np.asarray(ecr_conv(x, w)),
                               np.asarray(ecr_conv_ref(x, w)), atol=1e-4)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert seen == {"jax_compilation_cache_dir": str(tmp_path)}


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = pathlib.Path(compile_cache.compile_cache_dir())
    assert path == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_peaks_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite", "tpu")
    assert v5e is DEVICE_PEAKS["TPU v5 lite"]
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert peaks_for("cpu", "cpu") is CPU_TEST_PRIOR
    assert device_peaks() is CPU_TEST_PRIOR  # the tests run on the CPU
    with pytest.raises(KeyError, match="TPU v99"):
        peaks_for("TPU v99", "tpu")
