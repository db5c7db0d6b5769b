"""A four-chip cell on four virtual CPU devices, in a process of its own
(the device count is fixed when JAX starts): the sharded engine serves it
correctly, and a run whose answers skip the exchange between chips is not
correct."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest
from cb_helpers import REPO

SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import jax, jax.numpy as jnp
from cb_helpers import make_root
from pathlib import Path
from chipbench import harness

harness.check_devices = lambda chips: jax.devices()
harness.enable_compile_cache = lambda root: "off"
root = make_root(Path({tmp!r}))
bench = json.loads((root / "BENCHMARK.json").read_text())
cfg = json.loads((root / "chipbench/configs/tiny.json").read_text())
cfg["name"] = "tiny4"
cfg["serving"]["max_batch"] = 16
(root / "chipbench/configs/tiny4.json").write_text(json.dumps(cfg))
(root / "chipbench/traffic/closed64.json").write_text(json.dumps(
    {{"kind": "closed", "in_flight": 64, "buckets": [16], "warm_sizes": [16],
      "pool": 16, "dead_frac": 0.5}}))
bench["configs"].append({{"name": "tiny4", "file": "chipbench/configs/tiny4.json"}})
bench["workloads"].append({{"name": "tiny4.closed64", "config": "tiny4",
                            "traffic": "closed64", "chips": 4}})
(root / "BENCHMARK.json").write_text(json.dumps(bench))

if {fault!r} == "no_exchange":
    from repro.serving import engine as eng
    make = eng._make_runner

    def broken(plan, mesh=None):
        run_ = make(plan, mesh)

        def run2(params, imgs, n_valid):
            logits, occs = run_(params, imgs, n_valid)
            local = logits.shape[0] // mesh.shape["data"]
            # every chip's answers replaced by the first chip's own
            return jnp.tile(logits[:local], (mesh.shape["data"], 1)), occs

        return run2

    eng._make_runner = broken
out = harness.run_cell(root, "tiny4.closed64", 2**31 + 5, 0.6, False,
                       time.monotonic())
print(json.dumps({{"correct": out["correct"], "count": out["device"]["count"],
                  "attempted": out["attempted"],
                  "err": out["checks"]["logit_err"]["value"]}}))
"""


def _run(tmp_path, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = str(REPO / "src")
    code = SCRIPT.format(repo=str(REPO), tests=str(REPO / "tests/chipbench"),
                         tmp=str(tmp_path), fault=fault)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [(None, True), ("no_exchange", False)])
def test_four_chip_cell_on_virtual_devices(tmp_path, fault, correct):
    out = _run(tmp_path, fault)
    assert out["count"] == 4
    assert out["attempted"] >= 64
    assert out["correct"] is correct, out
