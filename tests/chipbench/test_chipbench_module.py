"""A configuration that brings its own model module: its weights, reference,
graph and shapes reach the harness and the readers, its reference decides
`correct`, and only its graph touches the system under test. The linear
configurations read as before."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest
from cb_helpers import MODULE, REPO, make_module_root

from chipbench import cnn, peaks

SEED = 2**31 + 23
WINDOW_S = 0.6


@pytest.fixture(scope="module")
def module_root(tmp_path_factory):
    return make_module_root(tmp_path_factory.mktemp("module"))


def test_a_module_configuration_runs_through_the_harness(on_cpu, module_root,
                                                         capfd):
    cell = on_cpu.find_cell(module_root, "tiny.closed32")
    cfg = cell.cfg
    assert "layers" not in cfg and "weights" not in cfg
    # the module's own shapes, weights and graph, not a layer list's
    assert [lyr.weight_shape for lyr in cnn.layer_shapes(cfg)] == [
        (8, 4, 3, 3), (16, 8, 3, 3), (16 * 8 * 8, 10)]
    params = cnn.make_weights(cfg, SEED)
    assert [w.shape for w in params["conv"]] == [(8, 4, 3, 3), (16, 8, 3, 3)]
    graph = on_cpu.layer_graph(cfg)
    assert [u.conv.c_out for u in graph.units()] == [8, 16]

    out = on_cpu.run_cell(module_root, "tiny.closed32", SEED, WINDOW_S, True,
                          time.monotonic())
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["logit_err"]["value"] <= 1e-3
    assert {"setup.plan_s", "setup.warmup_s"} <= set(out["metrics"])
    plan = [ln for ln in capfd.readouterr().err.splitlines() if "plan:" in ln]
    assert plan and "conv2=" in plan[0] and "conv3=" not in plan[0]


def test_the_readers_count_the_modules_work(on_cpu, module_root):
    """`conv_roofline` and `mfu` read the module's shapes through `cnn`."""
    cell = on_cpu.find_cell(module_root, "tiny.closed32")
    c1 = 8 * 4 * 9 * 16 * 16
    c2 = 16 * 8 * 9 * 16 * 16
    head = 16 * 8 * 8 * 10
    assert cnn.macs_per_image(cell.cfg, "conv") == c1 + c2
    assert cnn.macs_per_image(cell.cfg) == c1 + c2 + head
    assert cnn.n_params(cell.cfg) == 8 * 4 * 9 + 16 * 8 * 9 + head

    class Rec:
        done = {0: 0.5, 1: 0.5}
        formed = {0: 0.4, 1: 0.4}

    pk = peaks.peaks("TPU v5 lite")
    run = on_cpu.Run(cell=cell, seconds=1.0, peaks=pk, rec=Rec(),
                     trace={"conv_s": 1e-6, "window_s": 1.0, "host_t0": 0.0,
                            "host_t1": 1.0})
    load = on_cpu.load_module
    mfu = load(module_root / "chipbench/metrics/mfu.py", "mfu").read(run)
    roof = load(module_root / "chipbench/metrics/conv_roofline.py",
                "conv_roofline").read(run)
    assert mfu == pytest.approx(100 * 2 * (c1 + c2 + head) * 2 / pk["flops"])
    assert roof == pytest.approx(100 * cnn.roofline_s(cell.cfg, 2, pk) / 1e-6)


def test_a_wrong_reference_in_the_module_is_not_correct(on_cpu, tmp_path):
    root = make_module_root(tmp_path, scale=1.01)
    out = on_cpu.run_cell(root, "tiny.closed32", SEED, WINDOW_S, False,
                          time.monotonic())
    assert out["correct"] is False
    assert out["checks"]["logit_err"]["value"] == pytest.approx(0.01 / 1.01,
                                                                rel=1e-3)


BLOCKED = r"""
import json, sys
from pathlib import Path


class NoProgram:
    def find_spec(self, name, path=None, target=None):
        if name == "repro" or name.startswith("repro."):
            raise ImportError(f"{{name}} belongs to the system under test")


sys.meta_path.insert(0, NoProgram())
sys.path.insert(0, {repo!r})
import jax.numpy as jnp
import numpy as np
from chipbench import cnn, harness

root = Path({root!r})
cfg = cnn.load_config(root / "chipbench/configs/tiny.json", root)
shapes = cnn.layer_shapes(cfg)
params = cnn.make_weights(cfg, 5)
x = jnp.ones((2,) + cnn.in_shape(cfg))
ref = np.asarray(cnn.forward(cfg, params, x))
low = np.asarray(cnn.forward(cfg, params, x, jnp.int8))
try:
    harness.layer_graph(cfg)
    graph = "built"
except ImportError as e:
    graph = str(e)
print(json.dumps({{"layers": len(shapes), "logits": list(ref.shape),
                  "control_differs": bool(np.abs(low - ref).max() > 0),
                  "graph": graph,
                  "repro": [m for m in sys.modules if m.split(".")[0] == "repro"]}}))
"""


def test_the_modules_reference_imports_nothing_of_the_program(tmp_path):
    root = make_module_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))  # the program is there to find
    r = subprocess.run(
        [sys.executable, "-c", BLOCKED.format(repo=str(REPO), root=str(root))],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["layers"] == 3 and out["logits"] == [2, 10]
    assert out["control_differs"] is True
    assert out["repro"] == []
    assert "belongs to the system under test" in out["graph"]


def test_a_module_outside_the_root_is_refused(tmp_path):
    root = make_module_root(tmp_path)
    path = root / "chipbench/configs/tiny.json"
    cfg = json.loads(path.read_text())
    cfg["module"] = "../" + MODULE
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match="outside"):
        cnn.load_config(path, root)


# Dense-equivalent work at a batch of 8, as the parent of the model-module
# hook computed it: conv roofline 342.8 us (vgg19_96) and 55.4 us
# (alexnet_224) a batch, heads 195 and 287 us.
LINEAR = {
    "vgg19_96": (3_622_928_384, 59_766_464, 3.4282211928108436e-04,
                 1.9498666666666666e-04),
    "alexnet_224": (714_188_480, 61_090_496, 5.5354067717297934e-05,
                    2.873492161172161e-04),
    "vgg19_96_dp4": (3_622_928_384, 59_766_464, 3.4282211928108436e-04,
                     1.9498666666666666e-04),
}


@pytest.mark.parametrize("name", sorted(LINEAR))
def test_linear_configurations_read_as_before(name):
    cfg = cnn.load_config(REPO / f"chipbench/configs/{name}.json")
    macs, n_params, conv_s, head_s = LINEAR[name]
    pk = peaks.peaks("TPU v5 lite")
    assert cnn.model_module(cfg) is None
    assert cnn.macs_per_image(cfg) == macs
    assert cnn.n_params(cfg) == n_params
    assert cnn.roofline_s(cfg, 8, pk) == pytest.approx(conv_s, rel=1e-12)
    assert cnn.roofline_s(cfg, 8, pk, "dense") == pytest.approx(head_s,
                                                                rel=1e-12)
