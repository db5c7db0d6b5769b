"""The `googlenet_224` configuration's own model module: its work counted by
hand, its shapes against the program's graph, its weights' seed
permutation, a reference that imports nothing of the program, and a tiny
configuration of the same module through the harness on the CPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest
from cb_helpers import REPO, make_root, tiny_config

from chipbench import cnn, drive, harness, peaks, spans

MODULE = "chipbench/models/googlenet.py"
SEED = 2**31 + 41
WINDOW_S = 0.6


@pytest.fixture(scope="module")
def cfg():
    return cnn.load_config(REPO / "chipbench/configs/googlenet_224.json")


def tiny_googlenet(limit: float = 1e-3) -> dict:
    """googlenet_224's file at 32x32 with narrow widths and three modules."""
    full = json.loads((REPO / "chipbench/configs/googlenet_224.json").read_text())
    base = tiny_config(limit)
    for key in ("layers", "weights"):
        del base[key]
    base.update({k: full[k] for k in ("module", "lrn", "weights")})
    base.update(in_channels=3, image_size=32, stem=[8, 8, 16], classes=10,
                inception=[["3a", 8, 8, 16, 8, 8, 8], ["3b", 16, 8, 16, 8, 8, 8],
                           ["4a", 16, 8, 16, 8, 8, 8]],
                pool_after=["3b"])
    return base


def module_root(base, limit: float = 1e-3, edit=None):
    """A benchmark root whose tiny cell is `tiny_googlenet`, with a copy of
    the module (its source passed through `edit` first, where given)."""
    root = make_root(base, tiny_googlenet(limit))
    (root / "chipbench/models").mkdir()
    src = (REPO / MODULE).read_text()
    (root / MODULE).write_text(edit(src) if edit else src)
    return root


# ---------------------------------------------------------------------------
# the published network's work, by hand
# ---------------------------------------------------------------------------


def test_work_and_parameters_worked_out_by_hand(cfg):
    # Table 1, conv by conv (in channels, map, out channels, k): the stem,
    # then per module 1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj
    assert cnn.macs_per_image(cfg, "conv") == 1_581_647_872
    assert cnn.macs_per_image(cfg, "dense") == 1024 * 1000
    assert cnn.n_params(cfg) == 6_990_272
    pk = peaks.peaks("TPU v5 lite")
    # a batch of 8: per conv max(2 MACs x 8 / 197e12, f32 bytes (8 inputs, 8
    # outputs after conv1's pool, weights once) / 819e9), summed
    assert cnn.roofline_s(cfg, 8, pk) == pytest.approx(3.2016591986057033e-04,
                                                       rel=1e-12)
    # the FC reads its 4-MB weights: bound by bytes
    assert cnn.roofline_s(cfg, 8, pk, "dense") == pytest.approx(
        4 * (8 * (1024 + 1000) + 1024 * 1000) / 819e9, rel=1e-12)


def test_layer_shapes_agree_with_the_programs_graph(cfg):
    layers = cnn.layer_shapes(cfg)
    units = harness.layer_graph(cfg).units()
    convs = [lyr for lyr in layers if lyr.op == "conv"]
    assert len(convs) == len(units) == 57
    for lyr, u in zip(convs, units):
        assert lyr.in_shape == u.in_shape
        assert lyr.conv_shape == u.conv_out_shape
        assert lyr.out_shape == u.out_shape
        assert lyr.weight_shape == (u.conv.c_out, u.in_shape[0], u.conv.k,
                                    u.conv.k)
    assert layers[-1].weight_shape == (1024, 1000)


def test_every_seed_serves_the_same_function():
    """The seed permutes every conv's output channels but those of conv1
    and the stem's 3x3, which enter an LRN: other weights, the same logits."""
    tiny = tiny_googlenet()
    tiny[cnn.MODULE_PATH] = str(REPO / MODULE)
    a = cnn.make_weights(tiny, SEED)
    b = cnn.make_weights(tiny, 2**33 + 5)
    def rows(w):  # each filter's values, whatever order its inputs are in
        w = np.asarray(w)
        return np.sort(w.reshape(w.shape[0], -1), axis=1)

    kept = [i for i, (u, v) in enumerate(zip(a["conv"], b["conv"]))
            if np.array_equal(rows(u), rows(v))]
    assert kept == [0, 2]  # the two convs whose outputs enter an LRN
    for u, v in zip(a["conv"], b["conv"]):  # the same filters, reordered
        np.testing.assert_array_equal(np.sort(rows(u), axis=0),
                                      np.sort(rows(v), axis=0))
    x = drive.image_pool(cnn.in_shape(tiny), 4, 3, 0.5)
    ra = np.asarray(cnn.forward(tiny, a, x))
    rb = np.asarray(cnn.forward(tiny, b, x))
    np.testing.assert_allclose(rb, ra, rtol=1e-5, atol=1e-5 * np.abs(ra).max())


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

cfg = cnn.load_config(Path({repo!r}) / "chipbench/configs/googlenet_224.json")
shapes = cnn.layer_shapes(cfg)
params = cnn.make_weights(cfg, 5)
ref = np.asarray(cnn.forward(cfg, params, jnp.ones((1,) + cnn.in_shape(cfg))))
try:
    harness.layer_graph(cfg)
    graph = "built"
except ImportError as e:
    graph = str(e)
print(json.dumps({{"layers": len(shapes), "logits": list(ref.shape),
                  "finite": bool(np.isfinite(ref).all()), "graph": graph,
                  "repro": [m for m in sys.modules if m.split(".")[0] == "repro"]}}))
"""


def test_the_reference_imports_nothing_of_the_program(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))  # the program is there to find
    r = subprocess.run([sys.executable, "-c", BLOCKED.format(repo=str(REPO))],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["layers"] == 58 and out["logits"] == [1, 1000]
    assert out["finite"] is True and out["repro"] == []
    assert "belongs to the system under test" in out["graph"]


# ---------------------------------------------------------------------------
# a tiny configuration of the module through the harness
# ---------------------------------------------------------------------------


def test_a_branched_configuration_runs_through_the_harness(on_cpu, tmp_path,
                                                           capfd):
    root = module_root(tmp_path)
    out = on_cpu.run_cell(root, "tiny.closed32", SEED, WINDOW_S, False,
                          time.monotonic())
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["logit_err"]["value"] <= 1e-4
    plan = [ln for ln in capfd.readouterr().err.splitlines() if "plan:" in ln]
    assert plan and "conv21=" in plan[0] and "conv22=" not in plan[0]


# wrong references: each module's concat in another order, and the LRNs
# left out (visible because conv1 sees 0-255 pixels, `pixel_scale`)
CORRUPTIONS = {
    "concat_order": ("x = jnp.concatenate(outs, axis=1)",
                     "x = jnp.concatenate(outs[1:] + outs[:1], axis=1)"),
    "no_lrn": ("return x / (k + alpha / size * total) ** beta", "return x"),
}


@pytest.mark.parametrize("corruption", sorted(CORRUPTIONS))
def test_a_corrupted_branch_is_not_correct(on_cpu, tmp_path, corruption):
    """The reference with one part of the network wrong: the served answers
    disagree with it."""
    old, new = CORRUPTIONS[corruption]

    def edit(src):
        assert old in src
        return src.replace(old, new)

    root = module_root(tmp_path, edit=edit)
    out = on_cpu.run_cell(root, "tiny.closed32", SEED, WINDOW_S, False,
                          time.monotonic())
    assert out["correct"] is False
    assert out["checks"]["logit_err"]["value"] > 1e-2


def test_spans_attribute_every_conv_op_to_its_unit(tmp_path):
    """In the bucket program, every conv and ECR kernel op lies under its own
    conv<i>, inside its module's inception_<m> scope; `spans.LAYER` finds
    that conv<i>, and the ops between the units have their own scopes."""
    import jax
    import jax.numpy as jnp

    from repro.pipeline import plan_network
    from repro.serving.engine import _make_runner

    tiny = tiny_googlenet()
    tiny[cnn.MODULE_PATH] = str(REPO / MODULE)
    graph = harness.layer_graph(tiny)
    params = cnn.make_weights(tiny, SEED)
    calib = jnp.asarray(drive.image_pool(cnn.in_shape(tiny), 2, 0, 0.5))
    # ECR on the units that read more than one channel block, dense on conv1
    plan = plan_network(params, calib, graph, occ_threshold=0.99, block_c=8)
    assert {lp.impl for lp in plan.layers} == {"dense", "ecr_pallas"}
    text = jax.jit(_make_runner(plan)).lower(
        params, jnp.zeros((2,) + graph.in_shape), jnp.int32(2)).compile(
            ).as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # the CPU interprets the ECR kernel: its ops lie under jit(ecr_conv)
    convs = [n for n in names
             if "/jit(ecr_conv)/" in n or n.endswith("conv_general_dilated")]
    assert any("/jit(ecr_conv)/" in n for n in convs)
    assert any(n.endswith("conv_general_dilated") for n in convs)
    modules = dict(graph.joins())
    for n in convs:
        m = spans.LAYER.search(n)
        assert m, n
        i = int(m.group(1)[4:]) - 1
        outer = n.split("/")[1]
        if outer.startswith("inception_"):
            assert i in modules[outer], n
        else:
            assert outer == m.group(1), n
    top = {n.split("/")[1] for n in names if n.startswith("jit(run)/")}
    assert {"lrn1", "lrn2", "pool1", "avgpool", "head"} <= top
    assert any(n.startswith("jit(run)/inception_3b/concat/") for n in names)
