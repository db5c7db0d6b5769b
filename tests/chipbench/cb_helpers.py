"""Helpers of the chip benchmark's CPU tests: a throwaway benchmark root
with a tiny configuration."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_LAYERS = [
    {"op": "conv", "out": 16, "k": 3, "stride": 1, "pad": 1}, {"op": "relu"},
    {"op": "conv", "out": 16, "k": 3, "stride": 1, "pad": 1}, {"op": "relu"},
    {"op": "pool", "p": 2, "stride": 2},
    {"op": "conv", "out": 32, "k": 3, "stride": 1, "pad": 1}, {"op": "relu"},
    {"op": "pool", "p": 2, "stride": 2},
    {"op": "flatten"},
    {"op": "dense", "out": 32, "relu": True},
    {"op": "dense", "out": 10},
]


def tiny_config(limit: float) -> dict:
    """vgg19_96's file with a 4x16x16 input and three narrow convs."""
    cfg = json.loads((REPO / "chipbench/configs/vgg19_96.json").read_text())
    cfg.update(name="tiny", in_channels=4, image_size=16, layers=TINY_LAYERS,
               check={"logit_err": limit})
    return cfg


def make_root(base: Path, cfg: dict | None = None) -> Path:
    """A benchmark root holding BENCHMARK.json with one tiny cell (of `cfg`,
    by default `tiny_config(1e-3)`), its files, and copies of the real
    metric readers."""
    root = base / "bench"
    (root / "chipbench/configs").mkdir(parents=True)
    (root / "chipbench/traffic").mkdir(parents=True)
    shutil.copytree(REPO / "chipbench/metrics", root / "chipbench/metrics")
    (root / "chipbench/configs/tiny.json").write_text(
        json.dumps(cfg if cfg is not None else tiny_config(1e-3)))
    m = json.loads((REPO / "chipbench/traffic/closed32.json").read_text())
    m["pool"] = 16
    (root / "chipbench/traffic/closed32.json").write_text(json.dumps(m))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.closed32", "config": "tiny", "traffic": "closed32",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.closed32"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


# A model module as a configuration that is not a `layers` list brings it:
# its own shapes, weights, reference and graph, written out by hand.
MODULE_SRC = '''"""Two 3x3 convs, a 2x2 max-pool and a dense head, without a layers list."""
import math

import jax
import jax.numpy as jnp

from chipbench import cnn

WIDTHS = (8, 16)
CLASSES = 10
SCALE = {scale!r}


def layer_shapes(cfg):
    c, h, w = cnn.in_shape(cfg)
    c1, c2 = WIDTHS
    flat = c2 * (h // 2) * (w // 2)
    return (
        cnn.Layer("conv", 0, (c, h, w), (c1, h, w), (c1, h, w), (c1, c, 3, 3)),
        cnn.Layer("conv", 1, (c1, h, w), (c2, h, w), (c2, h // 2, w // 2),
                  (c2, c1, 3, 3)),
        cnn.Layer("dense", 0, (flat,), (CLASSES,), (CLASSES,), (flat, CLASSES)))


def make_weights(cfg, seed):
    shapes = [lyr.weight_shape for lyr in layer_shapes(cfg)]

    def build(key):
        keys = jax.random.split(key, len(shapes))
        ws = [jax.random.normal(k, s)
              / math.sqrt(math.prod(s[1:]) if len(s) == 4 else s[0])
              for k, s in zip(keys, shapes)]
        return {{"conv": ws[:2], "dense": ws[2:]}}

    return jax.jit(build)(cnn.seed_key(seed))


def forward(cfg, params, x, operand_dtype=None):
    hi = jax.lax.Precision.HIGHEST
    for w in params["conv"]:
        x = jax.lax.conv_general_dilated(
            cnn.operand(x, operand_dtype, (1, 2, 3)),
            cnn.operand(w, operand_dtype, (1, 2, 3)), (1, 1), ((1, 1), (1, 1)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=hi)
        x = jnp.maximum(x, 0.0)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 2, 2),
                              (1, 1, 2, 2), "VALID")
    x = x.reshape(x.shape[0], -1)
    return SCALE * jnp.dot(cnn.operand(x, operand_dtype, (1,)),
                           cnn.operand(params["dense"][0], operand_dtype, (0,)),
                           precision=hi)


def layer_graph(cfg):
    from repro.graph.ir import ConvSpec, DenseSpec, Flatten, LayerGraph, PoolSpec, ReLU

    c1, c2 = WIDTHS
    return LayerGraph(name=cfg["name"], in_shape=cnn.in_shape(cfg), nodes=(
        ConvSpec(c1, k=3, pad=1), ReLU(), ConvSpec(c2, k=3, pad=1), ReLU(),
        PoolSpec(2), Flatten(), DenseSpec(CLASSES)))
'''
MODULE = "chipbench/models/twoconv.py"


def make_module_root(base: Path, scale: float = 1.0) -> Path:
    """`make_root` with the tiny configuration given by a model module (its
    reference's logits multiplied by `scale`) in place of its layer list."""
    cfg = tiny_config(1e-3)
    del cfg["layers"], cfg["weights"]
    cfg["module"] = MODULE
    root = make_root(base, cfg)
    (root / "chipbench/models").mkdir()
    (root / MODULE).write_text(MODULE_SRC.format(scale=scale))
    return root
