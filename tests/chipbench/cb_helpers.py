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
