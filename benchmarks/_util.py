"""Benchmark helpers: timing, the paper's layer set, modeled-TPU time,
machine-readable result emission (BENCH_<name>.json)."""
from __future__ import annotations

import json
import os
import time

import jax

from repro.core import synth_feature_map

# the modeled-TPU columns price a v5e at its published peaks — ONE
# definition, in repro.obs.constants; a fitted obs.calibrate.CalibrationDB
# overrides them per (kind, impl) via the calibration= parameters
from repro.obs.constants import DEVICE_PEAKS  # noqa: E402

PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"].peak_flops
HBM_BW = DEVICE_PEAKS["TPU v5 lite"].hbm_bw


def time_fn(f, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall time (us) of a jitted callable — a thin wrapper over
    `repro.obs.profile.time_callable`, THE wall-time harness, so benchmark
    rows, autotune candidates, and profile measurements all enter the
    perf-history DB under one measurement discipline."""
    from repro.obs.profile import time_callable

    return time_callable(f, *args, iters=iters, warmup=warmup).median_us


def dead_band_calib(graph, n: int, seed: int = 0, dead_frac: float = 0.5):
    """(N,C,H,W) calibration batch with a shared dead trailing-channel band
    (the post-ReLU channel death the planner exploits; DESIGN.md §2.2) —
    the one calibration recipe the model-zoo and weight-sparsity sweeps
    share, so their plans are comparable. The first conv's input may be
    fully dense (3-channel images); deeper layers still go sparse from the
    net's own ReLU."""
    from repro.core import dead_channel_band

    c, h, w = graph.in_shape
    return dead_channel_band(
        jax.random.uniform(jax.random.PRNGKey(seed), (n, c, h, w)), dead_frac)


def serve_replay_point(engine, imgs, rate_rps: float):
    """Warm a serving engine, drive one open-loop replay at `rate_rps`, and
    return (results, point) — the throughput/latency/cache point dict the
    serving sweeps share (benchmarks/serve_vgg19.py, serve_sharded.py add
    their sweep-specific fields on top). The engine must be on a SimClock."""
    from repro.serving import replay_stream

    clock = engine.clock
    warm_compiles = engine.warmup()
    t0 = clock()
    results = replay_stream(engine, imgs, rate_rps=rate_rps)
    makespan = max(clock() - t0, 1e-9)
    stats = engine.stats()
    point = {
        "rate_rps": rate_rps,
        "throughput_rps": len(results) / makespan,
        # percentiles come from the engine's MetricsTracker reservoir — fed
        # per COMPLETED request inside the engine, so flush-tail requests
        # are aggregated exactly like poll()-completed ones
        "p50_ms": stats["p50_ms"],
        "p95_ms": stats["p95_ms"],
        "p99_ms": stats["p99_ms"],
        "mean_ms": stats["mean_ms"],
        "batches": stats["batches"],
        "mean_fill": round(stats["mean_fill"], 3),
        "warm_compiles": warm_compiles,
        "stream_compiles": stats["compiles"] - warm_compiles,
        "cache_hits": stats["hits"],
        "replans": stats["replans"],
    }
    return results, point


def git_sha() -> str:
    """Current repo HEAD (short), "unknown" outside a git checkout — stamped
    into every BENCH_*.json so the perf trajectory is attributable."""
    import subprocess

    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, timeout=5,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def jax_versions() -> dict:
    """{"jax": ..., "jaxlib": ...} of the producing environment — stamped
    into every BENCH_*.json next to the git SHA: two runs of the same commit
    on different jax/jaxlib builds are different perf points (XLA codegen
    moves between releases), and without the stamp they are
    indistinguishable in the trajectory."""
    out = {}
    for mod in ("jax", "jaxlib"):
        try:
            out[mod] = __import__(mod).__version__
        except Exception:
            out[mod] = "unknown"
    return out


def device_info() -> dict:
    """{"device_kind", "platform"} of the measuring device — stamped into
    every BENCH_*.json next to the git SHA. The perf-history DB keys its
    series on device_kind, so points from CPU-interpret runs and real-TPU
    runs form disjoint baselines instead of merging into one."""
    try:
        dev = jax.devices()[0]
        return {"device_kind": str(getattr(dev, "device_kind", dev.platform)),
                "platform": str(dev.platform)}
    except Exception:
        return {"device_kind": "unknown", "platform": "unknown"}


def write_bench_json(name: str, rows, out_dir: str = ".", extra: dict | None = None) -> str:
    """Write BENCH_<name>.json — the machine-readable twin of the CSV the
    benchmark modules print, so the perf trajectory is captured per run.
    Every payload is stamped with the git SHA, a UTC timestamp, the
    jax/jaxlib versions, and the device kind/platform, so a BENCH artifact
    is attributable to the commit AND the environment that produced it —
    and ingestible into the perf-history DB (`repro.obs.history`, DESIGN.md
    §13) as typed per-device series.

    rows: list of dicts; each needs at least name/us_per_call (derived and any
    metric keys ride along verbatim). Returns the written path.
    """
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {"name": name, "schema": "name,us_per_call,derived",
               "git_sha": git_sha(),
               "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               "versions": jax_versions(),
               **device_info(),
               "rows": list(rows)}
    if extra:
        payload.update(extra)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def parse_csv_rows(text: str):
    """Parse the `name,us_per_call,derived` CSV rows a benchmark module
    prints into write_bench_json row dicts (non-conforming lines skipped)."""
    rows = []
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) < 2 or parts[0] in ("", "name") or parts[0].startswith("_meta/"):
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append({"name": parts[0], "us_per_call": us,
                     "derived": parts[2] if len(parts) > 2 else ""})
    return rows


def modeled_tpu_us(c, h, w, o, kh, kw, stride, occupancy: float, dtype_bytes=2,
                   batch: int = 1) -> dict:
    """Roofline-modeled TPU time (us/IMAGE) for dense vs block-ECR conv.

    dense: max(MAC-time, HBM-time) with all channel blocks.
    ecr:   same with only `occupancy` fraction of channel blocks (DMA+MXU both
           skip dead blocks — the kernel's gathered schedule).
    batch: the kernel tensor is read once per OUTPUT BLOCK, not once per
           sample (the batched grid keeps it resident across the batch), so
           its bytes amortize by 1/batch; activation and output bytes and the
           MACs are per-image.
    """
    oh, ow = (h - kh) // stride + 1, (w - kw) // stride + 1
    macs = 2 * oh * ow * o * c * kh * kw
    k_bytes = o * c * kh * kw * dtype_bytes / batch
    bytes_dense = (c * h * w + o * oh * ow) * dtype_bytes + k_bytes
    t_dense = max(macs / PEAK_FLOPS, bytes_dense / HBM_BW) * 1e6
    bytes_ecr = (occupancy * c * h * w + o * oh * ow) * dtype_bytes + occupancy * k_bytes
    t_ecr = max(occupancy * macs / PEAK_FLOPS, bytes_ecr / HBM_BW) * 1e6
    return {"dense_us": t_dense, "ecr_us": t_ecr,
            "speedup": t_dense / max(t_ecr, 1e-12)}


def feature_map_with_sparsity(key, c, h, w, sparsity):
    return synth_feature_map(key, (c, h, w), sparsity)


# paper Table III layer set: (network, layer, size, sparsity, C, O, k)
TABLE3_LAYERS = [
    ("LeNet", "Conv2", 11, 0.95, 6, 16, 5),
    ("AlexNetC", "Conv3", 6, 0.90, 192, 384, 3),
    ("AlexNetI", "Conv4", 5, 0.90, 384, 256, 3),
    ("GoogLeNet", "Incep4a.1", 14, 0.90, 480, 192, 3),
    ("GoogLeNet", "Incep4a.2", 14, 0.90, 96, 208, 3),
    ("GoogLeNet", "Incep4e.3", 14, 0.90, 160, 320, 3),
    ("GoogLeNet", "Incep5a.1", 7, 0.95, 832, 256, 3),
    ("GoogLeNet", "Incep5a.2", 7, 0.90, 160, 320, 3),
    ("GoogLeNet", "Incep5b.3", 7, 0.95, 192, 384, 3),
    ("GoogLeNet", "Incep4a.7", 7, 0.95, 512, 128, 3),
]

# paper Fig. 2 sparsity curve for VGG-19 conv inputs (approximate red curve)
VGG19_SPARSITY = [0.00, 0.35, 0.45, 0.45, 0.55, 0.60, 0.65, 0.65,
                  0.70, 0.72, 0.75, 0.75, 0.78, 0.80, 0.82, 0.85]

# VGG-19 conv shapes at half resolution (CPU-budget; MACs reported at full)
VGG19_CONVS = []
_res, _cin = 112, 3
for _stage, (_c, _n) in enumerate(((64, 2), (128, 2), (256, 4), (512, 4), (512, 4))):
    for _i in range(_n):
        VGG19_CONVS.append((f"conv_{len(VGG19_CONVS)+1}", _cin, _c, _res))
        _cin = _c
    _res //= 2
