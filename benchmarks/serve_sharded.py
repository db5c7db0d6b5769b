"""Sharded serving benchmark: device count x request rate through the
data-parallel engine (DESIGN.md §6).

Claim checked: the serving spine scales out — `run_plan` under shard_map
over a 1-D "data" mesh keeps the sparse kernels' per-sample (ids, cnt)
schedules device-local (no collective in the conv path; only the occupancy
statistic crosses shards), the batcher's device-aligned buckets hand every
shard an equal >= min_bucket slice (logits stay bit-exact against the
single-device reference), and one plan cache serves the 1..N-device layouts
side by side. The sweep replays the same open-loop request stream at each
(devices, rate) point on a simulated clock carrying real measured execution
wall times, and reports throughput and latency percentiles per point.

On a CPU host the "devices" are XLA host-platform virtual devices (run it
under `XLA_FLAGS=--xla_force_host_platform_device_count=4`), so absolute
scaling numbers are synthetic — the artifact pins the harness shape
(per-device throughput points, compile counts, bit-exactness of the serving
path) that a real accelerator run fills in. On a TPU host the sweep spans
the chips JAX finds.

Emits BENCH_serve_sharded.json (always — this is the scale-out head of the
perf trajectory) in addition to the usual CSV rows.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=4 \
         PYTHONPATH=src:. python benchmarks/serve_sharded.py [--reduced] [--json DIR]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import serve_replay_point, write_bench_json
from repro.graph import init_graph
from repro.launch.serve_cnn import serving_graph, synth_requests
from repro.models.cnn import shift_dead_channels
from repro.parallel import data_mesh
from repro.serving import Engine, SimClock


def sweep(device_counts, rates, n_requests: int, graph, *, max_batch: int = 8,
          deadline_ms: float = 10.0, occ_threshold: float = 0.75,
          block_c: int = 8, seed: int = 0):
    """One engine per (devices, rate) point — fresh queue/latency state, same
    params/plan inputs; buckets are pre-compiled so every point measures
    steady-state serving, and each point's logits are checked against the
    shared single-device `run_plan` reference before timing is trusted (the
    scale-out claim is exactness-preserving throughput). The check is
    float32-tight rather than bitwise: the stream chops into rate-dependent
    bucket sizes, and under `--xla_force_host_platform_device_count` XLA's
    CPU backend re-blocks its reductions PER BATCH SIZE, so even unsharded
    M=2 rows differ from the M=8 reference in low-order bits — bucket-
    composition bit-exactness at fixed batch size is pinned by
    tests/test_serving_sharded.py, where composition is controlled."""
    from repro.pipeline import plan_network, run_plan

    params = shift_dead_channels(init_graph(jax.random.PRNGKey(seed), graph))
    calib = jnp.stack(synth_requests(graph, 2, seed=seed + 1))
    imgs = synth_requests(graph, n_requests, seed=seed + 2)
    # plan once — every point serves one schedule — and run the shared
    # single-device reference once, not per sweep point
    plan = plan_network(params, calib, graph, occ_threshold=occ_threshold,
                        block_c=block_c)
    ref = np.asarray(run_plan(plan, params, jnp.stack(imgs)))
    rows, points = [], []
    for n_dev in device_counts:
        mesh = data_mesh(n_dev)
        for rate in rates:
            engine = Engine(params, graph=graph, plan=plan,
                            max_batch=max_batch, deadline_s=deadline_ms * 1e-3,
                            clock=SimClock(), mesh=mesh)
            results, point = serve_replay_point(engine, imgs, rate)
            by_id = {r.id: r.logits for r in results}
            served = np.stack([by_id[i] for i in range(len(imgs))])
            err = float(np.abs(served - ref).max())
            assert np.allclose(served, ref, rtol=1e-5, atol=1e-5), \
                f"sharded serving diverged at devices={n_dev} rate={rate}: {err}"
            point = {
                "devices": n_dev,
                **point,
                "exec_buckets": list(engine.batcher.exec_buckets()),
                "max_abs_err_vs_run_plan": err,
            }
            points.append(point)
            rows.append({
                "name": f"serve_sharded/d{n_dev}/rate{rate:g}",
                "us_per_call": point["mean_ms"] * 1e3,
                "derived": (f"devices={n_dev} "
                            f"throughput_rps={point['throughput_rps']:.1f} "
                            f"p50_ms={point['p50_ms']:.2f} p95_ms={point['p95_ms']:.2f} "
                            f"fill={point['mean_fill']:.2f} "
                            f"stream_compiles={point['stream_compiles']}"),
                **point,
            })
    return rows, points, plan


def main(reduced: bool = True, json_dir: str = ".", device_counts=None,
         rates=None, n_requests: int | None = None, max_batch: int = 8) -> str:
    graph = serving_graph("vgg19", full=not reduced)
    if reduced:
        rates = rates or (50.0, 200.0)
        n_requests = n_requests or 16
    else:
        rates = rates or (5.0, 20.0, 50.0, 200.0)
        n_requests = n_requests or 32
    avail = jax.device_count()
    device_counts = device_counts or (1, 2, 4)
    # same admissibility rule as Engine/auto_mesh: the count must divide
    # max_batch AND leave every shard >= the min_bucket=2 bit-exactness
    # floor — d == max_batch divides but MicroBatcher(align=d) would refuse
    # the 1-sample shards, aborting the sweep after the points before it
    usable = [d for d in device_counts
              if d <= avail and max_batch % d == 0
              and (d == 1 or max_batch // d >= 2)]
    dropped = sorted(set(device_counts) - set(usable))
    if dropped:
        print(f"_meta/devices,0,skipping device counts {dropped} "
              f"(host exposes {avail}, max_batch={max_batch}, min_bucket=2)")
    rows, points, plan = sweep(usable, rates, n_requests, graph,
                               max_batch=max_batch)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    path = write_bench_json("serve_sharded", rows, json_dir, extra={
        "config": {"net": graph.name, "in_shape": list(graph.in_shape),
                   "n_requests": n_requests, "max_batch": max_batch,
                   "reduced": reduced, "host_devices": avail},
        "plan_counts": plan.counts(),
        "points": points,
    })
    print(f"_meta/serve_sharded_json,0,wrote {path}")
    return path


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    scale = ap.add_mutually_exclusive_group()
    scale.add_argument("--reduced", action="store_true",
                       help="CI-smoke scale (tiny net, fewer requests; the default)")
    scale.add_argument("--full", action="store_true",
                       help="full VGG-19 depth at reduced resolution")
    ap.add_argument("--devices", type=int, nargs="+", default=None,
                    metavar="N", help="device counts to sweep (default 1 2 4)")
    ap.add_argument("--json", default=".", metavar="DIR",
                    help="directory for BENCH_serve_sharded.json")
    args = ap.parse_args()
    main(reduced=not args.full, json_dir=args.json,
         device_counts=tuple(args.devices) if args.devices else None)
