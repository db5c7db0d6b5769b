"""Chip smoke test: serve full-width VGG-19 on one TPU and check its logits.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the data-parallel path on four chips

One process, which holds the chip from its first JAX call to its exit.

Default run, on one chip:
  1. `serve_cnn(model="vgg19", full=True)` — all 16 convs at their published
     widths (64 to 512), 96x96 input, 512-wide head, random weights from
     seed 0: plans, warms every bucket and serves 16 requests.
  2. One batch through a sparse-forced plan of the same graph (every conv
     on `ecr_pallas` / `pecr_pallas`, whatever the planner would pick),
     served by an `Engine`.
  Both outputs are compared with the dense reference run at the highest
  matmul precision.

`--four-chips`: the same graph served with `devices=4` (batch sharded over a
4-device "data" mesh) against the single-device `run_plan` of the same plan
on the same inputs and against the dense reference, plus a check that each
device holds its own shard of the sharded program's output. It runs no other
phase.

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. The last line of stdout is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Engine vs highest-precision dense reference, as a fraction of the largest
# reference logit. On the TPU an f32 matmul at default precision rounds its
# operands to bf16 (8-bit mantissa, relative error 2^-9 ~ 2e-3 per rounding),
# and the dense reference runs at full f32 ("highest"). Through VGG-19's 16
# convs and 2 dense layers the per-layer roundings add up to at most
# ~18 x 2^-9 ~ 3.5e-2 of the logit scale; 5e-2 leaves room above that.
LOGIT_TOL = 5e-2
# Sharded vs single-device run of the same plan on the same inputs, as a
# fraction of the largest logit. At default precision a TPU run depends on
# the batch it sits in: on one v5e, images run as a batch of 16 and as
# batches of 2 differ by 4.2e-3, and served one real image per bucket by
# 5.4e-3 (no sharding involved). A shard mix-up moves logits by O(1).
SHARD_TOL = 1e-2
N_REQUESTS = 16
SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def rel_dev(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        fail(f"logits shape {got.shape} != reference {ref.shape}")
    if not np.isfinite(got).all():
        fail("non-finite logits")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def dense_reference(graph, params, imgs):
    import jax

    from repro.graph import run_graph

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, x: run_graph(graph, p, x, impl="dense"))(
            params, imgs)


def check_engine(stats: dict, what: str) -> None:
    for key in ("replan_errors", "verify_rejects"):
        if stats[key]:
            fail(f"{what}: {key} = {stats[key]}")


def single_chip() -> None:
    import jax
    import jax.numpy as jnp

    from repro.graph import init_graph
    from repro.launch.serve_cnn import (
        plan_summary,
        serve_cnn,
        serving_graph,
        synth_requests,
    )
    from repro.models.cnn import shift_dead_channels
    from repro.pipeline.planner import plan_network
    from repro.serving import Engine

    graph = serving_graph("vgg19", full=True)
    # serve_cnn's own parameters and request stream, rebuilt from the seed
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(SEED),
                                            graph))
    imgs = jnp.stack(synth_requests(graph, N_REQUESTS,
                                    seed=SEED + 2))

    t0 = time.perf_counter()
    summary = serve_cnn(model="vgg19", full=True, n_requests=N_REQUESTS,
                        seed=SEED)
    print(f"served plan: {summary['plan']}")
    print(f"served: {summary['requests']} requests, {summary['batches']} "
          f"batches, {summary['compiles']} compiles, warm-up "
          f"{summary['warmup_s']:.1f} s, phase {time.perf_counter() - t0:.1f} s")
    check_engine(summary, "served engine")
    if summary["requests"] != N_REQUESTS:
        fail(f"served {summary['requests']} of {N_REQUESTS} requests")
    dev = rel_dev(summary["logits"], dense_reference(graph, params, imgs))
    print(f"served max |engine - reference| / max |reference| = {dev:.3e} "
          f"(tolerance {LOGIT_TOL})")
    if not dev <= LOGIT_TOL:
        fail(f"served logits deviate {dev:.3e} > {LOGIT_TOL}")

    t0 = time.perf_counter()
    calib = jnp.stack(synth_requests(graph, 2, seed=SEED + 1))
    plan = plan_network(params, calib, graph, occ_threshold=1.0, block_c=8)
    line = plan_summary(plan)
    print(f"sparse-forced plan: {line}")
    if "=ecr_pallas@" not in line or "=pecr_pallas@" not in line:
        fail("sparse-forced plan lacks ecr_pallas or pecr_pallas layers")
    batch = imgs[:8]
    engine = Engine(params, graph=graph, plan=plan, max_batch=8, mesh=None)
    compiled = engine.warmup([8])
    got = engine.serve(batch)
    check_engine(engine.stats(), "sparse-forced engine")
    dev = rel_dev(got, dense_reference(graph, params, batch))
    print(f"sparse-forced: {compiled} compiles, phase "
          f"{time.perf_counter() - t0:.1f} s, max |engine - reference| / "
          f"max |reference| = {dev:.3e} (tolerance {LOGIT_TOL})")
    if not dev <= LOGIT_TOL:
        fail(f"sparse-forced logits deviate {dev:.3e} > {LOGIT_TOL}")


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.graph import init_graph
    from repro.launch.serve_cnn import plan_summary, serving_graph, synth_requests
    from repro.models.cnn import shift_dead_channels
    from repro.parallel import data_mesh
    from repro.pipeline.planner import plan_network, run_plan, run_plan_sharded
    from repro.serving import Engine, SimClock, replay_stream

    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    graph = serving_graph("vgg19", full=True)
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(SEED),
                                            graph))
    reqs = synth_requests(graph, N_REQUESTS, seed=SEED + 2)
    imgs = jnp.stack(reqs)
    calib = jnp.stack(synth_requests(graph, 4, seed=SEED + 1))
    mesh = data_mesh(4)

    t0 = time.perf_counter()
    plan = plan_network(params, calib, graph, block_c=8)
    print(f"sharded plan: {plan_summary(plan)}")
    # one plan serves the whole stream, the plan the reference runs: a
    # drift re-plan would serve later batches on another plan. Occupancies
    # lie in [0, 1], so a band of 1.0 never triggers one.
    engine = Engine(params, graph=graph, plan=plan, max_batch=8, mesh=mesh,
                    clock=SimClock(), replan_band=1.0)
    compiled = engine.warmup()
    results = replay_stream(engine, reqs, rate_rps=50.0)
    stats = engine.stats()
    check_engine(stats, "sharded engine")
    if engine.n_devices != 4 or len(results) != N_REQUESTS:
        fail(f"sharded engine: {engine.n_devices} devices, "
             f"{len(results)} of {N_REQUESTS} requests")
    got = jnp.stack([r.logits for r in sorted(results, key=lambda r: r.id)])
    single = jax.jit(lambda p, x: run_plan(plan, p, x))(params, imgs)
    dev_single = rel_dev(got, single)
    dev_ref = rel_dev(got, dense_reference(graph, params, imgs))
    print(f"sharded: {compiled} compiles, {stats['batches']} batches, "
          f"{stats['replans']} replans, phase {time.perf_counter() - t0:.1f} s")
    print(f"served max |sharded - single-device| / max |single-device| = "
          f"{dev_single:.3e} (tolerance {SHARD_TOL}); against the dense "
          f"reference {dev_ref:.3e} (tolerance {LOGIT_TOL})")

    # the sharded program itself: every device holds its own batch shard
    batch = jax.device_put(imgs[:8], NamedSharding(mesh, P("data")))
    out = jax.jit(lambda p, x: run_plan_sharded(plan, p, x, mesh))(
        params, batch)
    shards = out.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted((s.index[0].start or 0, s.data.shape[0]) for s in shards)
    dev_prog = rel_dev(out, single[:8])
    print(f"sharded program: {len(devices)} devices, (row, rows) per shard "
          f"{rows}, max |sharded - single-device| / max |single-device| = "
          f"{dev_prog:.3e} (tolerance {SHARD_TOL})")
    if len(devices) != 4 or rows != [(0, 2), (2, 2), (4, 2), (6, 2)]:
        fail("the sharded output is not split one 2-row shard per device")
    if not max(dev_single, dev_prog) <= SHARD_TOL:
        fail(f"sharded logits deviate {max(dev_single, dev_prog):.3e} > "
             f"{SHARD_TOL}")
    if not dev_ref <= LOGIT_TOL:
        fail(f"sharded logits deviate {dev_ref:.3e} > {LOGIT_TOL} from the "
             "dense reference")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device data-parallel phase")
    args = ap.parse_args()

    import logging

    import jax

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        fail(f"JAX finds no TPU (platform {platform!r}); nothing was served")
    print(f"device: {kind} x {len(devices)} ({platform})", flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chips()
    else:
        single_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
