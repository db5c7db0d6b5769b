"""CNN serving launcher: single-image requests through the sparsity-aware
serving engine (dynamic batcher + plan cache + adaptive re-planning), over a
deterministic simulated-clock request stream that carries real measured
execution times. Any LayerGraph network serves through the same spine —
pick one with --model.

Run (reduced graph; on the CPU the Pallas kernels are interpreted):
    PYTHONPATH=src python -m repro.launch.serve_cnn --rate 50 --n-requests 24
Full VGG-19 depth and widths at 96x96 (on a TPU the kernels compile with
Mosaic; `python chip_smoke.py` runs this path and checks its logits):
    PYTHONPATH=src python -m repro.launch.serve_cnn --full
Other networks:
    PYTHONPATH=src python -m repro.launch.serve_cnn --model lenet
    PYTHONPATH=src python -m repro.launch.serve_cnn --model alexnet
    PYTHONPATH=src python -m repro.launch.serve_cnn --model googlenet
Autotuned plan:
    PYTHONPATH=src python -m repro.launch.serve_cnn --autotune
Data-parallel over 4 virtual CPU devices (DESIGN.md §6):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python -m repro.launch.serve_cnn --devices 4
Pruned-model serving (weight sparsity, DESIGN.md §7):
    PYTHONPATH=src python -m repro.launch.serve_cnn --prune-density 0.3
Traffic scenarios (telemetry + scenario library, DESIGN.md §8):
    PYTHONPATH=src python -m repro.launch.serve_cnn --scenario burst
    PYTHONPATH=src python -m repro.launch.serve_cnn --scenario diurnal
    PYTHONPATH=src python -m repro.launch.serve_cnn --scenario hotswap
    PYTHONPATH=src python -m repro.launch.serve_cnn --scenario multitenant
Kernel-level trace + measured cost-model calibration (DESIGN.md §9):
    PYTHONPATH=src python -m repro.launch.serve_cnn --trace-out trace.json
    PYTHONPATH=src python -m repro.launch.serve_cnn --calibrate \\
        --calib-out calibration.json
Tile-geometry search + int8 quantized placement (DESIGN.md §10):
    PYTHONPATH=src python -m repro.launch.serve_cnn --tile-search \\
        --calib-out calibration.json
    PYTHONPATH=src python -m repro.launch.serve_cnn --int8
Perf-history ingestion (DESIGN.md §13) — the serving summary + telemetry
snapshot (and any fitted calibration) land as first-class series in the
cross-run BenchDB, gate-able by `repro-bench check`:
    PYTHONPATH=src python -m repro.launch.serve_cnn --history benchdb.jsonl
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
from repro.graph import LayerGraph, init_graph
from repro.models.cnn import shift_dead_channels
from repro.parallel import data_mesh
from repro.serving import Engine, SimClock, auto_mesh, autotune, replay_stream

log = logging.getLogger("repro.serve_cnn")

MODELS = ("vgg19", "lenet", "alexnet", "googlenet")
SCENARIOS = ("steady", "burst", "diurnal", "hotswap", "multitenant")


def serving_graph(model: str = "vgg19", full: bool = False) -> LayerGraph:
    """Reduced: stacks CPU tests can serve in seconds. Full: the real
    network depth and conv widths (VGG at 96x96 with a 512-wide head: the
    published 224x224 maps do not fit the full-map VMEM tiles yet; 96 is
    the largest size below 112 whose five pooling stages all tile exactly,
    where 112 relied on the silent 7 -> 3 truncation PoolSpec rejects)."""
    if model == "lenet":
        from repro.configs.lenet import LENET, LENET_REDUCED

        return LENET if full else LENET_REDUCED
    if model == "alexnet":
        from repro.configs.alexnet import ALEXNET, ALEXNET_REDUCED

        return ALEXNET if full else ALEXNET_REDUCED
    if model == "googlenet":
        from repro.configs.googlenet import GOOGLENET, GOOGLENET_REDUCED

        return GOOGLENET if full else GOOGLENET_REDUCED
    if model != "vgg19":
        raise ValueError(f"unknown --model {model!r} (choose from {MODELS})")
    if full:
        return vgg19_graph(CNNConfig(img_size=96))
    return vgg19_graph(CNNConfig(name="vgg-tiny", in_channels=16, img_size=16,
                                 plan=((16, 2), (32, 1)), n_classes=16))


def synth_requests(graph, n: int, seed: int = 0, dead_frac: float = 0.5):
    """Single-image requests with a shared dead-channel band (the trained-net
    activation statistic the planner exploits; DESIGN.md §2.2). `graph` is a
    LayerGraph or a legacy CNNConfig."""
    from repro.core import dead_channel_band
    from repro.graph import as_graph

    shape = as_graph(graph).in_shape
    return [dead_channel_band(
        jax.random.uniform(jax.random.PRNGKey(seed * 1000 + i), shape),
        dead_frac) for i in range(n)]


def _scenario_setup(scenario, model, engine, *, n_requests, rate, seed):
    """The non-steady traffic regimes (DESIGN.md §8): returns the scenario
    plus the {stream: Engine} map `replay_scenario` drives. All regimes are
    timed off the stream's midpoint so the interesting event (burst cycle,
    drift onset, swap) lands while requests are still flowing."""
    from repro.serving import (
        DiurnalDriftScenario,
        HotSwapScenario,
        MultiTenantScenario,
        PoissonBurstScenario,
        TenantSpec,
    )

    shape = engine.graph.in_shape
    t_mid = n_requests / (2.0 * rate)
    if scenario == "burst":
        return PoissonBurstScenario(
            in_shape=shape, n_requests=n_requests, base_rps=rate,
            burst_rps=rate * 16, burst_every_s=t_mid,
            burst_len_s=t_mid / 4, seed=seed), {"": engine}
    if scenario == "diurnal":
        return DiurnalDriftScenario(
            in_shape=shape, n_requests=n_requests, rate_rps=rate,
            dead_lo=0.5, dead_hi=0.0, drift="step", t_drift=t_mid,
            seed=seed), {"": engine}
    if scenario == "hotswap":
        from repro.sparse_weights import prune_graph_params

        pruned, report = prune_graph_params(engine.params, 0.3, engine.graph)
        log.info("hot-swap variant: pruned to %.2f achieved block density",
                 report.density)

        def swap(engines):
            engines[""].hot_swap(pruned)

        return HotSwapScenario(
            in_shape=shape, n_requests=n_requests, rate_rps=rate,
            t_swap=t_mid, swap_fn=swap, seed=seed), {"": engine}
    if scenario == "multitenant":
        other = "lenet" if model != "lenet" else "vgg19"
        graph2 = serving_graph(other)
        params2 = shift_dead_channels(init_graph(jax.random.PRNGKey(seed + 1),
                                                 graph2))
        calib2 = jnp.stack(synth_requests(graph2, 2, seed=seed + 3))
        # the second tenant shares the first's clock AND PlanCache — the
        # PlanKey graph/weight signatures keep the programs from colliding
        engine2 = Engine(params2, graph=graph2, calib=calib2,
                         occ_threshold=engine.plan.occ_threshold,
                         block_c=engine.plan.block_c,
                         max_batch=engine.batcher.max_batch,
                         deadline_s=engine.batcher.deadline_s,
                         clock=engine.clock, cache=engine.cache,
                         mesh=engine.mesh)
        engine2.warmup()
        tenants = ((model, TenantSpec(in_shape=shape,
                                      n_requests=n_requests // 2,
                                      rate_rps=rate)),
                   (other, TenantSpec(in_shape=graph2.in_shape,
                                      n_requests=n_requests // 2,
                                      rate_rps=rate)))
        return MultiTenantScenario(tenants=tenants, seed=seed), \
            {model: engine, other: engine2}
    raise ValueError(f"unknown --scenario {scenario!r} "
                     f"(choose from {SCENARIOS})")


def serve_cnn(*, model: str = "vgg19", full: bool = False,
              n_requests: int = 24, rate: float = 50.0,
              max_batch: int = 8, deadline_ms: float = 10.0,
              occ_threshold: float = 0.75, block_c: int = 8,
              do_autotune: bool = False, replan_band: float = 0.15,
              devices: int = 0, prune_density: float = 1.0,
              scenario: str = "steady", seed: int = 0,
              trace_out: str | None = None, calibrate: bool = False,
              calib_out: str | None = None, tile_search: bool = False,
              int8: bool = False, int8_budget: float = 0.98,
              history: str | None = None) -> dict:
    graph = serving_graph(model, full)
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(seed), graph))
    # --devices 0 degrades like the Engine's auto policy (largest local
    # prefix dividing max_batch); an explicit count is honored or raises
    mesh = data_mesh(devices) if devices else auto_mesh(max_batch)
    # calib batch must divide the device count so autotune can time the
    # SHARDED executor the engine will actually run
    calib = jnp.stack(synth_requests(graph, max(2, mesh.size), seed=seed + 1))
    achieved_density = 1.0
    if prune_density < 1.0:
        from repro.sparse_weights import prune_graph_params

        params, report = prune_graph_params(params, prune_density, graph,
                                            probe=calib)
        achieved_density = report.density
        log.info("pruned to %.2f achieved block density (target %.2f): "
                 "max logit drift %.3g, top-1 agreement %.2f",
                 report.density, prune_density, report.max_logit_drift,
                 report.top1_agreement)
    clock = SimClock()
    tracer = None
    if trace_out:
        from repro.obs import Tracer

        # the tracer shares the engine's SimClock, so two identical runs
        # export bit-identical trace files (tests/test_obs.py pins this)
        tracer = Tracer(clock=clock)
    calibration = None
    if calibrate:
        from repro.obs import CalibrationDB, profile_plan
        from repro.pipeline.planner import plan_network

        # measure the DEFAULT-constants plan, fit effective constants from
        # the measured/modeled ratios, then let every later planning step
        # (autotune grid, engine initial plan, drift re-plans) price impls
        # at the fitted numbers (DESIGN.md §9)
        base = plan_network(params, calib, graph, occ_threshold=occ_threshold,
                            block_c=block_c)
        report = profile_plan(base, params, calib, tracer=tracer)
        calibration = CalibrationDB.from_report(report)
        log.info("calibrated %d (kind, impl) keys on %s: %s",
                 len(calibration.entries), calibration.device,
                 calibration.summary())
    tiles = None
    if tile_search:
        from repro.obs import tile_search as run_tile_search
        from repro.pipeline.planner import plan_network

        # search every layer of the base plan at its planned impl; winners
        # land in the tiles table of the calibration DB (shared with
        # --calibrate when both are on), and the per-tile fitted constants
        # make the searched geometries measured-backed in later planning
        base = plan_network(params, calib, graph, occ_threshold=occ_threshold,
                            block_c=block_c, calibration=calibration)
        ts_report, tiles = run_tile_search(base, params, calib,
                                           db=calibration,
                                           calibration=calibration,
                                           tracer=tracer)
        if calibration is None:
            calibration = tiles  # the fits double as measured constants
        log.info("tile search: %d/%d layers improved on defaults "
                 "(modeled speedup %.3fx, floor holds: %s)",
                 len(ts_report.improved_layers()), len(ts_report.layers),
                 ts_report.summary()["model_speedup"],
                 ts_report.floor_holds())
    if calib_out and calibration is not None:
        calibration.save(calib_out)
        log.info("calibration DB written to %s", calib_out)
    plan = None
    if do_autotune:
        result = autotune(params, calib, graph, thresholds=(0.5, 0.75, 0.9),
                          block_cs=(0, 8), mesh=mesh, calibration=calibration,
                          tiles=tiles, int8=int8, int8_budget=int8_budget)
        plan = result.plan
        log.info("autotune picked occ_threshold=%.2f block_c=%d (model fallback: %s)",
                 result.best.occ_threshold, result.best.block_c, result.used_model)
    engine = Engine(params, graph=graph, plan=plan, calib=calib,
                    occ_threshold=occ_threshold, block_c=block_c,
                    max_batch=max_batch, deadline_s=deadline_ms * 1e-3,
                    clock=clock, replan_band=replan_band, mesh=mesh,
                    tracer=tracer, calibration=calibration, tiles=tiles,
                    int8=int8, int8_budget=int8_budget)
    rep8 = engine.plan.int8_report
    if rep8 is not None:
        log.info("int8 probe: %d layers quantized (%d demoted), top-1 "
                 "agreement %.3f, max logit drift %.3g",
                 len(rep8.layers), len(rep8.demoted), rep8.top1_agreement,
                 rep8.max_logit_drift)
    plan_line = plan_summary(engine.plan)
    log.info("%s plan: %s", graph.name, plan_line)
    t_warm = time.perf_counter()
    compiled = engine.warmup()
    warmup_s = time.perf_counter() - t_warm
    log.info("warmed %d bucket programs in %.1f s (buckets=%s, devices=%d)",
             compiled, warmup_s, engine.batcher.exec_buckets(),
             engine.n_devices)

    t_start = clock()
    if scenario == "steady":
        results = replay_stream(engine,
                                synth_requests(graph, n_requests, seed=seed + 2),
                                rate_rps=rate)
    else:
        from repro.serving import replay_scenario

        scn, engines = _scenario_setup(scenario, model, engine,
                                       n_requests=n_requests, rate=rate,
                                       seed=seed)
        results = [r for out in replay_scenario(engines, scn).values()
                   for r in out]
    makespan = clock() - t_start
    lat_ms = np.array(sorted(r.latency_s for r in results)) * 1e3
    stats = engine.stats()
    summary = {
        "model": graph.name,
        "scenario": scenario,
        "plan": plan_line,
        "devices": engine.n_devices,
        "prune_density": achieved_density,
        "plan_bsr": stats["plan_bsr"],
        "plan_int8": stats["plan_int8"],
        "plan_tiled": stats["plan_tiled"],
        "requests": len(results),
        "rate_rps": rate,
        "throughput_rps": len(results) / max(makespan, 1e-9),
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p95_ms": float(np.percentile(lat_ms, 95)),
        "mean_fill": stats["mean_fill"],
        **{k: stats[k] for k in ("batches", "compiles", "hits", "replans",
                                 "hot_swaps", "replan_errors",
                                 "verify_rejects")},
        "warmup_s": warmup_s,
        "calibrated": 0 if calibration is None else len(calibration.entries),
        # the steady stream's logits in request order (other scenarios mix
        # tenants and streams)
        "logits": (np.stack([r.logits for r in sorted(results,
                                                      key=lambda r: r.id)])
                   if scenario == "steady" else None),
    }
    if tracer is not None:
        tracer.save(trace_out)
        log.info("wrote %d trace events to %s (chrome://tracing / Perfetto)",
                 len(tracer.events), trace_out)
    if history:
        from repro.obs.history import (
            BenchDB,
            calibration_rows,
            make_payload,
            telemetry_rows,
        )

        db = BenchDB(history)
        # the scalar serving summary + the engine's telemetry snapshot (and
        # the fitted calibration scales, when one was produced this run)
        # become first-class series next to the benchmark sweeps
        rows = [{"name": f"serve/{graph.name}/{scenario}",
                 **{k: v for k, v in summary.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)}}]
        rows += telemetry_rows(stats["telemetry"],
                               prefix=f"telemetry/{graph.name}/{scenario}")
        if calibration is not None:
            rows += calibration_rows(calibration)
        n_new = db.ingest_payload(make_payload("serve_cnn", rows))
        log.info("perf history: %d point(s) ingested into %s "
                 "(%d total, %d series)", n_new, history, len(db),
                 len(db.series()))
    log.info("served %d requests (%s traffic) at %.0f req/s offered: "
             "%.1f req/s, p50=%.1fms p95=%.1fms, %d batches (fill %.2f), "
             "%d compiles / %d cache hits, %d replans, %d hot swaps",
             summary["requests"], scenario, rate, summary["throughput_rps"],
             summary["p50_ms"], summary["p95_ms"], summary["batches"],
             summary["mean_fill"], summary["compiles"], summary["hits"],
             summary["replans"], summary["hot_swaps"])
    return summary


def plan_summary(plan) -> str:
    """One line naming each conv's impl and measured occupancy."""
    return " ".join(f"conv{lp.index + 1}={lp.impl}@{lp.occupancy:.2f}"
                    for lp in plan.layers)


def main():
    from repro.launch.compile_cache import enable_compile_cache

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=MODELS, default="vgg19",
                    help="which LayerGraph network to serve")
    ap.add_argument("--full", action="store_true",
                    help="full network depth and widths (slow on CPU)")
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=50.0, help="offered request rate (req/s)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=10.0)
    ap.add_argument("--occ-threshold", type=float, default=0.75)
    ap.add_argument("--block-c", type=int, default=8,
                    help="channel-block size (0 = auto: 128 channels, or one "
                         "block for a narrower layer — a single block for "
                         "the reduced net's 16 channels, so 8 by default)")
    ap.add_argument("--replan-band", type=float, default=0.15)
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="data-parallel device count (0 = auto: the largest "
                         "local count dividing max-batch; an explicit count "
                         "must divide max-batch; run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                         "for virtual CPU devices)")
    ap.add_argument("--prune-density", type=float, default=1.0,
                    help="magnitude-prune the weights to this BSR block "
                         "density before planning (1.0 = no pruning); the "
                         "planner then places ('conv','bsr') layers wherever "
                         "weight sparsity beats activation sparsity")
    ap.add_argument("--scenario", choices=SCENARIOS, default="steady",
                    help="traffic regime (DESIGN.md §8): steady open-loop "
                         "stream (default), Poisson bursts, diurnal "
                         "occupancy drift (forces a re-plan), hot swap to a "
                         "0.3-density pruned variant mid-stream, or two "
                         "models multi-tenant over one shared plan cache")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run (plan/"
                         "compile/execute/re-plan spans on the sim clock; "
                         "load in chrome://tracing or Perfetto)")
    ap.add_argument("--calibrate", action="store_true",
                    help="profile the base plan per impl, fit a CalibrationDB "
                         "of measured effective roofline constants, and plan "
                         "the served engine with it (DESIGN.md §9)")
    ap.add_argument("--calib-out", default=None, metavar="PATH",
                    help="with --calibrate/--tile-search: persist the fitted "
                         "CalibrationDB (constants + tile winners) as JSON "
                         "for later runs to load")
    ap.add_argument("--tile-search", action="store_true",
                    help="search each planned layer's kernel tile geometry "
                         "(obs.tilesearch), persist measured-best winners, "
                         "and serve with them stamped on the plan "
                         "(DESIGN.md §10)")
    ap.add_argument("--int8", action="store_true",
                    help="let the planner upgrade sparse/BSR layers to the "
                         "int8 quantized kernels where the model says they "
                         "win, gated by the probe accuracy budget")
    ap.add_argument("--int8-budget", type=float, default=0.98,
                    help="minimum top-1 agreement vs the fp32 oracle on the "
                         "calibration batch; int8 layers are demoted until met")
    ap.add_argument("--history", default=None, metavar="DB",
                    help="perf-history BenchDB (JSONL, DESIGN.md §13): "
                         "ingest this run's serving summary + telemetry "
                         "snapshot as cross-run series for repro-bench")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    serve_cnn(model=args.model, full=args.full, n_requests=args.n_requests,
              rate=args.rate, max_batch=args.max_batch,
              deadline_ms=args.deadline_ms, occ_threshold=args.occ_threshold,
              block_c=args.block_c, do_autotune=args.autotune,
              replan_band=args.replan_band, devices=args.devices,
              prune_density=args.prune_density, scenario=args.scenario,
              seed=args.seed, trace_out=args.trace_out,
              calibrate=args.calibrate, calib_out=args.calib_out,
              tile_search=args.tile_search, int8=args.int8,
              int8_budget=args.int8_budget, history=args.history)


if __name__ == "__main__":
    main()
