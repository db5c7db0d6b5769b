"""Offline plan autotuner: search (occ_threshold, block_c) on a calibration
batch, select by measured wall time, fall back to the cost model when timing
is too noisy.

The planner's two knobs interact: a bigger `block_c` amortizes schedule
overhead but rounds n_live up harder (fewer skippable blocks), and the
profitable `occ_threshold` shifts with both (paper Fig. 9/11: which layers
should run ECR/PECR is occupancy- and shape-dependent). The autotuner builds
one `PipelinePlan` per grid point (deduping points that collapse to the same
schedule), times the jitted whole-batch executor, and picks the fastest.

Timing on a shared machine is noisy; the fallback ranks by the modeled
roofline time instead: `hlo_cost.analyze` over the lowered executor for
all-dense plans (where the HLO is a faithful account of the math XLA will
run), and the kernel-level cost hooks (`ecr_conv_cost` / `conv_pool_cost`)
when the plan contains Pallas layers — interpret-mode Pallas lowers to an
emulation whose HLO counts the emulator, not the kernel, so sparse plans are
modeled at the granularity the kernels actually schedule (skipped blocks save
their MACs and their DMA).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax

from repro.graph import as_graph
from repro.graph.registry import get_op, unit_model_us
from repro.obs.constants import device_peaks
from repro.pipeline.planner import PipelinePlan, plan_network, run_plan, run_plan_sharded
from repro.serving.plan_cache import plan_key


@dataclass
class Candidate:
    occ_threshold: float
    block_c: int
    plan: PipelinePlan
    wall_us: float = float("inf")
    spread: float = 0.0  # (max-min)/median of the timing samples
    model_us: float = float("inf")
    timings_us: list = field(default_factory=list)

    def row(self) -> dict:
        return {"occ_threshold": self.occ_threshold, "block_c": self.block_c,
                "wall_us": round(self.wall_us, 1), "spread": round(self.spread, 3),
                "model_us": round(self.model_us, 3),
                "counts": self.plan.counts()}


@dataclass
class AutotuneResult:
    best: Candidate
    candidates: list
    used_model: bool  # True when the noisy-timing fallback decided the winner

    @property
    def plan(self) -> PipelinePlan:
        return self.best.plan


def plan_model_us(plan: PipelinePlan, params, batch: int = 1,
                  calibration=None) -> float:
    """Roofline-modeled execution time (us) of a plan at a given batch size:
    the registry's `unit_model_us` per layer (each LayerPlan's own IR specs —
    `to_unit` rejects pre-IR plans — so LeNet's 5x5 convs and AlexNet's
    strided/overlapping layers model at their real geometry; dense layers
    are the occupancy=1.0 point, BSR layers honour the plan's recorded
    weight density, unfused pools cost the round trip PECR deletes) plus the
    classifier GEMMs. Summing per-layer roofline maxima upper-bounds the
    whole-program roofline the pre-BSR version took over global totals —
    identical whenever one side of the roofline dominates every layer, which
    these conv stacks satisfy, and a consistent ranking either way.

    `calibration` (a `repro.obs.calibrate.CalibrationDB`) prices each layer
    at its impl's MEASURED effective constants (DESIGN.md §9); uncovered
    keys — and calibration=None — use the datasheet defaults. The head
    GEMMs always model at the defaults: they run as plain XLA dots, outside
    the per-impl kernel families the DB is keyed on."""
    from repro.graph.ir import graph_weights

    us = 0.0
    for lp in plan.layers:
        us += unit_model_us(lp.kind, lp.impl, lp.to_unit(),
                            occupancy=lp.occupancy,
                            weight_density=lp.weight_density, batch=batch,
                            block_c=plan.block_c,
                            tile=getattr(lp, "tile", None),
                            calibration=calibration)
    # classifier: flatten -> dense head GEMMs
    flops = 0.0
    nbytes = 0.0
    _, dense_ws = graph_weights(params)
    for w in dense_ws:
        d_in, d_out = w.shape
        flops += 2.0 * batch * d_in * d_out
        nbytes += 4.0 * (d_in * d_out + batch * (d_in + d_out))
    return us + device_peaks().time_us(flops, nbytes)


def hlo_model_us(fn, *args) -> float:
    """Roofline time (us) from `hlo_cost.analyze` over the lowered program —
    the faithful model for plans with no Pallas (interpret-emulated) layers."""
    from repro.launch import hlo_cost

    hlo = jax.jit(fn).lower(*args).compile().as_text()
    a = hlo_cost.analyze(hlo)
    return device_peaks().time_us(a["flops"], a["bytes"])


def _time_us(f, *args, iters: int = 3, warmup: int = 1) -> tuple:
    """(median_us, spread, samples) via the SHARED timing harness
    (`repro.obs.profile.time_callable` — jit warm-up, block_until_ready,
    median-of-k): autotune candidates and `obs.profile_plan` layer rows are
    measured by the same protocol, so their numbers are comparable.
    Outlier rejection stays off here — the spread feeds the noisy-clock
    fallback decision, which must see the raw clock quality."""
    from repro.obs.profile import time_callable

    t = time_callable(f, *args, iters=iters, warmup=warmup, outlier_tol=0.0)
    return t.median_us, t.spread, list(t.samples_us)


def _model_us(plan: PipelinePlan, params, calib, runner,
              calibration=None) -> float:
    if calibration is not None or \
            any(get_op(lp.kind, lp.impl).pallas for lp in plan.layers):
        return plan_model_us(plan, params, batch=calib.shape[0],
                             calibration=calibration)
    return hlo_model_us(runner, params, calib)


def autotune(params, calib, graph=None, *,
             thresholds=(0.0, 0.5, 0.75, 0.9), block_cs=(0, 8),
             iters: int = 3, warmup: int = 1, noise_tol: float = 0.25,
             use_pallas: bool = True, mode: str = "auto",
             mesh=None, calibration=None, tiles=None, int8: bool = False,
             int8_budget: float = 0.98) -> AutotuneResult:
    """Grid-search (occ_threshold, block_c); return the plan that serves the
    calibration batch fastest. `graph` is a LayerGraph or legacy CNNConfig
    (None = full VGG-19).

    mode="auto" selects by median wall time, unless the timing cannot
    separate the top two candidates — the winner's spread exceeds `noise_tol`,
    or the runner-up is within the larger of the two spreads — in which case
    the ranking falls back to the cost model (see module docstring).
    mode="time" / mode="model" force one criterion (used by tests and by
    callers that know their clock quality).

    `mesh` (a 1-D "data" mesh, DESIGN.md §6) times each candidate through the
    SHARDED executor the serving engine will actually run — the calibration
    batch must divide the device count. The cost-model fallback stays
    per-device (the roofline constants describe one chip, and the collective
    traffic is identical across candidates, so it cancels in the ranking).

    `calibration` (a `repro.obs.calibrate.CalibrationDB`) flows into both
    sides of the search: candidate plans are BUILT calibrated
    (`plan_network(calibration=)`) and the noisy-clock fallback ranks by the
    calibrated `plan_model_us` (a populated DB also retires the dense-plan
    HLO path — measured per-impl constants beat re-deriving the default
    roofline from lowered HLO). None keeps today's behavior exactly.

    `tiles` / `int8` / `int8_budget` pass straight through to `plan_network`:
    every candidate plan is built with the stored tile-search winners stamped
    and (when int8=True) the probe-gated quantized upgrades applied, so the
    search ranks the plans that would actually serve.
    """
    graph = as_graph(graph)
    if calib.ndim == 3:
        calib = calib[None]
    if mesh is not None and mesh.size == 1:
        mesh = None
    seen: dict = {}
    runners: dict = {}
    cands: list = []
    for th in thresholds:
        for bc in block_cs:
            plan = plan_network(params, calib, graph, occ_threshold=th,
                                block_c=bc, use_pallas=use_pallas,
                                calibration=calibration, tiles=tiles,
                                int8=int8, int8_budget=int8_budget)
            sig = plan_key(calib.shape[0], plan)
            if sig in seen:  # same schedule == same executable: reuse timing
                cands.append(Candidate(th, bc, plan, *seen[sig]))
                continue
            runners[sig] = _runner_for(plan)  # unsharded: the model fallback's HLO view
            if mode == "model":  # ranking by model only: skip the timing runs
                wall, spread, ts = float("inf"), 0.0, []
            else:
                wall, spread, ts = _time_us(jax.jit(_runner_for(plan, mesh)),
                                            params, calib,
                                            iters=iters, warmup=warmup)
            seen[sig] = (wall, spread, float("inf"), ts)
            cands.append(Candidate(th, bc, plan, wall, spread, float("inf"), ts))
    by_time = sorted(cands, key=lambda c: c.wall_us)
    # distinct schedules only: dedup aliases share one timing, and comparing
    # the winner against its own alias would read as margin 0 == "noisy"
    uniq: dict = {}
    for c in by_time:
        uniq.setdefault(plan_key(calib.shape[0], c.plan), c)
    distinct = list(uniq.values())
    used_model = mode == "model"
    if mode == "auto" and len(distinct) > 1:
        w0, w1 = distinct[0], distinct[1]
        margin = (w1.wall_us - w0.wall_us) / max(w0.wall_us, 1e-9)
        used_model = w0.spread > noise_tol or margin < max(w0.spread, w1.spread)
    elif mode == "auto":
        used_model = distinct[0].spread > noise_tol
    if used_model:
        # model cost is computed lazily, only when it actually decides the
        # ranking (hlo_model_us recompiles the dense programs to read HLO)
        model_by_sig: dict = {}
        for c in cands:
            sig = plan_key(calib.shape[0], c.plan)
            if sig not in model_by_sig:
                model_by_sig[sig] = _model_us(c.plan, params, calib,
                                              runners[sig], calibration)
            c.model_us = model_by_sig[sig]
    best = min(cands, key=lambda c: c.model_us) if used_model else by_time[0]
    return AutotuneResult(best=best, candidates=cands, used_model=used_model)


def _runner_for(plan: PipelinePlan, mesh=None):
    def run(params, imgs):
        if mesh is None:
            return run_plan(plan, params, imgs)
        return run_plan_sharded(plan, params, imgs, mesh)

    return run
