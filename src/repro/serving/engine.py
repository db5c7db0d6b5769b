"""Occupancy-adaptive serving engine over the static `PipelinePlan`.

`Engine` turns the planner's plan-once artifact into a request-serving loop:

- requests enter through the `MicroBatcher` (deadline-bounded power-of-two
  buckets); the ragged tail is padded with all-zero images, which the
  per-sample (ids, cnt) schedules skip at zero MAC cost (DESIGN.md §2.4);
- each (bucket, plan) pair executes through ONE ahead-of-time compiled
  program from the `PlanCache` — steady-state serving never compiles;
- every executed batch also measures the per-layer observed channel-block
  occupancy of its REAL samples (the traced `occupancy_stat` with an
  `n_valid` mask) and folds it into an EMA; when the EMA drifts out of the
  hysteresis band around the occupancies the current plan was calibrated at,
  the engine re-plans on the most recent real batch — optionally in a
  background thread — and swaps the new plan in atomically between batches;
- with more than one local device (or an explicit `mesh=`), execution is
  data-parallel: the bucket's batch dim shards over a 1-D "data" mesh under
  shard_map, per-sample (ids, cnt) schedules stay device-local, and the
  occupancy statistic is aggregated across shards so the EMA/re-plan
  hysteresis reacts to global traffic (DESIGN.md §6).

Exactness contract: a request's logits are bit-identical to `run_plan` on the
same image(s) whenever the co-batched samples share a live-channel union (the
shared-union compaction permutation is then batch-composition-invariant); the
all-zero pad samples never perturb the union. tests/test_serving.py pins this
on the CPU. On a TPU at default matmul precision the result also depends on
the batch an image runs in: on a v5e, VGG-19 `--full` logits served one image
per bucket differ from `run_plan` over all 16 images by 5.4e-3 of the
largest logit (`chip_smoke.py --four-chips` holds the sharded path to 1e-2).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph import as_graph
from repro.obs.trace import PROFILER_TRACER
from repro.parallel.api import data_mesh, sharding_for
from repro.pipeline.planner import PipelinePlan, plan_network, run_plan, run_plan_sharded
from repro.serving.batcher import MicroBatch, MicroBatcher, SimClock
from repro.serving.metrics import MetricsTracker
from repro.serving.plan_cache import PlanCache, plan_key


@dataclass(frozen=True)
class ServedResult:
    """One completed request: logits plus the latency-accounting timestamps.
    `t_formed` is when the batcher formed the request's bucket — the deadline
    contract bounds (t_formed - t_arrival), and the burst scenario tests pin
    it; pre-existing constructors that omit it get 0.0."""

    id: int
    logits: np.ndarray  # (n_classes,)
    t_arrival: float
    t_done: float
    t_formed: float = 0.0

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_arrival


def auto_mesh(max_batch: int = 8, min_bucket: int = 2):
    """The engine's mesh="auto" policy: a 1-D "data" mesh over the LARGEST
    local-device prefix whose size divides `max_batch` AND leaves every
    shard at least `min_bucket` samples per full bucket — the two
    constraints the batcher's device-aligned buckets enforce (an M=1 shard
    slice would void the bit-exactness contract, see MicroBatcher). Never
    raises for lack of devices: an awkward host degrades to fewer devices
    (3 devices, max_batch=8 -> 2; 8 devices, max_batch=8 -> 4) instead of
    refusing to serve, and 1 device is always acceptable."""
    n_avail = len(jax.devices())
    fits = [d for d in range(1, n_avail + 1)
            if max_batch % d == 0 and max_batch // d >= min_bucket]
    return data_mesh(max(fits) if fits else 1)


def _make_runner(plan: PipelinePlan, mesh=None):
    """The whole-batch executor the cache compiles: logits + per-layer
    observed occupancy over the first n_valid (real) samples. The plan
    carries its own LayerGraph, so the runner is model-agnostic; with a
    mesh it runs under shard_map (batch sharded over "data", occupancy
    aggregated across shards — DESIGN.md §6)."""

    def run(params, imgs, n_valid):
        if mesh is None:
            return run_plan(plan, params, imgs, collect_occupancy=True,
                            n_valid=n_valid)
        return run_plan_sharded(plan, params, imgs, mesh,
                                collect_occupancy=True, n_valid=n_valid)

    return run


class Engine:
    """Sparsity-aware serving engine for any planned LayerGraph conv stack
    (VGG-19, LeNet, AlexNet, ... — pass `graph=` or a legacy `CNNConfig`).

    Drive it with `submit()` + `poll()` (event loop), `drain()` (end of
    stream), or the synchronous convenience `serve(imgs)`.

    `mesh` selects the data-parallel layout (DESIGN.md §6): "auto" (default)
    spans the largest local-device prefix whose size divides max_batch (all
    devices on a well-shaped host, fewer on an awkward one — never a
    construction failure), an explicit 1-D "data" mesh pins the device
    count (and raises when max_batch is not a multiple of it), and None
    forces single-device execution. On a 1-device host every
    choice degenerates to the exact pre-mesh behavior. With N > 1 devices the
    batcher's buckets are N-aligned (each shard takes an equal slice, local
    slices keep the min_bucket floor so logits stay bit-exact), the plan
    cache keys gain the mesh shape, and the occupancy EMA consumes the
    cross-shard aggregated statistic — the drift detector sees GLOBAL
    traffic, not one shard's slice of it.
    """

    def __init__(self, params, ccfg=None, *, graph=None,
                 plan: PipelinePlan | None = None, calib=None,
                 occ_threshold: float = 0.75, block_c: int = 0,
                 use_pallas: bool = True, max_batch: int = 8,
                 min_bucket: int = 2, deadline_s: float = 0.010,
                 clock=time.monotonic, mesh="auto",
                 ema_alpha: float = 0.25, replan_band: float = 0.15,
                 replan_cooldown: int = 2, replan_async: bool = False,
                 cache_entries: int = 32, cache: PlanCache | None = None,
                 metrics: MetricsTracker | None = None,
                 sim_service_s=None, tracer=None, calibration=None,
                 tiles=None, int8: bool = False, int8_budget: float = 0.98):
        # tracer: where the engine's serve.* spans go (DESIGN.md §9). The
        # default writes them into the profiler's own trace, on the device
        # ops' clock (one TraceMe check a span while no profiler runs); a
        # repro.obs.trace.Tracer records them on the engine's clock instead.
        # calibration: a repro.obs.calibrate.CalibrationDB — every plan this
        # engine builds (initial, drift re-plans, hot-swap re-plans) prices
        # its impl choices at the measured effective constants; None (or an
        # empty DB) keeps the datasheet defaults bit-identically.
        # tiles: a CalibrationDB carrying tile-search winners — every plan
        # this engine builds stamps the stored measured-best geometry per
        # layer (plan_network(tiles=...)); often the same DB as calibration.
        # int8/int8_budget: let every plan upgrade layers to the quantized
        # impls under the probe-agreement budget (plan_network(int8=...)).
        self.tracer = tracer if tracer is not None else PROFILER_TRACER
        self.calibration = calibration
        self.tiles = tiles
        self.int8 = bool(int8)
        self.int8_budget = float(int8_budget)
        graph = plan.graph if plan is not None and plan.graph is not None \
            else as_graph(graph if graph is not None else ccfg)
        if plan is None:
            if calib is None:
                raise ValueError("Engine needs either a prebuilt plan= or calib= images to plan on")
            with self.tracer.span("serve.plan", graph=graph.name,
                                  occ_threshold=occ_threshold):
                plan = plan_network(params, calib, graph,
                                    occ_threshold=occ_threshold,
                                    block_c=block_c, use_pallas=use_pallas,
                                    calibration=calibration, tiles=tiles,
                                    int8=self.int8,
                                    int8_budget=self.int8_budget)
        # mesh="auto": 1-D data mesh over the largest local-device prefix
        # dividing max_batch (all devices when they divide; fewer on awkward
        # hosts rather than refusing to construct); a 1-device mesh (every
        # single-device host) normalizes to None, so the unsharded path —
        # and its cache keys — are bit-identical to pre-mesh engines. An
        # EXPLICIT mesh is never shrunk: a mismatch with max_batch raises.
        if mesh == "auto":
            mesh = auto_mesh(max_batch, min_bucket)
        if mesh is not None and mesh.size == 1:
            mesh = None
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError(f"Engine needs a mesh with a 'data' axis, got "
                             f"{tuple(mesh.axis_names)}")
        self.mesh = mesh
        self.n_devices = int(mesh.shape["data"]) if mesh is not None else 1
        self.params = params
        self.graph = graph
        self.plan = plan
        self.use_pallas = use_pallas
        self.clock = clock
        self.batcher = MicroBatcher(max_batch=max_batch, deadline_s=deadline_s,
                                    clock=clock, min_bucket=min_bucket,
                                    align=self.n_devices)
        # cache= shares one PlanCache across engines (multi-tenant serving);
        # the graph/mesh/weight signatures in PlanKey keep tenants from ever
        # colliding on a compiled program
        self.cache = cache if cache is not None else PlanCache(max_entries=cache_entries)
        self.metrics = metrics if metrics is not None else MetricsTracker()
        # sim_service_s: deterministic service-time model for SimClock replays
        # (None = charge measured wall time; a float or callable(bucket,
        # n_real) -> seconds makes two identical replays — logits AND metric
        # snapshots — bit-identical, the regression-diff contract)
        self.sim_service_s = sim_service_s
        self.ema_alpha = ema_alpha
        self.replan_band = replan_band
        self.replan_cooldown = replan_cooldown
        self.replan_async = replan_async
        self._lock = threading.Lock()
        self._pending_plan: PipelinePlan | None = None
        self._replanning = False
        self._replan_thread: threading.Thread | None = None
        self._plan_gen = 0  # bumped by hot_swap: stale background re-plans
        self._cooldown = 0  # (planned against the swapped-out params) drop
        self._calib_recent = None  # last real (unpadded) executed batch
        self._occ_ema = np.array([lp.occupancy for lp in plan.layers])
        self.n_replans = 0
        self.replan_errors = 0
        self.n_hot_swaps = 0
        self.verify_rejects = 0  # plans the static verifier refused to adopt
        self._profile_summary = None  # last Engine.profile() digest

    # ------------------------------------------------------------------
    # request loop
    # ------------------------------------------------------------------

    def submit(self, img, now: float | None = None) -> int:
        """Queue one (C,H,W) image; returns the request id. `now` overrides
        the arrival stamp — replay_stream passes the TRUE scheduled arrival,
        which can precede the clock when execution of a previous batch
        advanced the simulated timeline past it (the queueing delay behind an
        executing batch must count against latency and the deadline)."""
        with self.tracer.span("serve.submit", rid=self.batcher.next_id):
            with self.tracer.span("serve.put"):  # the host -> device copy
                x = jnp.asarray(img, jnp.float32)
            rid = self.batcher.submit(x, now=now)
            self.metrics.on_submit()
        return rid

    def next_deadline(self) -> float | None:
        """Absolute time the driver must poll by (batcher deadline contract)."""
        return self.batcher.next_deadline()

    def poll(self) -> list:
        """Adopt any finished re-plan, then run EVERY due batch — a burst of
        >= 2·max_batch requests leaves several full buckets queued, and
        serving only the first would strand the rest until the next deadline
        poll, breaking the batcher's wait bound under load. Each executed
        batch may advance a SimClock past further deadlines, so the drain
        loop re-checks readiness until nothing is due. Returns the completed
        `ServedResult`s ([] when nothing was due)."""
        out = []
        while True:
            self._adopt_pending_plan()
            batch = self.batcher.ready()
            if batch is None:
                return out
            out.extend(self._run_batch(batch))

    def drain(self) -> list:
        """Flush and run everything still queued (end of stream)."""
        out = []
        while self.batcher.pending():
            self._adopt_pending_plan()
            batch = self.batcher.flush()
            out.extend(self._run_batch(batch))
        self._adopt_pending_plan()  # a re-plan the last batch triggered
        return out

    def serve(self, imgs) -> np.ndarray:
        """Synchronous convenience: submit every (C,H,W) image in `imgs`,
        drain, and return (N, n_classes) logits in submission order. An
        empty stream returns an empty (0, n_classes) array (np.stack on
        zero results would raise)."""
        ids = [self.submit(img) for img in imgs]
        if not ids:
            return np.zeros((0, self.graph.n_classes()), np.float32)
        results = {r.id: r for r in self.drain()}
        return np.stack([results[i].logits for i in ids])

    def warmup(self, buckets=None) -> int:
        """Pre-compile the current plan at the given bucket sizes (default:
        all of them) so the serving path never compiles inline. Returns the
        number of fresh compilations triggered."""
        before = self.cache.compiles
        for b in buckets or self.batcher.exec_buckets():
            self._executable(int(b))
        return self.cache.compiles - before

    def stats(self) -> dict:
        """Serving state + telemetry. Latency percentiles come from the
        tracker's reservoir — fed per COMPLETED request in `_run_batch`, so
        drain()/flush-tail requests are aggregated exactly like
        poll()-completed ones (they used to escape latency accounting
        entirely: latency was only ever computed by external drivers over
        whatever subset of results they kept). The full time-series
        telemetry (occupancy-EMA timeline, re-plan events, per-bucket
        counts) rides under ``"telemetry"`` — `MetricsTracker.snapshot()`
        verbatim, ready for `write_bench_json`."""
        c = self.plan.counts()
        return {
            **self.cache.stats(),
            "devices": self.n_devices,
            "requests": self.metrics.submitted,
            "batches": self.metrics.batches,
            "pad_samples": self.metrics.pad_samples,
            "mean_fill": self.metrics.mean_fill(),
            "replans": self.n_replans,
            "replan_errors": self.replan_errors,
            "hot_swaps": self.n_hot_swaps,
            "verify_rejects": self.verify_rejects,
            "plan_sparse": c["sparse"],
            "plan_dense": c["dense"],
            "plan_bsr": c["bsr"],
            "plan_int8": c["int8"],
            "plan_tiled": sum(1 for lp in self.plan.layers
                              if getattr(lp, "tile", None)),
            "occ_ema": [float(v) for v in np.round(self._occ_ema, 4)],
            **{k: v for k, v in self.metrics.latency.percentiles_ms().items()
               if k != "count"},
            "lat_count": self.metrics.latency.count,
            "telemetry": {**self.metrics.snapshot(),
                          "profile": self._profile_summary},
        }

    def profile(self, imgs=None, *, impls=None, iters: int = 3,
                warmup: int = 1):
        """Per-layer measured-vs-modeled timing of the CURRENT plan
        (`repro.obs.profile.profile_plan` at the engine's real shapes): each
        layer of the plan is timed under every requested impl family and
        paired with the registry's `unit_model_us` prediction. The report's
        digest (per-impl medians + ranking agreement) lands in
        ``stats()["telemetry"]["profile"]`` so serving benchmarks carry it in
        the same artifact as the request-stream metrics; the full report is
        returned (feed it to `CalibrationDB.from_report` to close the loop).

        `imgs` defaults to the most recent real executed batch — same source
        the drift re-planner uses — so an engine that has served traffic can
        be profiled without new inputs."""
        from repro.obs.profile import PROFILE_IMPLS, profile_plan

        calib = self._calib_recent if imgs is None else jnp.asarray(imgs)
        if calib is None:
            raise ValueError("profile() needs imgs= before the engine has "
                             "executed its first batch")
        report = profile_plan(self.plan, self.params, calib,
                              impls=PROFILE_IMPLS if impls is None else impls,
                              iters=iters, warmup=warmup, tracer=self.tracer)
        self._profile_summary = report.summary()
        return report

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def _executable(self, bucket: int):
        key = plan_key(bucket, self.plan, self.mesh)
        plan, params, mesh = self.plan, self.params, self.mesh

        def build():
            with self.tracer.span("serve.compile", bucket=bucket,
                                  devices=self.n_devices):
                c, h, w = plan.layers[0].in_shape
                imgs_s = jax.ShapeDtypeStruct((bucket, c, h, w), jnp.float32)
                nv_s = jax.ShapeDtypeStruct((), jnp.int32)
                if mesh is None:
                    fn = jax.jit(_make_runner(plan))
                else:
                    # pin the AOT input layout: params/n_valid replicated,
                    # batch split over "data" (the batcher's align made it
                    # divisible)
                    fn = jax.jit(_make_runner(plan, mesh), in_shardings=(
                        sharding_for((), (), mesh),
                        self._batch_sharding((bucket, c, h, w)),
                        sharding_for((), (), mesh)))
                return fn.lower(params, imgs_s, nv_s).compile()

        return self.cache.get_or_compile(key, plan, build)

    def _batch_sharding(self, shape):
        """NamedSharding splitting dim 0 over the mesh's data axis (the
        logical-axis rules of parallel/api resolve "batch" -> ("data",))."""
        return sharding_for(shape, ("batch",) + (None,) * (len(shape) - 1),
                            self.mesh)

    def _run_batch(self, batch: MicroBatch) -> list:
        # one serve.batch span with a child span per host step. A batch's
        # requests have consecutive ids from first_rid, so each request's
        # serve.submit is found from its batch. Under a SimClock only
        # serve.fetch moves the clock (by the charged service time), so a
        # Tracer's serve.batch duration is exactly that charge and traced
        # replays are deterministic (tests/test_obs.py pins the bytes)
        span = self.tracer.span
        with span("serve.batch", bucket=batch.bucket, n_real=batch.n_real,
                  first_rid=batch.requests[0].id):
            with span("serve.stack"):
                imgs = jnp.stack([r.img for r in batch.requests])
                if batch.bucket > batch.n_real:  # ragged tail: all-zero pads
                    pad = jnp.zeros((batch.bucket - batch.n_real,) + imgs.shape[1:],
                                    imgs.dtype)
                    imgs = jnp.concatenate([imgs, pad])
                if self.mesh is not None:
                    # commit the batch to the compiled layout (a no-op re-put
                    # when already placed; uncommitted host arrays would also
                    # auto-shard, but an explicitly committed input must
                    # never silently reshard)
                    imgs = jax.device_put(imgs, self._batch_sharding(imgs.shape))
            with span("serve.lookup"):
                exe = self._executable(batch.bucket)
            t0 = time.perf_counter()
            with span("serve.dispatch"):
                logits, occs = exe(self.params, imgs,
                                   jnp.asarray(batch.n_real, jnp.int32))
            with span("serve.wait"):
                jax.block_until_ready(logits)
            wall = time.perf_counter() - t0
            with span("serve.fetch"):
                results = self._finish_batch(batch, imgs, logits, wall)
            with span("serve.observe"):
                # after results exist: a re-plan failure must not drop
                # served work
                self._observe(np.asarray(occs))
            return results

    def _finish_batch(self, batch: MicroBatch, imgs, logits, wall: float) -> list:
        # the time CHARGED to the timeline: measured wall by default, or the
        # deterministic sim_service_s model (fixed or per-bucket) so seeded
        # SimClock replays are bit-identical end to end
        if self.sim_service_s is None:
            dt = wall
        elif callable(self.sim_service_s):
            dt = float(self.sim_service_s(batch.bucket, batch.n_real))
        else:
            dt = float(self.sim_service_s)
        if isinstance(self.clock, SimClock):
            self.clock.advance(dt)  # charge service time to the sim timeline
        t_done = self.clock()
        logits = np.asarray(logits)
        self._calib_recent = imgs[: batch.n_real]
        results = [ServedResult(id=r.id, logits=logits[i], t_arrival=r.t_arrival,
                                t_done=t_done, t_formed=batch.t_formed)
                   for i, r in enumerate(batch.requests)]
        self.metrics.on_batch(t_done, batch.bucket, batch.n_real, dt)
        for r in results:
            self.metrics.on_result(r.latency_s)
        return results

    # ------------------------------------------------------------------
    # occupancy drift -> background re-plan
    # ------------------------------------------------------------------

    def _observe(self, occs: np.ndarray) -> None:
        a = self.ema_alpha
        self._occ_ema = (1.0 - a) * self._occ_ema + a * occs
        self.metrics.on_occupancy(self.clock(), self._occ_ema)
        if self._cooldown > 0:
            self._cooldown -= 1
            return
        if self._replanning:
            return
        planned = np.array([lp.occupancy for lp in self.plan.layers])
        delta = float(np.abs(self._occ_ema - planned).max())
        if delta > self.replan_band:
            self.metrics.on_replan_trigger(self.clock(), delta)
            self._launch_replan()

    def _launch_replan(self) -> None:
        calib = self._calib_recent
        if calib is None:
            return
        self._replanning = True
        plan = self.plan
        gen = self._plan_gen

        def work():
            try:
                with self.tracer.span("serve.replan", trigger="occupancy_drift"):
                    new = plan_network(self.params, calib, self.graph,
                                       occ_threshold=plan.occ_threshold,
                                       block_c=plan.block_c,
                                       use_pallas=self.use_pallas,
                                       calibration=self.calibration,
                                       tiles=self.tiles, int8=self.int8,
                                       int8_budget=self.int8_budget)
            except Exception:
                # a failed re-plan must neither wedge the drift detector nor
                # take down the serving loop — keep the current plan, count
                # the failure (stats()["replan_errors"]), and retry on the
                # next drift trigger
                with self._lock:
                    self._replanning = False
                    self.replan_errors += 1
                self.metrics.on_replan_error(self.clock())
                return
            with self._lock:
                if gen == self._plan_gen:
                    self._pending_plan = new
                else:
                    # a hot_swap landed while this re-plan was in flight: the
                    # result was planned against the swapped-out params, so
                    # adopting it would serve the OLD model's schedule on the
                    # new params — drop it and unblock the drift detector
                    self._replanning = False

        if self.replan_async:
            self._replan_thread = threading.Thread(target=work, daemon=True)
            self._replan_thread.start()
        else:
            work()

    def _adopt_pending_plan(self) -> None:
        """Atomic swap point: a finished re-plan replaces the live plan only
        BETWEEN batches (never mid-execution). Resetting the EMA reference to
        the new plan's calibrated occupancies closes the hysteresis loop —
        drift inside the band never re-plans, and a swap re-centers the band."""
        with self._lock:
            if self._pending_plan is None:
                return
            new, self._pending_plan = self._pending_plan, None
        self._replanning = False
        if not self._verify_candidate(new, self.params):
            return  # erroring re-plan result: keep serving the current plan
        changed = plan_key(0, new) != plan_key(0, self.plan)
        if changed:
            self.n_replans += 1  # schedule changed; same-key swaps only re-center
        self.plan = new
        self._occ_ema = np.array([lp.occupancy for lp in new.layers])
        self._cooldown = self.replan_cooldown
        self.metrics.on_replan_swap(self.clock(), changed)

    def _verify_candidate(self, plan, params) -> bool:
        """Static gate on every plan-adoption path (DESIGN.md §12): any
        error-severity diagnostic rejects the candidate BEFORE the engine
        mutates anything — the reject is counted (stats()
        ["verify_rejects"]), lands in the telemetry event stream, and
        serving continues on the current plan/params."""
        from repro.analysis import errors, verify_plan

        bad = errors(verify_plan(plan, params, graph=self.graph))
        if not bad:
            return True
        self.verify_rejects += 1
        self.metrics.on_verify_reject(self.clock(),
                                      tuple(d.code for d in bad))
        return False

    def hot_swap(self, params, *, plan: PipelinePlan | None = None,
                 calib=None) -> bool:
        """Swap the SERVED MODEL under load — canonically to a
        differently-pruned BSR variant of the same graph (DESIGN.md §7: the
        weight signature in `PlanKey` keeps both variants' programs resident
        side by side, so swapping back and forth never recompiles a warm
        bucket). The swap is atomic between batches exactly like a re-plan
        adoption: callers drive it from the scenario event loop (or any
        other point outside `poll()`/`serve()`), never mid-execution.

        `plan` pins the new schedule; otherwise the new params are planned on
        `calib` (default: the most recent real batch) at the current plan's
        occ_threshold/block_c. An in-flight background re-plan belongs to the
        OLD params — the generation bump makes its eventual result drop on
        arrival instead of clobbering the swapped-in model.

        Every candidate is statically verified against the NEW params before
        anything mutates: an erroring (plan, params) pair is rejected
        atomically — returns False, counts in stats()["verify_rejects"],
        and the engine keeps serving the current model (a freshly planned
        candidate raises from `plan_network` itself instead). Returns True
        on a completed swap."""
        if plan is None:
            calib = self._calib_recent if calib is None else calib
            if calib is None:
                raise ValueError("hot_swap needs plan= or calib= before the "
                                 "engine has executed its first batch")
            with self.tracer.span("serve.plan", graph=self.graph.name,
                                  trigger="hot_swap"):
                plan = plan_network(params, calib, self.graph,
                                    occ_threshold=self.plan.occ_threshold,
                                    block_c=self.plan.block_c,
                                    use_pallas=self.use_pallas,
                                    calibration=self.calibration,
                                    tiles=self.tiles, int8=self.int8,
                                    int8_budget=self.int8_budget)
        elif not self._verify_candidate(plan, params):
            return False
        with self._lock:
            self._plan_gen += 1
            self._pending_plan = None
        self.params = params
        self.plan = plan
        if plan.graph is not None:
            self.graph = plan.graph
        self._occ_ema = np.array([lp.occupancy for lp in plan.layers])
        self._cooldown = self.replan_cooldown
        self.n_hot_swaps += 1
        self.metrics.on_hot_swap(self.clock())
        return True

    def join_replan(self, timeout: float | None = 10.0) -> None:
        """Test/shutdown helper: wait for an in-flight background re-plan."""
        t = self._replan_thread
        if t is not None:
            t.join(timeout)


def replay_stream(engine: Engine, imgs, rate_rps: float,
                  arrivals=None) -> list:
    """Drive the engine's event loop over a deterministic open-loop request
    stream on a `SimClock`: images arrive at `rate_rps` (or at the explicit
    `arrivals` timestamps), the clock jumps to the next event (arrival or
    batcher deadline), and the engine charges service time into the
    simulated timeline (measured wall, or its `sim_service_s` model).
    Returns all `ServedResult`s.

    Thin wrapper over `repro.serving.scenarios.replay_scenario` — the
    steady-rate stream is just the degenerate single-stream `ListScenario`.
    The engine's clock must be a SimClock.
    """
    from repro.serving.scenarios import ListScenario, replay_scenario

    clock = engine.clock
    if not isinstance(clock, SimClock):
        raise ValueError("replay_stream needs an Engine built on a SimClock")
    if arrivals is None:
        t0 = clock()
        arrivals = [t0 + i / rate_rps for i in range(len(imgs))]
    scenario = ListScenario(imgs=tuple(imgs), arrivals=tuple(arrivals))
    return replay_scenario(engine, scenario)[""]
