"""Per-layer dense/ECR/PECR/BSR planning over the LayerGraph IR.

The paper's win is layer-dependent (Fig. 9: early layers are dense and big,
deep layers are small and very sparse), so a whole-network setting is always
wrong somewhere. The planner walks a `LayerGraph` (any linear CNN — VGG-19,
LeNet, AlexNet; a `CNNConfig` is lowered via `as_graph`) on a calibration
batch, measures per conv unit the channel-block occupancy the ECR kernel
would actually run at — the post-compaction ceil(n_live/bc)/n_cb of
DESIGN.md §2.2, averaged over samples — and emits a `PipelinePlan`: one
`LayerPlan` per conv unit, fused with its pooling (PECR) when the unit is
sparse AND the registry's fusion rule admits it (adjacent ReLU+pool,
stride == p, exact tiling), left as conv + unfused pool otherwise.

Weight sparsity is the second, STATIC axis (DESIGN.md §7): each layer's
params carry a measured BSR block density, and a pruned layer may run
`("conv", "bsr")` — weight blocks skipped instead of activation blocks.
The two axes trade off per layer (BSR reads every window but only the live
weight blocks; ECR reads every weight but only the live activation blocks),
so the planner arbitrates by the registry's modeled cost: below the density
gate, BSR displaces the occupancy-rule choice iff its roofline time wins.

The plan is a static, hashable schedule that carries its graph: `run_plan`
executes it over any batch of the calibrated shape, one jitted whole-batch op
per layer, every op resolved through the registry (`repro.graph.registry`) —
there is no impl dispatch here. This is the seam where serving (plan once,
execute per request batch) and autotuning (search over thresholds/block
sizes, keep the best plan) attach.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.graph import as_graph
from repro.graph.executor import run_head, run_unit
from repro.graph.ir import ConvSpec, LayerGraph, PoolSpec, graph_weights
from repro.graph.registry import fusion_eligible, get_op, unit_model_us
from repro.kernels.tiles import TileConfig, resolve_block_c


@dataclass(frozen=True)
class LayerPlan:
    """One conv unit's placement decision."""

    index: int  # conv index in network order (0-based)
    stage: int  # pooling stage (number of pools crossed before this conv)
    slot: int  # index within the stage
    kind: str  # "conv" | "conv_pool" (the chosen op kind; fused == conv_pool)
    impl: str  # "dense" | "ecr_pallas" | "pecr_pallas" | "ecr" | "pecr" | "bsr"
    occupancy: float  # measured mean channel-block occupancy of the input
    in_shape: tuple  # (C, H, W) entering the layer (pre-padding)
    out_shape: tuple  # (C, H, W) leaving the layer (post-pool if any)
    conv: ConvSpec = ConvSpec(0)  # the unit's conv node (k, stride, pad)
    relu: bool = True  # adjacent ReLU present
    pool: PoolSpec | None = None  # adjacent pool node (None = in-stage conv)
    weight_density: float = 1.0  # measured BSR block density of the params
    tile: TileConfig | None = None  # searched kernel geometry (None = defaults)

    def to_unit(self):
        """The `ConvUnit` this plan entry executes. The LayerPlan is the
        single source of structural truth at run time — `run_plan` executes
        from here, never by re-walking `plan.graph` (a mismatched graph must
        not be able to change what a validated plan runs)."""
        from repro.graph.ir import ConvUnit

        if self.conv.c_out == 0:
            raise ValueError(
                f"conv_{self.index + 1} carries no ConvSpec — this plan "
                "predates the LayerGraph IR; rebuild it with plan_network")
        return ConvUnit(index=self.index, stage=self.stage, slot=self.slot,
                        conv=self.conv, relu=self.relu, pool=self.pool,
                        in_shape=self.in_shape, out_shape=self.out_shape)


@dataclass(frozen=True)
class PipelinePlan:
    layers: tuple  # tuple[LayerPlan, ...]
    occ_threshold: float
    block_c: int  # 0 = auto per layer (tiles.resolve_block_c)
    graph: LayerGraph | None = None  # the IR the plan was made for
    int8_report: object = None  # quant.Int8Report when int8 planning probed

    def counts(self) -> dict:
        c = {"dense": 0, "sparse": 0, "fused": 0, "bsr": 0, "int8": 0}
        for lp in self.layers:
            op = get_op(lp.kind, lp.impl)
            if op.quantized:
                c["int8"] += 1  # counted in its own bucket AND its family's
            if op.weight_sparse:
                c["bsr"] += 1
            elif op.sparse:
                c["sparse"] += 1
                if lp.kind == "conv_pool":
                    c["fused"] += 1
            else:
                c["dense"] += 1
        return c


def occupancy_stat(x, block_c: int = 0, n_valid=None, tile=None):
    """Traced (jit-safe) channel-block occupancy, measured the way the batched
    kernel schedules: shared-union channel compaction, then PER-SAMPLE block
    occupancy on the packed layout (== mean_b cnt_b / n_cb of
    `batch_block_schedule`). For one image this reduces to the compacted
    ceil(n_live / bc) / n_cb of DESIGN.md §2.2.

    The block size is the one the kernel ACTUALLY resolves for this shape
    (`resolve_block_c` — same rule, same fallbacks), so the statistic and
    the executed schedule can never disagree about the geometry; `tile`
    (a TileConfig) takes precedence over the legacy `block_c` scalar.

    x: (N,C,H,W) or (C,H,W). `n_valid` (optional, traced) restricts the
    statistic to the first `n_valid` samples — the serving engine measures
    occupancy over the real requests of a padded bucket, and the all-zero pad
    samples contribute nothing to the union so the masked measurement equals
    what the kernel's per-sample schedules do for the real samples. `n_valid`
    is clamped to [0, N]: 0 (a bucket of pure pads) reports 0.0 occupancy,
    and a count beyond the batch cannot deflate the mean. Returns a scalar
    array (fraction of channel-block work NOT skipped).
    """
    if x.ndim == 3:
        x = x[None]
    n, c = x.shape[:2]
    t = tile if tile is not None and tile else TileConfig(block_c=block_c)
    bc = resolve_block_c(c, t)
    n_cb = -(-c // bc)
    live = jnp.any(x != 0, axis=(2, 3))  # (N, C) per-sample live channels
    if n_valid is not None:
        nv = jnp.clip(jnp.asarray(n_valid, jnp.int32), 0, n)
        live = live & (jnp.arange(n) < nv)[:, None]
    union_order = jnp.argsort(~jnp.any(live, axis=0), stable=True)
    packed = live[:, union_order]  # one shared permutation, like the kernel
    packed = jnp.pad(packed, ((0, 0), (0, n_cb * bc - c)))
    blk_live = packed.reshape(n, n_cb, bc).any(axis=2)  # (N, n_cb)
    if n_valid is None:
        return blk_live.mean()
    per_sample = blk_live.mean(axis=1)  # (N,)
    return jnp.where(jnp.arange(n) < nv, per_sample, 0.0).sum() / jnp.maximum(nv, 1)


def measure_occupancy(x, block_c: int = 0, tile=None) -> float:
    """Concrete-value wrapper of `occupancy_stat` (see its docstring)."""
    return float(occupancy_stat(x, block_c, tile=tile))


def plan_network(
    params,
    calib,
    graph=None,
    *,
    occ_threshold: float = 0.75,
    block_c: int = 0,
    use_pallas: bool = True,
    bsr_threshold: float = 0.5,
    calibration=None,
    tiles=None,
    int8: bool = False,
    int8_budget: float = 0.98,
) -> PipelinePlan:
    """Walk the graph's conv units on a calibration batch, emit the schedule.

    `graph` is a `LayerGraph` or a legacy `CNNConfig` (lowered via
    `as_graph`; None = full VGG-19). A unit goes sparse when its measured
    occupancy is <= occ_threshold (the skipped blocks must pay for the
    compaction gather; at occupancy ~1.0 the sparse path is pure overhead).
    A sparse unit whose structure passes the registry's fusion rule runs the
    fused conv+ReLU+pool op; any other pool stays unfused.

    The STATIC axis rides next to the measured one: each layer's weights
    carry a BSR block density (`repro.sparse_weights`; 1.0 for unpruned
    params, so nothing below fires on a dense model). When a layer's density
    is <= `bsr_threshold`, the `("conv", "bsr")` impl competes against the
    occupancy-rule choice on the registry's modeled roofline time
    (`unit_model_us`) and displaces it iff it wins — BSR trades reading
    every window for reading only live weight blocks, so it beats ECR
    exactly when the weight density undercuts the activation occupancy (and
    beats dense almost always once pruned).

    `calibration` (a `repro.obs.calibrate.CalibrationDB`) puts every one of
    those modeled-time comparisons on MEASURED effective constants
    (DESIGN.md §9): the BSR-displacement race runs calibrated, and the
    occupancy-rule choice itself is re-checked — a layer the threshold sent
    sparse falls back to dense when the calibrated model says the measured
    sparse kernel loses to the measured dense path at this occupancy (the
    device-specific crossover the hard-coded constants cannot see). The
    re-check only fires for (kind, impl) keys the DB actually covers, so an
    empty or absent DB reproduces the uncalibrated plan bit-identically.

    `tiles` (a `CalibrationDB`, typically the one `obs.tilesearch.tile_search`
    persisted winners into — it may be the same object as `calibration`)
    closes the measure -> search -> plan loop: after the (kind, impl) choice,
    the layer's shape is looked up in the winners table and the stored
    measured-best `TileConfig` is stamped onto `LayerPlan.tile`, with the
    occupancy re-measured at that geometry so the recorded statistic matches
    the schedule the kernel will actually run. No stored winner (or no
    `tiles`) leaves `tile=None` — the impl's default geometry, bit-identical
    to before.

    `int8=True` adds the PRECISION axis: a layer placed on a Pallas sparse or
    BSR impl is upgraded to its int8 sibling (`ecr_int8` / `bsr_int8`) iff
    the quantized roofline time wins — with occupancy re-measured at the
    int8 impl's own stored tile winner. Because quantization trades accuracy, the
    upgrades are then PROBED: planned logits vs the dense fp32 oracle on the
    calibration batch, and int8 layers are demoted back to their fp32 choice
    (least modeled saving first) until top-1 agreement >= `int8_budget`.
    The probe lands on the plan as `plan.int8_report` (an `Int8Report`,
    mirroring how pruning reports `PruneReport`).
    """
    from repro.obs.calibrate import unit_shape_key
    from repro.sparse_weights import weight_block_density

    graph = as_graph(graph)
    if calib.ndim == 3:
        calib = calib[None]
    if calibration is not None and not calibration:
        calibration = None  # empty DB == no calibration, one code path
    sparse_conv = "ecr_pallas" if use_pallas else "ecr"
    conv_ws, _ = graph_weights(params)
    layers = []
    fp32_alt: dict = {}  # conv index -> the (kind, impl, tile, occ) int8 displaced
    q_saving: dict = {}  # conv index -> modeled us the int8 upgrade saved
    x = calib
    batch = int(calib.shape[0])
    for unit, w in zip(graph.units(), conv_ws):
        occ = measure_occupancy(x, block_c)
        wd = weight_block_density(w)
        go_sparse = occ <= occ_threshold
        if go_sparse:
            fused = get_op("conv", sparse_conv).fused_with
            if fused is not None and fusion_eligible(unit):
                kind, impl = "conv_pool", fused
            else:
                kind, impl = "conv", sparse_conv
        else:
            kind, impl = "conv", "dense"
        if go_sparse and calibration is not None and (
                calibration.covers(kind, impl, block_c)
                or calibration.covers("conv", "dense", block_c)):
            sparse_us = unit_model_us(kind, impl, unit, occupancy=occ,
                                      batch=batch, block_c=block_c,
                                      calibration=calibration)
            dense_us = unit_model_us("conv", "dense", unit, batch=batch,
                                     block_c=block_c, calibration=calibration)
            if dense_us < sparse_us:
                kind, impl = "conv", "dense"
        if use_pallas and wd <= bsr_threshold:
            base_us = unit_model_us(kind, impl, unit, occupancy=occ,
                                    batch=batch, block_c=block_c,
                                    calibration=calibration)
            bsr_us = unit_model_us("conv", "bsr", unit, weight_density=wd,
                                   batch=batch, block_c=block_c,
                                   calibration=calibration)
            if bsr_us < base_us:
                kind, impl = "conv", "bsr"
        tile = None
        if tiles is not None and get_op(kind, impl).pallas:
            stored = tiles.best_tile(kind, impl, unit_shape_key(unit))
            if stored:
                tile = stored
                if get_op(kind, impl).sparse:
                    # the stat must describe the schedule the winner runs
                    occ = measure_occupancy(x, block_c, tile=tile)
        if int8 and use_pallas:
            op = get_op(kind, impl)
            q_impl = "bsr_int8" if op.weight_sparse else (
                "ecr_int8" if op.sparse else None)
            if q_impl is not None:
                q_tile = tiles.best_tile("conv", q_impl, unit_shape_key(unit)) \
                    if tiles is not None else None
                q_occ = occ
                if get_op("conv", q_impl).sparse:
                    # the stat must describe the int8 winner's schedule
                    q_occ = measure_occupancy(x, block_c, tile=q_tile)
                base_us = unit_model_us(kind, impl, unit, occupancy=occ,
                                        weight_density=wd, batch=batch,
                                        block_c=block_c, tile=tile,
                                        calibration=calibration)
                q_us = unit_model_us("conv", q_impl, unit, occupancy=q_occ,
                                     weight_density=wd, batch=batch,
                                     block_c=block_c, tile=q_tile,
                                     calibration=calibration)
                if q_us < base_us:
                    fp32_alt[unit.index] = (kind, impl, tile, occ)
                    q_saving[unit.index] = base_us - q_us
                    kind, impl, tile, occ = "conv", q_impl, q_tile, q_occ
        # the dense oracle produces the next calibration input
        x = run_unit(x, w, unit, "conv", "dense")
        layers.append(
            LayerPlan(
                index=unit.index,
                stage=unit.stage,
                slot=unit.slot,
                kind=kind,
                impl=impl,
                occupancy=occ,
                in_shape=unit.in_shape,
                out_shape=unit.out_shape,
                conv=unit.conv,
                relu=unit.relu,
                pool=unit.pool,
                weight_density=wd,
                tile=tile,
            )
        )
    plan = PipelinePlan(layers=tuple(layers), occ_threshold=occ_threshold,
                        block_c=block_c, graph=graph)
    if int8:
        plan = _probe_int8(plan, params, calib, fp32_alt, q_saving,
                           int8_budget)
    # a freshly planned schedule must verify clean before anyone caches,
    # compiles or serves it (DESIGN.md §12) — any error here is a planner bug
    from repro.analysis import assert_plan_ok

    assert_plan_ok(plan, params, graph=graph, batch=batch)
    return plan


def _probe_int8(plan: PipelinePlan, params, calib, fp32_alt: dict,
                q_saving: dict, budget: float) -> PipelinePlan:
    """Accuracy-gate a plan's int8 placements (`plan_network(int8=True)`).

    Probe: planned logits vs the dense fp32 oracle on the calibration batch
    (the fp32 plan is exact vs dense — DESIGN.md §3 — so ALL drift here is
    quantization). While top-1 agreement < `budget`, demote the int8 layer
    with the least modeled saving back to its recorded fp32 alternative and
    re-probe. The loop terminates: with every int8 layer demoted the plan is
    fp32-exact and agreement is 1.0. Returns the plan with `int8_report`."""
    from dataclasses import replace

    from repro.graph.executor import run_graph
    from repro.quant import Int8Report

    def probe(p):
        got = run_plan(p, params, calib)
        ref = run_graph(p.graph, params, calib, "dense")
        agree = float((jnp.argmax(got, -1) == jnp.argmax(ref, -1)).mean())
        drift = float(jnp.max(jnp.abs(got - ref)))
        return agree, drift

    agree, drift = probe(plan)
    demoted = []
    order = sorted(fp32_alt, key=lambda i: q_saving[i])  # cheapest give-back
    layers = list(plan.layers)
    while agree < budget and order:
        i = order.pop(0)
        kind, impl, tile, occ = fp32_alt[i]
        pos = next(p for p, lp in enumerate(layers) if lp.index == i)
        layers[pos] = replace(layers[pos], kind=kind, impl=impl, tile=tile,
                              occupancy=occ)
        demoted.append(i)
        plan = replace(plan, layers=tuple(layers))
        agree, drift = probe(plan)
    report = Int8Report(
        layers=tuple(i for i in sorted(fp32_alt) if i not in demoted),
        max_logit_drift=drift, top1_agreement=agree,
        demoted=tuple(demoted))
    return replace(plan, int8_report=report)


def _plan_graph(plan: PipelinePlan, fallback=None) -> LayerGraph:
    """The graph a plan executes (pre-IR plans fall back to a CNNConfig)."""
    return plan.graph if plan.graph is not None else as_graph(fallback)


def validate_plan(plan: PipelinePlan, params, imgs, graph=None) -> None:
    """Raise a clear ValueError on any plan/params/input mismatch.

    `run_plan` zips the plan with the params' weights and runs whatever the
    shapes allow — without these checks a wrong-resolution batch or a
    mismatched network executes silently and returns garbage logits. The
    serving engine depends on this contract: a plan only ever executes on the
    (C,H,W) it was calibrated for, against the params it was planned over.

    The input-batch checks live here (only this call site has the images);
    everything else — plan/graph/params invariants, fusion legality, launch
    geometry, BSR density — is the static verifier's job (DESIGN.md §12):
    `repro.analysis.assert_plan_ok`, which raises a `PlanVerificationError`
    (a ValueError subclass) listing every error-severity diagnostic.
    """
    from repro.analysis import assert_plan_ok

    if imgs.ndim not in (3, 4):
        raise ValueError(f"run_plan expects (C,H,W) or (N,C,H,W) images, got shape {tuple(imgs.shape)}")
    if not plan.layers:
        raise ValueError("run_plan got an empty PipelinePlan (no layers)")
    in_shape = tuple(imgs.shape[-3:])
    if in_shape != tuple(plan.layers[0].in_shape):
        raise ValueError(
            f"plan was calibrated for input shape {tuple(plan.layers[0].in_shape)}, "
            f"got images of shape {in_shape}")
    batch = int(imgs.shape[0]) if imgs.ndim == 4 else 1
    assert_plan_ok(plan, params, graph=_plan_graph(plan, graph), batch=batch)


def run_plan(plan: PipelinePlan, params, imgs, ccfg=None, *,
             collect_occupancy: bool = False, n_valid=None,
             axis_name: str | None = None):
    """Execute the planned layer sequence over a batch: (N,C,H,W) -> logits.

    Each entry is one whole-batch op resolved through the registry: the fused
    Pallas grid for sparse fused units, conv + ReLU (+ unfused pool)
    otherwise. Pallas layers run at the plan's `block_c` — the block size the
    occupancy was measured (and the sparse/dense decision made) at. `ccfg` is
    only consulted for pre-IR plans that carry no graph.

    collect_occupancy=True additionally returns the per-layer observed
    channel-block occupancy of each layer's INPUT (a (n_layers,) array,
    jit-traceable) — the signal the serving engine's drift detector consumes.
    `n_valid` (traced) masks the statistic to the first n_valid samples of a
    padded serving bucket.

    `axis_name` marks a call from inside a shard_map body (see
    `run_plan_sharded`): the per-layer math is per-sample and needs no
    collective, but the occupancy statistic is then shard-local, so it is
    aggregated across the mesh axis — weighted by each shard's valid-sample
    count when `n_valid` is given (a ragged bucket's tail shard holds fewer
    real samples), which reduces to a plain `lax.pmean` for full buckets.
    """
    if imgs.ndim == 3:
        imgs = imgs[None]
    validate_plan(plan, params, imgs, ccfg)
    graph = _plan_graph(plan, ccfg)
    conv_ws, dense_ws = graph_weights(params)
    x = imgs
    occs = []
    for lp, w in zip(plan.layers, conv_ws):
        lp_tile = getattr(lp, "tile", None)
        # named scopes put each layer's ops under conv<i> (and the head's
        # under head) in the compiled program's op metadata, so a profiler
        # trace attributes device time per layer; they change no op
        with jax.named_scope(f"conv{lp.index + 1}"):
            if collect_occupancy:
                with jax.named_scope("occupancy"):
                    occs.append(occupancy_stat(x, plan.block_c, n_valid,
                                               tile=lp_tile))
            x = run_unit(x, w, lp.to_unit(), lp.kind, lp.impl, plan.block_c,
                         tile=lp_tile)
    with jax.named_scope("head"):
        logits = run_head(x, dense_ws, graph.head())
    if collect_occupancy:
        occs = jnp.stack(occs)
        if axis_name is not None:
            if n_valid is None:
                occs = jax.lax.pmean(occs, axis_name)
            else:
                wt = jnp.clip(jnp.asarray(n_valid, jnp.float32), 0.0,
                              float(imgs.shape[0]))
                occs = jax.lax.psum(occs * wt, axis_name) / jnp.maximum(
                    jax.lax.psum(wt, axis_name), 1.0)
        return logits, occs
    return logits


def run_plan_sharded(plan: PipelinePlan, params, imgs, mesh, *,
                     collect_occupancy: bool = False, n_valid=None):
    """`run_plan` under `shard_map` over a 1-D "data" mesh (DESIGN.md §6).

    The batch dim is sharded across the mesh's data axis; params are
    replicated; each shard executes its slice with DEVICE-LOCAL per-sample
    (ids, cnt) schedules — sparsity skipping never needs a collective, so the
    only cross-device traffic is the occupancy aggregation above. `n_valid`
    is the GLOBAL count of real (non-pad) samples; each shard derives its
    local count from its `lax.axis_index` (pad samples sit at the tail of the
    batch, so they land on the highest-index shards).

    Exactness: shard-local logits are bit-identical to the single-device
    `run_plan` whenever every shard's local batch is >= 2 (the same XLA
    M=1-GEMV caveat as `MicroBatcher.min_bucket`) and co-batched samples
    share a live-channel union (all-zero pads never perturb it) — the serving
    engine's device-aligned buckets enforce both. `mesh=None` (or a 1-device
    mesh) falls back to plain `run_plan`, bit-identical to today.

    The batch must divide the data-axis size; the batcher's device-aligned
    buckets guarantee it, and anything else raises here rather than silently
    replicating.
    """
    from jax.sharding import PartitionSpec as P

    if imgs.ndim == 3:
        imgs = imgs[None]
    if mesh is None or mesh.size == 1:
        return run_plan(plan, params, imgs,
                        collect_occupancy=collect_occupancy, n_valid=n_valid)
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"run_plan_sharded needs a mesh with a 'data' axis, got axes "
            f"{tuple(mesh.axis_names)}")
    n_dev = int(mesh.shape["data"])
    n = int(imgs.shape[0])
    if n % n_dev:
        raise ValueError(
            f"batch of {n} does not divide the {n_dev}-device data axis — "
            "pad to a device-aligned bucket (MicroBatcher(align=n_dev))")
    validate_plan(plan, params, imgs)  # fail eagerly, outside the trace
    local_n = n // n_dev

    if collect_occupancy:
        nv = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)

        def mapped(params, imgs_local, nv):
            shard_i = jax.lax.axis_index("data")
            nv_local = jnp.clip(nv - shard_i * local_n, 0, local_n)
            return run_plan(plan, params, imgs_local, collect_occupancy=True,
                            n_valid=nv_local, axis_name="data")

        fn = jax.shard_map(mapped, mesh=mesh, in_specs=(P(), P("data"), P()),
                           out_specs=(P("data"), P()), check_vma=False)
        return fn(params, imgs, nv)

    def mapped(params, imgs_local):
        return run_plan(plan, params, imgs_local)

    fn = jax.shard_map(mapped, mesh=mesh, in_specs=(P(), P("data")),
                       out_specs=P("data"), check_vma=False)
    return fn(params, imgs)
