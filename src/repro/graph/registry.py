"""Op registry: THE one impl-dispatch site from planner to serving.

Every (node kind, impl) pair maps to one `OpImpl` carrying its forward
callable, its op-level cost hook (the autotuner's roofline fallback), and its
fusion metadata. The string-keyed if/elif chains that used to be duplicated
across `pipeline/planner.py`, `models/cnn.py` and the serving cost hooks all
collapse into `get_op` lookups; adding an impl (or a new fused epilogue) is
one `register_op` call, and planner/executor/serving pick it up unchanged.

Kinds:
  "conv"       plain convolution; ReLU / unfused pooling applied structurally
               by the executor around it.
  "conv_pool"  fused conv+ReLU+maxpool (the PECR family) — consumes the whole
               conv unit in one op, the conv result never leaves VMEM/registers.

The registry is also THE cost-dispatch site: `unit_cost` / `unit_model_us`
evaluate one conv unit's modeled FLOPs/bytes/roofline-time as any (kind,
impl) — the planner's joint dense/ECR/PECR/BSR decision and the autotuner's
noisy-clock fallback (`serving.autotune.plan_model_us`) both rank layers
through it, so an impl's cost hook is consulted identically everywhere.

The fusion rule lives here too: `fusion_eligible(unit)` says whether a conv
unit's structure admits the fused epilogue (adjacent ReLU + pool,
pooling stride == pool size, conv output tiled exactly by the pool — the
Pallas epilogue floors, so a remainder would silently change semantics), and
`fused_impl`/`conv_impl` map between a fused impl and the unfused conv impl of
the same family ("pecr_pallas" <-> "ecr_pallas").
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

from repro.graph.ir import ConvUnit

# ---------------------------------------------------------------------------
# Registry core
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OpImpl:
    """One registered (kind, impl) implementation.

    forward: kind "conv"      -> f(x_padded, w, *, stride, block_c, tile) -> y
             kind "conv_pool" -> f(x_padded, w, *, stride, pool, block_c,
             tile) -> y  (`tile` is a `repro.kernels.tiles.TileConfig` — the
             searched kernel geometry; None/all-zero = the impl's defaults,
             and non-Pallas impls ignore it entirely)
    cost:    f(c, h, w, o, kh, kw, *, stride, occupancy, batch, [pool]) -> dict
             with "flops"/"bytes"/"out_elems" (None = no model; autotune then
             treats the layer as dense roofline).
    sparse:  occupancy-dependent (skips dead channel blocks) — the planner may
             only place these below occ_threshold, and the cost hook honours
             the measured occupancy.
    weight_sparse: depends on STATIC weight block density (skips pruned-away
             weight blocks; activation occupancy buys it nothing) — the
             planner only places these below its density gate, the cost hook
             honours `weight_density`, and `validate_plan` re-checks the
             params' measured density against the plan's at run time.
    pallas:  realized as a Pallas kernel (vs a jnp oracle / XLA path).
    quantized: int8 compute path (fp32 in/out, int8 operands inside) — the
             planner only places these under an explicit accuracy budget
             (`plan_network(int8=..., int8_budget=...)`), mirroring how
             weight_sparse impls sit behind the density gate.
    fused_with: for kind "conv_pool", the kind-"conv" impl of the same family
             (used when a unit's pool is NOT fusion-eligible); for kind
             "conv", the kind-"conv_pool" impl it upgrades to when fusion IS
             eligible (None = never fuses).
    launch:  f(unit, *, tile, block_c, batch) -> a resolved launch descriptor
             (`repro.kernels.tiles.ConvLaunch` / `BsrLaunch`) describing the
             Pallas grid this impl would run on `unit` — the geometry seam
             the static checker (`repro.analysis.launch`) verifies WITHOUT
             compiling. None for impls with no Pallas grid (XLA/jnp paths).
    """

    kind: str
    impl: str
    forward: Callable
    cost: Callable | None = None
    sparse: bool = False
    weight_sparse: bool = False
    pallas: bool = False
    quantized: bool = False
    fused_with: str | None = None
    launch: Callable | None = None


_OPS: dict = {}


def register_op(op: OpImpl) -> OpImpl:
    key = (op.kind, op.impl)
    if key in _OPS:
        raise ValueError(f"op {key} already registered")
    _OPS[key] = op
    return op


def get_op(kind: str, impl: str) -> OpImpl:
    try:
        return _OPS[(kind, impl)]
    except KeyError:
        known = sorted(i for k, i in _OPS if k == kind)
        raise ValueError(
            f"unknown {kind} impl {impl!r} (registered: {known})") from None


def list_ops(kind: str | None = None) -> tuple:
    return tuple(op for op in _OPS.values() if kind is None or op.kind == kind)


# ---------------------------------------------------------------------------
# Fusion rule
# ---------------------------------------------------------------------------


def fusion_eligible(unit: ConvUnit) -> bool:
    """conv+ReLU+pool -> PECR is legal iff the triple is adjacent AND the
    pool is the kernel-supported form: an unpadded max-pool with stride == p
    (non-overlapping) and the conv output tiled exactly (the fused epilogue
    floors; an inexact tiling would silently truncate, exactly what PoolSpec
    mode='valid' guards)."""
    pool = unit.pool
    if pool is None or not unit.relu:
        return False
    if pool.s != pool.p or pool.mode == "ceil" or pool.pad \
            or pool.kind != "max":
        return False
    _, oh, ow = unit.conv_out_shape
    return oh % pool.p == 0 and ow % pool.p == 0


def fused_impl(conv_impl: str) -> str | None:
    """The kind-"conv_pool" impl of `conv_impl`'s family (None = no fusion)."""
    return get_op("conv", conv_impl).fused_with


def conv_impl(fused: str) -> str:
    """The kind-"conv" impl a fused impl falls back to on unfusable units."""
    op = get_op("conv_pool", fused)
    if op.fused_with is None:
        raise ValueError(f"fused impl {fused!r} declares no conv fallback")
    return op.fused_with


def unit_impl(unit: ConvUnit, impl: str) -> tuple:
    """Resolve a requested impl against one unit's structure -> (kind, impl).

    A fused-family request ("pecr", "pecr_pallas") becomes the fused op on
    fusion-eligible units and the family's plain conv elsewhere; a plain conv
    request passes through. This is the uniform-impl entry `models/cnn` uses;
    the planner makes the same call per layer with its own sparse decision.
    """
    if ("conv_pool", impl) in _OPS:
        if fusion_eligible(unit):
            return ("conv_pool", impl)
        return ("conv", conv_impl(impl))
    get_op("conv", impl)  # validate
    return ("conv", impl)


# ---------------------------------------------------------------------------
# Cost dispatch (the one place a unit is costed as a (kind, impl))
# ---------------------------------------------------------------------------

# THE roofline constants live in repro.obs.constants (peaks per device
# kind, which a measured CalibrationDB overrides per impl)
from repro.obs.constants import device_peaks  # noqa: E402


def _pool_round_trip(base: dict, pool: int, dtype_bytes: int = 4) -> dict:
    """Cost of running an UNFUSED pool after a conv whose cost is `base`: the
    intermediate write/read round trip and the pooled write that PECR fusion
    deletes (the comparison baseline of DESIGN.md §2.3), plus the pool max
    on the VPU."""
    conv_out = base["out_elems"] * dtype_bytes
    return {"flops": base["flops"] + base["out_elems"],
            "bytes": base["bytes"] + conv_out + conv_out / (pool * pool),
            "out_elems": base["out_elems"] // (pool * pool)}


def unit_cost(kind: str, impl: str, *, c, h, w, o, k, stride=1, pool=None,
              occupancy: float = 1.0, weight_density: float = 1.0,
              batch: int = 1) -> dict:
    """Modeled {"flops","bytes","out_elems"} of one conv unit executed as
    (kind, impl). h/w are the PADDED input dims; `pool` is the unit's pool
    window (None = no pool). A kind-"conv" impl with an adjacent pool is
    costed as its own hook + the unfused round trip; a kind-"conv_pool" impl
    consumes the pool in its hook. Occupancy/weight_density only reach hooks
    whose impl declares the corresponding sparsity (a dense impl is costed
    dense no matter what the input measured)."""
    op = get_op(kind, impl)
    kws = dict(stride=stride, batch=batch,
               occupancy=occupancy if op.sparse else 1.0)
    if op.weight_sparse:
        kws["weight_density"] = weight_density
    if pool is not None and kind != "conv_pool":
        return _pool_round_trip(op.cost(c, h, w, o, k, k, **kws), pool)
    if pool is not None:
        kws["pool"] = pool
    return op.cost(c, h, w, o, k, k, **kws)


def unit_model_us(kind: str, impl: str, unit: ConvUnit, *,
                  occupancy: float = 1.0, weight_density: float = 1.0,
                  batch: int = 1, block_c: int = 0, tile=None,
                  calibration=None) -> float:
    """Roofline-modeled time (us) of executing `unit` as (kind, impl) — the
    common currency of the planner's per-layer impl choice and the
    autotuner's whole-plan model (`plan_model_us` sums this per layer).

    `calibration` (a `repro.obs.calibrate.CalibrationDB`, or None) supplies
    MEASURED effective constants per (device kind, kind, impl, tile geometry);
    any key the DB does not cover — and calibration=None entirely — falls
    back to the device's published peaks (`obs.constants.device_peaks`).
    `block_c` is the plan's channel-block size (0 = auto) and `tile` the
    full searched `TileConfig` (None = defaults) — together the block
    geometry the calibration is keyed on."""
    conv = unit.conv
    c, h, w = unit.in_shape
    cost = unit_cost(kind, impl, c=c, h=h + 2 * conv.pad, w=w + 2 * conv.pad,
                     o=conv.c_out, k=conv.k, stride=conv.stride,
                     pool=unit.pool.p if unit.pool is not None else None,
                     occupancy=occupancy, weight_density=weight_density,
                     batch=batch)
    consts = device_peaks() if calibration is None else \
        calibration.constants_for(kind, impl, block_c, tile=tile)
    return consts.time_us(cost["flops"], cost["bytes"])


def unit_launch(kind: str, impl: str, unit: ConvUnit, *, tile=None,
                block_c: int = 0, batch: int = 1):
    """The resolved launch descriptor of executing `unit` as (kind, impl) —
    None when the impl has no Pallas grid to describe. This is the registry's
    geometry seam: the descriptor comes from the SAME builder the op's
    forward resolves through, so `repro.analysis` verifies the grid that
    would actually launch, never a re-derived approximation."""
    op = get_op(kind, impl)
    if op.launch is None:
        return None
    return op.launch(unit, tile=tile, block_c=block_c, batch=batch)


# ---------------------------------------------------------------------------
# Registrations — the entire impl surface, in one place
# ---------------------------------------------------------------------------


def _conv_dense(xp, w, *, stride, block_c=0, tile=None):
    from repro.core.ecr import conv2d_dense

    return conv2d_dense(xp, w, stride)


def _conv_im2col(xp, w, *, stride, block_c=0, tile=None):
    from repro.core.ecr import conv2d_im2col

    return conv2d_im2col(xp, w, stride)


def _conv_ecr(xp, w, *, stride, block_c=0, tile=None):
    from repro.core.ecr import conv2d_ecr

    return conv2d_ecr(xp, w, stride)


def _conv_ecr_pallas(xp, w, *, stride, block_c=0, tile=None):
    from repro.kernels.ecr_conv.ops import ecr_conv
    from repro.kernels.tiles import as_tile

    t = as_tile(tile, block_c)
    return ecr_conv(xp, w, stride, block_c=t.block_c, block_o=t.block_o)


def _conv_pool_unfused(xp, w, *, stride, pool, block_c=0, tile=None):
    from repro.core.pecr import conv_pool_unfused

    return conv_pool_unfused(xp, w, stride, pool.p, pool.s)


def _conv_pool_pecr(xp, w, *, stride, pool, block_c=0, tile=None):
    from repro.core.pecr import conv_pool_pecr

    return conv_pool_pecr(xp, w, stride, pool.p, pool.s)


def _conv_pool_pecr_pallas(xp, w, *, stride, pool, block_c=0, tile=None):
    from repro.kernels.conv_pool.ops import fused_conv_pool
    from repro.kernels.tiles import as_tile

    # p_s rides through so the kernel's stride==p assertion keeps guarding
    t = as_tile(tile, block_c)
    return fused_conv_pool(xp, w, stride, pool.p, p_s=pool.s,
                           block_c=t.block_c, block_o=t.block_o)


def _conv_cost(c, h, w, o, kh, kw, **kw_args):
    from repro.kernels.ecr_conv.ops import ecr_conv_cost

    return ecr_conv_cost(c, h, w, o, kh, kw, **kw_args)


def _conv_pool_cost(c, h, w, o, kh, kw, **kw_args):
    from repro.kernels.conv_pool.ops import conv_pool_cost

    return conv_pool_cost(c, h, w, o, kh, kw, **kw_args)


def _conv_pool_unfused_cost(c, h, w, o, kh, kw, *, pool=2, dtype_bytes=4, **kw_args):
    """Unfused conv -> ReLU -> pool: the conv cost plus the round trip PECR
    deletes (`_pool_round_trip` over the ECR/dense conv hook)."""
    from repro.kernels.ecr_conv.ops import ecr_conv_cost

    return _pool_round_trip(
        ecr_conv_cost(c, h, w, o, kh, kw, dtype_bytes=dtype_bytes, **kw_args),
        pool, dtype_bytes)


def _conv_bsr(xp, w, *, stride, block_c=0, tile=None):
    from repro.sparse_weights.conv import conv2d_bsr

    return conv2d_bsr(xp, w, stride, tile=tile if tile else None)


def _bsr_cost(c, h, w, o, kh, kw, **kw_args):
    from repro.sparse_weights.conv import bsr_conv_cost

    return bsr_conv_cost(c, h, w, o, kh, kw, **kw_args)


def _conv_ecr_int8(xp, w, *, stride, block_c=0, tile=None):
    from repro.kernels.tiles import as_tile
    from repro.quant.ops import ecr_conv_int8

    t = as_tile(tile, block_c)
    return ecr_conv_int8(xp, w, stride, block_c=t.block_c, block_o=t.block_o)


def _conv_bsr_int8(xp, w, *, stride, block_c=0, tile=None):
    from repro.quant.ops import conv2d_bsr_int8

    return conv2d_bsr_int8(xp, w, stride, tile=tile if tile else None)


def _ecr_int8_cost(c, h, w, o, kh, kw, **kw_args):
    from repro.quant.ops import ecr_conv_int8_cost

    return ecr_conv_int8_cost(c, h, w, o, kh, kw, **kw_args)


def _bsr_int8_cost(c, h, w, o, kh, kw, **kw_args):
    from repro.quant.ops import bsr_conv_int8_cost

    return bsr_conv_int8_cost(c, h, w, o, kh, kw, **kw_args)


# --- launch-descriptor adapters (OpImpl.launch): one per Pallas family ----


def _padded_unit_dims(unit):
    """(c, h, w, o, k, stride) of the kernel call `run_unit` makes for this
    unit — h/w carry the ConvSpec padding the executor applies first."""
    c, h, w = unit.in_shape
    conv = unit.conv
    return c, h + 2 * conv.pad, w + 2 * conv.pad, conv.c_out, conv.k, conv.stride


def _launch_ecr(unit, *, tile=None, block_c=0, batch=1):
    from repro.kernels.ecr_conv.ops import ecr_conv_launch
    from repro.kernels.tiles import as_tile

    c, h, w, o, k, stride = _padded_unit_dims(unit)
    return ecr_conv_launch(c, h, w, o, k, k, stride=stride,
                           tile=as_tile(tile, block_c), batch=batch)


def _launch_pecr(unit, *, tile=None, block_c=0, batch=1):
    from repro.kernels.conv_pool.ops import conv_pool_launch
    from repro.kernels.tiles import as_tile

    c, h, w, o, k, stride = _padded_unit_dims(unit)
    return conv_pool_launch(c, h, w, o, k, k, stride=stride,
                            pool=unit.pool.p if unit.pool is not None else 0,
                            tile=as_tile(tile, block_c), batch=batch)


def _bsr_unit_dims(unit, batch):
    c, _, _, o, k, _ = _padded_unit_dims(unit)
    _, oh, ow = unit.conv_out_shape
    return o, c * k * k, batch * oh * ow


def _launch_bsr(unit, *, tile=None, block_c=0, batch=1):
    from repro.kernels.tiles import as_tile
    from repro.sparse_weights.conv import bsr_conv_launch

    o, k_taps, p = _bsr_unit_dims(unit, batch)
    return bsr_conv_launch(o, k_taps, p, tile=as_tile(tile, block_c) or None)


def _launch_ecr_int8(unit, *, tile=None, block_c=0, batch=1):
    from repro.kernels.tiles import as_tile
    from repro.quant.ops import ecr_conv_int8_launch

    c, h, w, o, k, stride = _padded_unit_dims(unit)
    return ecr_conv_int8_launch(c, h, w, o, k, k, stride=stride,
                                tile=as_tile(tile, block_c), batch=batch)


def _launch_bsr_int8(unit, *, tile=None, block_c=0, batch=1):
    from repro.kernels.tiles import as_tile
    from repro.quant.ops import bsr_conv_int8_launch

    o, k_taps, p = _bsr_unit_dims(unit, batch)
    return bsr_conv_int8_launch(o, k_taps, p, tile=as_tile(tile, block_c) or None)


register_op(OpImpl("conv", "dense", _conv_dense, cost=_conv_cost))
register_op(OpImpl("conv", "im2col", _conv_im2col, cost=_conv_cost))
register_op(OpImpl("conv", "ecr", _conv_ecr, cost=_conv_cost, sparse=True,
                   fused_with="pecr"))
register_op(OpImpl("conv", "ecr_pallas", _conv_ecr_pallas, cost=_conv_cost,
                   sparse=True, pallas=True, fused_with="pecr_pallas",
                   launch=_launch_ecr))
register_op(OpImpl("conv", "bsr", _conv_bsr, cost=_bsr_cost,
                   weight_sparse=True, pallas=True, launch=_launch_bsr))
register_op(OpImpl("conv", "ecr_int8", _conv_ecr_int8, cost=_ecr_int8_cost,
                   sparse=True, pallas=True, quantized=True,
                   launch=_launch_ecr_int8))
register_op(OpImpl("conv", "bsr_int8", _conv_bsr_int8, cost=_bsr_int8_cost,
                   weight_sparse=True, pallas=True, quantized=True,
                   launch=_launch_bsr_int8))
register_op(OpImpl("conv_pool", "unfused", _conv_pool_unfused,
                   cost=_conv_pool_unfused_cost))
register_op(OpImpl("conv_pool", "pecr", _conv_pool_pecr, cost=_conv_pool_cost,
                   sparse=True, fused_with="ecr"))
register_op(OpImpl("conv_pool", "pecr_pallas", _conv_pool_pecr_pallas,
                   cost=_conv_pool_cost, sparse=True, pallas=True,
                   fused_with="ecr_pallas", launch=_launch_pecr))
