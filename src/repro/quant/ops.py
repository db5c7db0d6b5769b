"""Jitted int8 conv wrappers + cost hooks: the quantized (kind, impl) family.

`ecr_conv_int8` / `conv2d_bsr_int8` mirror their fp32 siblings
(`kernels.ecr_conv.ops.ecr_conv`, `sparse_weights.conv.conv2d_bsr`) exactly —
same compaction, same schedules, same tile-geometry resolution through
`repro.kernels.tiles`, and the same Pallas kernels (`conv_pallas`,
`bsr_matmul_pallas` with scales) — and differ only in precision: operands
are absmax-int8 (`repro.quant.quantize`), the MAC accumulates int32, and the
flush rescales to fp32. In/out dtypes are fp32 like every
registry forward, so the planner can swap an int8 impl into any layer
without touching its neighbors.

The `*_ref` oracles compute the SAME quantized math in plain JAX (dense conv
over the int8 values cast to fp32, rescaled), so kernel-vs-ref agreement is
tight (int32 accumulation is exact; the fp32 oracle is exact while
per-output sums stay under 2^24) and quantization ERROR is isolated to the
ref-vs-fp32 comparison the accuracy budget governs.

Cost hooks model the int8 arithmetic at 2x the fp32 MXU peak (flops * 0.5
against the fp-calibrated roofline constants) and operand traffic at 1 byte
per element (output still fp32) — compute-bound layers win ~2x modeled,
bandwidth-bound ones ~4x on the operand side, which is what lets
`plan_network`'s joint comparison place int8 only where it pays.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bsr_matmul.kernel import bsr_matmul_pallas
from repro.kernels.schedule_guard import guard_schedule
from repro.kernels.tiles import BsrLaunch, ConvLaunch, TileConfig
from repro.quant.quantize import quantize_acts, quantize_weights


@dataclass(frozen=True)
class Int8Report:
    """Accuracy probe of a plan's int8 placements — the quantized mirror of
    `sparse_weights.prune.PruneReport`: same probe protocol (dense fp32
    logits vs the planned-with-int8 logits on the calibration batch), same
    acceptance currency (top-1 agreement)."""

    layers: tuple  # conv indices running an int8 impl after planning
    max_logit_drift: float  # max |planned - fp32 dense| over calib logits
    top1_agreement: float  # fraction of calib samples with unchanged argmax
    demoted: tuple = ()  # indices demoted back to fp32 to meet the budget


def ecr_conv_int8_launch(c: int, h: int, w: int, o: int, kh: int = 3,
                         kw: int = 3, *, stride: int = 1, block_c: int = 0,
                         block_o: int = 0, tile: TileConfig | None = None,
                         batch: int = 1) -> ConvLaunch:
    """`ConvLaunch` of one int8 ECR conv call: the fp32 builder at
    dtype_bytes=1 (a quarter of the operand VMEM) with the int8 contract
    recorded — int32 accumulation, per-output-channel weight scales — for
    the static checker to verify."""
    from repro.kernels.ecr_conv.ops import ecr_conv_launch

    return ecr_conv_launch(c, h, w, o, kh, kw, stride=stride, block_c=block_c,
                           block_o=block_o, tile=tile, batch=batch,
                           dtype_bytes=1, kernel="ecr_conv_int8",
                           acc_dtype="int32",
                           weight_scales="per_output_channel")


def bsr_conv_int8_launch(o: int, k_taps: int, p: int, *,
                         tile: TileConfig | None = None) -> BsrLaunch:
    """`BsrLaunch` of one int8 BSR conv call (int32 accumulation, per-row =
    per-output-channel weight scales delivered as (bt, 1) tiles)."""
    from repro.sparse_weights.conv import bsr_conv_launch

    return bsr_conv_launch(o, k_taps, p, tile=tile, dtype_bytes=1,
                           kernel="bsr_matmul_int8", acc_dtype="int32",
                           weight_scales="per_output_channel")


@partial(jax.jit, static_argnames=("stride", "block_c", "block_o", "compact"))
def ecr_conv_int8(x_chw, kernels_oihw, stride: int = 1, block_c: int = 0,
                  block_o: int = 0, compact: bool = True):
    """int8 ECR conv: (C,H,W) x (O,C,kh,kw) -> fp32 (O,oh,ow), skipping dead
    input channel blocks; batched (N,C,H,W) -> (N,O,oh,ow) with per-sample
    schedules AND per-sample activation scales (one image runs as a batch
    of one, so its scale is per tensor). Quantization happens after channel
    compaction (compaction only permutes channels, so scales are invariant
    to it) and the block schedule is computed on the QUANTIZED values — a
    block that rounds to all-zero is skipped, which is exact (its
    dequantized contribution would be zero)."""
    from repro.core.ecr import compact_live_channels_batch
    from repro.kernels.ecr_conv.ops import as_conv_operands, run_conv_kernel

    x, kernels_oihw, single = as_conv_operands(x_chw, kernels_oihw)
    n, c, h, w = x.shape
    o, _, kh, kw = kernels_oihw.shape
    launch = ecr_conv_int8_launch(c, h, w, o, kh, kw, stride=stride,
                                  block_c=block_c, block_o=block_o, batch=n)
    if compact:
        x, kernels_oihw, _ = compact_live_channels_batch(x, kernels_oihw)
    xq, sx = quantize_acts(x, per_sample=True)  # (N,C,H,W) i8, (N,)
    wq, sw = quantize_weights(kernels_oihw)  # (O,C,kh,kw) i8, (O,)
    y = run_conv_kernel(xq, wq, launch, sx=sx, sw=sw)
    return y[0] if single else y


def ecr_conv_int8_ref(x, w, stride: int = 1):
    """Pure-JAX oracle of the int8 path: dense conv over the int8 VALUES cast
    to fp32, rescaled — bit-tight against the kernel (both accumulate the
    same integers exactly) and the right baseline for quantization-error
    tests against the true fp32 conv."""
    from repro.core.ecr import conv2d_dense

    per_sample = x.ndim == 4
    xq, sx = quantize_acts(x, per_sample=per_sample)
    wq, sw = quantize_weights(w)
    y = conv2d_dense(xq.astype(jnp.float32), wq.astype(jnp.float32), stride)
    if per_sample:
        return y * sx[:, None, None, None] * sw[None, :, None, None]
    return y * sx * sw[:, None, None]


@partial(jax.jit, static_argnames=("stride", "tile"))
def conv2d_bsr_int8(x, w, stride: int = 1, tile=None):
    """int8 weight-block-sparse conv: the `conv2d_bsr` im2col lowering with
    the quantized weight matrix as the sparse left operand. Weights carry one
    scale per output channel (= per row of W:(O,K), delivered as (bt, 1)
    tiles), patches one per-tensor scale; the (ids, cnt) schedule is computed
    on the QUANTIZED weight blocks so pruned-away and quantized-to-zero
    blocks both cost nothing. Returns fp32 (O,oh,ow) / (N,O,oh,ow)."""
    from repro.core.sparsity import extract_windows
    from repro.kernels.bsr_matmul.ops import block_schedule
    from repro.quant.quantize import absmax_scale, quantize_int8
    from repro.sparse_weights.format import conv_weight_matrix

    single = x.ndim == 3
    if single:
        x = x[None]
    n = x.shape[0]
    o, c, kh, kw = w.shape
    wins = jax.vmap(lambda xi: extract_windows(xi, kh, kw, stride))(
        x.astype(jnp.float32))  # (N, oh, ow, K)
    _, oh, ow, k_taps = wins.shape
    a = wins.reshape(n * oh * ow, k_taps)  # (P, K) patches
    wm = conv_weight_matrix(w).astype(jnp.float32)  # (O, K)
    p = a.shape[0]
    launch = bsr_conv_int8_launch(o, k_taps, p, tile=tile)
    bt, bf, bd = launch.bt, launch.bf, launch.bd
    sw = absmax_scale(wm, axis=1)  # (O,) per-row = per-output-channel
    wm_q = quantize_int8(wm, sw[:, None])
    sa = absmax_scale(a)  # scalar, per-tensor patches
    a_q = quantize_int8(a, sa)
    wm_p = jnp.pad(wm_q, ((0, launch.t_pad), (0, launch.f_pad)))
    at_p = jnp.pad(a_q, ((0, launch.d_pad), (0, launch.f_pad))).T  # (Kp, Pp)
    sw_p = jnp.pad(sw, (0, launch.t_pad), constant_values=1.0)[:, None]  # (Op,1)
    ids, cnt = block_schedule(wm_p, bt, bf)
    ids, cnt = guard_schedule(ids, cnt, launch.nf)
    yt = bsr_matmul_pallas(wm_p, at_p, ids, cnt, block=(bt, bf, bd),
                           sh=sw_p, sw=sa.reshape(1, 1))
    y = yt[:o, :p].T.reshape(n, oh, ow, o).transpose(0, 3, 1, 2)
    return y[0] if single else y


def conv2d_bsr_int8_ref(x, w, stride: int = 1):
    """Oracle of the int8 BSR path: same quantization granularity (per-tensor
    patches == per-tensor activations once extracted, per-output-channel
    weights), dense fp32 math over the quantized values."""
    from repro.core.ecr import conv2d_dense
    from repro.quant.quantize import absmax_scale, quantize_int8
    from repro.sparse_weights.format import conv_weight_matrix

    single = x.ndim == 3
    xs = x[None] if single else x
    o, c, kh, kw = w.shape
    wm = conv_weight_matrix(w).astype(jnp.float32)
    sw = absmax_scale(wm, axis=1)  # (O,)
    wq = quantize_int8(wm, sw[:, None]).astype(jnp.float32).reshape(w.shape)
    # patch scale: the im2col matrix holds exactly x's (padded-window) values,
    # so its absmax equals the activation absmax
    from repro.core.sparsity import extract_windows

    wins = jax.vmap(lambda xi: extract_windows(xi, kh, kw, stride))(
        xs.astype(jnp.float32))
    sa = absmax_scale(wins.reshape(-1, wins.shape[-1]))
    xq = quantize_int8(xs, sa).astype(jnp.float32)
    y = conv2d_dense(xq, wq, stride) * sa * sw[None, :, None, None]
    return y[0] if single else y


# ---------------------------------------------------------------------------
# Cost hooks — the registry's ("conv", "ecr_int8" / "bsr_int8") models
# ---------------------------------------------------------------------------


def ecr_conv_int8_cost(c: int, h: int, w: int, o: int, kh: int = 3,
                       kw: int = 3, *, stride: int = 1, occupancy: float = 1.0,
                       batch: int = 1, dtype_bytes: int = 4) -> dict:
    """`ecr_conv_cost` repriced for int8: operand traffic at 1 byte/elem
    (activations, weights — the output still leaves as fp32 at
    `dtype_bytes`), and flops * 0.5 because the int8 MXU path peaks at 2x
    the fp32 OPS (so halved "fp-equivalent" flops model halved time against
    the SAME fp-calibrated roofline constants)."""
    from repro.kernels.ecr_conv.ops import ecr_conv_cost

    base = ecr_conv_cost(c, h, w, o, kh, kw, stride=stride,
                         occupancy=occupancy, batch=batch, dtype_bytes=1)
    return {"flops": base["flops"] * 0.5,
            "bytes": base["bytes"] + (dtype_bytes - 1.0) * base["out_elems"],
            "out_elems": base["out_elems"]}


def bsr_conv_int8_cost(c: int, h: int, w: int, o: int, kh: int = 3,
                       kw: int = 3, *, stride: int = 1, occupancy: float = 1.0,
                       batch: int = 1, weight_density: float = 1.0,
                       dtype_bytes: int = 4) -> dict:
    """`bsr_conv_cost` repriced for int8 (same transform as
    `ecr_conv_int8_cost`; weight density keeps scaling the live traffic)."""
    from repro.sparse_weights.conv import bsr_conv_cost

    base = bsr_conv_cost(c, h, w, o, kh, kw, stride=stride,
                         occupancy=occupancy, batch=batch,
                         weight_density=weight_density, dtype_bytes=1)
    return {"flops": base["flops"] * 0.5,
            "bytes": base["bytes"] + (dtype_bytes - 1.0) * base["out_elems"],
            "out_elems": base["out_elems"]}
