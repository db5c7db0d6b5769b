"""Jitted wrapper: channel-block occupancy ("compression") + pallas ECR conv.

Registered as ("conv", "ecr_pallas") in `repro.graph.registry` (forward =
`ecr_conv`, cost hook = `ecr_conv_cost`); the stride/kernel parameters a
`ConvSpec` carries flow straight through — the kernel supports any k and the
strides the paper evaluates (Figs 9-10) plus AlexNet's stride-4 first conv.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sparsity import block_occupancy, compact_block_ids
from repro.kernels.ecr_conv.kernel import (
    channel_blocks,
    conv_pallas,
    unblock_output,
    weight_blocks,
)
from repro.kernels.schedule_guard import guard_schedule
from repro.kernels.tiles import ConvLaunch, TileConfig, resolve_conv_tile


def ecr_conv_launch(c: int, h: int, w: int, o: int, kh: int = 3, kw: int = 3,
                    *, stride: int = 1, block_c: int = 0, block_o: int = 0,
                    tile: TileConfig | None = None, batch: int = 1,
                    dtype_bytes: int = 4, pool: int = 0,
                    kernel: str = "ecr_conv", acc_dtype: str = "float32",
                    weight_scales: str = "none") -> ConvLaunch:
    """The resolved `ConvLaunch` descriptor of one ECR conv call: block sizes
    through `resolve_conv_tile` (exactly the resolution `ecr_conv` executes
    with — the op reads its geometry back out of this record, so there is ONE
    derivation), paddings/blocks/output dims derived once. `tile` wins over
    the legacy (block_c, block_o) scalars; `pool`/`kernel`/`acc_dtype` are
    pass-throughs for the fused and int8 variants that share this builder."""
    t = tile if tile is not None else TileConfig(block_c=block_c, block_o=block_o)
    bc, bo = resolve_conv_tile(c, o, t)
    cp, op = (-c) % bc, (-o) % bo
    return ConvLaunch(
        kernel=kernel, batch=batch, c=c, h=h, w=w, o=o, kh=kh, kw=kw,
        stride=stride, pool=pool, block_c=bc, block_o=bo, c_pad=cp, o_pad=op,
        n_cb=(c + cp) // bc, n_ob=(o + op) // bo,
        oh=(h - kh) // stride + 1, ow=(w - kw) // stride + 1,
        dtype_bytes=dtype_bytes, acc_dtype=acc_dtype,
        weight_scales=weight_scales)


def batch_block_schedule(xb):
    """Per-sample (ids, cnt) channel-block schedules of a blocked
    (N, n_cb, H, W, bc) tensor: each sample skips its own dead blocks
    (ragged batch sparsity)."""
    occ = jnp.any(xb != 0, axis=(2, 3, 4))  # (N, n_cb)
    return jax.vmap(compact_block_ids)(occ)  # ids (N, n_cb), cnt (N,)


def as_conv_operands(x_chw, kernels_oihw):
    """Lift the accepted input ranks to (N, C, H, W) x (O, C, kh, kw):
    (H, W) is one channel, (C, H, W) one image; 3-D kernels get O = 1.
    Returns (x, kernels, single) where `single` says to drop the batch."""
    if x_chw.ndim == 2:
        x_chw = x_chw[None]
    if kernels_oihw.ndim == 3:
        kernels_oihw = kernels_oihw[None]
    single = x_chw.ndim == 3
    x = x_chw[None] if single else x_chw
    assert x.shape[0] > 0, "empty batch: the conv kernels need N >= 1"
    return x, kernels_oihw, single


def run_conv_kernel(x_nchw, kernels_oihw, launch: ConvLaunch, *,
                    sx=None, sw=None):
    """Pad to the launch's blocks, schedule per sample, run `conv_pallas`
    and return (N, O, OH', OW'). `sx` (N,) / `sw` (O,) are the int8
    activation / weight scales of quantized operands."""
    bc, bo, cp, op = launch.block_c, launch.block_o, launch.c_pad, launch.o_pad
    xb = channel_blocks(jnp.pad(x_nchw, ((0, 0), (0, cp), (0, 0), (0, 0))), bc)
    wb = weight_blocks(
        jnp.pad(kernels_oihw, ((0, op), (0, cp), (0, 0), (0, 0))), bc, bo)
    ids, cnt = guard_schedule(*batch_block_schedule(xb), launch.n_cb)
    if sw is not None:
        sx = sx.reshape(-1, 1, 1)
        sw = jnp.pad(sw, (0, op), constant_values=1.0).reshape(
            launch.n_ob, 1, bo)
    out = conv_pallas(xb, wb, ids, cnt, stride=launch.stride,
                      pool=launch.pool, sx=sx, sw=sw)
    return unblock_output(out)[:, :launch.o]


@partial(jax.jit, static_argnames=("stride", "block_c", "block_o", "compact"))
def ecr_conv(x_chw, kernels_oihw, stride: int = 1, block_c: int = 0,
             block_o: int = 0, compact: bool = True):
    """(C,H,W) x (O,C,kh,kw) -> (O,oh,ow), skipping dead input channel blocks.
    Batched: (N,C,H,W) -> (N,O,oh,ow); one image runs as a batch of one.

    compact=True (default): ECR channel compaction first — live channels pack
    into a dense prefix so unstructured channel death still becomes contiguous
    skippable blocks (cnt = ceil(n_live / bc)). For a batch the pack uses one
    shared permutation (union of live channels — kernels stay shared) and
    per-sample raggedness is recovered by per-sample block schedules."""
    from repro.core.ecr import compact_live_channels_batch

    x, kernels_oihw, single = as_conv_operands(x_chw, kernels_oihw)
    n, c, h, w = x.shape
    o, _, kh, kw = kernels_oihw.shape
    launch = ecr_conv_launch(c, h, w, o, kh, kw, stride=stride,
                             block_c=block_c, block_o=block_o, batch=n,
                             dtype_bytes=jnp.dtype(x.dtype).itemsize)
    if compact:
        x, kernels_oihw, _ = compact_live_channels_batch(x, kernels_oihw)
    y = run_conv_kernel(x, kernels_oihw, launch)
    return y[0] if single else y


def ecr_conv_cost(c: int, h: int, w: int, o: int, kh: int = 3, kw: int = 3, *,
                  stride: int = 1, occupancy: float = 1.0, batch: int = 1,
                  dtype_bytes: int = 4) -> dict:
    """Modeled FLOPs / HBM bytes of the gathered-schedule ECR conv at a given
    channel-block occupancy (occupancy=1.0 models the dense path).

    This is the op-level cost hook the serving autotuner falls back to when
    wall-clock timing is too noisy: the skipped blocks save BOTH the MACs and
    the activation/weight DMA (the (ids, cnt) schedule never issues them), and
    the kernel tensor's read amortizes by 1/batch across the batched grid
    (DESIGN.md §2.4). Spatial dims are the padded input (pass h+2/w+2 for the
    SAME 3x3 layers). Returns {"flops", "bytes"} totals for the whole batch.
    """
    oh, ow = (h - kh) // stride + 1, (w - kw) // stride + 1
    flops = 2.0 * oh * ow * o * c * kh * kw * occupancy * batch
    act_bytes = occupancy * c * h * w * dtype_bytes * batch
    out_bytes = o * oh * ow * dtype_bytes * batch
    k_bytes = occupancy * o * c * kh * kw * dtype_bytes  # read once per batch
    return {"flops": flops, "bytes": act_bytes + out_bytes + k_bytes,
            "out_elems": o * oh * ow * batch}


def channel_block_occupancy(x_chw, block_c: int = 128, compact: bool = False) -> float:
    """Fraction of live channel blocks = fraction of MXU/DMA work not skipped.

    Measured at the block size `ecr_conv` ACTUALLY resolves for this shape
    (the `resolve_conv_tile` fallback rule): a block_c that does not divide C
    pads the tail channels up to a block multiple — never the silent
    block-size-1 degradation this statistic used to report, which made the
    stat disagree with the executed schedule on every non-dividing shape.

    compact=True reports the post-channel-compaction occupancy the kernel
    actually runs at: ceil(n_live / bc) / n_blocks."""
    import math

    c, h, w = x_chw.shape
    bc = resolve_conv_tile(c, c, TileConfig(block_c=block_c))[0]
    n_cb = math.ceil(c / bc)
    if compact:
        n_live = int(jnp.any(x_chw != 0, axis=(1, 2)).sum())
        return math.ceil(n_live / bc) / n_cb
    xp = jnp.pad(x_chw, ((0, n_cb * bc - c), (0, 0), (0, 0)))
    occ = block_occupancy(xp.transpose(1, 2, 0), (h, w, bc))
    return float(occ.mean())
