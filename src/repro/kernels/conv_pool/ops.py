"""Jitted wrapper for the PECR fused conv+ReLU+maxpool kernel.

Registered as ("conv_pool", "pecr_pallas") in `repro.graph.registry`
(forward = `fused_conv_pool`, cost hook = `conv_pool_cost`). The kernel form
requires pooling stride == pool size; the registry's `fusion_eligible` rule
only routes units here when that (and exact tiling) holds — overlapping or
ceil-mode pools run as ECR conv + an unfused pool instead.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ecr_conv.ops import (
    as_conv_operands,
    ecr_conv_launch,
    run_conv_kernel,
)
from repro.kernels.tiles import ConvLaunch, TileConfig


def conv_pool_launch(c: int, h: int, w: int, o: int, kh: int = 3, kw: int = 3,
                     *, stride: int = 1, pool: int = 2, block_c: int = 0,
                     block_o: int = 0, tile: TileConfig | None = None,
                     batch: int = 1, dtype_bytes: int = 4,
                     kernel: str = "conv_pool", acc_dtype: str = "float32",
                     weight_scales: str = "none") -> ConvLaunch:
    """`ConvLaunch` descriptor of one fused PECR conv+ReLU+pool call — the
    ECR builder with the pool window recorded, so the checker can verify the
    fused epilogue tiles the conv output exactly (the kernel floors)."""
    return ecr_conv_launch(c, h, w, o, kh, kw, stride=stride, block_c=block_c,
                           block_o=block_o, tile=tile, batch=batch,
                           dtype_bytes=dtype_bytes, pool=pool, kernel=kernel,
                           acc_dtype=acc_dtype, weight_scales=weight_scales)


@partial(jax.jit, static_argnames=("stride", "pool", "p_s", "block_c", "block_o", "compact"))
def fused_conv_pool(x_chw, kernels_oihw, stride: int = 1, pool: int = 2,
                    p_s=None, block_c: int = 0, block_o: int = 0,
                    compact: bool = True):
    """(C,H,W) x (O,C,kh,kw) -> (O, oh//p, ow//p). p_s must equal pool (kernel form).
    Batched: (N,C,H,W) -> (N, O, oh//p, ow//p) through the same batched grid
    as `ecr_conv`, per-sample channel-block schedules, shared-union
    compaction, and the PECR epilogue on the last channel block."""
    from repro.core.ecr import compact_live_channels_batch

    assert p_s is None or p_s == pool, "pallas kernel supports pooling stride == pool"
    x, kernels_oihw, single = as_conv_operands(x_chw, kernels_oihw)
    n, c, h, w = x.shape
    o, _, kh, kw = kernels_oihw.shape
    # the ONE shared (bc, bo) defaulting rule (repro.kernels.tiles), not a
    # drifting copy of ecr_conv's
    launch = conv_pool_launch(c, h, w, o, kh, kw, stride=stride, pool=pool,
                              block_c=block_c, block_o=block_o, batch=n,
                              dtype_bytes=jnp.dtype(x.dtype).itemsize)
    if compact:
        x, kernels_oihw, _ = compact_live_channels_batch(x, kernels_oihw)
    y = run_conv_kernel(x, kernels_oihw, launch)
    return y[0] if single else y


def conv_pool_cost(c: int, h: int, w: int, o: int, kh: int = 3, kw: int = 3, *,
                   stride: int = 1, pool: int = 2, occupancy: float = 1.0,
                   batch: int = 1, dtype_bytes: int = 4) -> dict:
    """Modeled FLOPs / HBM bytes of the fused PECR conv+ReLU+pool at a given
    channel-block occupancy — the serving autotuner's cost hook for fused
    stage-final layers.

    Relative to the unfused `ecr_conv_cost` + pool, the fusion (a) divides the
    output write by pool^2 (only the pooled tile leaves VMEM, DESIGN.md §2.3)
    and (b) deletes the intermediate conv-result write/read round trip that an
    unfused pool would pay. The pool max itself adds ~1 op per conv output
    element on the VPU.
    """
    from repro.kernels.ecr_conv.ops import ecr_conv_cost

    base = ecr_conv_cost(c, h, w, o, kh, kw, stride=stride, occupancy=occupancy,
                         batch=batch, dtype_bytes=dtype_bytes)
    oh, ow = (h - kh) // stride + 1, (w - kw) // stride + 1
    conv_out_bytes = o * oh * ow * dtype_bytes * batch
    pooled_bytes = o * (oh // pool) * (ow // pool) * dtype_bytes * batch
    return {"flops": base["flops"] + o * oh * ow * batch,  # pool max on the VPU
            "bytes": base["bytes"] - conv_out_bytes + pooled_bytes,
            "out_elems": o * (oh // pool) * (ow // pool) * batch}
