"""The ECR / PECR / int8-ECR convolution kernel on TPU — paper §IV-V adapted
per DESIGN.md §2.

One `pallas_call` fuses what the GPU kernel fused: *extension* (windows are
read by index arithmetic from the VMEM-resident tile — the im2col matrix
never exists), *compression* (the scalar-prefetched (ids, cnt) schedule —
ECR's F_data/Ptr at channel-block granularity), and the *SpMV* (per kernel
tap, an (OH*OW, bc) x (bc, bo) MXU contraction, accumulated in VMEM
scratch).

Dead channel-blocks of the input feature map (ReLU kills whole channels —
measured in benchmarks/fig2_sparsity.py) are skipped: the gather index_map
repeats the last live block (no DMA re-issue) and `@pl.when(k < cnt[b])`
skips the MACs, exactly as Algorithm 2 bounds its loop by Ptr.

Grid (n_ob, N, n_cb) — output-block j outermost, batch next — so the kernel
block for j is revisited by every sample before j advances (the batch-level
kernel reuse of Shi & Chu), with a PER-SAMPLE schedule: ids is (N, n_cb) and
sample b skips its own dead channel blocks (DESIGN.md §2.4).

Epilogues on the last channel block:
- plain (`pool=0`): the conv tile is written as is (ECR);
- `pool=p`: ReLU and a p x p max-reduction in VMEM, and ONLY the pooled
  tile is written to HBM (PECR, paper §V / Algorithm 4: the conv result
  never leaves VMEM, output traffic drops by p^2). Pooling stride == p;
  the general-stride form lives in the jnp reference;
- `sx`/`sw` given: int8 operands accumulate in int32 and the flush
  dequantizes in-register, `acc * sx[b] * sw[o]` (per-sample activation
  scale, per-output-channel weight scale), writing fp32.

Layouts are blocked so that every BlockSpec's last two dimensions are the
array's own — Mosaic accepts any (bc, bo) then, the 8-channel blocks the
CPU tests use included:
    x   (N, n_cb, H, W, bc)          one channel block of one sample
    w   (n_ob, n_cb, kh, kw, bc, bo)
    out (N, n_ob, OH', OW', bo)      OH', OW' pooled when pool > 0
The whole spatial map of a channel block is VMEM-resident (the paper's
shared-memory design; its regime is the small, deep, very sparse layers).
VALID padding; any stride (strided window reads).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _window(start: int, n: int, stride: int):
    return pl.ds(start, n) if stride == 1 else pl.ds(start, n, stride=stride)


def _kernel(ids_ref, cnt_ref, x_ref, w_ref, *refs, kh, kw, stride, n_cb, oh,
            ow, pool, scaled):
    if scaled:
        sx_ref, sw_ref, o_ref, acc_ref = refs
    else:
        o_ref, acc_ref = refs
    b = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < cnt_ref[b])
    def _mac():
        for i in range(kh):
            for j in range(kw):
                # the (i, j) tap's window rows, never materialized in HBM
                patch = x_ref[0, 0, _window(i, oh, stride),
                              _window(j, ow, stride), :]
                acc_ref[...] += jnp.dot(patch.reshape(oh * ow, -1),
                                        w_ref[0, 0, i, j],
                                        preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_cb - 1)
    def _flush():
        acc = acc_ref[...]
        if scaled:  # (oh*ow, bo) int32 * (1, 1) * (1, bo)
            acc = acc.astype(jnp.float32) * sx_ref[0] * sw_ref[0]
        conv = acc.reshape(oh, ow, -1)
        if pool:  # PECR: ReLU + max-pool in VMEM (paper §V-D), floored
            poh, pow_ = oh // pool, ow // pool
            conv = jnp.maximum(conv[:poh * pool, :pow_ * pool], 0.0)
            conv = conv.reshape(poh, pool, pow_, pool, -1).max(axis=(1, 3))
        o_ref[0, 0] = conv.astype(o_ref.dtype)


def conv_pallas(
    x: jax.Array,  # (N, n_cb, H, W, bc)
    w: jax.Array,  # (n_ob, n_cb, kh, kw, bc, bo) — shared across the batch
    ids: jax.Array,  # (N, n_cb) per-sample live channel-block gather lists
    cnt: jax.Array,  # (N,) per-sample live channel-block counts
    *,
    stride: int = 1,
    pool: int = 0,
    sx: jax.Array | None = None,  # (N, 1, 1) f32 int8 activation scales
    sw: jax.Array | None = None,  # (n_ob, 1, bo) f32 int8 weight scales
) -> jax.Array:
    """Blocked conv (+ fused ReLU/pool, + int8 dequantization) ->
    (N, n_ob, OH', OW', bo) fp32."""
    n, n_cb, h, wd, bc = x.shape
    n_ob, n_cb2, kh, kw, bc2, bo = w.shape
    assert n_cb == n_cb2 and bc == bc2, (x.shape, w.shape)
    oh = (h - kh) // stride + 1
    ow = (wd - kw) // stride + 1
    poh, pow_ = (oh // pool, ow // pool) if pool else (oh, ow)
    assert poh > 0 and pow_ > 0, "map too small for the pooling window"
    scaled = sx is not None
    in_specs = [
        pl.BlockSpec((1, 1, h, wd, bc), lambda j, b, k, ids, cnt: (b, ids[b, k], 0, 0, 0)),
        pl.BlockSpec((1, 1, kh, kw, bc, bo), lambda j, b, k, ids, cnt: (j, ids[b, k], 0, 0, 0, 0)),
    ]
    operands = [x, w]
    if scaled:
        in_specs += [
            pl.BlockSpec((1, 1, 1), lambda j, b, k, ids, cnt: (b, 0, 0)),
            pl.BlockSpec((1, 1, bo), lambda j, b, k, ids, cnt: (j, 0, 0)),
        ]
        operands += [sx, sw]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_ob, n, n_cb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, poh, pow_, bo), lambda j, b, k, ids, cnt: (b, j, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((oh * ow, bo),
                                   jnp.int32 if scaled else jnp.float32)],
    )
    return pallas_call(
        partial(_kernel, kh=kh, kw=kw, stride=stride, n_cb=n_cb, oh=oh,
                ow=ow, pool=pool, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, n_ob, poh, pow_, bo), jnp.float32),
    )(ids, cnt, *operands)


# ---------------------------------------------------------------------------
# Layout: NCHW / OIHW <-> the blocked layouts above (one XLA transpose each,
# the same pass the NHWC layout used to take)
# ---------------------------------------------------------------------------


def channel_blocks(x_nchw, bc: int):
    """(N, C', H, W) -> (N, C'/bc, H, W, bc); C' is a multiple of bc."""
    n, c, h, w = x_nchw.shape
    return x_nchw.reshape(n, c // bc, bc, h, w).transpose(0, 1, 3, 4, 2)


def weight_blocks(w_oihw, bc: int, bo: int):
    """(O', C', kh, kw) -> (O'/bo, C'/bc, kh, kw, bc, bo)."""
    o, c, kh, kw = w_oihw.shape
    return w_oihw.reshape(o // bo, bo, c // bc, bc, kh, kw).transpose(
        0, 2, 4, 5, 3, 1)


def unblock_output(out):
    """(N, n_ob, OH, OW, bo) -> (N, n_ob * bo, OH, OW)."""
    n, n_ob, oh, ow, bo = out.shape
    return out.transpose(0, 1, 4, 2, 3).reshape(n, n_ob * bo, oh, ow)
