"""Block-sparse matmul — ECR's compress-then-SpMV on the MXU.

y = h @ w where h:(T,F) carries *block* sparsity (pruned conv weights in the
BSR conv lowering, post-ReLU hidden states, ...). The caller provides, per
(bt)-row-block, the ECR-style compacted schedule:

  ids:(nt,nf) int32 — ids[i,k] = index of the k-th LIVE f-block of row-block i,
                      padded by repeating the last live id (no re-DMA: Pallas
                      skips the copy when the mapped block index is unchanged);
  cnt:(nt,)   int32 — number of live f-blocks (ECR's Ptr at block granularity).

Grid = (nt, nd, nf), k innermost. The index_map gathers only live blocks
(scalar prefetch), and `@pl.when(k < cnt[i])` bounds the reduction exactly as
Algorithm 2 bounds its loop by Ptr — dead blocks cost neither DMA nor MXU
cycles on real hardware. fp32 accumulation in VMEM scratch; with `sh`/`sw`
given the operands are int8, the accumulator int32, and the flush
dequantizes, `acc * sh[row] * sw`, writing fp32.

The operands are passed to the kernel in blocked layouts — h as
(nt, nf, bt, bf), w as (nf, nd, bf, bd), y as (nt, nd, bt, bd) — so the last
two dimensions of every block are the array's own and Mosaic accepts any
(bt, bf, bd); the wrapper does the reshapes in XLA.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call


def _kernel(ids_ref, cnt_ref, h_ref, w_ref, *refs, nf: int, scaled: bool):
    if scaled:
        sh_ref, sw_ref, o_ref, acc_ref = refs
    else:
        o_ref, acc_ref = refs
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < cnt_ref[i])
    def _mac():
        acc_ref[...] += jnp.dot(h_ref[0, 0], w_ref[0, 0],
                                preferred_element_type=acc_ref.dtype)

    @pl.when(k == nf - 1)
    def _flush():
        acc = acc_ref[...]
        if scaled:  # (bt, bd) int32 * (bt, 1) per-row scales * (1, 1)
            acc = acc.astype(jnp.float32) * sh_ref[0] * sw_ref[0]
        o_ref[0, 0] = acc.astype(o_ref.dtype)


def bsr_matmul_pallas(
    h: jax.Array,
    w: jax.Array,
    ids: jax.Array,
    cnt: jax.Array,
    *,
    block: tuple[int, int, int] = (8, 128, 128),
    sh: jax.Array | None = None,  # (T, 1) f32 per-row scales of int8 h
    sw: jax.Array | None = None,  # (1, 1) f32 scale of int8 w
) -> jax.Array:
    """h:(T,F) @ w:(F,D) with gathered live blocks -> (T, D) fp32. Shapes
    must divide blocks."""
    t, f = h.shape
    f2, d = w.shape
    assert f == f2, (h.shape, w.shape)
    bt, bf, bd = block
    assert t % bt == 0 and f % bf == 0 and d % bd == 0, (h.shape, w.shape, block)
    nt, nf, nd = t // bt, f // bf, d // bd
    assert ids.shape == (nt, nf) and cnt.shape == (nt,), (ids.shape, cnt.shape)
    scaled = sh is not None
    in_specs = [
        pl.BlockSpec((1, 1, bt, bf), lambda i, j, k, ids, cnt: (i, ids[i, k], 0, 0)),
        pl.BlockSpec((1, 1, bf, bd), lambda i, j, k, ids, cnt: (ids[i, k], j, 0, 0)),
    ]
    operands = [h.reshape(nt, bt, nf, bf).transpose(0, 2, 1, 3),
                w.reshape(nf, bf, nd, bd).transpose(0, 2, 1, 3)]
    if scaled:
        in_specs += [
            pl.BlockSpec((1, bt, 1), lambda i, j, k, ids, cnt: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, k, ids, cnt: (0, 0, 0)),
        ]
        operands += [sh.reshape(nt, bt, 1), sw.reshape(1, 1, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, nd, nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bt, bd), lambda i, j, k, ids, cnt: (i, j, 0, 0)),
        scratch_shapes=[pltpu.VMEM((bt, bd),
                                   jnp.int32 if scaled else jnp.float32)],
    )
    y = pallas_call(
        partial(_kernel, nf=nf, scaled=scaled),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nt, nd, bt, bd), jnp.float32),
    )(ids, cnt, *operands)
    return y.transpose(0, 2, 1, 3).reshape(t, d)
