"""conv2d_bsr: weight-block-sparse convolution via im2col onto `bsr_matmul`.

The activation kernels (DESIGN.md §2) skip work the *input* happens to make
zero; this is the complementary static axis — work the *pruner* made zero.
Lowering: im2col the (padded) input into patches A:(P, K), view the weight as
W:(O, K) (K = C*kh*kw), and compute

    y^T = W @ A^T

on the existing `kernels/bsr_matmul` Pallas kernel with W as the sparse LEFT
operand: the (ids, cnt) schedule — `block_schedule` over W's (bt, bf) blocks,
a compile-time constant once the weights are, since pruning is offline —
gathers only the live weight blocks, so a pruned-away block costs neither the
weight DMA, nor the MXU MACs, nor the DMA of the patch block it would have
multiplied (the A^T BlockSpec is indexed by the same ids). Orienting the
sparse operand as W (row-blocks = output-channel blocks) is what makes the
kernel's per-row-block schedule express per-output-channel-block raggedness;
A as the left operand would need a per-COLUMN schedule the kernel does not
have.

`conv2d_bsr_ref` is the pure-JAX ground truth: dense lax conv on the same
(pruned) weights — zeros contribute zero, so the two must agree to float32
tolerance on ANY weights, pruned or not.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sparsity import extract_windows
from repro.kernels.bsr_matmul.kernel import bsr_matmul_pallas
from repro.kernels.bsr_matmul.ops import block_schedule
from repro.kernels.schedule_guard import guard_schedule
from repro.kernels.tiles import BsrLaunch, resolve_bsr_tile
from repro.sparse_weights.format import conv_weight_matrix


def bsr_conv_launch(o: int, k_taps: int, p: int, *, tile=None,
                    dtype_bytes: int = 4, kernel: str = "bsr_matmul",
                    acc_dtype: str = "float32",
                    weight_scales: str = "none") -> BsrLaunch:
    """The resolved `BsrLaunch` descriptor of one conv2d_bsr call: the
    (O, K) weight against (K, P) patches at `resolve_bsr_tile`'s geometry —
    exactly the resolution the op executes with (it reads its block sizes
    back out of this record), so the static checker sees the real grid."""
    bt, bf, bd = resolve_bsr_tile(o, k_taps, p, tile)
    tp, fp, dp = (-o) % bt, (-k_taps) % bf, (-p) % bd
    return BsrLaunch(
        kernel=kernel, t=o, f=k_taps, d=p, bt=bt, bf=bf, bd=bd,
        t_pad=tp, f_pad=fp, d_pad=dp, nt=(o + tp) // bt,
        nf=(k_taps + fp) // bf, nd=(p + dp) // bd, dtype_bytes=dtype_bytes,
        acc_dtype=acc_dtype, weight_scales=weight_scales)


def conv2d_bsr_ref(x, w, stride: int = 1):
    """Dense-on-(possibly-pruned)-weights reference: lax conv, VALID padding.
    (C,H,W) -> (O,oh,ow) or (N,C,H,W) -> (N,O,oh,ow)."""
    from repro.core.ecr import conv2d_dense

    return conv2d_dense(x, w, stride)


@partial(jax.jit, static_argnames=("stride", "tile"))
def conv2d_bsr(x, w, stride: int = 1, tile=None):
    """Weight-block-sparse conv. x: (C,H,W) or (N,C,H,W) already padded
    (VALID semantics, like every registry conv forward); w: (O,C,kh,kw).
    Returns float32 (O,oh,ow) / (N,O,oh,ow).

    Activation sparsity is NOT exploited here — every patch is read. The
    planner's job is exactly this trade: BSR wins when the static weight
    density undercuts the measured activation occupancy (`plan_network`'s
    joint cost comparison), and loses to ECR/PECR on very sparse inputs.

    `tile` (a `repro.kernels.tiles.TileConfig`) overrides the (bt, bf, bd)
    block geometry per dimension (`resolve_bsr_tile`'s fallback contract);
    the (ids, cnt) schedule is computed on the actual weight VALUES at the
    resolved tiling, so any geometry is numerically exact — a tile finer
    than the pruner's `weight_block` just skips MORE blocks, a coarser one
    fewer.
    """
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
    launch = bsr_conv_launch(o, k_taps, p, tile=tile)
    bt, bf, bd = launch.bt, launch.bf, launch.bd
    wm_p = jnp.pad(wm, ((0, launch.t_pad), (0, launch.f_pad)))
    at_p = jnp.pad(a, ((0, launch.d_pad), (0, launch.f_pad))).T  # (Kp, Pp)
    ids, cnt = block_schedule(wm_p, bt, bf)
    ids, cnt = guard_schedule(ids, cnt, launch.nf)
    yt = bsr_matmul_pallas(wm_p, at_p, ids, cnt,
                           block=(bt, bf, bd))  # (Op, Pp) = y^T
    y = yt[:o, :p].T.reshape(n, oh, ow, o).transpose(0, 3, 1, 2)
    return y[0] if single else y


def bsr_conv_cost(c: int, h: int, w: int, o: int, kh: int = 3, kw: int = 3, *,
                  stride: int = 1, occupancy: float = 1.0, batch: int = 1,
                  weight_density: float = 1.0, dtype_bytes: int = 4) -> dict:
    """Modeled FLOPs / HBM bytes of the BSR conv at a static weight block
    density — the op-level cost hook `("conv", "bsr")` registers, mirroring
    `ecr_conv_cost` with the sparsity on the other operand.

    Models the production lowering, where the im2col extension is folded into
    the gather DMA (the same way the ECR kernel's window extension is
    implicit): a dead weight block skips its MACs, its weight bytes AND the
    activation taps it would have read — so activation bytes scale by
    `weight_density`, not by the (ignored) activation `occupancy`, and the
    weight read amortizes by 1/batch like every kernel tensor
    (DESIGN.md §2.4). Spatial dims are the padded input.
    """
    del occupancy  # BSR reads every window: activation sparsity buys nothing
    oh, ow = (h - kh) // stride + 1, (w - kw) // stride + 1
    wd = weight_density
    flops = 2.0 * oh * ow * o * c * kh * kw * wd * batch
    act_bytes = wd * c * h * w * dtype_bytes * batch
    out_bytes = o * oh * ow * dtype_bytes * batch
    k_bytes = wd * o * c * kh * kw * dtype_bytes  # read once per batch
    return {"flops": flops, "bytes": act_bytes + out_bytes + k_bytes,
            "out_elems": o * oh * ow * batch}
