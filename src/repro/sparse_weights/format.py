"""BSR weight format: the block geometry shared by pruning, planning and the
conv lowering.

Weight sparsity only pays on the MXU at *block* granularity (same argument as
DESIGN.md §2.1 for activations): the `kernels/bsr_matmul` Pallas kernel skips
whole (bt, bf) blocks of its LEFT operand via the scalar-prefetched
(ids, cnt) gather, so the pruner must zero whole blocks of the weight matrix
in exactly the tiling the kernel will later schedule. This module is the
single source of that geometry:

- a conv weight (O, C, kh, kw) is viewed as the GEMM operand W:(O, K) with
  K = C*kh*kw — the matrix `conv2d_bsr` hands the kernel as its sparse left
  operand (y^T = W @ patches^T, so sparsity varies along W's row-blocks =
  output-channel blocks, which is what a per-row-block schedule can express);
- `weight_block(o, k_taps)` picks the (bt, bf) block for that matrix — one
  deterministic function of the shape, so the pruner, the density
  measurement, the planner's cost model and the forward all agree without
  threading a block tuple through every call;
- `weight_block_density` is the achieved-density statistic everything above
  reports and `validate_plan` re-checks at run time.
"""
from __future__ import annotations

import jax.numpy as jnp


def _pow2_le(n: int) -> int:
    """Largest power of two <= n (n >= 1)."""
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def weight_block(o: int, k_taps: int) -> tuple:
    """(bt, bf) BSR block of an (O, K) weight matrix — callers pass the
    matrix shape so the geometry contract is explicit, though only K moves
    the answer today.

    bt = 8 rows always (the MXU sublane tile — matches `bsr_matmul`'s
    default; small O just pads, shrinking bt would change pruning
    granularity for no kernel benefit). bf is capped at the 128-lane tile
    but shrinks on small layers so a row-block still spans >= ~4 schedulable
    K-blocks: a reduced LeNet conv with K = 25 taps pruned at bf = 128 would
    be a single all-or-nothing block, which is no sparsity at all.
    """
    del o
    bf = max(8, min(128, _pow2_le(max(8, k_taps // 4))))
    return 8, bf


def conv_weight_matrix(w) -> jnp.ndarray:
    """(O, C, kh, kw) -> the (O, K) GEMM view `conv2d_bsr` runs (K = C*kh*kw,
    taps in (c, kh, kw) scan order — the same flattening `extract_windows`
    produces for the patches)."""
    o = w.shape[0]
    return w.reshape(o, -1)


def block_norms(m, block: tuple):
    """(n_row_blocks, n_col_blocks) L2 norms of the (bt, bf) blocks of a 2-D
    matrix (padded with zeros to block multiples — pad blocks norm 0)."""
    bt, bf = block
    r, c = m.shape
    mp = jnp.pad(m, ((0, (-r) % bt), (0, (-c) % bf)))
    nr, nc = mp.shape[0] // bt, mp.shape[1] // bf
    return jnp.sqrt((mp.reshape(nr, bt, nc, bf) ** 2).sum(axis=(1, 3)))


def block_density(mask) -> float:
    """Fraction of True entries of a block mask (the grid size is the
    denominator: every block overlaps real weight, a ragged edge pads by
    less than one block)."""
    return float(mask.sum()) / max(mask.size, 1)


def matrix_block_density(m, block: tuple) -> float:
    """Fraction of (bt, bf) blocks of a 2-D matrix with any nonzero entry."""
    return block_density(block_norms(m, block) > 0)


def weight_block_mask(w):
    """Which blocks of one weight hold any nonzero (traceable): a conv weight
    (O, C, kh, kw) on its (O, K) GEMM view, a dense-head weight (d_in, d_out)
    on its (d_out, d_in) orientation, at the layer's own `weight_block`
    tiling."""
    if w.ndim == 4:
        m = conv_weight_matrix(w)
    elif w.ndim == 2:
        m = w.T  # (d_out, d_in): rows = output features, like conv's O
    else:
        raise ValueError(f"weight_block_density expects a conv (O,C,kh,kw) or "
                         f"dense (d_in,d_out) weight, got shape {w.shape}")
    return block_norms(m, weight_block(m.shape[0], m.shape[1])) > 0


def weight_block_density(w) -> float:
    """Achieved block density of one weight (`weight_block_mask`). 1.0 for
    any unpruned (fully dense) weight."""
    return block_density(weight_block_mask(w))
