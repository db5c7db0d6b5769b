"""Jitted wrapper: ECR-style block compaction + pallas BSR matmul."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sparsity import block_occupancy
from repro.kernels.bsr_matmul.kernel import bsr_matmul_pallas
from repro.kernels.schedule_guard import guard_schedule


def block_schedule(h: jax.Array, bt: int, bf: int):
    """Compute (ids, cnt) — the block-granularity ECR compression of h."""
    occ = block_occupancy(h, (bt, bf))  # (nt, nf) bool
    nt, nf = occ.shape
    order = jnp.argsort(~occ, axis=1, stable=True).astype(jnp.int32)
    cnt = occ.sum(1).astype(jnp.int32)
    lane = jnp.arange(nf, dtype=jnp.int32)[None, :]
    ids = jnp.where(lane < cnt[:, None], order, order[:, :1])
    return ids, cnt


@partial(jax.jit, static_argnames=("block", "tile"))
def sparse_matmul(h, w, block=(8, 128, 128), tile=None):
    """y = h @ w skipping all-zero (bt,bf) blocks of h. Pads to block multiples.

    `tile` (a `repro.kernels.tiles.TileConfig`) overrides the (bt, bf, bd)
    geometry per dimension; a non-conforming dimension (<= 0 or larger than
    the extent it tiles, up to the one-block padding rule) keeps the
    `block` default — the same fallback contract as the conv ops."""
    t, f = h.shape
    f2, d = w.shape
    bt, bf, bd = block
    if tile is not None and tile:
        bt = tile.bt if 0 < tile.bt <= max(8, t) else bt
        bf = tile.bf if 0 < tile.bf <= max(8, f) else bf
        bd = tile.bd if 0 < tile.bd <= max(8, d) else bd
    tp, fp, dp = (-t) % bt, (-f) % bf, (-d) % bd
    hp = jnp.pad(h, ((0, tp), (0, fp)))
    wp = jnp.pad(w, ((0, fp), (0, dp)))
    ids, cnt = block_schedule(hp, bt, bf)
    ids, cnt = guard_schedule(ids, cnt, (f + fp) // bf)
    # launch at the RESOLVED geometry — passing the default `block` here while
    # padding/scheduling at the tile override was exactly the silent
    # grid-vs-schedule mismatch repro.analysis' RPA101 check exists to catch
    y = bsr_matmul_pallas(hp, wp, ids, cnt, block=(bt, bf, bd))
    return y[:t, :d]


def schedule_occupancy(h, bt: int = 8, bf: int = 128) -> float:
    """Fraction of blocks that are live (== fraction of MXU work not skipped)."""
    occ = block_occupancy(h, (bt, bf))
    return float(occ.mean())
