"""Host-side (ids, cnt) bounds guard at the Pallas op entry points.

The kernels trust their scalar-prefetched (ids, cnt) schedules blindly: an
out-of-range id gathers a wrong (or out-of-bounds) operand block and a cnt
beyond n_blocks walks the grid off the schedule — both silently, since the
index maps are baked into the compiled grid. The static checker
(`repro.analysis`) verifies schedules it can see at plan time, but schedules
are computed inside jit from traced VALUES, so this is the complementary
dynamic guard: a traced-safe clamp of both fields into range, applied at the
`ecr_conv` / `fused_conv_pool` / `sparse_matmul` / `conv2d_bsr` entry points.

Gated by REPRO_CHECK_SCHEDULES=1 (read at trace time): the default hot path is bit-identical to before — no extra ops in the
compiled program. On valid schedules the clamp is the identity, so enabling
the guard never changes correct results; it exists to turn a corrupted
schedule's silent garbage into in-range (wrong-but-bounded) reads while the
static pass pinpoints the source.
"""
from __future__ import annotations

import os


def schedules_checked() -> bool:
    """Whether the REPRO_CHECK_SCHEDULES=1 guard is on (checked per call, so
    tests can flip the env var without re-importing)."""
    return os.environ.get("REPRO_CHECK_SCHEDULES", "") == "1"


def guard_schedule(ids, cnt, n_blocks: int):
    """Clamp (ids, cnt) into the kernel's valid range when the guard is on.

    ids -> [0, n_blocks); cnt -> [0, n_blocks]. Works on traced values
    (the schedules are computed inside jit) and on any batching layout —
    ids (n_cb,) or (N, n_cb), cnt scalar, (1,) or (N,).
    """
    if not schedules_checked():
        return ids, cnt
    import jax.numpy as jnp

    ids = jnp.clip(ids, 0, max(n_blocks - 1, 0)).astype(ids.dtype)
    cnt = jnp.clip(cnt, 0, n_blocks).astype(cnt.dtype)
    return ids, cnt
