"""LayerGraph executor: walk the graph, dispatch every op through the registry.

This is the ONE place forward execution happens. `walk_graph` is the one
structural walk over a graph's body: it hands each conv unit the tensor it
actually reads, runs the stand-alone pools and LRNs and joins branches by a
channel concat, each under its named scope, and calls back per unit. Every
caller that runs a graph — `run_graph` (uniform impl), the planner's
calibration walk and `run_plan` (per-layer planned impls, the serving
engine's compiled runners), the profiler, the tile search and
`models/cnn.cnn_feature_maps` — is that walk with its own per-unit callback,
then `run_head`. Structural concerns (padding, unfused ReLU/pool around a
plain conv, concat, LRN, flatten, the dense head) live here; impl selection
lives in `repro.graph.registry`; numerical kernels live in core/ and
kernels/.

The executor is deliberately mesh-OBLIVIOUS: every op is per-sample along
the batch dim, so under the sharded serving path (DESIGN.md §6) this exact
code runs unchanged inside a shard_map body on each device's batch slice —
the per-sample (ids, cnt) schedules it dispatches to are built shard-local,
and the only collective (the cross-shard occupancy aggregation) lives in
`repro.pipeline.planner.run_plan`, never here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.graph.ir import LRN, ConvUnit, Join, LayerGraph, PoolSpec, graph_weights
from repro.graph.registry import get_op, unit_impl

# ---------------------------------------------------------------------------
# Structural primitives (impl-independent)
# ---------------------------------------------------------------------------


def pad2d(x, pad: int):
    """`pad`-pixel spatial zero padding, (C,H,W) / (N,C,H,W) (no-op pad=0)."""
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((pad, pad), (pad, pad)))


def maxpool2d(x, pool: PoolSpec):
    """Pool the trailing two dims per `pool` (p, stride, mode, pad, kind).

    mode="valid" raises on an inexact tiling (the explicit-truncation guard —
    shapes are static, so this is a plain python check even under jit);
    "floor" drops the tail; "ceil" pads with -inf to keep a partial window.
    A max-pool's `pad` is -inf on each edge; kind="avg" averages each whole
    window.

    A max-pool is separable: the max of `p` strided shifted slices along H,
    then along W, of the padded map. XLA fuses those into loop fusions; a
    `reduce-window` over NCHW maps with 14 or 7 on the lanes took about
    1.8 ms a pool at a batch of 8 on a TPU v5e, GoogLeNet's inception pools
    nearly half its device time. The max is exact, so the result is the
    `reduce-window`'s bit for bit.
    """
    from repro.graph.ir import pool_out_len

    h, w = x.shape[-2:]
    oh, ow = pool_out_len(h, pool), pool_out_len(w, pool)  # validates mode
    lead = x.ndim - 2
    window = dict(window_dimensions=(1,) * lead + (pool.p, pool.p),
                  window_strides=(1,) * lead + (pool.s, pool.s))
    if pool.kind == "avg":
        total = jax.lax.reduce_window(x, 0.0, jax.lax.add, padding="VALID",
                                      **window)
        return total / (pool.p * pool.p)
    tail_h = (oh - 1) * pool.s + pool.p - h - 2 * pool.pad
    tail_w = (ow - 1) * pool.s + pool.p - w - 2 * pool.pad
    x = jnp.pad(
        x, ((0, 0),) * lead + ((pool.pad, pool.pad + max(tail_h, 0)),
                               (pool.pad, pool.pad + max(tail_w, 0))),
        constant_values=-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
        else jnp.iinfo(x.dtype).min)
    for axis, n in ((x.ndim - 2, oh), (x.ndim - 1, ow)):
        span = (n - 1) * pool.s + 1
        x = functools.reduce(jnp.maximum, (
            jax.lax.slice_in_dim(x, i, i + span, stride=pool.s, axis=axis)
            for i in range(pool.p)))
    return x


def lrn(x, spec: LRN):
    """Cross-channel local response normalization of (C,H,W) / (N,C,H,W).
    The window sum is `size` shifted slices of the zero-padded squares
    along the channel axis: a channel `reduce_window` here is slow on the
    TPU, and its fusion with the division fails to compile there at
    batches 1-4 ("Binary op with incompatible shapes")."""
    half = spec.size // 2
    c = x.shape[-3]
    sq = jnp.pad(x * x, ((0, 0),) * (x.ndim - 3)
                 + ((half, spec.size - 1 - half), (0, 0), (0, 0)))
    total = sum(sq[..., i:i + c, :, :] for i in range(spec.size))
    return x / (spec.k + spec.alpha / spec.size * total) ** spec.beta


# ---------------------------------------------------------------------------
# Unit / graph execution
# ---------------------------------------------------------------------------


def run_unit(x, w, unit: ConvUnit, kind: str, impl: str, block_c: int = 0,
             tile=None):
    """Execute one conv unit as (kind, impl): the fused op consumes the whole
    conv+ReLU+pool triple; a plain conv gets the unit's ReLU / unfused pool
    applied structurally around it. `tile` is the layer's searched
    `TileConfig` (None = the impl's default geometry); non-Pallas impls
    ignore it."""
    op = get_op(kind, impl)
    xp = pad2d(x, unit.conv.pad)
    if kind == "conv_pool":
        return op.forward(xp, w, stride=unit.conv.stride, pool=unit.pool,
                          block_c=block_c, tile=tile)
    x = op.forward(xp, w, stride=unit.conv.stride, block_c=block_c, tile=tile)
    if unit.relu:
        x = jnp.maximum(x, 0.0)
    if unit.pool is not None:
        x = maxpool2d(x, unit.pool)
    return x


def walk_graph(graph: LayerGraph, x, on_unit):
    """Run the graph's body on x ((C,H,W) or (N,C,H,W)) and return what
    enters the head. `on_unit(unit, x) -> y` runs each `ConvUnit` on the
    tensor it reads, in program order; a stand-alone step runs under its
    scope (`pool<j>`, `avgpool`, `lrn<j>`), a join's paths under its name and
    their channel concat under `concat` inside it."""

    def run(steps, x):
        for st in steps:
            if isinstance(st, ConvUnit):
                x = on_unit(st, x)
            elif isinstance(st, Join):
                with jax.named_scope(st.name):
                    outs = [run(path, x) for path in st.paths]
                    with jax.named_scope("concat"):
                        x = jnp.concatenate(outs, axis=-3)
            else:
                with jax.named_scope(st.scope):
                    x = lrn(x, st.node) if isinstance(st.node, LRN) \
                        else maxpool2d(x, st.node)
        return x

    return run(graph.body(), x)


def run_head(x, dense_ws, head):
    """Flatten + the dense head ((N,C,H,W) -> (N,classes), or unbatched)."""
    x = x.reshape(x.shape[0], -1) if x.ndim == 4 else x.reshape(-1)
    for w, spec in zip(dense_ws, head):
        x = x @ w
        if spec.relu:
            x = jnp.maximum(x, 0.0)
    return x


def run_graph(graph: LayerGraph, params, x, impl: str = "dense",
              block_c: int = 0):
    """(C,H,W) or (N,C,H,W) -> logits through the whole graph at one uniform
    impl. Per-layer planned execution is `repro.pipeline.run_plan`."""
    conv_ws, dense_ws = graph_weights(params)

    def on_unit(unit, x):
        kind, op = unit_impl(unit, impl)
        return run_unit(x, conv_ws[unit.index], unit, kind, op, block_c)

    return run_head(walk_graph(graph, x, on_unit), dense_ws, graph.head())
