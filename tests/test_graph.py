"""LayerGraph IR + op registry: shape inference, pool modes, fusion rule,
single-site dispatch, LeNet/AlexNet end-to-end through plan -> run -> serve,
and the occupancy_stat edge cases the serving engine relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.alexnet import ALEXNET, ALEXNET_REDUCED
from repro.configs.lenet import LENET, LENET_REDUCED
from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
from repro.graph import (
    ConvSpec,
    DenseSpec,
    Flatten,
    LayerGraph,
    PoolSpec,
    ReLU,
    as_graph,
    fusion_eligible,
    get_op,
    init_graph,
    maxpool2d,
    run_graph,
    unit_impl,
    weight_shapes,
)
from repro.pipeline import occupancy_stat, plan_network, run_plan
from repro.serving import Engine, SimClock, plan_key

# ---------------------------------------------------------------------------
# IR: shape inference on the canonical networks
# ---------------------------------------------------------------------------


def test_canonical_shapes():
    assert LENET.feature_shape() == (16, 5, 5) and LENET.flat_dim() == 400
    assert ALEXNET.feature_shape() == (256, 6, 6) and ALEXNET.flat_dim() == 9216
    vgg = vgg19_graph(CNNConfig())
    assert vgg.feature_shape() == (512, 7, 7) and vgg.flat_dim() == 25088
    assert len(vgg.units()) == 16 and vgg.n_classes() == 1000
    # AlexNet's overlapping pools: 55 -> 27 -> 13 -> 6
    outs = [u.out_shape for u in ALEXNET.units()]
    assert outs[0] == (64, 27, 27) and outs[1] == (192, 13, 13)
    assert outs[-1] == (256, 6, 6)


def test_units_group_conv_relu_pool():
    units = LENET.units()
    assert len(units) == 2
    assert all(u.relu and u.pool is not None for u in units)
    assert units[0].conv == ConvSpec(6, k=5, stride=1, pad=0)
    assert units[1].stage == 1 and units[1].slot == 0
    assert ALEXNET.units()[3].pool is None  # conv4 is in-stage


def test_graph_rejects_bad_topology():
    with pytest.raises(ValueError, match="ReLU must follow a conv"):
        LayerGraph("bad", (1, 8, 8), (ReLU(), Flatten(), DenseSpec(2))).units()
    with pytest.raises(ValueError, match="pool must follow"):
        LayerGraph("bad", (1, 8, 8), (PoolSpec(2), Flatten(), DenseSpec(2))).units()
    with pytest.raises(ValueError, match="dense head"):
        LayerGraph("bad", (1, 8, 8), (ConvSpec(4),)).units()
    with pytest.raises(ValueError, match="only DenseSpec may follow Flatten"):
        LayerGraph("bad", (1, 8, 8), (Flatten(), ConvSpec(4))).units()


def test_signature_is_structural():
    a = vgg19_graph(CNNConfig(name="a", img_size=32, plan=((8, 1),), n_classes=4))
    b = vgg19_graph(CNNConfig(name="b", img_size=32, plan=((8, 1),), n_classes=4))
    c = vgg19_graph(CNNConfig(name="c", img_size=32, plan=((16, 1),), n_classes=4))
    assert a.signature() == b.signature()  # names don't split compiled programs
    assert a.signature() != c.signature()
    assert as_graph(None).signature() == vgg19_graph(CNNConfig()).signature()


def test_weight_shapes_and_init_graph():
    conv_shapes, dense_shapes = weight_shapes(LENET)
    assert conv_shapes == ((6, 1, 5, 5), (16, 6, 5, 5))
    assert dense_shapes == ((400, 120), (120, 84), (84, 10))
    params = init_graph(jax.random.PRNGKey(0), LENET_REDUCED)
    out = run_graph(LENET_REDUCED, params,
                    jnp.ones((2,) + LENET_REDUCED.in_shape))
    assert out.shape == (2, LENET_REDUCED.n_classes())


# ---------------------------------------------------------------------------
# pool modes: the explicit-truncation satellite
# ---------------------------------------------------------------------------


def test_maxpool_valid_raises_on_truncation():
    x = jnp.arange(25.0).reshape(1, 5, 5)
    with pytest.raises(ValueError, match="silently drop"):
        maxpool2d(x, PoolSpec(2))  # 5 % 2 != 0: the old code dropped a row
    out = maxpool2d(x, PoolSpec(2, mode="floor"))
    assert out.shape == (1, 2, 2)
    np.testing.assert_array_equal(np.asarray(out), [[[6.0, 8.0], [16.0, 18.0]]])


def test_maxpool_ceil_last_window_starts_inside_input():
    """ceil_mode must never emit a window lying entirely in the -inf padding
    (the cuDNN/PyTorch rule) — stride > p with naive ceil arithmetic would
    leak -inf into the feature map."""
    x = jnp.arange(16.0).reshape(1, 4, 4)
    out = maxpool2d(x, PoolSpec(1, stride=2, mode="ceil"))
    assert out.shape == (1, 2, 2)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(out), [[[0.0, 2.0], [8.0, 10.0]]])


def test_maxpool_ceil_keeps_partial_tail():
    x = jnp.arange(36.0).reshape(1, 6, 6)
    spec = PoolSpec(3, stride=2, mode="ceil")
    out = maxpool2d(x, spec)
    assert out.shape == (1, 3, 3)
    # the tail window covers rows/cols 4..5 only; max of the map is 35
    assert float(out[0, -1, -1]) == 35.0
    with pytest.raises(ValueError, match="silently drop"):
        maxpool2d(x, PoolSpec(3, stride=2))  # (6-3) % 2 != 0
    # overlapping valid pool on a tiling map works (AlexNet's 13 -> 6)
    y = jnp.zeros((2, 4, 13, 13))
    assert maxpool2d(y, PoolSpec(3, stride=2)).shape == (2, 4, 6, 6)


def test_models_maxpool_compat_modes():
    from repro.models.cnn import _maxpool

    x = jnp.arange(16.0).reshape(1, 4, 4)
    np.testing.assert_array_equal(np.asarray(_maxpool(x, 2)),
                                  [[[5.0, 7.0], [13.0, 15.0]]])
    with pytest.raises(ValueError, match="silently drop"):
        _maxpool(jnp.zeros((1, 5, 5)), 2)
    assert _maxpool(jnp.zeros((1, 5, 5)), 2, mode="floor").shape == (1, 2, 2)


def _reduce_window_maxpool(x, pool: PoolSpec):
    """The plain oracle: one `reduce_window(max)`, -inf (or the int minimum)
    on each edge and in the ceil tail."""
    from repro.graph.ir import pool_out_len

    h, w = x.shape[-2:]
    oh, ow = pool_out_len(h, pool), pool_out_len(w, pool)
    tail_h = max((oh - 1) * pool.s + pool.p - h - 2 * pool.pad, 0)
    tail_w = max((ow - 1) * pool.s + pool.p - w - 2 * pool.pad, 0)
    lead = x.ndim - 2
    fill = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).min)
    return jax.lax.reduce_window(
        x, fill, jax.lax.max,
        window_dimensions=(1,) * lead + (pool.p, pool.p),
        window_strides=(1,) * lead + (pool.s, pool.s),
        padding=((0, 0),) * lead + ((pool.pad, pool.pad + tail_h),
                                    (pool.pad, pool.pad + tail_w)))


@pytest.mark.parametrize("shape,pool,dtype,negative", [
    ((2, 6, 8, 8), PoolSpec(2), jnp.float32, False),
    ((3, 9, 9), PoolSpec(2, mode="floor"), jnp.float32, False),
    ((2, 4, 13, 13), PoolSpec(3, stride=2), jnp.float32, False),
    ((2, 3, 10, 10), PoolSpec(3, stride=2, mode="floor"), jnp.float32, False),
    ((2, 3, 10, 9), PoolSpec(3, stride=2, mode="ceil"), jnp.float32, True),
    ((1, 512, 14, 14), PoolSpec(3, stride=1, pad=1), jnp.float32, False),
    ((1, 8, 14, 14), PoolSpec(3, stride=1, pad=1), jnp.float32, True),
    ((2, 8, 7, 7), PoolSpec(3, stride=1, pad=1), jnp.float32, True),
    ((1, 16, 14, 14), PoolSpec(3, stride=2, mode="ceil"), jnp.float32, True),
    ((1, 16, 28, 28), PoolSpec(3, stride=2, mode="ceil"), jnp.float32, False),
    ((1, 4, 112, 112), PoolSpec(3, stride=2, mode="ceil"), jnp.float32, True),
    ((1, 4, 11, 11), PoolSpec(3, stride=2, pad=1, mode="ceil"), jnp.float32, True),
    ((4, 10, 10), PoolSpec(2, stride=2, pad=1), jnp.float32, True),
    ((1, 8, 55, 55), PoolSpec(3, stride=2), jnp.float32, False),
    ((1, 8, 96, 96), PoolSpec(2), jnp.float32, False),
    ((2, 5, 14, 14), PoolSpec(3, stride=1, pad=1), jnp.int32, True),
    ((5, 13, 12), PoolSpec(3, stride=2, mode="ceil"), jnp.int32, False),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_maxpool_equals_reduce_window(shape, pool, dtype, negative):
    """The shifted-slice max-pool is the `reduce_window` one, bit for bit:
    every mode, pad, rank and dtype; all-negative maps show the fill."""
    x = jax.random.normal(jax.random.PRNGKey(sum(shape)), shape) * 100
    if negative:
        x = -jnp.abs(x) - 1
    x = x.astype(dtype)
    out = maxpool2d(x, pool)
    want = _reduce_window_maxpool(x, pool)
    assert out.shape == want.shape and out.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_maxpool_lowers_without_reduce_window():
    """GoogLeNet's 4b pool (3x3/1 pad 1 on 512 x 14 x 14) lowers to slices
    and maxima: a `reduce-window` there took 1.8 ms a batch on a v5e."""
    x = jax.ShapeDtypeStruct((1, 512, 14, 14), jnp.float32)
    pool = PoolSpec(3, stride=1, pad=1)
    hlo = jax.jit(lambda x: maxpool2d(x, pool)).lower(x).compile().as_text()
    assert "reduce-window" not in hlo
    assert "maximum" in hlo


# ---------------------------------------------------------------------------
# registry: one dispatch site, fusion rule
# ---------------------------------------------------------------------------


def test_registry_unknown_impl_raises():
    with pytest.raises(ValueError, match="unknown conv impl"):
        get_op("conv", "nope")
    from repro.core import conv2d
    from repro.core.pecr import conv_pool

    with pytest.raises(ValueError, match="unknown conv impl"):
        conv2d(jnp.ones((1, 4, 4)), jnp.ones((1, 1, 3, 3)), 1, "nope")
    with pytest.raises(ValueError, match="unknown conv_pool impl"):
        conv_pool(jnp.ones((1, 6, 6)), jnp.ones((1, 1, 3, 3)), impl="nope")


def test_fusion_rule():
    lenet_units = LENET.units()
    assert all(fusion_eligible(u) for u in lenet_units)  # 28->14, 10->5 tile
    alex_units = ALEXNET.units()
    assert not any(fusion_eligible(u) for u in alex_units)  # overlapping pools
    # a fused request resolves per-unit: fused where eligible, family conv else
    assert unit_impl(lenet_units[0], "pecr_pallas") == ("conv_pool", "pecr_pallas")
    assert unit_impl(alex_units[0], "pecr_pallas") == ("conv", "ecr_pallas")
    assert unit_impl(alex_units[0], "dense") == ("conv", "dense")


def test_registry_cost_hooks_present_for_planned_impls():
    for kind, impl in (("conv", "dense"), ("conv", "ecr"), ("conv", "ecr_pallas"),
                       ("conv_pool", "unfused"), ("conv_pool", "pecr"),
                       ("conv_pool", "pecr_pallas")):
        op = get_op(kind, impl)
        kw = {"pool": 2} if kind == "conv_pool" else {}
        cost = op.cost(8, 10, 10, 16, 3, 3, stride=1, occupancy=0.5, **kw)
        assert cost["flops"] > 0 and cost["bytes"] > 0
    # the unfused baseline pays the intermediate round trip fusion deletes
    unfused = get_op("conv_pool", "unfused").cost(8, 10, 10, 16, 3, 3,
                                                  stride=1, pool=2)
    fused = get_op("conv_pool", "pecr").cost(8, 10, 10, 16, 3, 3,
                                             stride=1, pool=2)
    assert unfused["bytes"] > fused["bytes"]


def test_serving_graphs_all_build():
    """Every CLI-reachable graph must pass shape inference — the full VGG
    serving resolution regressed once on a stage-5 pool that only worked via
    the silent-truncation bug PoolSpec now rejects."""
    from repro.launch.serve_cnn import MODELS, serving_graph

    for model in MODELS:
        for full in (False, True):
            g = serving_graph(model, full)
            assert g.units() and g.flat_dim() > 0


# ---------------------------------------------------------------------------
# LeNet / AlexNet end-to-end: plan -> run -> serve (acceptance)
# ---------------------------------------------------------------------------


def _graph_calib(graph, n=3, seed=1):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n,) + graph.in_shape)


@pytest.mark.parametrize("graph", [LENET_REDUCED, ALEXNET_REDUCED],
                         ids=["lenet", "alexnet"])
def test_sparse_plan_matches_dense_reference(graph):
    """occ_threshold=1.0 forces every layer sparse; the executed plan must
    reproduce the all-dense logits within tolerance on the real topology
    (5x5 pad-0 fused LeNet stacks / strided + ceil-pool AlexNet stacks)."""
    params = init_graph(jax.random.PRNGKey(0), graph)
    imgs = _graph_calib(graph)
    plan = plan_network(params, imgs, graph, occ_threshold=1.0, block_c=8)
    assert all(get_op(lp.kind, lp.impl).sparse for lp in plan.layers)
    out = run_plan(plan, params, imgs)
    ref = run_graph(graph, params, imgs, "dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_lenet_plan_fuses_alexnet_plan_does_not():
    lp = plan_network(init_graph(jax.random.PRNGKey(0), LENET_REDUCED),
                      _graph_calib(LENET_REDUCED), LENET_REDUCED,
                      occ_threshold=1.0, block_c=8)
    assert [l.impl for l in lp.layers] == ["pecr_pallas", "pecr_pallas"]
    ap = plan_network(init_graph(jax.random.PRNGKey(0), ALEXNET_REDUCED),
                      _graph_calib(ALEXNET_REDUCED), ALEXNET_REDUCED,
                      occ_threshold=1.0, block_c=8)
    assert all(l.impl == "ecr_pallas" and l.kind == "conv" for l in ap.layers)
    assert ap.counts()["fused"] == 0 and lp.counts()["fused"] == 2


@pytest.mark.parametrize("graph", [LENET_REDUCED, ALEXNET_REDUCED],
                         ids=["lenet", "alexnet"])
def test_engine_serves_graph_network_exactly(graph):
    """N single-image requests through the engine == run_plan on the same
    images (the serving acceptance, on non-VGG topologies). Tolerance note:
    deep ReLU stacks kill channels sample-dependently, so a bucket of 4 and
    the whole batch of 5 can have different live-channel UNIONS — the
    shared-union compaction permutation (and with it the fp32 contraction
    order) then differs in low-order bits; bit-exactness is only contracted
    when co-batched samples share a union (DESIGN.md §4, pinned for VGG in
    test_serving)."""
    params = init_graph(jax.random.PRNGKey(0), graph)
    calib = _graph_calib(graph, n=2, seed=9)
    eng = Engine(params, graph=graph, calib=calib, occ_threshold=1.0,
                 block_c=8, max_batch=4, deadline_s=0.005, clock=SimClock())
    imgs = [_graph_calib(graph, n=1, seed=100 + i)[0] for i in range(5)]
    served = eng.serve(imgs)
    ref = np.asarray(run_plan(eng.plan, params, jnp.stack(imgs)))
    np.testing.assert_allclose(served, ref, rtol=1e-5, atol=1e-6)
    assert eng.stats()["compiles"] > 0


def test_plan_key_carries_graph_signature():
    lenet_params = init_graph(jax.random.PRNGKey(0), LENET_REDUCED)
    alex_params = init_graph(jax.random.PRNGKey(0), ALEXNET_REDUCED)
    lp = plan_network(lenet_params, _graph_calib(LENET_REDUCED), LENET_REDUCED,
                      occ_threshold=0.0, block_c=8)
    ap = plan_network(alex_params, _graph_calib(ALEXNET_REDUCED), ALEXNET_REDUCED,
                      occ_threshold=0.0, block_c=8)
    kl, ka = plan_key(4, lp), plan_key(4, ap)
    assert kl.graph_sig == LENET_REDUCED.signature()
    assert kl != ka  # two all-dense plans must not share a compiled program
    # same graph, different name: programs ARE shared
    other = plan_network(lenet_params, _graph_calib(LENET_REDUCED),
                         LENET_REDUCED, occ_threshold=0.0, block_c=8)
    assert plan_key(4, other) == kl


def test_run_plan_validates_dense_head():
    graph = LENET_REDUCED
    params = init_graph(jax.random.PRNGKey(0), graph)
    plan = plan_network(params, _graph_calib(graph), graph, block_c=8)
    bad = {"conv": params["conv"], "dense": params["dense"][:1]}
    with pytest.raises(ValueError, match="dense weights"):
        run_plan(plan, bad, _graph_calib(graph))


def test_layerplan_is_the_structural_truth():
    """run_plan executes from each LayerPlan's own specs — a plan whose
    layers predate the IR (sentinel ConvSpec) is rejected, and a plan/graph
    unit-count mismatch is caught by validation, not zip-truncated."""
    from repro.pipeline.planner import LayerPlan

    graph = LENET_REDUCED
    params = init_graph(jax.random.PRNGKey(0), graph)
    plan = plan_network(params, _graph_calib(graph), graph, block_c=8)
    legacy = LayerPlan(index=0, stage=0, slot=0, kind="conv", impl="dense",
                       occupancy=1.0, in_shape=(1, 16, 16), out_shape=(4, 6, 6))
    with pytest.raises(ValueError, match="predates the LayerGraph IR"):
        legacy.to_unit()
    mismatched = plan.__class__(layers=plan.layers[:1],
                                occ_threshold=plan.occ_threshold,
                                block_c=plan.block_c, graph=plan.graph)
    bad_params = {"conv": params["conv"][:1], "dense": params["dense"]}
    with pytest.raises(ValueError, match="plan/graph mismatch"):
        run_plan(mismatched, bad_params, _graph_calib(graph))


# ---------------------------------------------------------------------------
# occupancy_stat edge cases (serving drift-detector inputs)
# ---------------------------------------------------------------------------


def _band_batch(n=4, c=16, dead=8, hw=6):
    x = np.array(jax.random.uniform(jax.random.PRNGKey(0), (n, c, hw, hw)),
                 np.float32)
    if dead:
        x[:, c - dead:] = 0.0
    return jnp.asarray(x)


def test_occupancy_stat_n_valid_zero_is_zero():
    assert float(occupancy_stat(_band_batch(), 8, n_valid=0)) == 0.0


def test_occupancy_stat_n_valid_clamped_to_batch():
    x = _band_batch(n=4)
    full = float(occupancy_stat(x, 8, n_valid=4))
    over = float(occupancy_stat(x, 8, n_valid=9))  # beyond N must not deflate
    assert over == pytest.approx(full)
    assert full == pytest.approx(float(occupancy_stat(x, 8)))


def test_occupancy_stat_c_not_divisible_by_block():
    x = _band_batch(c=12, dead=6)  # 6 live channels, block_c=8 -> blocks 8+4
    occ = float(occupancy_stat(x, 8))
    # packed live prefix spans ceil(6/8)=1 of ceil(12/8)=2 blocks
    assert occ == pytest.approx(0.5)


def test_occupancy_stat_all_zero_batch():
    z = jnp.zeros((3, 16, 5, 5))
    assert float(occupancy_stat(z, 8)) == 0.0
    assert float(occupancy_stat(z, 8, n_valid=3)) == 0.0
    # all-zero pads appended to real samples don't change the masked stat
    x = _band_batch(n=2)
    padded = jnp.concatenate([x, jnp.zeros_like(x)])
    masked = float(occupancy_stat(padded, 8, n_valid=2))
    assert masked == pytest.approx(float(occupancy_stat(x, 8)))
