"""GoogLeNet on the LayerGraph IR: branches joined by a channel concat, LRN,
padded and average pools — shape inference, topology errors, the plan
verifier, the bucket program's scopes, and the Engine against a plain
jax.numpy reference (CPU; the Pallas kernels are interpreted)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import verify_plan
from repro.configs.alexnet import ALEXNET, ALEXNET_REDUCED
from repro.configs.googlenet import GOOGLENET, GOOGLENET_REDUCED
from repro.configs.lenet import LENET, LENET_REDUCED
from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
from repro.graph import (
    LRN,
    Branches,
    ConvSpec,
    DenseSpec,
    Flatten,
    LayerGraph,
    PoolSpec,
    ReLU,
    fusion_eligible,
    init_graph,
    weight_shapes,
)
from repro.graph.ir import Join, Step
from repro.models.cnn import shift_dead_channels
from repro.pipeline import plan_network
from repro.serving import Engine

HI = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# the plain reference: each node's published semantics in jax.numpy, f32 at
# the highest matmul precision, nothing of the executor or the kernels
# ---------------------------------------------------------------------------


def _ref_pool(x, pool):
    n = x.shape[-1]
    span = n + 2 * pool.pad
    out = -(-(span - pool.p) // pool.s) + 1 if pool.mode == "ceil" else \
        (span - pool.p) // pool.s + 1
    if pool.mode == "ceil" and (out - 1) * pool.s >= n + pool.pad:
        out -= 1
    if pool.kind == "avg":
        return jnp.stack([jnp.stack([
            x[:, :, i * pool.s:i * pool.s + pool.p,
              j * pool.s:j * pool.s + pool.p].mean(axis=(2, 3))
            for j in range(out)], -1) for i in range(out)], -2)
    hi = (out - 1) * pool.s + pool.p - n - pool.pad
    xp = jnp.pad(x, ((0, 0), (0, 0), (pool.pad, hi), (pool.pad, hi)),
                 constant_values=-jnp.inf)
    return jnp.stack([jnp.stack([
        xp[:, :, i * pool.s:i * pool.s + pool.p,
           j * pool.s:j * pool.s + pool.p].max(axis=(2, 3))
        for j in range(out)], -1) for i in range(out)], -2)


def _ref_lrn(x, spec):
    c = x.shape[1]
    half = spec.size // 2
    sums = jnp.stack([
        (x[:, max(0, i - half):i - half + spec.size] ** 2).sum(axis=1)
        for i in range(c)], axis=1)
    return x / (spec.k + spec.alpha / spec.size * sums) ** spec.beta


def reference(graph, params, x):
    ws = iter(params["conv"])

    def chain(nodes, x):
        for node in nodes:
            if isinstance(node, ConvSpec):
                x = jax.lax.conv_general_dilated(
                    x, next(ws), (node.stride,) * 2, ((node.pad, node.pad),) * 2,
                    dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI)
            elif isinstance(node, ReLU):
                x = jnp.maximum(x, 0.0)
            elif isinstance(node, PoolSpec):
                x = _ref_pool(x, node)
            elif isinstance(node, LRN):
                x = _ref_lrn(x, node)
            elif isinstance(node, Branches):
                x = jnp.concatenate([chain(p, x) for p in node.paths], axis=1)
            elif isinstance(node, Flatten):
                x = x.reshape(x.shape[0], -1)
            else:
                x = jnp.dot(x, dense.pop(0), precision=HI)
                if node.relu:
                    x = jnp.maximum(x, 0.0)
        return x

    dense = list(params["dense"])
    return chain(graph.nodes, x)


def _params(graph, seed=0):
    return shift_dead_channels(init_graph(jax.random.PRNGKey(seed), graph),
                               rate=0.03)


def _images(graph, n, seed=1):
    """Uniform 0-255 pixels (Caffe's range, which GoogLeNet's LRN constants
    were set for) with the trailing half of the channels dead (the
    benchmark's image recipe)."""
    c = graph.in_shape[0]
    x = 255.0 * jax.random.uniform(jax.random.PRNGKey(seed),
                                   (n,) + graph.in_shape)
    return x.at[:, c - c // 2:].set(0.0)


# ---------------------------------------------------------------------------
# shape inference and topology
# ---------------------------------------------------------------------------


def test_googlenet_shapes_follow_table_1():
    units = GOOGLENET.units()
    assert len(units) == 57
    joins = [st for st in GOOGLENET.body() if isinstance(st, Join)]
    assert [j.out_shape[0] for j in joins] == [256, 480, 512, 512, 512, 528,
                                               832, 832, 1024]
    assert [j.out_shape[1] for j in joins] == [28] * 2 + [14] * 5 + [7] * 2
    assert GOOGLENET.feature_shape() == (1024, 1, 1)
    assert GOOGLENET.flat_dim() == 1024
    assert units[0].out_shape == (64, 56, 56)  # conv1 carries its 3x3/2 pool
    # Table 1's column order inside a module: 1x1; 3x3 reduce, 3x3; 5x5
    # reduce, 5x5; pool proj — each reading what the table says it reads
    m = units[3:9]
    assert [u.conv.c_out for u in m] == [64, 96, 128, 16, 32, 32]
    assert [u.conv.k for u in m] == [1, 1, 3, 1, 5, 1]
    assert [u.reads for u in m] == [-1, -1, 4, -1, 6, -1]
    assert all(u.in_shape[0] == 192 for u in (m[0], m[1], m[3], m[5]))
    # every name the bucket program scopes by
    assert [name for name, _ in GOOGLENET.joins()] == [
        f"inception_{n}" for n in ("3a", "3b", "4a", "4b", "4c", "4d", "4e",
                                   "5a", "5b")]
    assert [idx for _, idx in GOOGLENET.joins()][0] == tuple(range(3, 9))
    steps = [st.scope for st in GOOGLENET.body() if isinstance(st, Step)]
    assert steps == ["lrn1", "lrn2", "pool1", "pool4", "pool10", "avgpool"]
    conv, dense = weight_shapes(GOOGLENET)
    assert len(conv) == 57 and dense == ((1024, 1000),)


def test_reduced_graph_has_every_node_kind():
    g = GOOGLENET_REDUCED
    nodes = g.nodes

    def kinds(nodes):
        for n in nodes:
            yield n
            if isinstance(n, Branches):
                for p in n.paths:
                    yield from kinds(p)

    all_nodes = list(kinds(nodes))
    assert sum(isinstance(n, Branches) for n in nodes) >= 2
    assert any(isinstance(n, LRN) for n in nodes)
    assert any(isinstance(n, PoolSpec) and n.pad for n in all_nodes)
    assert any(isinstance(n, PoolSpec) and n.kind == "avg" for n in nodes)
    assert any(isinstance(n, PoolSpec) and n.mode == "ceil" for n in nodes)
    assert g.feature_shape()[1:] == (1, 1)


def _graph(*body):
    return LayerGraph("t", (8, 8, 8), (ConvSpec(8), ReLU()) + body
                      + (Flatten(), DenseSpec(2)))


def test_topology_errors():
    with pytest.raises(ValueError, match="path 1 is empty"):
        _graph(Branches(((ConvSpec(4),), ()))).units()
    with pytest.raises(ValueError, match="no paths"):
        _graph(Branches(())).units()
    with pytest.raises(ValueError, match="different sizes"):
        _graph(Branches(((ConvSpec(4),), (ConvSpec(4, stride=2),)))).units()
    with pytest.raises(ValueError, match="larger than input"):
        _graph(PoolSpec(3, stride=1), PoolSpec(9)).units()
    with pytest.raises(ValueError, match="larger than input"):
        _graph(Branches(((PoolSpec(9, pad=0),), (ConvSpec(4),)))).units()
    with pytest.raises(ValueError, match="inside a branch"):
        _graph(Branches(((ConvSpec(4), Flatten()),))).units()
    with pytest.raises(ValueError, match="LRN must follow"):
        LayerGraph("t", (8, 8, 8), (LRN(), ConvSpec(8), Flatten(),
                                    DenseSpec(2))).units()
    with pytest.raises(ValueError, match="no padding"):
        _graph(PoolSpec(2, pad=1, kind="avg")).units()
    # stand-alone steps may follow a unit, a concat or another step
    g = _graph(LRN(), PoolSpec(3, stride=1, pad=1), Branches((
        (ConvSpec(4, k=1, pad=0),), (PoolSpec(3, stride=1, pad=1),))),
        LRN(), PoolSpec(2, kind="avg"))
    assert g.feature_shape() == (12, 4, 4)


def test_signature_sees_inside_branches():
    def g(width):
        return _graph(Branches(((ConvSpec(4, k=1, pad=0),),
                                (ConvSpec(width, k=3),))))

    assert g(4).signature() == g(4).signature()
    assert g(4).signature() != g(6).signature()
    a = _graph(Branches(((ConvSpec(4),), (PoolSpec(3, stride=1, pad=1),))))
    b = _graph(Branches(((ConvSpec(4),), (PoolSpec(3, stride=1, pad=0,
                                                   mode="floor"),))))
    assert a.signature() != b.signature()


def _old_signature(graph):
    """`signature()` as it was before PoolSpec had `pad` and `kind`."""
    def sig(n):
        vals = tuple(vars(n).values())
        return (type(n).__name__,) + (vals[:3] if isinstance(n, PoolSpec)
                                      else vals)
    return (tuple(graph.in_shape), tuple(sig(n) for n in graph.nodes))


@pytest.mark.parametrize("graph", [
    LENET, LENET_REDUCED, ALEXNET, ALEXNET_REDUCED, vgg19_graph(CNNConfig()),
    vgg19_graph(CNNConfig(img_size=96))], ids=lambda g: g.name)
def test_straight_chains_keep_their_units_and_signature(graph):
    assert graph.signature() == _old_signature(graph)
    units = graph.units()
    assert [u.reads for u in units] == list(range(-1, len(units) - 1))
    assert graph.body() == units  # no stand-alone step, no join
    for prev, nxt in zip(units, units[1:]):
        assert prev.out_shape == nxt.in_shape
    assert graph.feature_shape() == units[-1].out_shape


def test_lenet_units_pinned():
    u = LENET.units()
    assert [(x.index, x.stage, x.slot, x.conv, x.relu, x.pool, x.in_shape,
             x.out_shape) for x in u] == [
        (0, 0, 0, ConvSpec(6, k=5, stride=1, pad=0), True, PoolSpec(2),
         (1, 32, 32), (6, 14, 14)),
        (1, 1, 0, ConvSpec(16, k=5, stride=1, pad=0), True, PoolSpec(2),
         (6, 14, 14), (16, 5, 5))]
    assert LENET.signature() == ((1, 32, 32), (
        ("ConvSpec", 6, 5, 1, 0), ("ReLU",), ("PoolSpec", 2, 0, "valid"),
        ("ConvSpec", 16, 5, 1, 0), ("ReLU",), ("PoolSpec", 2, 0, "valid"),
        ("Flatten",), ("DenseSpec", 120, True), ("DenseSpec", 84, True),
        ("DenseSpec", 10, False)))


def test_fusion_refuses_padded_and_average_pools():
    pool_path = [st for st in GOOGLENET.body() if isinstance(st, Join)][0]
    step = pool_path.paths[3][0]
    assert isinstance(step, Step) and step.node == PoolSpec(3, stride=1, pad=1)
    assert not any(fusion_eligible(u) for u in GOOGLENET.units())
    # the same 3x3/1 padded pool, and a 2x2/2 that tiles but is padded or an
    # average, right after a conv: the unit carries it and PECR may not fuse
    for pool in (PoolSpec(3, stride=1, pad=1), PoolSpec(2, pad=1, mode="floor"),
                 PoolSpec(2, kind="avg")):
        unit = _graph(ConvSpec(8), ReLU(), pool).units()[1]
        assert unit.pool == pool and not fusion_eligible(unit)
    assert fusion_eligible(_graph(ConvSpec(8), ReLU(), PoolSpec(2)).units()[1])


# ---------------------------------------------------------------------------
# ECR and the concat: compaction leaves the output channels in their order
# ---------------------------------------------------------------------------


def test_ecr_output_channels_come_out_in_natural_order():
    """Compaction permutes a conv's input channels and its weights together,
    so each output channel is where the dense conv puts it: a concat of ECR
    outputs needs no permutation."""
    from repro.graph.executor import run_unit

    g = GOOGLENET_REDUCED
    unit = g.units()[4]  # inception_3a's 3x3 reduce, reading a concat-free input
    x = jax.random.uniform(jax.random.PRNGKey(3), (2,) + unit.in_shape)
    x = x * (jnp.arange(unit.in_shape[0]) % 3 != 0)[None, :, None, None]
    w = jax.random.normal(jax.random.PRNGKey(4), (unit.conv.c_out,)
                          + (unit.in_shape[0], unit.conv.k, unit.conv.k))
    dense = run_unit(x, w, unit, "conv", "dense")
    ecr = run_unit(x, w, unit, "conv", "ecr_pallas", block_c=8)
    np.testing.assert_allclose(np.asarray(ecr), np.asarray(dense), rtol=1e-5,
                               atol=1e-5)
    both = jnp.concatenate([ecr, run_unit(x, -w, unit, "conv", "ecr_pallas",
                                          block_c=8)], axis=1)
    ref = jnp.concatenate([dense, run_unit(x, -w, unit, "conv", "dense")],
                          axis=1)
    np.testing.assert_allclose(np.asarray(both), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the plan verifier on a branched plan
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def reduced():
    g = GOOGLENET_REDUCED
    params = _params(g)
    calib = _images(g, 2)
    plan = plan_network(params, calib, g, block_c=8)
    return g, params, calib, plan


def test_verifier_accepts_a_branched_plan_and_rejects_a_mismatched_one(reduced):
    from dataclasses import replace

    g, params, _, plan = reduced
    assert [d for d in verify_plan(plan, params) if d.severity == "error"] == []
    # conv6 (inception_3a's 3x3) reads conv5, its reduce: corrupt its input
    lp = plan.layers[5]
    assert lp.reads == 4
    bad = replace(lp, in_shape=(lp.in_shape[0] + 8,) + lp.in_shape[1:])
    diags = verify_plan(replace(plan, layers=plan.layers[:5] + (bad,)
                                + plan.layers[6:]))
    assert any(d.code == "RPA201" and "conv_5 produces" in d.message
               for d in diags)
    # a unit that claims the wrong producer disagrees with the graph
    wrong = replace(plan.layers[3], reads=2)
    diags = verify_plan(replace(plan, layers=plan.layers[:3] + (wrong,)
                                + plan.layers[4:]))
    assert any(d.code == "RPA201" and "reads" in d.message for d in diags)


def test_plan_measures_each_unit_on_the_tensor_it_reads(reduced):
    """The planner's one compiled calibration pass gives every unit the
    occupancy and weight density that `measure_occupancy` and
    `weight_block_density` read, op by op, on that unit's own input."""
    from repro.graph import graph_weights, run_unit, walk_graph
    from repro.pipeline import measure_occupancy
    from repro.sparse_weights import weight_block_density

    g, params, calib, plan = reduced
    conv_ws, _ = graph_weights(params)
    seen = []

    def on_unit(unit, x):
        seen.append((measure_occupancy(x, 8),
                     weight_block_density(conv_ws[unit.index])))
        return run_unit(x, conv_ws[unit.index], unit, "conv", "dense")

    walk_graph(g, calib, on_unit)
    assert len(seen) == len(plan.layers) == 21
    assert [lp.occupancy for lp in plan.layers] == pytest.approx(
        [occ for occ, _ in seen], abs=1e-6)
    assert [lp.weight_density for lp in plan.layers] == [wd for _, wd in seen]


def test_plan_span_counts_units_concats_and_ecr_per_module(reduced):
    from repro.serving.engine import plan_span_args

    g, _, _, plan = reduced
    args = plan_span_args(plan)
    sparse = [lp.index for lp in plan.layers if lp.impl != "dense"]
    assert args["units"] == 21 and args["concats"] == 3
    assert args["ecr"] == len(sparse)
    assert sum(v for k, v in args.items() if k.startswith("ecr_")) == \
        len([i for i in sparse if i >= 3])
    assert set(args) == {"units", "concats", "ecr", "ecr_inception_3a",
                         "ecr_inception_3b", "ecr_inception_4a"}


def test_bucket_program_scopes_the_new_ops(reduced):
    """inception_<m> around each module with its conv<i> scopes inside, and
    concat, lrn<j>, pool<j>, avgpool around the ops between the units."""
    from repro.serving.engine import _make_runner

    g, params, calib, plan = reduced
    text = jax.jit(_make_runner(plan)).lower(
        params, jnp.zeros((2,) + g.in_shape), jnp.int32(2)).as_text(
            debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    top = {n.split("/")[1] for n in names if n.startswith("jit(run)/")}
    assert {"conv1", "conv2", "conv3", "lrn1", "lrn2", "pool1", "pool4",
            "avgpool", "head", "inception_3a", "inception_3b",
            "inception_4a"} <= top
    inner = {n.split("/")[2] for n in names
             if n.startswith("jit(run)/inception_3a/")}
    assert inner == {"conv4", "conv5", "conv6", "conv7", "conv8", "conv9",
                     "concat", "pool2"}


# ---------------------------------------------------------------------------
# the Engine against the plain reference
# ---------------------------------------------------------------------------

# f32 on the CPU: every path computes the same sums in another order (the
# ECR kernel over compacted channel blocks, XLA's conv, the reference's
# HIGHEST), which moves the logits by about 5e-7 of the largest. 5e-6 leaves
# ten times that, far below what a lost LRN moves them by on 0-255 pixels
# (about 0.4 of the largest, the last assertion), a wrong branch order, a
# missing path or an unpadded pool.
TOL = 5e-6


def _rel_err(got, ref):
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float((np.abs(got - ref).max(axis=1)
                  / np.abs(ref).max(axis=1)).max())


@pytest.mark.parametrize("occ_threshold", [0.75, 1.0])
def test_engine_serves_googlenet_reduced_like_the_reference(reduced,
                                                            occ_threshold):
    g, params, calib, _ = reduced
    x = _images(g, 4, seed=7)
    eng = Engine(params, graph=g, calib=calib, block_c=8, max_batch=4,
                 occ_threshold=occ_threshold, mesh=None)
    impls = [lp.impl for lp in eng.plan.layers]
    if occ_threshold == 1.0:  # every unit runs the interpreted ECR kernel
        assert impls == ["ecr_pallas"] * 21
    else:
        assert "dense" in impls and "ecr_pallas" in impls
    got = eng.serve(x)
    ref = reference(g, params, x)
    assert _rel_err(got, ref) < TOL
    if occ_threshold == 1.0:
        no_lrn = LayerGraph(g.name, g.in_shape, tuple(
            n for n in g.nodes if not isinstance(n, LRN)))
        assert _rel_err(got, reference(no_lrn, params, x)) > 0.1


def test_serve_cnn_serves_googlenet():
    from repro.launch.serve_cnn import MODELS, serving_graph

    assert "googlenet" in MODELS
    assert serving_graph("googlenet") is GOOGLENET_REDUCED
    assert serving_graph("googlenet", full=True) is GOOGLENET
