"""Compile-only rehearsals of the serving path's Pallas kernels for a TPU v5e.

Each test compiles one op at the widths the VGG-19 `--full` plan (and
AlexNet-224) hands it, for a v5e that is described, not attached: the TPU
compiler runs here and refuses what the chip would refuse (block shapes,
in-kernel ops, VMEM). Every test asserts `tpu_custom_call` in the compiled
text, which proves the Mosaic branch of `repro.kernels.platform` was taken
and not the interpreter's HLO. Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module-scoped fixture (only the worker
that runs this file loads the TPU compiler), and the tests skip only when it
cannot be described.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import tiles
from repro.kernels.conv_pool.ops import conv_pool_launch, fused_conv_pool
from repro.kernels.ecr_conv.kernel import conv_pallas
from repro.kernels.ecr_conv.ops import ecr_conv, ecr_conv_launch
from repro.quant.ops import conv2d_bsr_int8, ecr_conv_int8, ecr_conv_int8_launch
from repro.sparse_weights.conv import conv2d_bsr

BATCH = 8  # the engine's largest bucket

# (name, C, padded H=W, O): VGG-19 at 96x96 — stage 5 (6x6 maps), stage 1
# (96x96 maps) — and AlexNet-224's conv4 (13x13 maps)
STAGE5 = (512, 8, 512)
STAGE1 = (64, 98, 64)
ALEXNET_CONV4 = (384, 15, 384)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _conv_specs(sharding, c, hp, o):
    return (_spec(sharding, (BATCH, c, hp, hp)), _spec(sharding, (o, c, 3, 3)))


@pytest.mark.parametrize("block_c", [8, 0])
@pytest.mark.parametrize("shape", [STAGE5, STAGE1], ids=["stage5", "stage1"])
def test_ecr_conv_compiles_for_v5e(one_chip, shape, block_c):
    text = _compiled_text(lambda x, w: ecr_conv(x, w, block_c=block_c),
                          *_conv_specs(one_chip, *shape))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_c", [8, 0])
@pytest.mark.parametrize("shape", [STAGE5, STAGE1], ids=["stage5", "stage1"])
def test_pecr_fused_compiles_for_v5e(one_chip, shape, block_c):
    text = _compiled_text(
        lambda x, w: fused_conv_pool(x, w, 1, 2, block_c=block_c),
        *_conv_specs(one_chip, *shape))
    assert "tpu_custom_call" in text


def test_alexnet_conv4_compiles_for_v5e(one_chip):
    text = _compiled_text(ecr_conv, *_conv_specs(one_chip, *ALEXNET_CONV4))
    assert "tpu_custom_call" in text


def test_ecr_int8_compiles_for_v5e(one_chip):
    text = _compiled_text(ecr_conv_int8, *_conv_specs(one_chip, *STAGE5))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", [conv2d_bsr, conv2d_bsr_int8],
                         ids=["fp32", "int8"])
def test_bsr_conv_compiles_for_v5e(one_chip, op):
    # the 512-wide stage-5 conv lowered onto the BSR matmul: W (512, 4608)
    # against (4608, 8 * 36) patches
    text = _compiled_text(op, *_conv_specs(one_chip, *STAGE5))
    assert "tpu_custom_call" in text


# ConvLaunch.vmem_bytes must bound what Mosaic allocates: each launch
# compiles with the scoped VMEM limit set to exactly the modeled need
@pytest.mark.parametrize("launch", [
    ecr_conv_launch(*STAGE5[:2], STAGE5[1], STAGE5[2], block_c=8, batch=BATCH),
    ecr_conv_launch(*STAGE5[:2], STAGE5[1], STAGE5[2], batch=BATCH),
    conv_pool_launch(*STAGE5[:2], STAGE5[1], STAGE5[2], batch=BATCH),
    ecr_conv_launch(*STAGE1[:2], STAGE1[1], STAGE1[2], batch=BATCH),
    conv_pool_launch(*STAGE1[:2], STAGE1[1], STAGE1[2], batch=BATCH),
    conv_pool_launch(*STAGE1[:2], STAGE1[1], STAGE1[2], block_c=8,
                     batch=BATCH),
    ecr_conv_int8_launch(*STAGE5[:2], STAGE5[1], STAGE5[2], batch=BATCH),
    ecr_conv_launch(*ALEXNET_CONV4[:2], ALEXNET_CONV4[1], ALEXNET_CONV4[2],
                    batch=BATCH),
], ids=["ecr-stage5-bc8", "ecr-stage5", "pecr-stage5", "ecr-stage1",
        "pecr-stage1", "pecr-stage1-bc8", "ecr_int8-stage5", "ecr-alexnet4"])
def test_vmem_model_bounds_the_compiler(one_chip, monkeypatch, launch):
    monkeypatch.setattr(tiles, "VMEM_LIMIT_BYTES", launch.vmem_bytes)
    assert launch.vmem_bytes <= tiles.VMEM_LIMIT_BYTES
    L = launch
    dt = jnp.int8 if L.dtype_bytes == 1 else jnp.float32
    specs = [
        _spec(one_chip, (L.batch, L.n_cb, L.h, L.w, L.block_c), dt),
        _spec(one_chip, (L.n_ob, L.n_cb, L.kh, L.kw, L.block_c, L.block_o), dt),
        _spec(one_chip, (L.batch, L.n_cb), jnp.int32),
        _spec(one_chip, (L.batch,), jnp.int32),
    ]
    if L.dtype_bytes == 1:
        specs += [_spec(one_chip, (L.batch, 1, 1)),
                  _spec(one_chip, (L.n_ob, 1, L.block_o))]

    def run(x, w, ids, cnt, *scales):
        sx, sw = scales or (None, None)
        return conv_pallas(x, w, ids, cnt, stride=L.stride, pool=L.pool,
                           sx=sx, sw=sw)

    assert "tpu_custom_call" in _compiled_text(run, *specs)


def _op_names(text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_bucket_program_names_layers_and_kernels_for_v5e(one_chip):
    """The engine's bucket program puts each planned layer's ops under
    conv<i>, the head's under head, and each Pallas kernel under its name:
    the op paths a profiler trace reports as `tf_op`."""
    from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
    from repro.core import dead_channel_band
    from repro.graph import init_graph
    from repro.models.cnn import shift_dead_channels
    from repro.pipeline import plan_network
    from repro.serving.engine import _make_runner

    graph = vgg19_graph(CNNConfig(name="names", in_channels=16, img_size=12,
                                  plan=((8, 2), (16, 1)), n_classes=4))
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(0), graph))
    c, h, w = graph.in_shape
    calib = dead_channel_band(
        jax.random.uniform(jax.random.PRNGKey(1), (2, c, h, w)), 0.5)
    # a threshold above every occupancy puts each layer on a Pallas kernel
    plan = plan_network(params, calib, graph, occ_threshold=1.01, block_c=8)
    assert [lp.impl for lp in plan.layers] == ["ecr_pallas", "pecr_pallas",
                                               "pecr_pallas"]
    specs = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), params)
    text = jax.jit(_make_runner(plan)).lower(
        specs, _spec(one_chip, (4, c, h, w)),
        _spec(one_chip, (), jnp.int32)).compile().as_text()
    names = _op_names(text)
    scopes = {n.split("/")[1] for n in names if n.startswith("jit(run)/")}
    assert {"conv1", "conv2", "conv3", "head"} <= scopes
    assert any(n.startswith("jit(run)/conv2/occupancy/") for n in names)
    kernels = {n for n in names if n.endswith("/pallas_call")}
    assert kernels == {
        "jit(run)/conv1/jit(ecr_conv)/cond/branch_0_fun/ecr_conv/pallas_call",
        "jit(run)/conv2/jit(fused_conv_pool)/cond/branch_0_fun/pecr_conv/pallas_call",
        "jit(run)/conv3/jit(fused_conv_pool)/cond/branch_0_fun/pecr_conv/pallas_call",
    }
    assert any(n.startswith("jit(run)/head/") and n.endswith("dot_general")
               for n in names)


@pytest.mark.parametrize("op,kernel", [
    (ecr_conv_int8, "ecr_conv_int8"),
    (conv2d_bsr, "bsr_matmul"),
    (conv2d_bsr_int8, "bsr_matmul_int8"),
], ids=["ecr_int8", "bsr", "bsr_int8"])
def test_kernel_names_for_v5e(one_chip, op, kernel):
    text = _compiled_text(op, *_conv_specs(one_chip, 16, 10, 16))
    assert {n.rsplit("/", 2)[-2] for n in _op_names(text)
            if n.endswith("/pallas_call")} == {kernel}
