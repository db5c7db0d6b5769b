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
