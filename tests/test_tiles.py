"""Tile-geometry threading (DESIGN.md §10): the `TileConfig` resolution
fallback contract, exactness of every Pallas kernel across a geometry grid
(including non-dividing and oversized requests), and the stat-vs-schedule
regression — `channel_block_occupancy` / `occupancy_stat` must measure at
the block size the kernel ACTUALLY resolves, never a silently different one
(the block-size-1 degradation bug on non-dividing shapes)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import synth_feature_map
from repro.kernels.conv_pool.ops import fused_conv_pool
from repro.kernels.ecr_conv.ops import channel_block_occupancy, ecr_conv
from repro.kernels.tiles import (
    DEFAULT_TILE,
    TileConfig,
    as_tile,
    resolve_block_c,
    resolve_bsr_tile,
    resolve_conv_tile,
)
from repro.kernels.ecr_conv.ops import ecr_conv_launch
from repro.quant.ops import ecr_conv_int8_launch
from repro.pipeline.planner import occupancy_stat
from repro.sparse_weights import conv2d_bsr, conv2d_bsr_ref, prune_matrix, weight_block
from repro.sparse_weights.format import conv_weight_matrix


def _fm(shape, sparsity, seed=0):
    return synth_feature_map(jax.random.PRNGKey(seed), shape, sparsity)


# ---------------------------------------------------------------------------
# resolution contract
# ---------------------------------------------------------------------------


def test_tileconfig_falsy_and_key_roundtrip():
    assert not TileConfig()
    assert not DEFAULT_TILE
    t = TileConfig(block_c=12, bt=8)
    assert t
    assert TileConfig.from_key(t.key()) == t
    assert t.key() == (12, 0, 8, 0, 0)


def test_as_tile_precedence():
    # explicit tile wins outright; else legacy block_c lifts into one
    t = TileConfig(block_c=16, block_o=32)
    assert as_tile(t, 8) is t
    assert as_tile(None, 8) == TileConfig(block_c=8)
    assert as_tile(TileConfig(), 0) is DEFAULT_TILE


def test_resolve_block_c_honors_conforming_and_rejects_oversized():
    # conforming: 0 < bc <= max(8, c) honored EXACTLY, even non-dividing
    assert resolve_block_c(16, TileConfig(block_c=12)) == 12
    assert resolve_block_c(16, TileConfig(block_c=16)) == 16
    # oversized / non-positive -> the default policy, independently
    auto = resolve_block_c(16, None)
    assert resolve_block_c(16, TileConfig(block_c=256)) == auto
    assert resolve_block_c(16, TileConfig()) == auto
    # small c: bc request up to max(8, c) still honored
    assert resolve_block_c(3, TileConfig(block_c=8)) == 8
    # the default is one lane width of channels, or one block of a narrow layer
    assert resolve_block_c(512, None) == 128
    assert resolve_block_c(64, None) == 64


def test_resolve_block_c_dtype_bytes_widens_int8():
    # a block narrower than 128 channels takes the same lane-padded VMEM, so
    # neither width shrinks at a huge map (the old fp32 pick halved here);
    # int8 operands take a quarter of the operand VMEM at the same blocks
    h = w = 512
    f32 = ecr_conv_launch(256, h, w, 256)
    i8 = ecr_conv_int8_launch(256, h, w, 256)
    assert f32.block_c == i8.block_c == resolve_block_c(256, None) == 128
    assert 4 * h * w * 128 < f32.vmem_bytes
    assert i8.vmem_bytes < f32.vmem_bytes


def test_resolve_conv_tile_bo_clamp():
    bc, bo = resolve_conv_tile(16, 24, TileConfig(block_c=8, block_o=8))
    assert (bc, bo) == (8, 8)
    # default bo = min(128, max(8, o)); an oversized request clamps the same
    assert resolve_conv_tile(16, 24, None)[1] == 24
    assert resolve_conv_tile(16, 24, TileConfig(block_o=999))[1] == 24


def test_resolve_bsr_tile_per_dim_independent_fallback():
    o, k_taps, p = 24, 144, 100
    dbt, dbf = weight_block(o, k_taps)
    # a good bf request survives a silly bd request (and vice versa)
    bt, bf, bd = resolve_bsr_tile(o, k_taps, p, TileConfig(bt=8, bf=16, bd=10 ** 6))
    assert (bt, bf) == (8, 16)
    assert bd == resolve_bsr_tile(o, k_taps, p, None)[2]
    bt, bf, bd = resolve_bsr_tile(o, k_taps, p, TileConfig(bt=10 ** 6, bf=16, bd=32))
    assert bt == dbt and (bf, bd) == (16, 32)
    assert resolve_bsr_tile(o, k_taps, p, TileConfig()) == (dbt, dbf,
                                                           resolve_bsr_tile(o, k_taps, p)[2])


# ---------------------------------------------------------------------------
# exactness across the geometry grid (ECR / PECR / BSR)
# ---------------------------------------------------------------------------

# includes the non-dividing 12-on-16 fallback shape and a small bo
_CONV_GRID = [(8, 8), (8, 32), (12, 8), (16, 128)]


@pytest.mark.parametrize("bc,bo", _CONV_GRID)
def test_ecr_conv_tile_grid_matches_default(bc, bo):
    x = _fm((16, 12, 12), 0.6)
    k = jax.random.normal(jax.random.PRNGKey(1), (24, 16, 3, 3))
    ref = ecr_conv(x, k)
    out = ecr_conv(x, k, block_c=bc, block_o=bo)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("bc,bo", _CONV_GRID)
def test_pecr_fused_tile_grid_matches_default(bc, bo):
    x = _fm((16, 12, 12), 0.6, seed=2)
    k = jax.random.normal(jax.random.PRNGKey(3), (24, 16, 3, 3))
    ref = fused_conv_pool(x, k, 1, 2)
    out = fused_conv_pool(x, k, 1, 2, block_c=bc, block_o=bo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ecr_conv_batched_tile_grid_matches_default():
    x = jnp.stack([_fm((16, 12, 12), 0.5, seed=s) for s in range(3)])
    k = jax.random.normal(jax.random.PRNGKey(4), (24, 16, 3, 3))
    ref = ecr_conv(x, k)
    out = ecr_conv(x, k, block_c=12, block_o=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_oversized_request_is_bit_identical_to_default():
    """A non-conforming request FALLS BACK (same resolved geometry), so the
    output must be bit-identical to the default path, not merely close."""
    x = _fm((16, 12, 12), 0.5, seed=5)
    k = jax.random.normal(jax.random.PRNGKey(6), (24, 16, 3, 3))
    ref = ecr_conv(x, k)
    out = ecr_conv(x, k, block_c=4096, block_o=4096)
    assert jnp.array_equal(out, ref)
    pref = fused_conv_pool(x, k, 1, 2)
    pout = fused_conv_pool(x, k, 1, 2, block_c=4096)
    assert jnp.array_equal(pout, pref)


@pytest.mark.parametrize("tile", [TileConfig(bt=8, bf=16, bd=32),
                                  TileConfig(bt=16, bf=32, bd=64),
                                  TileConfig(bt=8, bf=10 ** 6, bd=64),
                                  TileConfig()])
def test_bsr_tile_grid_matches_ref(tile):
    w = jax.random.normal(jax.random.PRNGKey(7), (24, 16, 3, 3))
    wm, _, _ = prune_matrix(np.asarray(conv_weight_matrix(w)), 0.4,
                            weight_block(24, 16 * 9))
    w = jnp.asarray(wm.reshape(w.shape))
    x = _fm((16, 12, 12), 0.3, seed=8)
    ref = conv2d_bsr_ref(x, w)
    out = conv2d_bsr(x, w, tile=tile if tile else None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# stat == executed schedule (the block-size-1 degradation regression)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_c", [8, 12, 16, 128])
def test_channel_block_occupancy_matches_executed_schedule(block_c):
    """The statistic must be measured at the kernel's RESOLVED geometry: for
    a non-dividing block_c the kernel pads the tail up to a block multiple
    (resolve_conv_tile), so the stat equals ceil(n_live/bc)/ceil(c/bc) at
    that same bc — never the silent block-size-1 reading."""
    c, h, w = 16, 10, 10
    x = _fm((c, h, w), 0.0, seed=9)
    x = x.at[5:].set(0.0)  # 5 live channels
    bc = resolve_conv_tile(c, c, TileConfig(block_c=block_c))[0]
    n_cb = math.ceil(c / bc)
    expect = math.ceil(5 / bc) / n_cb
    got = channel_block_occupancy(x, block_c=block_c, compact=True)
    assert got == pytest.approx(expect)
    # the planner's traced statistic resolves through the SAME rule
    stat = float(occupancy_stat(x[None], block_c))
    assert stat == pytest.approx(expect)
    # and at block_c=12 on c=16 specifically, the resolved size IS 12 (two
    # blocks, one of them padding-tailed) — the old stat degraded to bc=1
    if block_c == 12:
        assert bc == 12 and n_cb == 2 and expect == 0.5


def test_occupancy_stat_tile_beats_legacy_block_c():
    x = _fm((16, 10, 10), 0.0, seed=10).at[5:].set(0.0)
    # an explicit tile takes precedence over the scalar argument
    via_tile = float(occupancy_stat(x[None], 128, tile=TileConfig(block_c=8)))
    via_scalar = float(occupancy_stat(x[None], 8))
    assert via_tile == via_scalar == pytest.approx(1 / 2)


def test_occupancy_stat_int8_geometry():
    # int8 and fp32 launches resolve the same channel block, so the stat
    # measured at either geometry agrees exactly
    x = _fm((16, 10, 10), 0.0, seed=11).at[5:].set(0.0)
    f32 = ecr_conv_launch(16, 12, 12, 16, block_c=8)
    i8 = ecr_conv_int8_launch(16, 12, 12, 16, block_c=8)
    a = float(occupancy_stat(x[None], f32.block_c))
    b = float(occupancy_stat(x[None], i8.block_c))
    assert a == b == pytest.approx(1 / 2)
