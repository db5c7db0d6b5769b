"""The plain reference, its weights and its lower-precision control, against
the system's own dense path on a tiny graph (CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from cb_helpers import tiny_config

from chipbench import cnn, drive, harness


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(1e-3)
    params = cnn.make_weights(cfg, 12345)
    x = jnp.asarray(drive.image_pool(cnn.in_shape(cfg), 6, 12345, 0.5))
    return cfg, params, x


def test_seed_key_matches_prngkey_and_keeps_high_bits():
    for seed in (0, 7, 2**31 + 5, 2**32 - 1):
        assert np.array_equal(np.asarray(cnn.seed_key(seed)),
                              np.asarray(jax.random.PRNGKey(seed)))
    assert not np.array_equal(np.asarray(cnn.seed_key(2**33 + 7)),
                              np.asarray(cnn.seed_key(7)))


def test_base_weights_are_the_systems_emulation(tiny):
    from repro.graph import init_graph
    from repro.models.cnn import shift_dead_channels

    cfg, _, _ = tiny
    graph = harness.layer_graph(cfg)
    key = jax.random.PRNGKey(cfg["weights"]["base_seed"])
    mine = cnn.base_weights(cfg, key)
    theirs = shift_dead_channels(init_graph(key, graph))
    for a, b in zip(mine["conv"] + mine["dense"],
                    theirs["conv"] + theirs["dense"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # the emulation kills some filters of the deeper convs
    assert (np.asarray(mine["conv"][2]).reshape(32, -1).max(axis=1) <= 0).any()


def test_every_seed_serves_the_same_function_permuted(tiny):
    """A seed permutes channels: other weights, the same logits, the same
    filters (hence the same dead channels and plan)."""
    cfg, params, x = tiny
    other = cnn.make_weights(cfg, 2**31 + 77)
    assert not np.array_equal(np.asarray(params["conv"][0]),
                              np.asarray(other["conv"][0]))
    for a, b in zip(params["conv"], other["conv"]):
        rows = [np.sort(np.asarray(w).reshape(w.shape[0], -1), axis=0)
                for w in (a, b)]
        np.testing.assert_array_equal(np.sort(rows[0], axis=1),
                                      np.sort(rows[1], axis=1))
    ref = np.asarray(cnn.forward(cfg, params, x))
    np.testing.assert_allclose(np.asarray(cnn.forward(cfg, other, x)), ref,
                               rtol=1e-5, atol=1e-5 * np.abs(ref).max())
    # the same seed gives the same weights
    again = cnn.make_weights(cfg, 2**31 + 77)
    np.testing.assert_array_equal(np.asarray(again["dense"][0]),
                                  np.asarray(other["dense"][0]))


def test_reference_matches_the_systems_dense_path(tiny):
    from repro.graph import run_graph

    cfg, params, x = tiny
    ref = np.asarray(cnn.forward(cfg, params, x))
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(run_graph(harness.layer_graph(cfg), params, x,
                                      impl="dense"))
    assert ref.shape == (6, 10)
    np.testing.assert_allclose(ref, theirs, rtol=1e-5,
                               atol=1e-5 * np.abs(ref).max())


def test_control_precision_ladder(tiny):
    """bfloat16 operands move the logits by about the bf16 rounding, int8
    by more: the control the limits are set against."""
    cfg, params, x = tiny
    ref = np.asarray(cnn.forward(cfg, params, x), np.float64)

    def err(dtype):
        got = np.asarray(cnn.forward(cfg, params, x, dtype), np.float64)
        return float((np.abs(got - ref).max(axis=1)
                      / np.abs(ref).max(axis=1)).max())

    bf16, int8 = err(jnp.bfloat16), err(jnp.int8)
    assert 1e-4 < bf16 < 2e-2
    assert int8 > 2 * bf16


def test_int8_operands_take_127_levels_a_channel():
    """The int8 control rounds each weight's output channel and each
    sample's activations to its own absmax / 127 grid."""
    a = jnp.asarray(np.random.default_rng(0).normal(size=(4, 3, 5, 5)),
                    jnp.float32) * jnp.asarray([1.0, 10.0, 100.0, 0.01])[:, None,
                                                                      None, None]
    q = np.asarray(cnn.operand(a, jnp.int8, (1, 2, 3)))
    for o in range(4):
        step = np.abs(np.asarray(a[o])).max() / 127.0
        levels = q[o] / step
        np.testing.assert_allclose(levels, np.round(levels), atol=1e-3)
        assert np.abs(levels).max() == pytest.approx(127.0)
        assert np.abs(q[o] - np.asarray(a[o])).max() <= step / 2 * (1 + 1e-5)
    assert cnn.operand(a, None, (1, 2, 3)) is a
