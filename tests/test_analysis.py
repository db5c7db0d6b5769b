"""Static verifier (repro.analysis): every documented diagnostic code fires
under one targeted corruption, clean plans verify clean across the model zoo
(dense and pruned+int8), and the serving hook points reject erroring plans
without interrupting serving."""
import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    CODES,
    PlanVerificationError,
    check_launch_descriptor,
    check_schedule,
    schedule_ok,
    verify_plan,
)
from repro.analysis.diagnostics import DiagnosticSink, errors
from repro.graph import init_graph
from repro.graph.ir import ConvSpec
from repro.kernels.ecr_conv.ops import ecr_conv_launch
from repro.kernels.conv_pool.ops import conv_pool_launch
from repro.kernels.tiles import TileConfig
from repro.launch.serve_cnn import serving_graph, synth_requests
from repro.models.cnn import shift_dead_channels
from repro.pipeline.planner import plan_network, run_plan
from repro.quant.ops import ecr_conv_int8_launch
from repro.sparse_weights.conv import bsr_conv_launch


def _setup(model, prune=None, int8=False, seed=0):
    graph = serving_graph(model)
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(seed), graph))
    calib = jnp.stack(synth_requests(graph, 2, seed=seed + 1))
    if prune is not None:
        from repro.sparse_weights import prune_graph_params

        params, _ = prune_graph_params(params, prune, graph, probe=calib)
    plan = plan_network(params, calib, graph, int8=int8)
    return plan, params, calib


@pytest.fixture(scope="module")
def lenet():
    return _setup("lenet")


def _codes(diags):
    return {d.code for d in diags}


# ---------------------------------------------------------------------------
# clean plans verify clean (zoo sweep, dense and pruned+int8)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", ["lenet", "alexnet", "vgg19"])
def test_clean_plan_verifies_clean(model):
    plan, params, calib = _setup(model)
    assert verify_plan(plan, params, batch=int(calib.shape[0])) == []


def test_clean_pruned_int8_plan_verifies_clean():
    plan, params, calib = _setup("lenet", prune=0.3, int8=True)
    assert verify_plan(plan, params, batch=int(calib.shape[0])) == []


def test_every_code_documented_and_tested():
    # the table is the contract: every code this file corrupts toward exists
    assert set(CODES) == {
        "RPA101", "RPA102", "RPA103", "RPA104", "RPA105",
        "RPA201", "RPA202", "RPA203", "RPA204", "RPA205", "RPA206",
        "RPA207", "RPA208", "RPA209", "RPA301", "RPA901",
    }


# ---------------------------------------------------------------------------
# launch geometry (RPA101-RPA105): corrupt a descriptor field, re-check
# ---------------------------------------------------------------------------


def _conv_launch(**kw):
    return ecr_conv_launch(16, 12, 12, 32, 3, 3, **kw)


def test_clean_launches_check_clean():
    assert check_launch_descriptor(_conv_launch(batch=4)) == []
    assert check_launch_descriptor(
        conv_pool_launch(16, 12, 12, 32, pool=2)) == []
    assert check_launch_descriptor(bsr_conv_launch(32, 144, 100)) == []
    assert check_launch_descriptor(ecr_conv_int8_launch(16, 12, 12, 32)) == []


def test_rpa101_grid_mismatch_conv():
    bad = replace(_conv_launch(), n_cb=3)  # 16 channels / block 8 needs 2
    assert "RPA101" in _codes(check_launch_descriptor(bad))
    bad = replace(_conv_launch(), o_pad=5)  # pad no longer minimal
    assert "RPA101" in _codes(check_launch_descriptor(bad))


def test_rpa101_grid_mismatch_bsr():
    good = bsr_conv_launch(32, 144, 100)
    bad = replace(good, nt=good.nt + 1)
    assert "RPA101" in _codes(check_launch_descriptor(bad))
    # the pre-fix sparse_matmul bug: schedule/padding at one geometry, the
    # kernel launched at another — representable as a corrupted block size
    bad = replace(good, bf=good.bf * 2)
    assert "RPA101" in _codes(check_launch_descriptor(bad))


def test_rpa102_out_of_bounds_gather():
    bad = replace(_conv_launch(), stride=0)
    assert "RPA102" in _codes(check_launch_descriptor(bad))
    bad = replace(_conv_launch(), kh=13)  # kernel taller than the input
    assert "RPA102" in _codes(check_launch_descriptor(bad))
    bad = replace(bsr_conv_launch(32, 144, 100), bd=0)
    assert "RPA102" in _codes(check_launch_descriptor(bad))


def test_rpa103_vmem_budget():
    # default resolution at the block_c floor: over budget is a WARN
    big = ecr_conv_launch(8, 2048, 2048, 8)
    diags = check_launch_descriptor(big)
    assert [d.code for d in diags] == ["RPA103"]
    assert diags[0].severity == "warn"
    # an explicitly requested tile above the default blocks is an ERROR:
    # the default policy would not have chosen it
    big = ecr_conv_launch(256, 512, 512, 128,
                          tile=TileConfig(block_c=256))
    diags = check_launch_descriptor(big)
    assert [d.code for d in diags] == ["RPA103"]
    assert diags[0].severity == "error"


def test_rpa104_int8_contract():
    good = ecr_conv_int8_launch(16, 12, 12, 32)
    assert good.acc_dtype == "int32"
    assert "RPA104" in _codes(
        check_launch_descriptor(replace(good, acc_dtype="float32")))
    assert "RPA104" in _codes(
        check_launch_descriptor(replace(good, weight_scales="none")))


def test_rpa105_fused_pool_inexact():
    good = conv_pool_launch(16, 12, 12, 32, pool=2)  # oh=ow=10, 2 divides
    assert check_launch_descriptor(good) == []
    bad = replace(good, pool=3)  # 10 % 3 != 0: the kernel would floor
    assert "RPA105" in _codes(check_launch_descriptor(bad))


# ---------------------------------------------------------------------------
# plan invariants (RPA201-RPA209, RPA301): one targeted corruption per code
# ---------------------------------------------------------------------------


def test_rpa201_empty_plan(lenet):
    plan, params, _ = lenet
    diags = verify_plan(replace(plan, layers=()))
    assert _codes(diags) == {"RPA201"}
    assert "empty PipelinePlan" in diags[0].message


def test_rpa201_pre_ir_layer(lenet):
    plan, params, _ = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], conv=ConvSpec(0)),) + plan.layers[1:])
    diags = verify_plan(bad)
    assert "RPA201" in _codes(diags)
    assert any("predates the LayerGraph IR" in d.message for d in diags)


def test_rpa201_plan_graph_mismatch(lenet):
    plan, params, _ = lenet
    other = serving_graph("alexnet")
    diags = verify_plan(replace(plan, graph=other))
    assert "RPA201" in _codes(diags)
    assert any("plan/graph mismatch" in d.message for d in diags)


def test_rpa202_graph_fails_shape_inference(lenet):
    plan, params, _ = lenet
    # conv + ReLU only: no Flatten + dense head, so _parse refuses
    bad_graph = replace(plan.graph, nodes=plan.graph.nodes[:2])
    assert "RPA202" in _codes(verify_plan(replace(plan, graph=bad_graph)))


def test_rpa203_illegal_fusion(lenet):
    plan, params, _ = lenet
    # claim fusion on a unit with no pool: the fusion rule must refuse
    bad = replace(plan, layers=(
        replace(plan.layers[0], kind="conv_pool", impl="pecr_pallas",
                pool=None),
    ) + plan.layers[1:], graph=None)  # graph=None isolates the fusion check
    assert "RPA203" in _codes(verify_plan(bad))


def test_rpa204_nonconforming_tile_is_warn(lenet):
    plan, params, _ = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], impl="ecr_pallas",
                tile=TileConfig(block_c=1000)),
    ) + plan.layers[1:])
    diags = verify_plan(bad, params, batch=2)
    assert "RPA204" in _codes(diags)
    assert errors(diags) == []  # a fallback is advisory, the plan still runs


def test_rpa205_density_mismatch(lenet):
    plan, params, _ = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], kind="conv", impl="bsr", weight_density=0.3),
    ) + plan.layers[1:])
    diags = verify_plan(bad, params, batch=2)  # params are UNPRUNED
    assert "RPA205" in _codes(diags)
    assert any("weight block density" in d.message for d in diags)


def test_rpa206_int8_without_report(lenet):
    plan, params, _ = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], impl="ecr_int8"),) + plan.layers[1:],
        int8_report=None)
    diags = verify_plan(bad)
    rpa206 = [d for d in diags if d.code == "RPA206"]
    assert rpa206 and rpa206[0].severity == "warn"


def test_rpa208_unknown_impl(lenet):
    plan, params, _ = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], impl="nope"),) + plan.layers[1:])
    assert "RPA208" in _codes(verify_plan(bad))


def test_rpa209_field_sanity(lenet):
    plan, params, _ = lenet
    assert "RPA209" in _codes(verify_plan(replace(plan, block_c=-1)))
    bad = replace(plan, layers=(
        replace(plan.layers[0], occupancy=1.5),) + plan.layers[1:])
    assert "RPA209" in _codes(verify_plan(bad))
    bad = replace(plan, layers=(
        replace(plan.layers[0], weight_density=-0.1),) + plan.layers[1:])
    assert "RPA209" in _codes(verify_plan(bad))


def test_rpa301_params_mismatch(lenet):
    plan, params, _ = lenet
    dropped = {"conv": params["conv"][:-1], "dense": params["dense"]}
    diags = verify_plan(plan, dropped)
    assert "RPA301" in _codes(diags)
    assert any("silently truncate" in d.message for d in diags)
    # wrong C_in on one weight
    w0 = params["conv"][0]
    widened = {"conv": [jnp.concatenate([w0, w0], axis=1)]
               + list(params["conv"][1:]), "dense": params["dense"]}
    diags = verify_plan(plan, widened)
    assert "RPA301" in _codes(diags)


# ---------------------------------------------------------------------------
# schedules (RPA207) + the run-time guard
# ---------------------------------------------------------------------------


def test_rpa207_schedule_invariants():
    ids = np.array([0, 1, 2, 0], np.int32)
    assert schedule_ok(ids, 3, 4)
    assert schedule_ok(ids, 3, 4) and schedule_ok(ids[:3], 3, 3)
    # cnt out of range
    assert not schedule_ok(ids, 5, 4)
    # id out of range
    assert not schedule_ok(np.array([0, 9, 2, 0]), 3, 4)
    # duplicate / unsorted live prefix
    assert not schedule_ok(np.array([0, 0, 2, 0]), 3, 4)
    assert not schedule_ok(np.array([2, 0, 1, 0]), 3, 4)
    # padding beyond cnt is unconstrained (both builders pad arbitrarily)
    assert schedule_ok(np.array([1, 3, 1, 1]), 2, 4)
    # batched form: per-row cnt
    ids2 = np.array([[0, 1, 0], [1, 2, 1]], np.int32)
    assert schedule_ok(ids2, np.array([2, 2]), 3)
    sink = DiagnosticSink()
    check_schedule(ids2, np.array([2, 4]), 3, sink, layer=1)
    assert [d.code for d in sink.items] == ["RPA207"]
    assert sink.items[0].layer == 1


def test_guard_schedule_off_by_default():
    from repro.kernels.schedule_guard import guard_schedule, schedules_checked

    assert not schedules_checked()
    ids = jnp.array([7, 0, 0], jnp.int32)
    out_ids, out_cnt = guard_schedule(ids, jnp.int32(9), 3)
    assert out_ids is ids  # identity: the hot path is untouched


def test_guard_schedule_clamps_when_enabled(monkeypatch):
    monkeypatch.setenv("REPRO_CHECK_SCHEDULES", "1")
    from repro.kernels.schedule_guard import guard_schedule, schedules_checked

    assert schedules_checked()
    ids, cnt = guard_schedule(jnp.array([-1, 7, 2], jnp.int32),
                              jnp.int32(9), 3)
    assert ids.tolist() == [0, 2, 2] and int(cnt) == 3
    # a valid schedule passes through unchanged (values, not identity)
    ids, cnt = guard_schedule(jnp.array([0, 2, 1], jnp.int32),
                              jnp.int32(2), 3)
    assert ids.tolist() == [0, 2, 1] and int(cnt) == 2


def test_guarded_ops_stay_exact(monkeypatch):
    monkeypatch.setenv("REPRO_CHECK_SCHEDULES", "1")
    from repro.core.ecr import conv2d_dense
    from repro.kernels.ecr_conv.ops import ecr_conv

    x = jax.random.uniform(jax.random.PRNGKey(0), (8, 10, 10))
    x = x.at[4:].set(0.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 8, 3, 3))
    np.testing.assert_allclose(ecr_conv(x, w), conv2d_dense(x, w, 1),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# dead imports (RPA901)
# ---------------------------------------------------------------------------


def test_rpa901_dead_imports():
    from pathlib import Path

    from repro.analysis.deadcode import check_dead_imports, dead_modules

    src = Path(__file__).resolve().parents[1] / "src"
    dead, _ = dead_modules(src)
    assert "repro.configs.arctic_480b" in dead  # seed leftover
    assert "repro.launch.train" in dead
    # the CNN spine is reachable
    for mod in ("repro.pipeline.planner", "repro.serving.engine",
                "repro.kernels.ecr_conv.ops", "repro.analysis.plan"):
        assert mod not in dead
    sink = DiagnosticSink()
    check_dead_imports(src, sink)
    assert sink.items and all(d.code == "RPA901" and d.severity == "info"
                              for d in sink.items)


# ---------------------------------------------------------------------------
# hook points: validate_plan wrapper, PlanCache, Engine.hot_swap
# ---------------------------------------------------------------------------


def test_validate_plan_raises_value_error(lenet):
    plan, params, calib = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], impl="nope"),) + plan.layers[1:])
    with pytest.raises(ValueError, match="RPA208"):
        run_plan(bad, params, calib)


def test_plan_network_verifies_before_returning(lenet):
    # planning against params missing a conv layer must raise, not emit a
    # broken plan (the zip inside planning would silently truncate)
    plan, params, calib = lenet
    dropped = {"conv": params["conv"][:-1], "dense": params["dense"]}
    with pytest.raises(ValueError):
        plan_network(dropped, calib, plan.graph)


def test_plan_cache_refuses_erroring_plan(lenet):
    from repro.serving import PlanCache, plan_key

    plan, params, _ = lenet
    bad = replace(plan, layers=(
        replace(plan.layers[0], impl="nope"),) + plan.layers[1:])
    cache = PlanCache()
    built = []
    with pytest.raises(PlanVerificationError):
        cache.get_or_compile(plan_key(2, plan), bad,
                             lambda: built.append(1) or "exe")
    assert built == []  # the expensive AOT compile never ran
    # a good plan still compiles, and sentinel plans stay allowed
    assert cache.get_or_compile(plan_key(2, plan), plan, lambda: "exe") == "exe"
    assert cache.get_or_compile(plan_key(4, plan), None, lambda: "exe2") == "exe2"


def test_engine_hot_swap_rejects_corrupted_plan():
    from repro.serving import Engine, SimClock, replay_stream

    graph = serving_graph("lenet")
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(0), graph))
    calib = jnp.stack(synth_requests(graph, 2, seed=1))
    eng = Engine(params, graph, calib=calib, max_batch=2,
                 deadline_s=0.005, clock=SimClock())
    good = eng.plan
    bad = replace(good, layers=(
        replace(good.layers[0], impl="nope"),) + good.layers[1:])
    assert eng.hot_swap(params, plan=bad) is False
    assert eng.plan is good  # rejected atomically, nothing mutated
    assert eng.verify_rejects == 1
    assert eng.stats()["verify_rejects"] == 1
    events = eng.stats()["telemetry"]["replan_events"]
    rejects = [e for e in events if e["kind"] == "verify_reject"]
    assert rejects and "RPA208" in rejects[0]["codes"]
    # serving continues on the old plan...
    results = replay_stream(eng, synth_requests(graph, 4, seed=2),
                            rate_rps=200.0)
    assert len(results) == 4
    # ...and a valid swap still lands
    assert eng.hot_swap(params, plan=good) is True
    assert eng.n_hot_swaps == 1


# ---------------------------------------------------------------------------
# repro-lint CLI
# ---------------------------------------------------------------------------


def test_cli_clean_zoo_json(capsys):
    from repro.analysis.cli import main

    rc = main(["--model", "lenet", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["n_errors"] == 0
    assert doc["reports"][0]["model"].startswith("lenet")
    assert doc["reports"][0]["plan"]["layers"]


def test_cli_dead_imports(capsys):
    from repro.analysis.cli import main

    rc = main(["--model", "lenet", "--dead-imports", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0  # infos never fail the lint
    repo = [r for r in doc["reports"] if r["model"] == "<repo>"][0]
    assert any(d["code"] == "RPA901" and "arctic_480b" in d["message"]
               for d in repo["diagnostics"])


def test_cli_pruned_int8(capsys):
    from repro.analysis.cli import main

    rc = main(["--model", "lenet", "--prune-density", "0.3", "--int8",
               "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["n_errors"] == 0
