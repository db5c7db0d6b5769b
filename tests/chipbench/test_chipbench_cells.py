"""Whole runs of tiny cells on the CPU, with the harness's look for a chip
skipped: a cell, configuration, mix and metric added from files alone; the
comparison that decides `correct` failing the control and a broken timed
path; the traced path's reduction."""
from __future__ import annotations

import json
import time

import jax.numpy as jnp
import pytest
from cb_helpers import make_root, tiny_config

SEED = 2**31 + 11
WINDOW_S = 0.6


def run(harness, root, cell, **kw):
    return harness.run_cell(root, cell, SEED, WINDOW_S, False, time.monotonic(),
                            **kw)


def test_a_cell_added_from_files_alone_is_found_by_name(on_cpu, tmp_path):
    """A throwaway configuration, mix and metric, and the cell that uses
    them, exist only as files and BENCHMARK.json entries."""
    root = tmp_path
    (root / "chipbench/configs").mkdir(parents=True)
    (root / "chipbench/traffic").mkdir(parents=True)
    (root / "chipbench/metrics").mkdir(parents=True)
    cfg = tiny_config(1e-3)
    cfg.update(name="throwaway", in_channels=3, image_size=12,
               layers=[{"op": "conv", "out": 8, "k": 3, "pad": 1},
                       {"op": "relu"}, {"op": "pool", "p": 2},
                       {"op": "flatten"}, {"op": "dense", "out": 5}])
    (root / "chipbench/configs/throwaway.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/four.json").write_text(json.dumps(
        {"kind": "closed", "in_flight": 4, "buckets": [4],
         "warm_sizes": [4], "pool": 8, "dead_frac": 0.0}))
    (root / "chipbench/metrics/answers_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.done_between(run.t0, run.t1)) / run.seconds\n")
    (root / "chipbench/metrics/never_there.py").write_text(
        "def read(run):\n    return None\n")
    bench = {
        "configs": [{"name": "throwaway", "file": "chipbench/configs/throwaway.json"}],
        "workloads": [{"name": "throwaway.four", "config": "throwaway",
                       "traffic": "four", "chips": 1}],
        "end_to_end": [{"name": "answers_per_s", "unit": "answers/s"},
                       {"name": "never_there", "unit": "s"}],
        "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = on_cpu.run_cell(root, "throwaway.four", SEED, 0.3, False,
                          time.monotonic())
    assert out["correct"] is True
    assert out["metrics"]["answers_per_s"]["unit"] == "answers/s"
    assert out["metrics"]["answers_per_s"]["value"] > 0
    assert "never_there" not in out["metrics"]  # a reader with nothing to read
    assert list(out)[-1] == "checks"


def test_a_sound_closed_run(on_cpu, tiny_root):
    out = run(on_cpu, tiny_root, "tiny.closed32")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 32
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert out["checks"]["logit_err"]["value"] <= 1e-3
    assert list(out)[-1] == "checks"


def test_the_traced_run_reports_the_layers(on_cpu, tiny_root):
    out = on_cpu.run_cell(tiny_root, "tiny.closed32", SEED, WINDOW_S, True,
                          time.monotonic())
    assert out["correct"] is True
    # setup metrics always; the device ones need a TPU plane, absent here
    assert {"setup.plan_s", "setup.warmup_s"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.fixture(scope="module")
def sparse_root(tmp_path_factory):
    """The tiny cell at 2-channel blocks: its first conv sees half its input
    channels dead and goes to the ECR kernel, which the program's int8 path
    can quantize."""
    cfg = tiny_config(1e-3)
    cfg["serving"]["block_c"] = 2
    return make_root(tmp_path_factory.mktemp("sparse"), cfg)


@pytest.mark.parametrize("control", ["int8", "program_int8"])
def test_a_control_in_the_programs_place_is_not_correct(on_cpu, sparse_root,
                                                        control, capfd):
    """The same cell with a control in the program's place: the program's
    own int8 kernels, or the reference with int8 operands in place of the
    served answers, comes out not correct where the program is correct."""
    sound = run(on_cpu, sparse_root, "tiny.closed32")
    assert sound["correct"] is True
    out = run(on_cpu, sparse_root, "tiny.closed32", control=control)
    assert out["correct"] is False
    assert out["failed"] == 0
    assert out["checks"]["logit_err"]["value"] > \
        3 * sound["checks"]["logit_err"]["value"]
    if control == "program_int8":
        assert "ecr_int8" in capfd.readouterr().err  # the plan line


def test_an_unknown_control_is_refused(on_cpu, tiny_root):
    with pytest.raises(ValueError, match="unknown control"):
        run(on_cpu, tiny_root, "tiny.closed32", control="float8")


def _broken_runner(monkeypatch, break_logits):
    """The timed path broken underneath: the engine's compiled batch runner
    returns `break_logits(logits, imgs)` instead of its logits."""
    from repro.serving import engine as eng

    make = eng._make_runner

    def broken(plan, mesh=None):
        run_ = make(plan, mesh)

        def run2(params, imgs, n_valid):
            logits, occs = run_(params, imgs, n_valid)
            return break_logits(logits, imgs, run_, params, n_valid), occs

        return run2

    monkeypatch.setattr(eng, "_make_runner", broken)


def _altered(logits, imgs, run_, params, n_valid):
    # one answer of every batch altered where it is produced
    return logits.at[0, 0].add(jnp.abs(logits).max() + 1.0)


def _half_left_out(logits, imgs, run_, params, n_valid):
    # only the first half of the batch computed, its answers reused
    h = imgs.shape[0] // 2
    first, _ = run_(params, jnp.concatenate([imgs[:h], imgs[:h]]), n_valid)
    return first


@pytest.mark.parametrize("fault", [_altered, _half_left_out],
                         ids=["altered", "half_left_out"])
def test_a_broken_timed_path_is_not_correct(on_cpu, tiny_root, monkeypatch,
                                            fault):
    _broken_runner(monkeypatch, fault)
    out = run(on_cpu, tiny_root, "tiny.closed32")
    assert out["correct"] is False
    assert out["checks"]["logit_err"]["value"] > 1e-3


def test_stale_answers_are_not_correct(on_cpu, tiny_root, monkeypatch):
    """A step that hands back its previous state: every batch is answered
    with the logits of the batch before it."""
    from repro.serving.engine import Engine, ServedResult

    real = Engine._run_batch
    last = {}

    def stale(self, batch):
        results = real(self, batch)
        prev, last["logits"] = last.get("logits"), [r.logits for r in results]
        if prev is None:
            return results
        return [ServedResult(r.id, prev[i % len(prev)], r.t_arrival, r.t_done,
                             r.t_formed) for i, r in enumerate(results)]

    monkeypatch.setattr(Engine, "_run_batch", stale)
    out = run(on_cpu, tiny_root, "tiny.closed32")
    assert out["correct"] is False


def test_unanswered_requests_are_not_correct(on_cpu, tiny_root, monkeypatch):
    """Answers that never come fail `correct`, however right the others."""
    from repro.serving.engine import Engine

    real = Engine._run_batch
    monkeypatch.setattr(Engine, "_run_batch",
                        lambda self, batch: real(self, batch)[1:])
    out = run(on_cpu, tiny_root, "tiny.closed32")
    assert out["failed"] > 0
    assert out["checks"]["answered"]["value"] < out["checks"]["answered"]["limit"]
    assert out["correct"] is False
