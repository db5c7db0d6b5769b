"""Observability subsystem (DESIGN.md §9): tracer span nesting/ordering and
bit-identical SimClock replays, Chrome trace_event schema validity, the
NullTracer zero-overhead contract, the shared timing harness's outlier
rejection, CalibrationDB fit/lookup/persistence, and the planner-facing
calibration contract — an empty DB plans bit-identically to no calibration,
a populated one can flip a layer's impl choice."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
from repro.core import dead_channel_band
from repro.graph import init_graph
from repro.models.cnn import shift_dead_channels
from repro.obs import (
    CPU_TEST_PRIOR,
    NULL_TRACER,
    PROFILER_TRACER,
    CalibEntry,
    CalibrationDB,
    LayerTiming,
    ProfileReport,
    Tracer,
    profile_plan,
    time_callable,
)
from repro.obs.calibrate import device_kind
from repro.pipeline import plan_network
from repro.serving import Engine, SimClock, plan_key, replay_stream

TINY = CNNConfig(name="vgg-obs-tiny", in_channels=16, img_size=12,
                 plan=((8, 1), (16, 1)), n_classes=4)


@pytest.fixture(scope="module")
def graph():
    return vgg19_graph(TINY)


@pytest.fixture(scope="module")
def params(graph):
    return shift_dead_channels(init_graph(jax.random.PRNGKey(0), graph))


@pytest.fixture(scope="module")
def calib(graph):
    c, h, w = graph.in_shape
    return dead_channel_band(
        jax.random.uniform(jax.random.PRNGKey(1), (2, c, h, w)), 0.5)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_exit_order():
    clock = SimClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", a=1):
        clock.advance(0.001)
        with tr.span("inner"):
            clock.advance(0.002)
        clock.advance(0.003)
    # events land in span-EXIT order: inner closes first
    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    inner, outer = tr.events
    assert inner["args"]["depth"] == 1 and outer["args"]["depth"] == 0
    assert outer["args"]["a"] == 1
    # the inner interval is contained in the outer one (ts/dur in us)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert inner["dur"] == pytest.approx(2000.0)
    assert outer["dur"] == pytest.approx(6000.0)


def test_span_annotate_and_error_visibility():
    tr = Tracer(clock=SimClock())
    with pytest.raises(RuntimeError):
        with tr.span("batch") as sp:
            sp.annotate(fill=0.75)
            raise RuntimeError("boom")
    (e,) = tr.events
    assert e["args"]["fill"] == 0.75
    assert e["args"]["error"] == "RuntimeError"  # crashed span stays visible


def _scripted_trace() -> bytes:
    clock = SimClock()
    tr = Tracer(clock=clock)
    with tr.span("serve.plan", graph="g"):
        clock.advance(0.004)
    for b in (2, 4):
        with tr.span("serve.batch", bucket=b):
            clock.advance(0.001 * b)
    return json.dumps(tr.chrome_trace(), sort_keys=True).encode()


def test_simclock_replay_bit_identical():
    assert _scripted_trace() == _scripted_trace()


def test_chrome_trace_schema():
    clock = SimClock()
    tr = Tracer(clock=clock)
    with tr.span("a"):
        clock.advance(0.001)
        with tr.span("b", n=1):
            clock.advance(0.001)
    payload = tr.chrome_trace()
    assert payload["displayTimeUnit"] == "ms"
    assert json.loads(json.dumps(payload)) == payload  # JSON-serializable
    assert len(payload["traceEvents"]) == 2
    for e in payload["traceEvents"]:
        assert set(e) >= {"name", "ph", "ts", "pid", "tid", "args", "dur"}
        assert e["ph"] == "X"
        assert e["ts"] >= 0.0
        assert e["dur"] >= 0.0


def test_logical_tids_not_os_idents():
    import threading

    tr = Tracer(clock=SimClock())
    with tr.span("main"):
        pass

    def worker():
        with tr.span("bg"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["main"]["tid"] == 0  # first-span order, not get_ident()
    assert by_name["bg"]["tid"] == 1


def test_null_tracer_zero_overhead():
    s1 = NULL_TRACER.span("a", x=1)
    s2 = NULL_TRACER.span("b")
    assert s1 is s2  # one shared no-op object, no per-span allocation
    with s1:
        pass
    assert NULL_TRACER.events == ()
    assert NULL_TRACER.chrome_trace() == {"traceEvents": [],
                                          "displayTimeUnit": "ms"}
    with pytest.raises(ValueError):
        NULL_TRACER.save("/tmp/never.json")


# ---------------------------------------------------------------------------
# timing harness
# ---------------------------------------------------------------------------

def test_time_callable_outlier_rejection():
    sleeps = iter([0.0, 0.002, 0.002, 0.080, 0.002, 0.002])  # warmup + 5

    def f():
        time.sleep(next(sleeps))
        return 0

    t = time_callable(f, iters=5, warmup=1, outlier_tol=2.0)
    assert t.rejected >= 1  # the 80ms spike is dropped ...
    assert t.median_us < 40_000.0  # ... and cannot drag the median
    assert len(t.samples_us) == 5  # raw samples are all kept for inspection


def test_time_callable_no_rejection_by_default():
    t = time_callable(lambda: 0, iters=3, warmup=0)
    assert t.rejected == 0 and len(t.samples_us) == 3


# ---------------------------------------------------------------------------
# calibration DB
# ---------------------------------------------------------------------------

def _timing(index, kind, impl, measured, predicted, block_c=8):
    return LayerTiming(index=index, kind=kind, impl=impl, occupancy=0.5,
                       weight_density=1.0, batch=2, block_c=block_c,
                       measured_us=measured, spread=0.0,
                       predicted_us=predicted, flops=1e6, bytes=1e4)


def test_calibration_fit_and_lookup():
    report = ProfileReport(
        graph_name="g", device_kind="testdev", batch=2, block_c=8,
        timings=(
            _timing(0, "conv", "dense", measured=100.0, predicted=10.0),
            _timing(1, "conv", "dense", measured=200.0, predicted=20.0),
            _timing(0, "conv", "ecr_pallas", measured=1000.0, predicted=10.0),
        ))
    db = CalibrationDB.from_report(report)
    # dense: ratio 0.1 on both layers -> scale 0.1
    c = db.lookup("conv", "dense", 8, device="testdev")
    assert c.peak_flops == pytest.approx(CPU_TEST_PRIOR.peak_flops * 0.1)
    assert c.hbm_bw == pytest.approx(CPU_TEST_PRIOR.hbm_bw * 0.1)
    # scaled constants predict the measured time for the fitted rows
    t = report.timings[0]
    assert c.time_us(t.flops, t.bytes) == pytest.approx(
        CPU_TEST_PRIOR.time_us(t.flops, t.bytes) / 0.1)
    assert db.covers("conv", "ecr_pallas", 8, device="testdev")
    assert not db.covers("conv", "bsr", 8, device="testdev")
    # block_c fallback: an explicit geometry falls back to the bc=0 entry
    db.put("conv", "bsr", 0, CalibEntry(1e12, 1e9, 0.5, 1, 0.0),
           device="testdev")
    assert db.covers("conv", "bsr", 16, device="testdev")
    # device isolation: another device's fit is never consulted
    assert not db.covers("conv", "dense", 8, device="elsewhere")


def test_calibration_save_load_roundtrip(tmp_path):
    db = CalibrationDB(device="testdev")
    db.put("conv", "dense", 8, CalibEntry(1e12, 2e9, 0.25, 3, 0.1),
           device="testdev")
    path = db.save(str(tmp_path / "calib.json"))
    back = CalibrationDB.load(path)
    assert back.device == "testdev"
    assert back.entries == db.entries
    with pytest.raises(ValueError):  # schema guard
        (tmp_path / "bad.json").write_text('{"schema": "other"}')
        CalibrationDB.load(str(tmp_path / "bad.json"))


def test_empty_db_is_falsy_and_defaults():
    db = CalibrationDB(device="testdev")
    assert not db and len(db) == 0
    assert db.constants_for("conv", "dense", 8) is CPU_TEST_PRIOR


def test_report_agreement_and_recalibration():
    # model says ecr is faster; the clock says dense is: top1 = 0 before
    # calibration, 1 after (the fitted per-impl scales reorder the pair)
    report = ProfileReport(
        graph_name="g", device_kind="testdev", batch=2, block_c=8,
        timings=(
            _timing(0, "conv", "dense", measured=100.0, predicted=20.0),
            _timing(0, "conv", "ecr_pallas", measured=400.0, predicted=10.0),
        ))
    assert report.agreement()["top1"] == 0.0
    db = CalibrationDB.from_report(report)
    # recalibrated() needs the units to re-predict -> exercise the scales
    # directly: predicted/scale reproduces the measured ordering
    dense, ecr = report.timings
    s_dense = db.entries[("testdev", "conv", "dense", (8, 0, 0, 0, 0))].scale
    s_ecr = db.entries[("testdev", "conv", "ecr_pallas", (8, 0, 0, 0, 0))].scale
    assert dense.predicted_us / s_dense < ecr.predicted_us / s_ecr


# ---------------------------------------------------------------------------
# planner contract
# ---------------------------------------------------------------------------

def test_empty_db_plans_bit_identically(graph, params, calib):
    base = plan_network(params, calib, graph, occ_threshold=0.75, block_c=8)
    empty = plan_network(params, calib, graph, occ_threshold=0.75, block_c=8,
                         calibration=CalibrationDB())
    assert plan_key(2, empty) == plan_key(2, base)


def test_calibration_shift_flips_impl_choice(graph, params, calib):
    base = plan_network(params, calib, graph, occ_threshold=0.75, block_c=8)
    n_sparse = base.counts()["sparse"]
    assert n_sparse >= 1  # the premise: default constants pick sparse layers
    # a DB fitted on THIS device saying the sparse kernels run at 1e-6 of
    # the roofline while dense runs at it: the occupancy-rule re-check must
    # flip those layers to dense
    dev = device_kind()
    db = CalibrationDB(device=dev)
    slow = CalibEntry(CPU_TEST_PRIOR.peak_flops * 1e-6,
                      CPU_TEST_PRIOR.hbm_bw * 1e-6, 1e-6, 2, 0.0)
    fast = CalibEntry(CPU_TEST_PRIOR.peak_flops,
                      CPU_TEST_PRIOR.hbm_bw, 1.0, 2, 0.0)
    for kind, impl in (("conv", "ecr_pallas"), ("conv_pool", "pecr_pallas"),
                       ("conv_pool", "ecr_pallas")):
        db.put(kind, impl, 8, slow, device=dev)
    db.put("conv", "dense", 8, fast, device=dev)
    flipped = plan_network(params, calib, graph, occ_threshold=0.75,
                           block_c=8, calibration=db)
    assert flipped.counts()["sparse"] < n_sparse
    assert plan_key(2, flipped) != plan_key(2, base)


# ---------------------------------------------------------------------------
# profile_plan + engine integration (one real end-to-end pass)
# ---------------------------------------------------------------------------

def test_profile_plan_rows_and_fit(graph, params, calib):
    plan = plan_network(params, calib, graph, occ_threshold=0.75, block_c=8)
    tr = Tracer(clock=SimClock())
    report = profile_plan(plan, params, calib, iters=1, warmup=1, tracer=tr)
    impls = {t.impl for t in report.timings}
    assert {"dense", "ecr_pallas"} <= impls  # sparse families resolved
    assert all(t.measured_us > 0 and t.predicted_us > 0
               for t in report.timings)
    assert report.batch == 2 and report.block_c == 8
    # trace: one profile span wrapping one profile_layer span per row
    names = [e["name"] for e in tr.events]
    assert names.count("profile_layer") == len(report.timings)
    assert names[-1] == "profile"  # the wrapper exits last
    db = CalibrationDB.from_report(report)
    assert db  # every profiled (kind, impl) fitted
    recal = report.recalibrated(db)
    assert recal.agreement()["top1"] >= report.agreement()["top1"]


def test_engine_traces_and_telemetry(graph, params, calib):
    clock = SimClock()
    tr = Tracer(clock=clock)
    engine = Engine(params, graph=graph, calib=calib, occ_threshold=0.75,
                    block_c=8, max_batch=4, deadline_s=0.005, clock=clock,
                    mesh=None, sim_service_s=0.003, tracer=tr)
    imgs = [calib[i % 2] for i in range(6)]
    replay_stream(engine, imgs, rate_rps=200.0)
    names = [e["name"] for e in tr.events]
    assert "serve.plan" in names and "serve.compile" in names
    n_exec = names.count("serve.batch")
    assert n_exec == engine.stats()["batches"] >= 1
    assert names.count("serve.submit") == names.count("serve.put") == 6
    # sim_service_s model: the batch span's duration IS the charged time
    execs = [e for e in tr.events if e["name"] == "serve.batch"]
    assert all(e["dur"] == pytest.approx(3000.0) for e in execs)
    # telemetry carries the profile digest once profile() has run
    assert engine.stats()["telemetry"]["profile"] is None
    report = engine.profile(iters=1, warmup=1)  # uses the last real batch
    digest = engine.stats()["telemetry"]["profile"]
    assert digest["graph"] == graph.name
    assert digest["agreement"]["layers"] == len(report.layers())
    assert len(digest["rows"]) == len(report.timings)


def test_engine_default_tracer_is_null(graph, params, calib):
    """The default records nothing of its own: its spans go to the
    profiler, which keeps them only while a trace runs."""
    engine = Engine(params, graph=graph, calib=calib, occ_threshold=0.0,
                    block_c=8, mesh=None)
    assert engine.tracer is PROFILER_TRACER
    assert isinstance(engine.tracer.span("serve.x", a=1),
                      jax.profiler.TraceAnnotation)
    assert jnp.asarray(engine.serve([calib[0]])).shape == (1, TINY.n_classes)
