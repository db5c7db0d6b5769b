"""The serving program's spans and per-layer scopes in a profiler trace: a
tiny engine served under `jax.profiler` on the CPU, the reduction on
hand-made events, one number each, and a short trace recorded on a TPU v5e
with the spans in it."""
from __future__ import annotations

import glob
import gzip
import json
import os
from pathlib import Path

import pytest

from chipbench import spans as sp
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def op(plane, start, dur, tf_op="jit(run)/add:", category="loop fusion"):
    return {"plane": plane, "name": "%f.1", "start_ns": start, "dur_ns": dur,
            "category": category, "tf_op": tf_op}


def span(name, start, dur, line="main"):
    e = {"plane": "/host:CPU", "name": name, "start_ns": start, "dur_ns": dur}
    if name.startswith(sp.PREFIX):
        e["line"] = line
    return e


# -- the engine under the profiler, on the CPU ----------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Six requests through a tiny engine whose bucket 4 is warm: one full
    batch from the cache and a tail of two that compiles bucket 2."""
    import jax

    from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
    from repro.core import dead_channel_band
    from repro.graph import init_graph
    from repro.models.cnn import shift_dead_channels
    from repro.serving import Engine

    graph = vgg19_graph(CNNConfig(name="spans", in_channels=16, img_size=12,
                                  plan=((8, 1), (16, 1)), n_classes=4))
    params = shift_dead_channels(init_graph(jax.random.PRNGKey(0), graph))
    c, h, w = graph.in_shape
    calib = dead_channel_band(
        jax.random.uniform(jax.random.PRNGKey(1), (2, c, h, w)), 0.5)
    engine = Engine(params, graph=graph, calib=calib, occ_threshold=0.75,
                    block_c=8, max_batch=4, mesh=None)
    engine.warmup([4])
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for i in range(6):
            engine.submit(calib[i % 2])
        engine.poll()
        engine.drain()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        data = f.read()
    return sp.read_spans(data), tr.read_xspace(data)


def _children(events, parent):
    a, b = parent["start_ns"], parent["start_ns"] + parent["dur_ns"]
    return sorted((e for e in events if e is not parent
                   and a <= e["start_ns"] and e["start_ns"] + e["dur_ns"] <= b),
                  key=lambda e: e["start_ns"])


def test_engine_spans_reach_the_profilers_trace(served):
    events, _ = served
    assert len({e["line"] for e in events}) == 1  # the serving thread
    submits = [e for e in events if e["name"] == "serve.submit"]
    assert len(submits) == 6
    for s in submits:
        assert [e["name"] for e in _children(events, s)] == ["serve.put"]
    batches = [e for e in events if e["name"] == "serve.batch"]
    assert len(batches) == 2
    steps = ["serve.stack", "serve.lookup", "serve.dispatch", "serve.wait",
             "serve.fetch", "serve.observe"]
    hit, miss = ([e["name"] for e in _children(events, b)] for b in batches)
    assert hit == steps  # bucket 4 was warm
    # the tail's bucket 2 compiles, inside its lookup
    assert miss == steps[:2] + ["serve.compile"] + steps[2:]
    assert [e["name"] for e in events].count("serve.compile") == 1


def test_reduction_of_a_cpu_trace_names_the_window(served):
    spans, ops = served
    events = spans + ops
    # the CPU has no device plane: every span is host time, nothing idles
    r = sp.reduce_spans(events, 1)
    assert r["idle_by_span"] == {} and r["layer_s"] == {}
    names = [n for n, _, _ in r["program_spans"]]
    assert names.count("serve.submit") == 6 and names.count("serve.batch") == 2
    assert all(d >= 0 for _, _, d in r["program_spans"])
    assert sp.submit_us({**r, "window_s": 1.0}) > 0
    assert sp.batch_host_ms({**r, "window_s": 1.0}) > 0


# -- the reduction on hand-made events -------------------------------------------


def test_an_idle_gap_half_inside_a_span_splits_exactly():
    events = [span(tr.WINDOW_SPAN, 0, 1000),
              op(D0, 0, 400), op(D0, 600, 400),          # idle 400..600
              span("engine.submit", 300, 400),
              span("serve.submit", 500, 200),            # half the gap
              span("serve.put", 550, 50)]                # inside it
    r = sp.reduce_spans(events, 1)
    assert r["idle_by_span"] == pytest.approx(
        {"": 100e-9, "serve.submit": 50e-9, "serve.put": 50e-9})
    # the gap's midpoint (500) opens serve.submit: the innermost span there
    assert r["idle_gaps"] == [["serve.submit", pytest.approx(200e-9)]]
    assert [n for n, _, _ in r["program_spans"]] == ["serve.submit", "serve.put"]


def test_a_gap_under_no_program_span_keeps_the_harness_name():
    events = [span(tr.WINDOW_SPAN, 0, 1000), op(D0, 0, 100),
              span("engine.poll", 100, 900), span("serve.batch", 900, 50)]
    r = sp.reduce_spans(events, 1)
    assert r["idle_gaps"] == [["engine.poll", pytest.approx(900e-9)]]
    assert r["idle_by_span"] == pytest.approx({"": 850e-9, "serve.batch": 50e-9})


def test_idle_by_span_sums_to_window_less_busy_per_chip():
    events = [span(tr.WINDOW_SPAN, 100, 2000),
              op(D0, 0, 300), op(D0, 250, 100), op(D0, 900, 700),
              op(D1, 400, 200), op(D1, 1800, 900),
              op("/device:TPU:2", 0, 5000),  # not one of the cell's chips
              span("serve.batch", 50, 1000), span("serve.stack", 60, 100),
              span("serve.wait", 500, 400), span("serve.submit", 1200, 300),
              span("serve.put", 1250, 100),
              span("serve.replan", 1300, 500, line="other")]  # another thread
    base = tr.reduce(events, 2)
    r = sp.reduce_spans(events, 2)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-12)
    assert "serve.replan" not in r["idle_by_span"]
    for n in (1, 3):
        base, r = tr.reduce(events, n), sp.reduce_spans(events, n)
        assert sum(r["idle_by_span"].values()) == pytest.approx(
            base["window_s"] - base["busy_s"], rel=1e-12)


def test_layer_time_and_kernels_by_scope():
    pallas = "jit(run)/conv7/jit(ecr_conv)/cond/branch_0_fun/ecr_conv/pallas_call:"
    events = [span(tr.WINDOW_SPAN, 0, 1000),
              op(D0, 0, 100, pallas, "custom-call"),
              op(D0, 100, 50, "jit(run)/conv7/jit(ecr_conv)/reduce_or:"),
              op(D0, 150, 20, "jit(run)/conv7/occupancy/reduce_sum:"),
              op(D0, 170, 30, "jit(run)/conv12/jit(fused_conv_pool)/cond/"
                 "branch_0_fun/pecr_conv/pallas_call:", "custom-call"),
              op(D0, 200, 40, "jit(run)/conv1/conv_general_dilated:",
                 "convolution fusion"),
              op(D0, 240, 60, "jit(run)/head/dot_general:", "convolution fusion"),
              op(D0, 300, 10, "jit(run)/conv1/copy:", "copy-done"),
              op(D0, 310, 5, "params['conv'][8]:", "data formatting"),
              op(D0, 990, 50, "jit(run)/conv2/max:")]  # clipped at the end
    r = sp.reduce_spans(events, 1)
    assert r["layer_s"] == pytest.approx(
        {"conv1": 40e-9, "conv12": 30e-9, "conv2": 10e-9, "conv7": 170e-9})
    assert r["layer_kernels"] == {"conv12": ["pecr_conv"], "conv7": ["ecr_conv"]}
    assert sp.sparse_layers(r) == ["conv12", "conv7"]


# -- one number each ------------------------------------------------------------


def _reduced():
    """trace.reduce | reduce_spans of a 10-us stretch: two submits, one
    batch whose wait is 3 us of its 5."""
    events = [span(tr.WINDOW_SPAN, 0, 10_000),
              op(D0, 6_000, 3_000, "jit(run)/conv1/jit(ecr_conv)/cond/"
                 "branch_0_fun/ecr_conv/pallas_call:", "custom-call"),
              span("serve.submit", 0, 1_000), span("serve.put", 200, 500),
              span("serve.submit", 1_000, 3_000),
              span("serve.batch", 5_000, 5_000), span("serve.stack", 5_000, 500),
              span("serve.dispatch", 5_500, 500), span("serve.wait", 6_000, 3_000),
              span("serve.fetch", 9_000, 1_000)]
    return {**tr.reduce(events, 1), **sp.reduce_spans(events, 1)}


def _empty():
    """A program without spans: the device idles, nothing names it."""
    events = [span(tr.WINDOW_SPAN, 0, 10_000), op(D0, 6_000, 3_000)]
    return {**tr.reduce(events, 1), **sp.reduce_spans(events, 1)}


CFG = {"in_channels": 8, "image_size": 8, "layers": [
    {"op": "conv", "out": 8, "k": 3, "pad": 1}, {"op": "relu"},
    {"op": "flatten"}, {"op": "dense", "out": 2}]}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e10}


@pytest.mark.parametrize("fn,want", [
    (sp.submit_us, 2.0),                  # median of 1 and 3 us
    (sp.batch_host_ms, 2e-3),             # 5 us less the 3-us wait
    (sp.idle_submit_pct, 40.0),           # 0..4 us of the 10, all idle
    (sp.idle_executor_pct, 20.0),         # 5..6 and 9..10 us
    (lambda t: sp.sparse_conv_roofline(t, CFG, [2, 2], PEAKS),
     # conv1, 2 images a batch: bytes bind, 2 x f32 (2 x (512 + 512) + 576)
     100.0 * 2 * 4 * (2 * 1024 + 576) / 1e10 / 3e-6),
], ids=["submit_us", "batch_host_ms", "idle_submit_pct", "idle_executor_pct",
        "sparse_conv_roofline"])
def test_numbers_from_the_spans(fn, want):
    assert fn(_reduced()) == pytest.approx(want)


@pytest.mark.parametrize("fn", [
    sp.submit_us, sp.batch_host_ms, sp.idle_submit_pct, sp.idle_executor_pct,
    lambda t: sp.sparse_conv_roofline(t, CFG, [2, 2], PEAKS),
], ids=["submit_us", "batch_host_ms", "idle_submit_pct", "idle_executor_pct",
        "sparse_conv_roofline"])
def test_numbers_are_none_without_program_spans(fn):
    assert fn(_empty()) is None


def test_idle_shares_sum_with_the_rest_to_the_idle_share():
    t = _reduced()
    idle_pct = 100.0 * (1 - t["busy_s"] / t["window_s"])
    shares = {n: 100.0 * s / t["window_s"] for n, s in t["idle_by_span"].items()}
    assert sum(shares.values()) == pytest.approx(idle_pct)
    assert sp.idle_submit_pct(t) + sp.idle_executor_pct(t) == pytest.approx(
        idle_pct - shares.get("", 0.0) - shares.get("serve.wait", 0.0))


# -- traces recorded on the chip ------------------------------------------------


def _recorded(name):
    path = DATA / name
    assert path.stat().st_size < 1 << 20
    data = gzip.decompress(path.read_bytes())
    return tr.read_xspace(data) + sp.read_spans(data)


def test_the_first_recording_has_no_program_spans():
    """Recorded before the program had spans or scopes: every number is
    None, as on a program without them."""
    events = _recorded("vgg19_96.closed32.xplane.pb.gz")
    t = {**tr.reduce(events, 1), **sp.reduce_spans(events, 1)}
    assert t["program_spans"] == [] and t["layer_s"] == {}
    assert list(t["idle_by_span"]) == [""]
    for fn in (sp.submit_us, sp.batch_host_ms, sp.idle_submit_pct,
               sp.idle_executor_pct):
        assert fn(t) is None


@pytest.fixture(scope="module")
def recorded_spans():
    return _recorded("vgg19_96.closed32.spans.xplane.pb.gz")


def test_recorded_spans_reduce_as_recorded(recorded_spans):
    """The reduction of the committed trace with the program's spans, as
    computed when it was recorded: a change of the reduction shows here."""
    want = json.loads((DATA / "vgg19_96.closed32.spans.json").read_text())
    r = sp.reduce_spans(recorded_spans, 1)
    names = [n for n, _, _ in r["program_spans"]]
    assert {n: names.count(n) for n in set(names)} == want["span_counts"]
    assert r["idle_by_span"] == pytest.approx(want["idle_by_span"], rel=1e-9)
    assert r["layer_s"] == pytest.approx(want["layer_s"], rel=1e-9)
    assert r["layer_kernels"] == want["layer_kernels"]
    assert [g for g, _ in r["idle_gaps"]] == [g for g, _ in want["idle_gaps"]]


def test_recorded_spans_cover_the_conv_time(recorded_spans):
    base = tr.reduce(recorded_spans, 1)
    r = sp.reduce_spans(recorded_spans, 1)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    assert sum(r["layer_s"].values()) >= 0.95 * base["conv_s"]
    # the plan the harness printed: ECR on conv7, 9-11, 13-15, PECR on 12, 16
    assert r["layer_kernels"] == {
        **{f"conv{i}": ["ecr_conv"] for i in (7, 9, 10, 11, 13, 14, 15)},
        **{f"conv{i}": ["pecr_conv"] for i in (12, 16)}}
    assert any(g.startswith(sp.PREFIX) for g, _ in r["idle_gaps"])


def test_a_chip_whose_trace_dropped_events_stands_aside_here_too():
    """Idle by span and layer time leave out chip 0, whose record ends in a
    dropped-buffer marker, as `trace.reduce` does."""
    scope = "jit(run)/shard_map/conv9/jit(ecr_conv)/cond/branch_0_fun/ecr_conv/pallas_call:"
    events = [span(tr.WINDOW_SPAN, 0, 1000),
              op(D0, 0, 200, scope, "custom-call"),
              {"plane": D0, "name": tr.DROPPED, "start_ns": 200, "dur_ns": 800},
              op(D1, 0, 600, scope, "custom-call"),
              span("serve.wait", 0, 700), span("serve.fetch", 700, 300)]
    base = tr.reduce(events, 2)
    r = sp.reduce_spans(events, 2)
    assert r["idle_by_span"] == pytest.approx({"serve.wait": 100e-9,
                                               "serve.fetch": 300e-9})
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-12)
    assert r["layer_s"] == pytest.approx({"conv9": 2 * 600e-9})
    assert r["layer_kernels"] == {"conv9": ["ecr_conv"]}
