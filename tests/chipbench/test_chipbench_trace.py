"""The reduction from a profiler trace to busy time, conv time and a
breakdown: on hand-made events, on a hand-encoded XSpace, and on a short
trace recorded on a TPU v5e."""
from __future__ import annotations

import gzip
import struct
from pathlib import Path

import pytest

from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def op(plane, start, dur, category="loop fusion", tf_op="jit(run)/add:",
       name="%fusion.1"):
    return {"plane": plane, "name": name, "start_ns": start, "dur_ns": dur,
            "category": category, "tf_op": tf_op}


def span(name, start, dur):
    return {"plane": "/host:CPU", "name": name, "start_ns": start,
            "dur_ns": dur}


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    d0 = "/device:TPU:0"
    events = [
        span(tr.WINDOW_SPAN, 100, 1000),
        op(d0, 50, 100),           # half inside the window
        op(d0, 200, 100), op(d0, 250, 100),  # overlapping: 150 busy
        op(d0, 900, 400),          # runs past the window's end: 200 busy
        span("engine.submit", 400, 400),
        span("engine.poll", 380, 100),
    ]
    r = tr.reduce(events, n_devices=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((50 + 150 + 200) * 1e-9)
    gaps = r["breakdown"]["idle_gaps"]
    # the longest gap (350..900) sits in engine.submit; 150..200 in none
    assert gaps[0] == ["engine.submit", pytest.approx(550e-9)]
    assert ["host: outside the harness's spans", pytest.approx(50e-9)] in gaps


def test_busy_is_averaged_over_the_cells_chips_only():
    events = [span(tr.WINDOW_SPAN, 0, 1000)]
    for i, dur in enumerate((1000, 500, 0, 250)):
        if dur:
            events.append(op(f"/device:TPU:{i}", 0, dur))
    assert tr.reduce(events, 2)["busy_s"] == pytest.approx(750e-9)
    assert tr.reduce(events, 4)["busy_s"] == pytest.approx(1750e-9 / 3)


@pytest.mark.parametrize("category,tf_op,conv", [
    ("convolution fusion", "jit(run)/conv_general_dilated:", True),
    ("convolution fusion", "jit(run)/dot_general:", False),  # the dense head
    ("custom-call",
     "jit(run)/jit(ecr_conv)/cond/branch_0_fun/pallas_call:", True),
    ("custom-call", "", True),  # an XLA layout custom call
    ("output fusion", "jit(run)/reduce_window_max:", True),  # a pool
    # the ECR path's own helpers, around its kernel
    ("loop fusion", "jit(run)/jit(ecr_conv)/reduce_or:", True),
    ("data formatting", "jit(run)/jit(fused_conv_pool)/reshape:", True),
    ("pad", "jit(run)/jit(_pad)/pad:", True),
    ("copy-done", "", False),  # an asynchronous copy's marker
    ("async-start", "jit(run)/gather:", False),
])
def test_conv_unit_ops_are_classified_by_the_traces_op_path(category, tf_op,
                                                            conv):
    assert tr.in_conv_unit(op("/device:TPU:0", 0, 1, category, tf_op)) is conv


def test_conv_time_and_top_ops():
    d0 = "/device:TPU:0"
    events = [span(tr.WINDOW_SPAN, 0, 100),
              op(d0, 0, 30, "custom-call", "a/pallas_call:", "%k.1"),
              op(d0, 30, 20, "convolution fusion", "conv_general_dilated:",
                 "%c.2"),
              op(d0, 50, 40, "convolution fusion", "dot_general:", "%d.3")]
    r = tr.reduce(events, 1)
    assert r["conv_s"] == pytest.approx(50e-9)
    names = [k for k, _ in r["breakdown"]["device_ops"]]
    assert names[0].startswith("%d.3 [convolution fusion]")
    assert len(names) == 3


# -- a hand-encoded XSpace ----------------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xstat(sid, text):
    return _field(1, sid) + _field(5, text.encode())


def _plane(name, metas, stat_names, lines):
    out = _field(2, name.encode())
    for mid, (mname, stats) in metas.items():
        md = _field(1, mid) + _field(2, mname.encode())
        for sid, text in stats:
            md += _field(5, _xstat(sid, text))
        out += _field(4, _field(1, mid) + _field(2, md))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                + _field(2, sname.encode())))
    for lname, t0, evs in lines:
        line = _field(2, lname.encode()) + _field(3, t0)
        for mid, off_ps, dur_ps in evs:
            line += _field(4, _field(1, mid) + _field(2, off_ps)
                           + _field(3, dur_ps))
        out += _field(3, line)
    return out


def test_read_xspace_decodes_ops_and_host_spans():
    dev = _plane("/device:TPU:0",
                 {1: ("%conv.1 = f32[8] convolution(...)", [(7, "convolution fusion"),
                                                           (8, "jit(run)/conv_general_dilated:")])},
                 {7: "hlo_category", 8: "tf_op"},
                 [("XLA Ops", 1000, [(1, 5000, 2000)]),
                  ("XLA Modules", 1000, [(1, 0, 9000)])])
    host = _plane("/host:CPU", {1: (tr.WINDOW_SPAN, []), 2: ("engine.poll", []),
                                3: ("something else", [])}, {},
                  [("main", 900, [(1, 0, 200000), (2, 1000, 3000),
                                  (3, 0, 100)])])
    events = tr.read_xspace(_field(1, dev) + _field(1, host))
    ops = [e for e in events if "category" in e]
    assert ops == [{"plane": "/device:TPU:0", "name": "%conv.1",
                    "start_ns": 1005.0, "dur_ns": 2.0,
                    "category": "convolution fusion",
                    "tf_op": "jit(run)/conv_general_dilated:"}]
    assert sorted(e["name"] for e in events if "category" not in e) == \
        ["chipbench.window", "engine.poll"]
    r = tr.reduce(events, 1)
    assert r["conv_s"] == pytest.approx(2e-9)


def test_read_xspace_keeps_the_dropped_buffer_markers():
    dev = _plane("/device:TPU:0",
                 {1: ("%fusion.1", [(7, "loop fusion"), (8, "jit(run)/add:")]),
                  2: (tr.DROPPED, []), 3: ("barrier-cores", [])},
                 {7: "hlo_category", 8: "tf_op"},
                 [("XLA Ops", 1000, [(1, 0, 2000)]),
                  ("XLA TraceMe", 1000, [(3, 0, 500), (2, 4000, 9000)])])
    events = tr.read_xspace(_field(1, dev))
    assert [e for e in events if "category" not in e] == [
        {"plane": "/device:TPU:0", "name": tr.DROPPED, "start_ns": 1004.0,
         "dur_ns": 9.0}]
    assert [e["name"] for e in events if "category" in e] == ["%fusion.1"]


# -- a trace recorded on the chip ----------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "vgg19_96.closed32.xplane.pb.gz"
    assert path.stat().st_size < 1 << 20
    return tr.read_xspace(gzip.decompress(path.read_bytes()))


def test_recorded_trace_reduces(recorded):
    ops = [e for e in recorded if "category" in e]
    assert {e["plane"] for e in ops} == {"/device:TPU:0"}
    r = tr.reduce(recorded, 1)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["conv_s"] < r["busy_s"]
    assert 0 < len(r["breakdown"]["device_ops"]) <= tr.TOP
    assert 0 < len(r["breakdown"]["idle_gaps"]) <= tr.TOP
    labels = {g for g, _ in r["breakdown"]["idle_gaps"]}
    assert labels <= set(tr.HOST_SPANS) | {"host: outside the harness's spans"}
    # VGG-19's sparse layers run as Pallas kernels, its early ones as XLA
    # convs, its head as dot_general convolution fusions
    conv = [e for e in ops if tr.in_conv_unit(e)]
    assert any("pallas_call" in e["tf_op"] for e in conv)
    assert any(e["category"] == "convolution fusion" for e in conv)
    assert any("jit(ecr_conv)/reduce_or" in e["tf_op"] for e in conv)
    assert any(e["tf_op"].endswith("dot_general:") and not tr.in_conv_unit(e)
               for e in ops)


def test_recorded_trace_numbers_are_stable(recorded):
    """The reduction of the committed trace, as computed when it was
    recorded: a change of the reduction shows here."""
    r = tr.reduce(recorded, 1)
    want = _expected()
    for k in ("busy_s", "window_s", "conv_s"):
        assert r[k] == pytest.approx(want[k], rel=1e-9)


def _expected():
    import json

    return json.loads((DATA / "vgg19_96.closed32.reduced.json").read_text())


def test_xspace_fixed64_and_refs():
    # a double stat and a ref stat decode (the wire types the reader skips
    # or resolves), inside one device op's metadata
    stats = _field(1, 9) + _varint(2 << 3 | 1) + struct.pack("<d", 1.5)
    ref = _field(1, 7) + _field(7, 10)
    md = (_field(1, 1) + _field(2, b"%x.1 = f32[] add()")
          + _field(5, stats) + _field(5, ref))
    plane = (_field(2, b"/device:TPU:3")
             + _field(4, _field(1, 1) + _field(2, md))
             + b"".join(_field(5, _field(1, k) + _field(2, _field(1, k)
                                                      + _field(2, v)))
                        for k, v in ((7, b"hlo_category"), (9, b"flops"),
                                     (10, b"loop fusion")))
             + _field(3, _field(2, b"XLA Ops") + _field(3, 0)
                      + _field(4, _field(1, 1) + _field(2, 0)
                               + _field(3, 1000))))
    (ev,) = tr.read_xspace(_field(1, plane))
    assert ev["category"] == "loop fusion" and ev["plane"] == "/device:TPU:3"


def test_collective_time_is_the_union_of_collective_ops_per_chip():
    """`collective_s`: per chip, the seconds in which some collective op ran
    inside the window, summed over the cell's chips; no other key moves."""
    events = [span(tr.WINDOW_SPAN, 0, 1000)]
    for i in range(4):
        d = f"/device:TPU:{i}"
        events += [
            op(d, 0, 600, "custom-call", "jit(run)/conv3/ecr_conv/pallas_call:"),
            op(d, 600, 50, "all-reduce", "jit(run)/psum:", "%all-reduce.1"),
            op(d, 640, 30, "all-reduce", "jit(run)/psum:", "%all-reduce.2"),
            op(d, 700, 200, "convolution fusion", "jit(run)/head/dot_general:"),
        ]
    events.append(op("/device:TPU:0", 990, 40, "all-reduce",
                     "jit(run)/psum:", "%all-reduce.3"))  # half inside
    r = tr.reduce(events, n_devices=4)
    assert r["collective_s"] == pytest.approx((4 * 70 + 10) * 1e-9)
    assert r["busy_s"] == pytest.approx((4 * 870 + 10) / 4 * 1e-9)
    assert r["conv_s"] == pytest.approx((4 * (600 + 50 + 30) + 10) * 1e-9)
    assert tr.reduce(events[:5], 1)["collective_s"] == pytest.approx(70e-9)
    without = [e for e in events if "category" not in e
               or not tr.is_collective(e)]
    assert tr.reduce(without, 4)["collective_s"] == 0


@pytest.mark.parametrize("category,name,collective", [
    ("all-reduce", "%all-reduce.7", True),
    ("loop fusion", "%all-reduce-start.2", True),
    ("async-done", "%all-gather-done", True),
    ("collective-permute", "%fusion.3", True),
    ("loop fusion", "%fusion.12", False),
    ("copy-start", "%copy-start.1", False),
])
def test_collective_ops_are_named_by_their_hlo_opcode(category, name,
                                                      collective):
    ev = op("/device:TPU:0", 0, 1, category, "jit(run)/x:", name)
    assert tr.is_collective(ev) is collective


def test_a_chip_whose_trace_dropped_events_stands_aside():
    """Chip 0's record ends in a dropped-buffer marker: busy time comes from
    the whole records, and sums over the chips are scaled from them."""
    events = [span(tr.WINDOW_SPAN, 0, 1000),
              op("/device:TPU:0", 0, 300),
              {"plane": "/device:TPU:0", "name": tr.DROPPED, "start_ns": 300,
               "dur_ns": 700},
              op("/device:TPU:1", 0, 600),
              op("/device:TPU:1", 600, 10, "all-reduce", "jit(run)/psum:",
                 "%all-reduce")]
    r = tr.reduce(events, 2)
    assert r["dropped"] == 1 and r["devices"] == 2
    assert r["busy_s"] == pytest.approx(610e-9)
    assert r["conv_s"] == pytest.approx(2 * 610e-9)
    assert r["collective_s"] == pytest.approx(2 * 10e-9)
    assert all(g[1] == pytest.approx(390e-9) for g in r["breakdown"]["idle_gaps"])
    # with no whole record left, the cut one is all there is
    alone = tr.reduce(events[:3], 1)
    assert alone["dropped"] == 1 and alone["busy_s"] == pytest.approx(300e-9)
    # a marker outside the window cuts nothing
    late = dict(events[2], start_ns=2000)
    assert tr.reduce(events[:2] + [late] + events[3:], 2)["dropped"] == 0
