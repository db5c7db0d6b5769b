"""From a profiler trace to device busy time, conv-op time and a breakdown.

`read_xspace` decodes the `.xplane.pb` that `jax.profiler` writes (the
XSpace protobuf, read field by field here: `jax.profiler.ProfileData` does
not expose the per-op metadata the classification needs) into plain event
dicts; `reduce` turns them into numbers. Only events inside the harness's
`WINDOW_SPAN` host annotation count.

A device op is one event on the "XLA Ops" line of a `/device:TPU:<n>`
plane. The trace gives each op its HLO text, an `hlo_category` and a
`tf_op`, the JAX op path it came from. A served CNN step is its conv units
(conv, ReLU, pool) and its dense head, so an op belongs to a conv unit
unless
- its `tf_op` ends in `dot_general`: the head's matmuls (XLA's convolution
  fusions from `dot_general`; its ReLUs fuse into them), or
- its category ends in "-start" or "-done": the markers of an asynchronous
  copy, whose time overlaps the ops that run meanwhile.
Everything else counts, wherever an implementation draws its op
boundaries: XLA's convolutions, the Mosaic ECR/PECR kernels and, around
them, the layout copies, pads, gathers, schedule sorts and occupancy
reductions that serve them.

A collective op is one whose `hlo_category`, or HLO op name, starts with
the opcode of an exchange between chips (`COLLECTIVES`). On a v5e 2x2 the
data-parallel bucket program has one: XLA combines the occupancy `psum`s
of a batch into one op of category "all-reduce", named `%all-reduce`, with
`tf_op` `jit(run)/shard_map/psum:`, about 8 us a batch on each chip. It
also counts as conv-unit time by the rule above.

Where a chip's trace buffer overflows, the profiler drops that chip's
events and marks the stretch with a "Trace Buffers Dropped" event on the
plane's "XLA TraceMe" line (on a v5e 2x2, chip 0 about 1 s into a 2-s
stretch, its record then empty to the end). Such a chip's record is left
out where another chip of the cell has a whole one: busy time is averaged
over the whole records, and sums over the chips are scaled from them to
the cell's chips, which run the same program on equal shares of a batch.
"""
from __future__ import annotations

import glob
import os
import re
import struct

WINDOW_SPAN = "chipbench.window"
# the host spans the harness puts around its calls into the system
HOST_SPANS = ("client.send", "engine.submit", "engine.poll", "client.wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"
TOP = 10
# HLO opcodes of the ops that exchange data between chips
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
# protobuf wire format, as far as XSpace needs it
# ---------------------------------------------------------------------------


def _varint(b: bytes, i: int) -> tuple:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of each field of a message; a length-delimited
    value is the raw bytes, a fixed64 one the 8 bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wire == 1:
            v = b[i:i + 8]
            i += 8
        elif wire == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _stat(b: bytes, stat_names: dict) -> tuple:
    """XStat -> (name, value); a ref_value names another stat's metadata."""
    sid = val = None
    for f, v in _fields(b):
        if f == 1:
            sid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = v
        elif f == 5:
            val = v.decode(errors="replace")
        elif f == 7:
            val = stat_names.get(v)
    return stat_names.get(sid), val


def _map_entry(b: bytes) -> tuple:
    key = val = None
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def read_xspace(data: bytes) -> list:
    """Device ops, the harness's host spans and the devices' dropped-buffer
    markers of one XSpace: dicts with plane, name, start_ns, dur_ns, and for
    device ops category and tf_op."""
    events = []
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, lines, meta_raw, stat_names = "", [], {}, {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = pv.decode()
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:
                k, v = _map_entry(pv)
                meta_raw[k] = v
            elif pf == 5:
                k, v = _map_entry(pv)
                stat_names[k] = next((sv.decode() for sf, sv in _fields(v)
                                      if sf == 2), None)
        device = bool(DEVICE_PLANE.match(name))
        meta = {}
        for k, raw in meta_raw.items():
            mname, stats = "", {}
            for mf, mv in _fields(raw):
                if mf == 2:
                    mname = mv.decode(errors="replace")
                elif mf == 5 and device:
                    sk, sv = _stat(mv, stat_names)
                    stats[sk] = sv
            meta[k] = (mname, stats)
        for raw_line in lines:
            lname, t0_ns, evs = "", 0, []
            for lf, lv in _fields(raw_line):
                if lf == 2:
                    lname = lv.decode()
                elif lf == 3:
                    t0_ns = lv
                elif lf == 4:
                    evs.append(lv)
            for raw_ev in evs:
                mid = off = dur = 0
                for ef, ev in _fields(raw_ev):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        off = ev
                    elif ef == 3:
                        dur = ev
                mname, stats = meta.get(mid, ("", {}))
                if device and lname != OPS_LINE and mname != DROPPED:
                    continue
                if not device and mname not in HOST_SPANS + (WINDOW_SPAN,):
                    continue
                e = {"plane": name, "name": mname.split(" = ")[0],
                     "start_ns": t0_ns + off / 1000.0, "dur_ns": dur / 1000.0}
                if device and lname == OPS_LINE:
                    e["category"] = str(stats.get("hlo_category", "unknown"))
                    e["tf_op"] = str(stats.get("tf_op", ""))
                events.append(e)
    return events


def load_events(trace_dir: str) -> list:
    """The events of the one trace `jax.profiler` wrote under `trace_dir`."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    with open(paths[0], "rb") as f:
        return read_xspace(f.read())


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def in_conv_unit(ev: dict) -> bool:
    """A device op that belongs to a conv unit (module docstring)."""
    if ev["category"].endswith(("-start", "-done")):
        return False
    return not ev["tf_op"].rstrip(":").endswith("dot_general")


def is_collective(ev: dict) -> bool:
    """A device op that exchanges data between chips (module docstring)."""
    op = ev["name"].lstrip("%")
    return ev["category"].startswith(COLLECTIVES) or op.startswith(COLLECTIVES)


def whole_planes(events: list, planes: list, w0: float, w1: float) -> list:
    """The device planes among `planes` whose record dropped no events
    inside the window [w0, w1] (module docstring)."""
    cut = {e["plane"] for e in events
           if e["name"] == DROPPED and "category" not in e
           and e["start_ns"] < w1 and e["start_ns"] + e["dur_ns"] > w0}
    return [p for p in planes if p not in cut]


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(events: list, n_devices: int) -> dict:
    """busy_s: seconds in which some op ran, averaged over the devices;
    window_s: the window's length; conv_s: device seconds in the ops of conv
    units, summed over the devices; collective_s: seconds in which some
    collective op ran, summed over the devices; devices: the cell's chips in
    the trace; dropped: how many of them lost events inside the window
    (`whole_planes`); breakdown: the ops that took most time and the longest
    idle gaps, each gap named by the innermost host span it fell in."""
    win = [e for e in events if e["name"] == WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    w0 = win[0]["start_ns"]
    w1 = w0 + win[0]["dur_ns"]

    def clip(e):
        return max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)

    ops = [e for e in events if "category" in e]
    planes = sorted({e["plane"] for e in ops},
                    key=lambda p: int(p.rsplit(":", 1)[1]))[:n_devices]
    whole = whole_planes(events, planes, w0, w1)
    used = whole or planes  # where no record is whole, all of them
    scale = len(planes) / len(used) if used else 1.0
    busy_ns = collective_ns = 0.0
    gaps = []
    for plane in used:
        merged = _union([ab for ab in (clip(e) for e in ops
                                       if e["plane"] == plane)
                         if ab[1] > ab[0]])
        busy_ns += sum(b - a for a, b in merged)
        collective_ns += sum(b - a for a, b in _union(
            [ab for ab in (clip(e) for e in ops
                           if e["plane"] == plane and is_collective(e))
             if ab[1] > ab[0]]))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    op_ns: dict = {}
    conv_ns = 0.0
    for e in ops:
        if e["plane"] not in used:
            continue
        a, b = clip(e)
        if b <= a:
            continue
        key = f"{e['name']} [{e['category']}] {e['tf_op']}".rstrip()
        op_ns[key] = op_ns.get(key, 0.0) + (b - a) * scale
        if in_conv_unit(e):
            conv_ns += (b - a) * scale
    host = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
            for e in events if "category" not in e and e["name"] in HOST_SPANS]

    def host_span(a, b):
        mid = (a + b) / 2
        inside = [h for h in host if h[0] <= mid <= h[1]]
        if not inside:
            return "host: outside the harness's spans"
        return min(inside, key=lambda h: h[1] - h[0])[2]

    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda ab: ab[0] - ab[1])[:TOP]
    return {
        "busy_s": busy_ns / len(used) * 1e-9 if used else 0.0,
        "window_s": (w1 - w0) * 1e-9,
        "conv_s": conv_ns * 1e-9,
        "collective_s": collective_ns * scale * 1e-9,
        "devices": len(planes),
        "dropped": len(planes) - len(whole),
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in top_ops],
            "idle_gaps": [[host_span(a, b), (b - a) * 1e-9] for a, b in top_gaps],
        },
    }
