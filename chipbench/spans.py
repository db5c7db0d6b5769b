"""The serving program's own spans and per-layer scopes in a profiler trace.

The engine writes `serve.*` host spans into the profiler's trace (its
default tracer, `repro.obs.trace.PROFILER_TRACER`), on the clock of the
device's ops. The compiled bucket program puts each planned conv layer's
ops under a `conv<i>` named scope and each Pallas kernel under its `name=`,
so a device op's `tf_op` reads, for a sparse layer on a TPU,
`jit(run)/conv7/jit(ecr_conv)/cond/branch_0_fun/ecr_conv/pallas_call:`.

`read_spans` decodes the `serve.*` spans of an XSpace (`trace.read_xspace`
keeps only the harness's spans); `reduce_spans` reduces them, with the
device ops and the window of `trace.read_xspace`, to what the harness's
reduction cannot name:
- `program_spans`: [name, start (s from the window's start), duration (s)]
  of each `serve.*` span wholly inside the window;
- `idle_by_span`: device idle seconds, averaged over the cell's chips,
  under each innermost `serve.*` span of the serving thread (the thread that
  holds most `serve.*` spans), by exact interval intersection; idle time
  under no program span goes under "". The entries sum to window - busy;
- `layer_s`: device seconds, summed over the chips, of the ops whose
  `tf_op` has a `conv<i>` scope, per scope, with `trace.in_conv_unit`'s
  exclusions;
- `layer_kernels`: the Pallas kernels (the `tf_op` component before
  `pallas_call`) seen in each scope;
- `idle_gaps`: the longest idle gaps, each named by the innermost `serve.*`
  span its midpoint falls in, else by the harness's span.

The functions at the end compute one number each from the union of
`trace.reduce` and `reduce_spans`, and return None where the trace holds no
program span (a program without these spans).
"""
from __future__ import annotations

import bisect
import re
import statistics

from chipbench import cnn
from chipbench import trace as tr

PREFIX = "serve."
LAYER = re.compile(r"(?:^|/)(conv\d+)(?=[/:]|$)")
KERNEL = re.compile(r"([^/:]+)/pallas_call(?=[/:]|$)")
# the paper's kernels: ECR, and PECR (conv + ReLU + pool fused)
SPARSE_KERNELS = ("ecr_conv", "pecr_conv", "ecr_conv_int8")
SUBMIT_SPANS = ("serve.submit", "serve.put")
# serve.batch and its children but serve.wait (the device runs meanwhile);
# serve.compile and serve.replan have entries of their own
EXECUTOR_SPANS = ("serve.batch", "serve.stack", "serve.lookup",
                  "serve.dispatch", "serve.fetch", "serve.observe")


def read_spans(data: bytes) -> list:
    """The `serve.*` host spans of one XSpace: dicts with plane, line (the
    host thread's name), name, start_ns and dur_ns."""
    events = []
    for f, plane in tr._fields(data):
        if f != 1:
            continue
        name, lines, names = "", [], {}
        for pf, pv in tr._fields(plane):
            if pf == 2:
                name = pv.decode()
            elif pf == 3:
                lines.append(pv)
            elif pf == 4:
                k, v = tr._map_entry(pv)
                names[k] = next((mv.decode(errors="replace")
                                 for mf, mv in tr._fields(v) if mf == 2), "")
        if tr.DEVICE_PLANE.match(name):
            continue
        for raw_line in lines:
            lname, t0_ns, evs = "", 0, []
            for lf, lv in tr._fields(raw_line):
                if lf == 2:
                    lname = lv.decode()
                elif lf == 3:
                    t0_ns = lv
                elif lf == 4:
                    evs.append(lv)
            for raw_ev in evs:
                mid = off = dur = 0
                for ef, ev in tr._fields(raw_ev):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        off = ev
                    elif ef == 3:
                        dur = ev
                ename = names.get(mid, "")
                if ename.startswith(PREFIX):
                    events.append({"plane": name, "line": lname, "name": ename,
                                   "start_ns": t0_ns + off / 1000.0,
                                   "dur_ns": dur / 1000.0})
    return events


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _window(events: list) -> tuple:
    win = [e for e in events if e["name"] == tr.WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"no {tr.WINDOW_SPAN!r} span in the trace")
    return win[0]["start_ns"], win[0]["start_ns"] + win[0]["dur_ns"]


def _idle_gaps(ops: list, planes: list, w0: float, w1: float) -> dict:
    """Per device plane, the window's stretches in which no op ran."""
    out = {}
    for plane in planes:
        merged = tr._union([ab for ab in (
            (max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1))
            for e in ops if e["plane"] == plane) if ab[1] > ab[0]])
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        out[plane] = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    return out


def _innermost(spans: list, w0: float, w1: float) -> list:
    """[w0, w1] cut into (a, b, name) pieces, each named by the innermost
    of the nested `spans` ((start, end, name)) open over it, or "" where
    none is."""
    pieces, stack, t = [], [], w0

    def advance(to):
        nonlocal t
        while stack and stack[-1][0] <= to:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if to > t:
            pieces.append((t, to, stack[-1][1] if stack else ""))
            t = to

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        advance(a)
        if stack:  # spans of one thread nest; clip one that would not
            b = min(b, stack[-1][0])
        stack.append((b, name))
    advance(w1)
    return pieces


def _intersect(gaps: list, pieces: list, into: dict, weight: float) -> None:
    """Add `weight` x the overlap of each gap with each named piece; both
    lists are sorted and the pieces tile the window."""
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            pa, pb, name = pieces[k]
            ov = min(b, pb) - max(a, pa)
            if ov > 0:
                into[name] = into.get(name, 0.0) + ov * weight
            k += 1


def reduce_spans(events: list, n_devices: int) -> dict:
    """The module docstring's keys, from `trace.read_xspace(data) +
    read_spans(data)` (or hand-made events of the same form)."""
    w0, w1 = _window(events)
    ops = [e for e in events if "category" in e]
    cell = sorted({e["plane"] for e in ops},
                  key=lambda p: int(p.rsplit(":", 1)[1]))[:n_devices]
    # a chip whose trace dropped events stands aside (`trace.whole_planes`)
    planes = tr.whole_planes(events, cell, w0, w1) or cell
    scale = len(cell) / len(planes) if planes else 1.0
    prog = [e for e in events if e["name"].startswith(PREFIX)]
    lines: dict = {}
    for e in prog:
        lines[e.get("line")] = lines.get(e.get("line"), 0) + 1
    serving = max(lines, key=lines.get) if lines else None
    pieces = _innermost([(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                         for e in prog if e.get("line") == serving], w0, w1)

    gaps = _idle_gaps(ops, planes, w0, w1)
    idle: dict = {}
    for plane in planes:
        _intersect(gaps[plane], pieces, idle, 1e-9 / len(planes))

    layer_ns: dict = {}
    kernels: dict = {}
    for e in ops:
        if e["plane"] not in planes or not tr.in_conv_unit(e):
            continue
        m = LAYER.search(e["tf_op"])
        if m is None:
            continue
        a, b = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        if b > a:
            layer_ns[m.group(1)] = layer_ns.get(m.group(1), 0.0) + (b - a) * scale
        k = KERNEL.search(e["tf_op"])
        if k is not None:
            kernels.setdefault(m.group(1), set()).add(k.group(1))

    starts = [p[0] for p in pieces]
    host = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
            for e in events if "category" not in e and e["name"] in tr.HOST_SPANS]

    def name_gap(a, b):
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and pieces[i][2]:
            return pieces[i][2]
        inside = [h for h in host if h[0] <= mid <= h[1]]
        if not inside:
            return "host: outside the harness's spans"
        return min(inside, key=lambda h: h[1] - h[0])[2]

    all_gaps = [g for plane in planes for g in gaps[plane]]
    top = sorted(all_gaps, key=lambda ab: ab[0] - ab[1])[:tr.TOP]
    return {
        "program_spans": [[e["name"], (e["start_ns"] - w0) * 1e-9, e["dur_ns"] * 1e-9]
                          for e in sorted(prog, key=lambda e: e["start_ns"])
                          if e["start_ns"] >= w0
                          and e["start_ns"] + e["dur_ns"] <= w1],
        "idle_by_span": idle,
        "layer_s": {k: v * 1e-9 for k, v in sorted(layer_ns.items())},
        "layer_kernels": {k: sorted(v) for k, v in sorted(kernels.items())},
        "idle_gaps": [[name_gap(a, b), (b - a) * 1e-9] for a, b in top],
    }


# ---------------------------------------------------------------------------
# one number each, from trace.reduce(...) | reduce_spans(...)
# ---------------------------------------------------------------------------


def _durations(t: dict, name: str) -> list:
    return [d for n, _, d in t["program_spans"] if n == name]


def submit_us(t: dict) -> float | None:
    """Median `serve.submit` duration in the traced stretch, microseconds."""
    d = _durations(t, "serve.submit")
    return 1e6 * statistics.median(d) if d else None


def batch_host_ms(t: dict) -> float | None:
    """Median over `serve.batch` spans of the duration less that of the
    `serve.wait` span inside it: the host's own time per batch, ms."""
    waits = sorted((s, s + d) for n, s, d in t["program_spans"] if n == "serve.wait")
    starts = [a for a, _ in waits]
    host = []
    for n, s, d in t["program_spans"]:
        if n != "serve.batch":
            continue
        i = bisect.bisect_left(starts, s)
        inside = [b - a for a, b in waits[i:bisect.bisect_right(starts, s + d)]
                  if b <= s + d]
        host.append(d - sum(inside))
    return 1e3 * statistics.median(host) if host else None


def _idle_pct(t: dict, names: tuple) -> float | None:
    if not t["program_spans"] or t["window_s"] <= 0:
        return None
    return 100.0 * sum(t["idle_by_span"].get(n, 0.0) for n in names) / t["window_s"]


def idle_submit_pct(t: dict) -> float | None:
    """Device idle under `serve.submit` and `serve.put`, % of the stretch."""
    return _idle_pct(t, SUBMIT_SPANS)


def idle_executor_pct(t: dict) -> float | None:
    """Device idle under `serve.batch` and its children but `serve.wait`,
    % of the stretch."""
    return _idle_pct(t, EXECUTOR_SPANS)


def sparse_layers(t: dict) -> list:
    """The `conv<i>` scopes that ran one of the paper's kernels."""
    return [s for s, ks in t["layer_kernels"].items()
            if any(k in SPARSE_KERNELS for k in ks)]


def sparse_conv_roofline(t: dict, cfg: dict, batches: list,
                         peaks: dict | None) -> float | None:
    """`conv_roofline`'s rule over the sparse layers alone: Σ over the
    batches (`batches`: real images of each) and those layers of
    max(2·MACs / peak FLOP/s, bytes / peak B/s), over Σ `layer_s` of those
    layers, %. None where no layer ran a sparse kernel."""
    scopes = sparse_layers(t)
    spent = sum(t["layer_s"].get(s, 0.0) for s in scopes)
    if peaks is None or not scopes or spent <= 0 or not batches:
        return None
    idx = {int(s[len("conv"):]) - 1 for s in scopes}
    least = sum(max(2 * lyr.macs * n / peaks["flops"],
                    lyr.bytes(n) / peaks["hbm_bytes_per_s"])
                for lyr in cnn.layer_shapes(cfg)
                if lyr.op == "conv" and lyr.index in idx for n in batches)
    return 100.0 * least / spent
