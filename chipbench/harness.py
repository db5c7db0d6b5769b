"""One run of one benchmark cell, driven by `BENCHMARK.json` and the files
it names.

The cell's entry names a configuration (`chipbench/configs/<config>.json`,
with a model module of its own where it is not a linear `layers` list:
`chipbench/cnn.py`), a traffic mix (`chipbench/traffic/<mix>.json`) and,
through the metric lists, one reader per metric
(`chipbench/metrics/<metric>.py`, a module with `read(run) -> float |
None`). Adding a cell, a configuration, a mix or a metric adds files and
entries; nothing here names one.

A run: check the chips, make the weights and the image pool from the seed,
build the served path (LayerGraph -> plan_network -> PlanCache -> Engine),
warm the cell's buckets and its request shapes, then measure for
`--seconds` on the host's clock. With `--trace 1` the same traffic goes on
for `TRACE_S` more seconds under the profiler. Then the engine is freed and
a sample of the window's answers is compared with the plain reference.

A control (`run_cell(control=...)`, never in a benchmark run) puts a lower
precision in the program's place and has to come out not correct: the
program's own int8 kernels (`"program_int8"`), or the plain reference with
its operands in int8 or bfloat16 in place of the served answers.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# profiled stretch after the window, seconds
TRACE_S = 2.0
# answers per run compared with the reference, and the reference's batch
SAMPLE = 256
REF_BLOCK = 32


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: tuple  # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: Path, workload: str) -> Cell:
    from chipbench import cnn

    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = cnn.load_config(root / configs[w["config"]]["file"], root)
    mix = load_json(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), cfg=cfg, mix=mix,
        end_to_end=tuple(m for m in bench["end_to_end"] if applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m, workload)))


# ---------------------------------------------------------------------------
# what a metric reader sees
# ---------------------------------------------------------------------------


@dataclass
class Run:
    cell: Cell
    seconds: float
    peaks: dict | None
    setup: dict = field(default_factory=dict)  # setup_s, plan_s, warmup_s
    t0: float = 0.0  # the window, on the host's monotonic clock
    t1: float = 0.0
    rec: object = None  # drive.Records
    trace: dict | None = None  # trace.reduce() of the traced stretch

    def window_requests(self) -> list:
        """Indices of the requests sent inside the window."""
        return [i for i, t in enumerate(self.rec.sent) if self.t0 <= t < self.t1]

    def done_between(self, a: float, b: float) -> list:
        return [i for i, t in self.rec.done.items() if a <= t <= b]

    def batches_between(self, a: float, b: float) -> list:
        """Real images of each batch whose logits reached the host in
        [a, b]."""
        sizes: dict = {}
        for i in self.done_between(a, b):
            key = (self.rec.formed[i], self.rec.done[i])
            sizes[key] = sizes.get(key, 0) + 1
        return list(sizes.values())


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def check_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def layer_graph(cfg):
    """The configuration as the system's LayerGraph (the model module's, where
    the configuration names one)."""
    from chipbench import cnn

    mod = cnn.model_module(cfg)
    if mod is not None:
        return mod.layer_graph(cfg)
    from repro.graph.ir import ConvSpec, DenseSpec, Flatten, LayerGraph, PoolSpec, ReLU

    nodes = []
    for node in cfg["layers"]:
        op = node["op"]
        if op == "conv":
            nodes.append(ConvSpec(node["out"], k=node["k"],
                                  stride=node.get("stride", 1),
                                  pad=node.get("pad", 0)))
        elif op == "relu":
            nodes.append(ReLU())
        elif op == "pool":
            nodes.append(PoolSpec(node["p"], stride=node.get("stride", 0)))
        elif op == "flatten":
            nodes.append(Flatten())
        else:
            nodes.append(DenseSpec(node["out"], relu=bool(node.get("relu"))))
    return LayerGraph(name=cfg["name"], in_shape=cnn.in_shape(cfg),
                      nodes=tuple(nodes))


def build_engine(cfg, params, calib, chips: int, int8: bool = False):
    from repro.serving import Engine

    srv = cfg["serving"]
    mesh = None
    if chips > 1:
        from repro.parallel import data_mesh

        mesh = data_mesh(chips)
    return Engine(params, graph=layer_graph(cfg), calib=calib,
                  occ_threshold=srv["occ_threshold"], block_c=srv["block_c"],
                  replan_band=srv["replan_band"],
                  deadline_s=srv["deadline_ms"] * 1e-3,
                  max_batch=srv["max_batch"], mesh=mesh, int8=int8,
                  clock=time.monotonic)


def plan_line(engine) -> str:
    return " ".join(f"conv{lp.index + 1}={lp.impl}@{lp.occupancy:.2f}"
                    for lp in engine.plan.layers)


class CompileCounter:
    """XLA programs built or loaded from the persistent cache, with the
    seconds spent on them, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return (self.programs, self.seconds, self.cache_hits, self.cache_misses)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at the fixed `<checkout>/.jax_cache`,
    whatever the environment names, so that two checkouts share nothing;
    every program is kept, `plan_network`'s small ones too."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------


def logit_errs(cfg, params, pool, rec, sample: list, operand_dtype=None):
    """For each sampled request, the widest gap between a served logit and
    the plain reference's, as a share of its largest reference logit
    (infinite for an answer of the wrong shape or not finite). With
    `operand_dtype` the reference with its operands in that precision
    stands in for the served answers (a control)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import cnn

    images = sorted({rec.image[i] for i in sample})
    fwd = jax.jit(lambda p, x: cnn.forward(cfg, p, x))
    ctl = (jax.jit(lambda p, x: cnn.forward(cfg, p, x, operand_dtype))
           if operand_dtype is not None else None)
    ref, low = {}, {}
    for b in range(0, len(images), REF_BLOCK):
        ids = images[b:b + REF_BLOCK]
        x = pool[ids]
        if len(ids) < REF_BLOCK:  # one compiled shape
            x = np.concatenate([x, np.zeros((REF_BLOCK - len(ids),) + x.shape[1:],
                                            x.dtype)])
        x = jnp.asarray(x)
        out = np.asarray(fwd(params, x), np.float64)
        lo = np.asarray(ctl(params, x), np.float64) if ctl is not None else None
        for k, j in enumerate(ids):
            ref[j] = out[k]
            if lo is not None:
                low[j] = lo[k]
    errs = []
    for i in sample:
        r = ref[rec.image[i]]
        got = low[rec.image[i]] if ctl is not None else np.asarray(
            rec.logits[i], np.float64)
        if got.shape != r.shape or not np.isfinite(got).all():
            errs.append(math.inf)
        else:
            errs.append(float(np.abs(got - r).max()
                              / max(np.abs(r).max(), 1e-30)))
    return np.asarray(errs)


def draw_sample(run: Run, seed: int) -> list:
    import numpy as np

    done = [i for i in run.window_requests() if i in run.rec.done]
    if len(done) <= SAMPLE:
        return done
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 3])
    return sorted(rng.choice(done, SAMPLE, replace=False).tolist())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


CONTROLS = ("program_int8", "int8", "bfloat16")


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, control: str | None = None) -> dict:
    """Run one cell and return the result line's object. `t_start` is the
    process's start on the monotonic clock. `control`, one of `CONTROLS`,
    puts that control in the program's place (module docstring)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; known: {CONTROLS}")
    cell = find_cell(root, workload)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import cnn, drive, peaks

    cache_dir = enable_compile_cache(root)  # before anything compiles
    devices = check_devices(cell.chips)
    kind = devices[0].device_kind
    log(f"device: {kind} x {len(devices)} ({devices[0].platform}); "
        f"cell {cell.name} on {cell.chips} chip(s)")
    sys.path.insert(0, str(root / "src"))
    importlib.import_module("repro.serving")  # the system under test
    log(f"compile cache: {cache_dir}")
    compiles = CompileCounter()
    device_peaks = peaks.peaks(kind) if devices[0].platform == "tpu" else None

    cfg, mix = cell.cfg, cell.mix
    params = cnn.make_weights(cfg, seed)
    jax.block_until_ready(params)
    pool = drive.image_pool(cnn.in_shape(cfg), int(mix["pool"]), seed,
                            float(mix["dead_frac"]))
    srv = cfg["serving"]
    calib = jnp.asarray(drive.image_pool(cnn.in_shape(cfg), srv["calib_images"],
                                         srv["calib_seed"], float(mix["dead_frac"])))

    t = time.monotonic()
    engine = build_engine(cfg, params, calib, cell.chips,
                          int8=control == "program_int8")
    plan_s = time.monotonic() - t
    log(f"plan: {plan_line(engine)}")
    t = time.monotonic()
    warm = engine.warmup(mix["buckets"])
    warmup_s = time.monotonic() - t
    for n in mix["warm_sizes"]:  # the request path at every batch size served
        for img in pool[:n]:
            engine.submit(img)
        engine.drain()
    run = Run(cell=cell, seconds=seconds, peaks=device_peaks)
    load = drive.Load(engine, pool, mix, span=jax.profiler.TraceAnnotation)
    at_window = compiles.snapshot()
    cache_at_window = engine.cache.compiles
    replans_at_window = (engine.metrics.replan_triggers, engine.n_replans)

    run.t0 = time.monotonic()
    run.setup = {"setup_s": run.t0 - t_start, "plan_s": plan_s,
                 "warmup_s": warmup_s}
    run.t1 = run.t0 + seconds
    load.run_until(run.t1)
    traced = None
    if trace:
        traced = profile_stretch(load, devices[:cell.chips])
    load.finish()
    after = compiles.snapshot()
    log(f"set-up: {run.setup['setup_s']:.3f} s (plan {plan_s:.3f} s, warm-up of "
        f"buckets {mix['buckets']} {warmup_s:.3f} s, {warm} bucket programs); "
        f"XLA programs before the window: {at_window[0]} in {at_window[1]:.3f} s, "
        f"persistent-cache hits {at_window[2]}, misses {at_window[3]}")
    log(f"inside the window: {after[0] - at_window[0]} XLA programs built and "
        f"{after[2] - at_window[2]} loaded from the persistent cache, "
        f"{engine.cache.compiles - cache_at_window} plan-cache compiles, "
        f"{engine.metrics.replan_triggers - replans_at_window[0]} re-plans "
        f"run ({engine.n_replans - replans_at_window[1]} that changed the "
        f"plan), {engine.replan_errors} re-plan errors")
    run.rec = load.rec
    used = devices[:cell.chips]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)
    del load, engine
    gc.collect()

    attempted = run.window_requests()
    failed = [i for i in attempted if i not in run.rec.done]
    if traced is not None:
        run.trace = traced
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = load_module(root / "chipbench" / "metrics" / f"{m['name']}.py",
                            m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    sample = draw_sample(run, seed)
    limits = cfg["check"]
    operand_dtype = control if control not in (None, "program_int8") else None
    errs = logit_errs(cfg, params, pool, run.rec, sample, operand_dtype)
    err = float(errs.max()) if len(errs) else math.inf
    checks = {"logit_err": {"value": err, "limit": limits["logit_err"]},
              "answered": {"value": len(attempted) - len(failed),
                           "limit": len(attempted)}}
    correct = bool(err <= limits["logit_err"] and not failed and attempted)
    median = float(np.median(errs)) if len(errs) else math.inf
    log(f"compared {len(sample)} of {len(attempted)} answers sent in the "
        f"window with the reference{f' (control: {control})' if control else ''}"
        f"; logit_err median over them {median}")
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = traced["breakdown"]
    out["checks"] = checks
    return out


def profile_stretch(load, devices) -> dict:
    """Keep the traffic going for TRACE_S seconds under the profiler and
    reduce the trace."""
    import shutil

    import jax

    from chipbench import trace as tr

    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python calls would swamp the trace
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            a = time.monotonic()
            load.run_until(a + TRACE_S)
            b = time.monotonic()
        jax.profiler.stop_trace()
        reduced = tr.reduce(tr.load_events(tmp), n_devices=len(devices))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if reduced["dropped"]:
        log(f"trace: {reduced['dropped']} of {reduced['devices']} chips' "
            f"records dropped events inside the stretch (trace.whole_planes)")
    reduced["host_t0"], reduced["host_t1"] = a, b
    return reduced
