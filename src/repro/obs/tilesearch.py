"""Per-layer tile-geometry search: measure -> search -> plan, closed.

PR 7 built the measure half (profile_plan -> CalibrationDB); this module is
the SEARCH half. For each conv layer of a plan, at the (kind, impl) the
planner chose, it enumerates candidate `TileConfig` geometries (power-of-two
grids over the dimensions that impl actually tiles), prices each on the
roofline model — re-measuring the layer's channel-block occupancy at the
candidate's block_c and the weight block density at the candidate's (bt, bf),
because geometry changes WHAT the schedule can skip, not just how it tiles —
prunes the obviously-losing geometries without timing them, wall-times the
survivors through the shared `time_callable` harness, and picks a winner by
the rule:

    S      = { timed candidates with measured_us <= default's measured_us }
    winner = argmin over S of (model_us, measured_us)

The default geometry is always timed and always in S, so BY CONSTRUCTION the
winner's modeled time AND measured time are <= the default's — a searched
plan can only tie or beat the shipped constants, never regress them (the
floor `benchmarks/kernels_micro.py --check-floor` pins in CI).

Winners persist into the `CalibrationDB` tiles table
(`put_tile`/`best_tile`, keyed by (device, kind, impl, layer shape)), which
is how the loop closes: `plan_network(tiles=db)` consults the table and
stamps each layer's `LayerPlan.tile`, `run_unit` threads it into the
kernels, and `PlanKey.tile_sig` keeps compiled executables per geometry.
Timings can also be FITTED back as per-tile calibration entries (fit=True),
so `plan_model_us` prices a searched geometry at its measured efficiency.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.kernels.tiles import DEFAULT_TILE, TileConfig

# power-of-two grids per tiled dimension (intersected with each layer's
# extents; the fallback rule would silently map a too-big size onto the
# default, which would only re-time the default under another name)
_CONV_BC = (8, 16, 32, 64, 128)
_CONV_BO = (8, 32, 128)
_BSR_BT = (8, 16, 32)
_BSR_BF = (16, 32, 64, 128)
_BSR_BD = (32, 64, 128)


@dataclass(frozen=True)
class TileCandidate:
    """One priced geometry; measured_us < 0 means pruned before timing."""

    key: tuple  # TileConfig.key()
    model_us: float
    measured_us: float = -1.0
    spread: float = 0.0

    @property
    def timed(self) -> bool:
        return self.measured_us >= 0.0

    def row(self) -> dict:
        return {"tile": list(self.key), "model_us": round(self.model_us, 4),
                "measured_us": round(self.measured_us, 2),
                "spread": round(self.spread, 3), "timed": self.timed}


@dataclass(frozen=True)
class LayerTileSearch:
    """One layer's search result. `best` is the winning candidate; when the
    geometry search does not apply (non-Pallas impl) it is the default with
    no alternatives."""

    index: int
    kind: str
    impl: str
    shape_key: tuple
    best: TileCandidate
    default: TileCandidate
    candidates: tuple  # every priced TileCandidate, default included

    @property
    def improved(self) -> bool:
        return self.best.key != DEFAULT_TILE.key() and (
            self.best.model_us < self.default.model_us
            or self.best.measured_us < self.default.measured_us)

    def row(self) -> dict:
        return {"layer": self.index, "kind": self.kind, "impl": self.impl,
                "shape": list(self.shape_key),
                "best": self.best.row(), "default": self.default.row(),
                "improved": self.improved,
                "n_candidates": len(self.candidates),
                "n_timed": sum(c.timed for c in self.candidates)}


@dataclass(frozen=True)
class TileSearchReport:
    graph_name: str
    device_kind: str
    batch: int
    layers: tuple  # tuple[LayerTileSearch, ...]

    def improved_layers(self) -> tuple:
        return tuple(r for r in self.layers if r.improved)

    def floor_holds(self) -> bool:
        """The by-construction guarantee, re-checked on the recorded numbers:
        every layer's winner models AND measures no slower than its default."""
        return all(r.best.model_us <= r.default.model_us
                   and (not r.best.timed
                        or r.best.measured_us <= r.default.measured_us)
                   for r in self.layers)

    def summary(self) -> dict:
        return {"graph": self.graph_name, "device_kind": self.device_kind,
                "batch": self.batch, "layers": len(self.layers),
                "improved": len(self.improved_layers()),
                "floor_holds": self.floor_holds(),
                "model_speedup": round(
                    sum(r.default.model_us for r in self.layers)
                    / max(sum(r.best.model_us for r in self.layers), 1e-9), 4),
                "rows": [r.row() for r in self.layers]}


def _conv_candidates(c: int, o: int) -> list:
    out = [DEFAULT_TILE]
    for bc in _CONV_BC:
        if bc > max(8, c):
            continue
        for bo in _CONV_BO:
            if bo > max(8, o):
                continue
            out.append(TileConfig(block_c=bc, block_o=bo))
    return out


def _bsr_candidates(o: int, k_taps: int, p: int) -> list:
    out = [DEFAULT_TILE]
    for bt in _BSR_BT:
        if bt > max(8, o):
            continue
        for bf in _BSR_BF:
            if bf > max(8, k_taps):
                continue
            for bd in _BSR_BD:
                if bd > max(8, p):
                    continue
                out.append(TileConfig(bt=bt, bf=bf, bd=bd))
    return out


def layer_tile_candidates(unit, kind: str, impl: str, batch: int) -> list:
    """The geometry grid one (layer, impl) searches over — the dimensions
    that impl tiles, intersected with the layer's extents, default first."""
    from repro.graph.registry import get_op

    op = get_op(kind, impl)
    c, h, w = unit.in_shape
    if op.weight_sparse:
        conv = unit.conv
        k_taps = c * conv.k * conv.k
        _, oh, ow = unit.conv_out_shape
        return _bsr_candidates(conv.c_out, k_taps, batch * oh * ow)
    return _conv_candidates(c, unit.conv.c_out)


def search_layer(unit, w, x, kind: str, impl: str, *, iters: int = 2,
                 warmup: int = 1, prune_factor: float = 1.25,
                 max_timed: int = 4, calibration=None,
                 tracer=None) -> LayerTileSearch:
    """Search one layer's tile geometry at its planned (kind, impl).

    x is the layer's REAL input (the dense-oracle walk of `tile_search`), so
    occupancy — re-measured per candidate block_c — prices exactly the schedule each geometry would run. Candidates
    whose modeled time exceeds `prune_factor` x the modeled minimum are not
    timed (the roofline prune); of the rest the `max_timed` modeled-best are
    (the default always is). Winner rule: see module docstring.
    """
    import jax

    from repro.graph.executor import run_unit
    from repro.graph.registry import get_op, unit_model_us
    from repro.obs.calibrate import unit_shape_key
    from repro.obs.profile import time_callable
    from repro.obs.trace import NULL_TRACER
    from repro.pipeline.planner import measure_occupancy
    from repro.sparse_weights.format import conv_weight_matrix, matrix_block_density

    tracer = tracer or NULL_TRACER
    op = get_op(kind, impl)
    shape_key = unit_shape_key(unit)
    batch = int(x.shape[0]) if x.ndim == 4 else 1
    if not op.pallas:
        # nothing to search: non-Pallas impls have no tile geometry
        m = unit_model_us(kind, impl, unit, batch=batch,
                          calibration=calibration)
        cand = TileCandidate(key=DEFAULT_TILE.key(), model_us=m)
        return LayerTileSearch(index=unit.index, kind=kind, impl=impl,
                               shape_key=shape_key, best=cand, default=cand,
                               candidates=(cand,))

    c = unit.in_shape[0]
    conv = unit.conv
    k_taps = c * conv.k * conv.k
    wm = conv_weight_matrix(w) if op.weight_sparse else None

    priced: list = []
    for t in layer_tile_candidates(unit, kind, impl, batch):
        occ = 1.0
        wd = 1.0
        if op.sparse:
            occ = measure_occupancy(x, tile=t)
        if op.weight_sparse:
            from repro.kernels.tiles import resolve_bsr_tile

            _, oh, ow = unit.conv_out_shape
            bt, bf, _ = resolve_bsr_tile(conv.c_out, k_taps, batch * oh * ow, t)
            wd = matrix_block_density(wm, (bt, bf))
        priced.append((t, unit_model_us(
            kind, impl, unit, occupancy=occ, weight_density=wd, batch=batch,
            tile=t if t else None, calibration=calibration)))

    best_model = min(m for _, m in priced)
    keep = [(t, m) for t, m in priced
            if not t or m <= prune_factor * best_model]
    # default first, then the modeled-best survivors up to the timing budget
    keep = [keep[0]] + sorted(keep[1:], key=lambda tm: tm[1])[:max_timed]

    cands: dict = {}
    for t, m in priced:
        cands[t.key()] = TileCandidate(key=t.key(), model_us=float(m))
    for t, m in keep:
        def fwd(x_, w_, t=t):
            return run_unit(x_, w_, unit, kind, impl, tile=t if t else None)

        with tracer.span("tile_search_layer", cat="kernel", layer=unit.index,
                         kind=kind, impl=impl, tile=str(t.key())):
            tm = time_callable(jax.jit(fwd), x, w, iters=iters, warmup=warmup,
                               outlier_tol=2.0)
        cands[t.key()] = TileCandidate(key=t.key(), model_us=float(m),
                                       measured_us=tm.median_us,
                                       spread=tm.spread)

    default = cands[DEFAULT_TILE.key()]
    eligible = [cd for cd in cands.values()
                if cd.timed and cd.measured_us <= default.measured_us]
    best = min(eligible, key=lambda cd: (cd.model_us, cd.measured_us))
    return LayerTileSearch(
        index=unit.index, kind=kind, impl=impl, shape_key=shape_key,
        best=best, default=default,
        candidates=tuple(sorted(cands.values(), key=lambda cd: cd.model_us)))


def tile_search(plan, params, calib, *, iters: int = 2, warmup: int = 1,
                prune_factor: float = 1.25, max_timed: int = 4,
                db=None, fit: bool = True, calibration=None,
                tracer=None):
    """Search every layer of `plan` at its planned impl; persist winners.

    Walks the plan's graph on `calib` with the dense oracle (each layer is
    searched on the input distribution the plan was made for), runs
    `search_layer` per conv unit, and writes each non-default winner into
    `db` (a `CalibrationDB`; one is created when None) via `put_tile` — an
    all-default winner ERASES a stale stored winner rather than recording a
    no-op. fit=True additionally fits per-(impl, tile) calibration entries
    from the collected timings (scale = median of modeled-default/measured,
    the `fit_report` rule), so the winners' modeled times are measured-backed
    the next time `plan_model_us` prices them.

    Returns (TileSearchReport, db).
    """
    import jax

    from repro.graph.executor import run_unit, walk_graph
    from repro.graph.ir import graph_weights
    from repro.graph.registry import get_op
    from repro.obs.calibrate import CalibrationDB
    from repro.obs.constants import device_peaks
    from repro.obs.trace import NULL_TRACER

    tracer = tracer or NULL_TRACER
    graph = plan.graph
    if graph is None:
        raise ValueError("tile_search needs a plan that carries its graph "
                         "(pre-IR plans: rebuild with plan_network)")
    if calib.ndim == 3:
        calib = calib[None]
    batch = int(calib.shape[0])
    db = db if db is not None else CalibrationDB()
    conv_ws, _ = graph_weights(params)
    rows: list = []

    def on_unit(unit, x):
        lp = plan.layers[unit.index]
        w = conv_ws[unit.index]
        r = search_layer(unit, w, x, lp.kind, lp.impl, iters=iters,
                         warmup=warmup, prune_factor=prune_factor,
                         max_timed=max_timed, calibration=calibration,
                         tracer=tracer)
        rows.append(r)
        if get_op(lp.kind, lp.impl).pallas:
            db.put_tile(lp.kind, lp.impl, r.shape_key,
                        TileConfig.from_key(r.best.key))
        return run_unit(x, w, unit, "conv", "dense")  # dense-oracle walk

    with tracer.span("tile_search", graph=graph.name, batch=batch):
        walk_graph(graph, calib, on_unit)
    if fit and calibration is None:
        # per-(kind, impl, tile) entries from every timed candidate, the
        # fit_report rule: scale = median(modeled_default_us / measured_us).
        # Only when the candidates were priced at the DEFAULT constants — a
        # ratio against an already-calibrated model would double-apply scales.
        from repro.obs.calibrate import CalibEntry, _median

        peaks = device_peaks()
        ratios: dict = {}
        for r in rows:
            for cd in r.candidates:
                if cd.timed:
                    ratios.setdefault((r.kind, r.impl, cd.key), []).append(
                        cd.model_us / max(cd.measured_us, 1e-9))
        for (kind, impl, tkey), rs in ratios.items():
            rs = sorted(rs)
            s = _median(rs)
            if s <= 0.0:
                continue
            db.put(kind, impl, 0, CalibEntry(
                peak_flops=peaks.peak_flops * s,
                hbm_bw=peaks.hbm_bw * s, scale=float(s),
                n_samples=len(rs),
                resid_spread=float((rs[-1] - rs[0]) / max(s, 1e-12))),
                tile=TileConfig.from_key(tkey))
    dev = jax.devices()[0]
    report = TileSearchReport(
        graph_name=graph.name,
        device_kind=getattr(dev, "device_kind", dev.platform),
        batch=batch, layers=tuple(rows))
    return report, db
