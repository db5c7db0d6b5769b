"""CalibrationDB: measured effective roofline constants per (device, impl).

The datasheet constants in `repro.obs.constants` describe what the chip CAN
do; the planner needs what each impl DOES — interpret-mode Pallas on CPU,
an XLA conv, and a gathered sparse kernel on a real accelerator sit at
wildly different fractions of the roofline, and the dense-vs-sparse
crossover moves with them (the measured-not-assumed point of
Pietroń & Żurek, arXiv:2011.06295). The DB stores, per

    (device kind x op kind x impl x tile geometry)        — PlanKey-style

an EFFECTIVE `RooflineConstants` pair fitted from `profile_plan`
measurements, and every modeled time in the repo (`unit_model_us`,
`plan_model_us`, `plan_network`'s occupancy-rule and BSR-displacement
arbitration) consults it through an explicit `calibration=` parameter — the
hard-coded defaults remain the fallback for any key the DB does not cover,
so an EMPTY DB reproduces the uncalibrated behavior bit-identically.

The geometry axis is the full `TileConfig` 5-tuple key (block_c, block_o,
bt, bf, bd); the pre-tile (block_c,)-keyed entries embed as
(block_c, 0, 0, 0, 0), which is also how a v1 JSON loads. Lookup walks
exact tile -> block_c-only -> geometry-agnostic (all-zero), so a coarse fit
covers finer keys until one is measured.

The DB also carries the TILE-SEARCH winners table (`put_tile`/`best_tile`):
per (device, op kind, impl, layer shape) the measured-best `TileConfig` key
that `obs.tilesearch` found — this is the persisted half of the
measure -> search -> plan loop, consulted by `plan_network(tiles=...)` so a
plan built tomorrow starts from today's measured-best geometry.

Fit model: one efficiency scalar per key. A kernel is assumed to run at a
fixed fraction `s` of the datasheet roofline (both ceilings scaled
together), so `s = median over layers of (modeled_default_us /
measured_us)` and the effective constants are `defaults x s`. The median
makes the fit robust to one outlier layer; the per-key residual spread is
recorded so a caller can see when one scalar does NOT explain an impl's
behavior across shapes (the cue to split the block-geometry key further).

Persistence is plain JSON (`save`/`load`) so a calibration survives across
processes and ships next to BENCH artifacts.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass

from repro.obs.constants import RooflineConstants, device_peaks


def device_kind() -> str:
    """The running device's kind string (the DB's device axis)."""
    import jax

    dev = jax.devices()[0]
    return getattr(dev, "device_kind", dev.platform)


def unit_shape_key(unit) -> tuple:
    """The layer-shape key the tile winners table is indexed by: everything
    that determines a conv unit's kernel geometry problem — (c, h, w, o, k,
    stride, pool). Duck-typed over `graph.ir.ConvUnit` so obs stays free of
    a graph import; two units with equal keys face the identical search
    space, whatever network they sit in."""
    c, h, w = unit.in_shape
    conv = unit.conv
    pool = unit.pool.p if unit.pool is not None else 0
    return (int(c), int(h), int(w), int(conv.c_out), int(conv.k),
            int(conv.stride), int(pool))


def _tile_key(block_c: int = 0, tile=None) -> tuple:
    """Normalize (block_c, tile) to the canonical 5-tuple geometry key."""
    if tile is not None and tile:
        return tuple(int(v) for v in tile.key())
    return (int(block_c), 0, 0, 0, 0)


def _fmt_tkey(tkey: tuple) -> str:
    if not any(tkey[1:]):
        return f"bc{tkey[0]}"
    return "t" + ".".join(str(v) for v in tkey)


@dataclass(frozen=True)
class CalibEntry:
    """One fitted key: the effective constants plus fit diagnostics."""

    peak_flops: float
    hbm_bw: float
    scale: float  # fitted efficiency vs the datasheet defaults
    n_samples: int
    resid_spread: float  # (max-min)/median of the per-layer ratios

    def constants(self) -> RooflineConstants:
        return RooflineConstants(self.peak_flops, self.hbm_bw)


class CalibrationDB:
    """{(device_kind, kind, impl, tile_key): CalibEntry} with default fallback,
    plus {(device_kind, kind, impl, shape_key): tile_key} search winners.

    `lookup` tries the exact tile geometry first, then the block_c-only key
    (a fit at one channel-block size covers searched (block_o, bt, bf, bd)
    refinements until one is measured), then the geometry-agnostic all-zero
    key, then gives up (None -> caller uses the defaults).
    `device` pins the device axis; entries fitted on other device kinds are
    never consulted (a CPU calibration must not steer a TPU plan).
    """

    def __init__(self, entries: dict | None = None, device: str | None = None,
                 tiles: dict | None = None):
        self.entries: dict = dict(entries or {})
        self.tiles: dict = dict(tiles or {})
        self.device = device

    def __len__(self) -> int:
        return len(self.entries)

    def __bool__(self) -> bool:
        # an empty DB is falsy ON PURPOSE: `calibration or None` normalizes
        # "no calibration" and "nothing fitted yet" to the same fallback;
        # a DB holding only tile winners still counts as calibration
        return bool(self.entries) or bool(self.tiles)

    def _device(self) -> str:
        if self.device is None:
            self.device = device_kind()
        return self.device

    def put(self, kind: str, impl: str, block_c: int, entry: CalibEntry,
            device: str | None = None, tile=None) -> None:
        key = (device or self._device(), kind, impl, _tile_key(block_c, tile))
        self.entries[key] = entry

    def lookup(self, kind: str, impl: str, block_c: int = 0,
               device: str | None = None, tile=None) -> RooflineConstants | None:
        dev = device or self._device()
        tkey = _tile_key(block_c, tile)
        chain = [tkey]
        if any(tkey[1:]):
            chain.append((tkey[0], 0, 0, 0, 0))  # block_c-only fit
        if tkey[0] != 0 or any(tkey[1:]):
            chain.append((0, 0, 0, 0, 0))  # geometry-agnostic fit
        for k in chain:
            e = self.entries.get((dev, kind, impl, k))
            if e is not None:
                return e.constants()
        return None

    def covers(self, kind: str, impl: str, block_c: int = 0,
               device: str | None = None, tile=None) -> bool:
        return self.lookup(kind, impl, block_c, device, tile=tile) is not None

    def constants_for(self, kind: str, impl: str, block_c: int = 0,
                      device: str | None = None, tile=None) -> RooflineConstants:
        """The effective constants for a key: calibrated, else the device's
        published peaks (the one resolution rule every modeled time goes
        through)."""
        return self.lookup(kind, impl, block_c, device, tile=tile) \
            or device_peaks()

    # -- tile-search winners ---------------------------------------------------

    def put_tile(self, kind: str, impl: str, shape_key: tuple, tile,
                 device: str | None = None) -> None:
        """Record the measured-best geometry for one (impl, layer shape).
        `tile` is a TileConfig (or its 5-tuple key); an all-zero/None tile
        means "defaults won" and ERASES any stored winner instead of storing
        a no-op row."""
        key = (device or self._device(), kind, impl, tuple(shape_key))
        tkey = _tile_key(0, tile) if not isinstance(tile, tuple) else \
            tuple(int(v) for v in tile)
        if not any(tkey):
            self.tiles.pop(key, None)
        else:
            self.tiles[key] = tkey

    def best_tile(self, kind: str, impl: str, shape_key: tuple,
                  device: str | None = None):
        """The stored winner as a `TileConfig`, or None when the defaults are
        (or are assumed) best — callers can pass the result straight to
        `run_unit(..., tile=...)` either way."""
        tkey = self.tiles.get(
            (device or self._device(), kind, impl, tuple(shape_key)))
        if tkey is None:
            return None
        from repro.kernels.tiles import TileConfig

        return TileConfig.from_key(tkey)

    # -- fitting -------------------------------------------------------------

    def fit_report(self, report) -> "CalibrationDB":
        """Fold a `ProfileReport` in: one entry per (kind, impl, geometry)
        group, scale = median(predicted_default / measured) (see module
        docstring). Returns self (chainable)."""
        peaks = device_peaks()
        for (kind, impl), rows in report.by_impl().items():
            by_tk: dict = {}
            for t in rows:
                tk = tuple(getattr(t, "tile", ()) or ()) \
                    or (int(t.block_c), 0, 0, 0, 0)
                by_tk.setdefault(tk, []).append(t)
            for tk, grp in by_tk.items():
                ratios = sorted(t.ratio for t in grp)
                s = _median(ratios)
                if s <= 0.0:
                    continue  # degenerate measurement; keep the defaults
                spread = (ratios[-1] - ratios[0]) / max(s, 1e-12)
                self.entries[(report.device_kind, kind, impl, tk)] = CalibEntry(
                    peak_flops=peaks.peak_flops * s,
                    hbm_bw=peaks.hbm_bw * s,
                    scale=float(s), n_samples=len(grp),
                    resid_spread=float(spread))
        if self.device is None:
            self.device = report.device_kind
        return self

    @classmethod
    def from_report(cls, report) -> "CalibrationDB":
        return cls(device=report.device_kind).fit_report(report)

    # -- persistence ----------------------------------------------------------

    def to_json(self) -> dict:
        return {"schema": "calibration-v2", "device": self.device,
                "entries": [
                    {"device": d, "kind": k, "impl": i, "tile": list(tk),
                     **asdict(e)}
                    for (d, k, i, tk), e in sorted(self.entries.items())],
                "tiles": [
                    {"device": d, "kind": k, "impl": i, "shape": list(sk),
                     "tile": list(tk)}
                    for (d, k, i, sk), tk in sorted(self.tiles.items())]}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")
        return path

    @classmethod
    def load(cls, path: str) -> "CalibrationDB":
        with open(path) as f:
            payload = json.load(f)
        schema = payload.get("schema")
        if schema not in ("calibration-v1", "calibration-v2"):
            raise ValueError(f"{path}: not a calibration DB "
                             f"(schema={schema!r})")
        db = cls(device=payload.get("device"))
        for row in payload["entries"]:
            # v1 rows carry "block_c"; v2 rows the full "tile" 5-tuple
            tk = tuple(row["tile"]) if "tile" in row else \
                (int(row["block_c"]), 0, 0, 0, 0)
            db.entries[(row["device"], row["kind"], row["impl"], tk)] = \
                CalibEntry(peak_flops=row["peak_flops"],
                           hbm_bw=row["hbm_bw"], scale=row["scale"],
                           n_samples=row["n_samples"],
                           resid_spread=row["resid_spread"])
        for row in payload.get("tiles", []):
            db.tiles[(row["device"], row["kind"], row["impl"],
                      tuple(row["shape"]))] = tuple(row["tile"])
        return db

    def history_rows(self) -> list:
        """The fitted entries as perf-history rows (scale + residual spread
        per key) — `repro.obs.history.calibration_rows(self)`, so kernel
        efficiency drift across commits is a gate-able BenchDB series
        (DESIGN.md §13)."""
        from repro.obs.history.records import calibration_rows

        return calibration_rows(self)

    def summary(self) -> dict:
        """JSON-ready digest (scales per key) for logs and BENCH extras."""
        out = {f"{d}/{k}/{i}/{_fmt_tkey(tk)}": round(e.scale, 6)
               for (d, k, i, tk), e in sorted(self.entries.items())}
        for (d, k, i, sk), tk in sorted(self.tiles.items()):
            out[f"{d}/{k}/{i}/shape{'x'.join(map(str, sk))}"] = \
                _fmt_tkey(tk)
        return out


def _median(sorted_vals) -> float:
    n = len(sorted_vals)
    if n == 0:
        return 0.0
    return float(sorted_vals[n // 2]) if n % 2 else \
        float((sorted_vals[n // 2 - 1] + sorted_vals[n // 2]) / 2)
