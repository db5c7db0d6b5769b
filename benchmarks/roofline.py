"""§Roofline report: renders the per-(arch x shape x mesh) table from the
dry-run JSONs in experiments/dryrun/ (see repro.launch.dryrun).

The compute/memory terms are RECOMPUTED here from each record's raw HLO
flops/bytes under the repo's unified roofline constants
(`repro.obs.constants` — the single definition every modeled time divides
by), so a constants change re-prices old dry-run artifacts instead of
reading terms frozen at record-production time. `--calib-db` prices them at
a fitted `CalibrationDB`'s measured effective constants instead (the
('conv','dense') key — dry-run programs are whole-network XLA, the plain
dense family); records predating the raw fields fall back to their recorded
terms. The collective term always comes from the record: link bandwidth is
a topology constant, not a roofline one.
"""
from __future__ import annotations

import argparse
import json
import pathlib

from repro.obs.constants import DEVICE_PEAKS

ROOT = pathlib.Path(__file__).resolve().parents[1]
DRY = ROOT / "experiments" / "dryrun"


def load_records(mesh: str | None = None):
    recs = []
    for p in sorted(DRY.glob("*.json")):
        r = json.loads(p.read_text())
        if mesh and r.get("mesh") != mesh:
            continue
        recs.append(r)
    return recs


def reprice(rec: dict, calibration=None) -> dict:
    """Record with compute/memory terms recomputed from the raw per-device
    HLO flops/bytes under the unified (or calibrated) constants; the
    dominant term is re-derived to match. No-op for error/skip records and
    for old records without the raw fields."""
    if rec.get("status") != "ok" or "hlo_flops_per_device" not in rec:
        return rec
    consts = DEVICE_PEAKS["TPU v5 lite"] if calibration is None else \
        calibration.constants_for("conv", "dense")
    out = dict(rec)
    out["compute_term_s"] = rec["hlo_flops_per_device"] / consts.peak_flops
    out["memory_term_s"] = rec["hlo_bytes_per_device"] / consts.hbm_bw
    terms = {"compute": out["compute_term_s"],
             "memory": out["memory_term_s"],
             "collective": rec.get("collective_term_s", 0.0)}
    out["dominant_term"] = max(terms, key=terms.get)
    return out


def render_table(mesh: str = "16x16", calibration=None) -> str:
    rows = ["| arch | shape | compute s | memory s | collective s | dominant | "
            "MODEL/HLO flops | compile s |",
            "|---|---|---|---|---|---|---|---|"]
    for r in load_records(mesh):
        if r.get("status") == "skipped":
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | — | SKIP: "
                        f"{r['reason'][:60]}… | — | — |")
            continue
        if r.get("status") != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | ERROR {r.get('error','')[:50]} | | | | | |")
            continue
        r = reprice(r, calibration)
        rows.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_term_s']:.3f} | "
            f"{r['memory_term_s']:.3f} | {r['collective_term_s']:.3f} | "
            f"{r['dominant_term']} | {r['useful_flop_ratio']:.2f} | {r['compile_s']:.0f} |")
    return "\n".join(rows)


def main(calib_db: str | None = None):
    calibration = None
    if calib_db:
        from repro.obs.calibrate import CalibrationDB

        calibration = CalibrationDB.load(calib_db)
    for mesh in ("16x16", "2x16x16"):
        recs = load_records(mesh)
        if not recs:
            continue
        ok = [reprice(r, calibration) for r in recs if r.get("status") == "ok"]
        for r in ok:
            mfu_proxy = r["compute_term_s"] / max(
                r["compute_term_s"], r["memory_term_s"], r["collective_term_s"])
            print(f"roofline/{r['arch']}/{r['shape']}/{mesh},0.0,"
                  f"compute={r['compute_term_s']:.3f}s memory={r['memory_term_s']:.3f}s "
                  f"collective={r['collective_term_s']:.3f}s dom={r['dominant_term']} "
                  f"roofline_frac={mfu_proxy:.3f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--calib-db", default=None, metavar="PATH",
                    help="price the terms at a fitted CalibrationDB's "
                         "measured effective constants (obs.calibrate JSON) "
                         "instead of the datasheet defaults")
    args = ap.parse_args()
    main(calib_db=args.calib_db)
