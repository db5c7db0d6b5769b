"""Readings that a cell's limit is set from, on the chip, in one process.

    python3 chipbench/control.py --workload vgg19_96.closed32 \
        --seeds 11,12,13,14,15,16,17,18,19,20,21,22 \
        --controls program_int8,int8,bfloat16 --control-seeds 3 \
        --seconds 3 --out control_vgg19_96.closed32.json

For every seed: one run of the cell as the benchmark makes it (a shorter
window), and its `logit_err`, the program's reading. For the first
`--control-seeds` seeds also one run with each control in the program's
place (`harness.CONTROLS`: the program's own int8 kernels, or the plain
reference with int8 or bfloat16 operands), read and judged by the same
comparison. The benchmark's own runs never run a control.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--controls", default="int8",
                    help="comma-separated controls, each run on the first seeds")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None, help="also write the readings here")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [c for c in args.controls.split(",") if c]
    runs = [(seed, None) for seed in seeds] + [
        (seed, c) for c in controls for seed in seeds[:args.control_seeds]]
    readings = []
    for k, (seed, control) in enumerate(runs):
        t = time.monotonic() if k else T_START
        try:
            out = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, t, control=control)
        except Exception as e:  # a control that crashes has failed
            if control is None:
                raise
            row = {"seed": seed, "control": control, "correct": False,
                   "logit_err": None, "error": f"{type(e).__name__}: {e}"}
            readings.append(row)
            print(json.dumps(row), flush=True)
            continue
        row = {"seed": seed, "control": control, "correct": out["correct"],
               "attempted": out["attempted"],
               "logit_err": out["checks"]["logit_err"]["value"],
               "metrics": {m: v["value"] for m, v in out["metrics"].items()}}
        readings.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "readings": readings}
    for c in [None] + controls:
        errs = [r["logit_err"] for r in readings
                if r["control"] == c and r["logit_err"] is not None]
        summary[c or "program"] = {
            "min": min(errs, default=None), "max": max(errs, default=None),
            "correct": sum(r["correct"] for r in readings if r["control"] == c),
            "runs": len(errs)}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: v for k, v in summary.items() if k != "readings"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
