"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload vgg19_96.closed32 --seed 7 \
        --seconds 10 --trace 0

The cell is an entry of `workloads` in BENCHMARK.json. The last line of
standard output is the result, one JSON object; everything else goes to
standard error, and its last lines are the numbers that decide `correct`,
each beside its limit. Exits non-zero, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for, or the system under test is
missing.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    try:
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.NoChip as e:
        harness.log(f"no result: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
