"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Wall times are CPU (this
container); the paper-metric (MAC reduction) and modeled-TPU columns carry the
cross-platform story — see EXPERIMENTS.md §Paper-claims.

``--json [DIR]`` additionally writes one machine-readable BENCH_<module>.json
per module (same rows), each stamped with the producing git SHA + UTC
timestamp + device kind (see `_util.write_bench_json`), so every run appends
an attributable point to the perf trajectory instead of scrolling away.
``--history DB`` (requires ``--json``) goes one step further: after each
module the freshly written BENCH files are ingested into the append-only
perf-history DB (`repro.obs.history.BenchDB`, DESIGN.md §13) — dedup makes
the per-module blanket re-scan free — so `repro-bench check` can gate the
run against the rolling baselines and `repro-bench report` can render the
cross-run trajectory. The
serving benchmark (`serve_vgg19`) always writes its own
BENCH_serve_vgg19.json and is part of the default set; the model-zoo smoke
(`model_zoo`) runs the reduced LeNet/AlexNet/VGG graphs through the planned
pipeline, the weight-sparsity sweep (`sparse_weights`) runs the same
zoo pruned at each target BSR density through the joint planner, and the
scenario sweep (`scenarios`) drives regime-diverse traffic — bursts,
diurnal occupancy drift, hot swap, multi-tenant — through the engine's
telemetry layer, and the kernel microbenchmarks (`kernels_micro`) add the
tile-geometry search + int8 probe over the reduced zoo (BENCH_kernels_micro
carries the floor-check verdict).
"""
from __future__ import annotations

import argparse
import contextlib
import io
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        _util,
        fig2_sparsity,
        fig3_traffic,
        fig9_vgg19,
        fig10_strides,
        fig11_theta,
        fig12_pecr,
        kernels_micro,
        model_zoo,
        roofline,
        scenarios,
        serve_sharded,
        serve_vgg19,
        sparse_weights,
        table3_single_layer,
    )

    modules = [
        ("table3", table3_single_layer),
        ("fig2", fig2_sparsity),
        ("fig3", fig3_traffic),
        ("fig9", fig9_vgg19),
        ("fig10", fig10_strides),
        ("fig11", fig11_theta),
        ("fig12", fig12_pecr),
        ("kernels", kernels_micro),
        ("roofline", roofline),
        ("zoo", model_zoo),
        ("sparse_weights", sparse_weights),
        ("serve", serve_vgg19),
        ("scenarios", scenarios),
        # jax is initialized by the imports above, so the sharded sweep sees
        # however many devices the operator's XLA_FLAGS exposed (1 by
        # default — the full 1/2/4 sweep runs in the dedicated CI job)
        ("serve_sharded", serve_sharded),
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single module (short name, e.g. fig9)")
    ap.add_argument("--json", nargs="?", const=".", default=None, metavar="DIR",
                    help="also write BENCH_<module>.json files (default: cwd)")
    ap.add_argument("--history", default=None, metavar="DB",
                    help="perf-history BenchDB (JSONL) to auto-ingest each "
                         "module's BENCH json into (requires --json)")
    args = ap.parse_args()
    if args.history and args.json is None:
        ap.error("--history requires --json (the BENCH files are what gets "
                 "ingested)")
    history = None
    if args.history:
        from repro.obs.history import BenchDB

        history = BenchDB(args.history)

    print("name,us_per_call,derived")
    for name, mod in modules:
        if args.only and name != args.only:
            continue
        # these benchmarks write their own (richer) BENCH json; same dir
        own_json = name in ("serve", "serve_sharded", "sparse_weights",
                            "scenarios", "kernels")
        kwargs = {"json_dir": args.json} if (args.json and own_json) else {}
        t0 = time.time()
        if args.json is None:
            mod.main(**kwargs)
        else:
            buf = io.StringIO()
            try:
                with contextlib.redirect_stdout(buf):
                    mod.main(**kwargs)
            finally:
                print(buf.getvalue(), end="")  # keep partial rows on a crash
            if not own_json:  # serving benchmarks already wrote richer json
                _util.write_bench_json(name, _util.parse_csv_rows(buf.getvalue()),
                                       args.json)
            if history is not None:
                # blanket re-scan of the output dir: dedup skips everything
                # already ingested, so only this module's fresh points land
                n_new = sum(history.ingest_dir(args.json).values())
                print(f"_meta/{name}_history,{n_new},points ingested into "
                      f"{args.history}")
        # wall time in SECONDS, as the name says (it was scaled 1e6 into
        # microseconds before PR 10 while still claiming _wall_s)
        print(f"_meta/{name}_wall_s,{time.time()-t0:.3f},benchmark module wall time (seconds)")


if __name__ == "__main__":
    main()
