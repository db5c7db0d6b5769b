"""Observability: kernel-level tracing, profiling, cost-model calibration.

The layer below `serving.metrics` (which aggregates the REQUEST stream):
this package observes the EXECUTION itself and closes the loop back into
the planner (DESIGN.md §9):

- `trace`     span tracer (plan -> compile -> per-batch execute ->
              per-layer kernel), deterministic on a SimClock, exported as
              Chrome trace_event JSON loadable in Perfetto;
- `profile`   the wall-time harness (jit warm-up, block_until_ready,
              median-of-k with outlier rejection — shared with
              `serving.autotune`) and `profile_plan`, which times every
              layer of a `PipelinePlan` per impl at its real shapes and
              pairs each measurement with the registry's modeled time;
- `calibrate` `CalibrationDB`: effective roofline constants fitted per
              (device kind x op kind x impl x block geometry) from a
              `ProfileReport`, consumed by `unit_model_us` /
              `plan_model_us` / `plan_network` via `calibration=` — the
              hard-coded `constants` defaults stay the fallback, so an
              empty DB is bit-identical to no calibration;
- `constants` the ONE definition of the datasheet roofline pair every
              modeled time in the repo divides by;
- `tilesearch` the per-layer kernel-geometry search (`tile_search`): price
              candidate `TileConfig`s on the (re-measured-occupancy)
              roofline, wall-time the survivors, persist measured-best
              winners into the CalibrationDB tiles table for
              `plan_network(tiles=...)` — closing measure -> search -> plan;
- `history`   the CROSS-RUN layer (DESIGN.md §13): `BenchDB` append-only
              JSONL trajectory of every BENCH_*.json / telemetry /
              profile / calibration point, noise-aware rolling-baseline
              verdicts, and the `repro-bench` CLI whose `check` is the CI
              regression gate.

Entry points: `launch/serve_cnn.py --trace-out/--calibrate/--tile-search/
--history`, `benchmarks/cost_model.py` (predicted-vs-measured regression
artifact), `benchmarks/kernels_micro.py` (tile-search sweep + floor),
`benchmarks/run.py --history` (auto-ingest), `python -m
repro.obs.history.cli` (repro-bench), `Engine(tracer=..., calibration=...)`
/ `Engine.profile()`.
"""
from repro.obs.calibrate import CalibEntry, CalibrationDB, device_kind, unit_shape_key
from repro.obs.history import (
    BenchDB,
    Thresholds,
    calibration_rows,
    check_db,
    make_payload,
    profile_rows,
    telemetry_rows,
)
from repro.obs.constants import (
    CPU_TEST_PRIOR,
    DEVICE_PEAKS,
    RooflineConstants,
    device_peaks,
    peaks_for,
)
from repro.obs.profile import (
    PROFILE_IMPLS,
    LayerTiming,
    ProfileReport,
    TimingResult,
    profile_plan,
    time_callable,
)
from repro.obs.tilesearch import (
    LayerTileSearch,
    TileCandidate,
    TileSearchReport,
    layer_tile_candidates,
    search_layer,
    tile_search,
)
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer

__all__ = [
    "BenchDB",
    "CalibEntry",
    "CalibrationDB",
    "CPU_TEST_PRIOR",
    "DEVICE_PEAKS",
    "LayerTileSearch",
    "LayerTiming",
    "NULL_TRACER",
    "NullTracer",
    "PROFILE_IMPLS",
    "ProfileReport",
    "RooflineConstants",
    "device_peaks",
    "peaks_for",
    "Thresholds",
    "TileCandidate",
    "TileSearchReport",
    "TimingResult",
    "Tracer",
    "calibration_rows",
    "check_db",
    "device_kind",
    "layer_tile_candidates",
    "make_payload",
    "profile_plan",
    "profile_rows",
    "search_layer",
    "telemetry_rows",
    "tile_search",
    "time_callable",
    "unit_shape_key",
]
