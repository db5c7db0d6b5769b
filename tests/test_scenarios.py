"""Telemetry + scenario library: MetricsTracker/LatencyReservoir semantics,
seeded-scenario determinism, and the per-regime serving contracts —
diurnal drift re-plans within K batches to the plan `plan_network` would
pick at the drifted occupancy, bursts never strand a request, multi-tenant
streams over one shared PlanCache never cross-contaminate, hot swap is
atomic under load, and identical seeded replays are bit-identical
including metric snapshots (the BENCH-diff regression contract)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vgg19_sparse import CNNConfig
from repro.models.cnn import init_cnn
from repro.pipeline import plan_network, run_plan
from repro.serving import (
    DiurnalDriftScenario,
    Engine,
    HotSwapScenario,
    LatencyReservoir,
    ListScenario,
    MetricsTracker,
    MultiTenantScenario,
    PlanCache,
    PoissonBurstScenario,
    SimClock,
    TenantSpec,
    plan_key,
    replay_scenario,
    replay_stream,
    synth_image,
)

TINY = CNNConfig(name="vgg-serve-tiny", in_channels=16, img_size=12,
                 plan=((8, 1), (16, 1)), n_classes=4)
SHAPE = (16, TINY.img_size, TINY.img_size)
SERVICE_S = 0.002  # deterministic service-time model for every sim replay


@pytest.fixture(scope="module")
def params():
    return init_cnn(jax.random.PRNGKey(0), TINY)


def _engine(params, *, dead_frac=0.5, seed=900, **kw):
    """Scenario engine planned at the `dead_frac` regime, on a SimClock with
    the deterministic service model (so whole replays — logits AND metric
    snapshots — are pure functions of the seeds)."""
    kw.setdefault("calib", jnp.stack([synth_image(SHAPE, seed, i, dead_frac)
                                      for i in range(2)]))
    kw.setdefault("occ_threshold", 0.9)
    kw.setdefault("block_c", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("deadline_s", 0.005)
    kw.setdefault("clock", SimClock())
    kw.setdefault("sim_service_s", SERVICE_S)
    kw.setdefault("ema_alpha", 0.5)
    kw.setdefault("replan_band", 0.15)
    kw.setdefault("replan_cooldown", 0)
    return Engine(params, TINY, **kw)


# ---------------------------------------------------------------------------
# MetricsTracker / LatencyReservoir
# ---------------------------------------------------------------------------


def test_latency_reservoir_percentiles_exact_when_unsaturated():
    """count <= size: every latency is in the sample, so the percentiles are
    numpy's linear-interpolated values exactly."""
    r = LatencyReservoir(size=256)
    vals = [i / 1e3 for i in range(1, 101)]  # 1..100 ms, in seconds
    for v in vals:
        r.add(v)
    p = r.percentiles_ms()
    ref = np.array(vals) * 1e3
    assert p["count"] == 100
    assert p["mean_ms"] == pytest.approx(float(ref.mean()))
    assert p["max_ms"] == pytest.approx(100.0)
    for q in (50, 95, 99):
        assert p[f"p{q}_ms"] == pytest.approx(float(np.percentile(ref, q)))
    empty = LatencyReservoir().percentiles_ms()
    assert empty == {"count": 0, "mean_ms": 0.0, "max_ms": 0.0,
                     "p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}


def test_latency_reservoir_bounded_and_seed_deterministic():
    """Beyond `size` the sample stays bounded (algorithm R) while count/mean/
    max stay exact, and the seeded PRNG makes two identical streams sample
    identically — the snapshot-determinism contract."""
    a, b = LatencyReservoir(size=8, seed=3), LatencyReservoir(size=8, seed=3)
    for i in range(1000):
        a.add(i * 1e-3)
        b.add(i * 1e-3)
    assert len(a.values) == 8 and a.count == 1000
    assert a.values == b.values
    assert a.percentiles_ms() == b.percentiles_ms()
    assert a.percentiles_ms()["max_ms"] == pytest.approx(999.0)
    with pytest.raises(ValueError):
        LatencyReservoir(size=0)


def test_metrics_tracker_snapshot_counts_and_json():
    t = MetricsTracker()
    t.on_submit()
    t.on_submit()
    t.on_batch(0.01, bucket=4, n_real=3, service_s=SERVICE_S)
    t.on_result(0.010)
    t.on_result(0.009)
    t.on_occupancy(0.01, np.array([0.5, 1.0]))
    t.on_replan_trigger(0.02, delta=0.3)
    t.on_replan_swap(0.03, changed=True)
    t.on_replan_error(0.04)
    t.on_hot_swap(0.05)
    s = t.snapshot()
    assert s["submitted"] == 2 and s["completed"] == 2 and s["batches"] == 1
    assert s["pad_samples"] == 1 and s["mean_fill"] == pytest.approx(0.75)
    assert s["bucket_counts"] == {"4": 1}
    assert s["service_s_total"] == pytest.approx(SERVICE_S)
    assert s["occ_timeline"] == [[0.01, [0.5, 1.0]]]
    assert [e["kind"] for e in s["replan_events"]] == [
        "trigger", "swap", "error", "hot_swap"]
    assert s["replans"] == {"triggers": 1, "swaps": 1, "errors": 1,
                            "hot_swaps": 1, "verify_rejects": 0}
    json.dumps(s)  # the whole snapshot must be JSON-serializable verbatim


def test_metrics_tracker_timelines_are_bounded():
    t = MetricsTracker(timeline_max=4)
    for i in range(10):
        t.on_occupancy(float(i), [0.5])
        t.on_replan_trigger(float(i), 0.2)
    s = t.snapshot()
    assert [row[0] for row in s["occ_timeline"]] == [6.0, 7.0, 8.0, 9.0]
    assert len(s["replan_events"]) == 4  # most recent kept, count stays exact
    assert s["replans"]["triggers"] == 10


def test_engine_stats_latency_covers_flush_tail(params):
    """A lone request completed only by drain() (never poll()) must reach the
    percentile accounting — the flush tail used to escape it entirely."""
    eng = _engine(params)
    eng.submit(synth_image(SHAPE, 1, 0))
    eng.clock.advance(0.001)
    assert eng.poll() == []  # not due: nothing completed through poll
    results = eng.drain()
    assert len(results) == 1
    st = eng.stats()
    assert st["lat_count"] == 1
    expect_ms = results[0].latency_s * 1e3
    assert st["p50_ms"] == pytest.approx(expect_ms)
    assert st["p99_ms"] == pytest.approx(expect_ms)
    assert st["mean_ms"] == pytest.approx(expect_ms)
    tel = st["telemetry"]
    assert tel["completed"] == 1 and tel["submitted"] == 1
    assert tel["bucket_counts"] == {"2": 1}  # min_bucket pad, not a 1-bucket


# ---------------------------------------------------------------------------
# scenario definitions: seeded determinism + regime shapes
# ---------------------------------------------------------------------------


def test_scenario_requests_deterministic_per_seed():
    def arrivals(seed):
        return [r.t for r in PoissonBurstScenario(
            in_shape=SHAPE, n_requests=12, seed=seed).requests()]

    assert arrivals(5) == arrivals(5)
    assert arrivals(5) != arrivals(6)
    ts = arrivals(5)
    assert all(b > a for a, b in zip(ts, ts[1:]))  # strictly increasing
    a = PoissonBurstScenario(in_shape=SHAPE, n_requests=3, seed=5).requests()
    b = PoissonBurstScenario(in_shape=SHAPE, n_requests=3, seed=5).requests()
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra.img, rb.img)


def test_burst_rate_modulation():
    s = PoissonBurstScenario(base_rps=50.0, burst_rps=800.0,
                             burst_every_s=0.1, burst_len_s=0.03)
    assert s.rate_at(0.01) == 800.0  # inside the burst window
    assert s.rate_at(0.05) == 50.0  # between bursts
    assert s.rate_at(0.11) == 800.0  # the cycle repeats


def test_diurnal_dead_frac_profiles():
    step = DiurnalDriftScenario(dead_lo=0.5, dead_hi=0.0, drift="step",
                                t_drift=0.05)
    assert step.dead_frac_at(0.049) == 0.5
    assert step.dead_frac_at(0.05) == 0.0
    sine = DiurnalDriftScenario(dead_lo=0.1, dead_hi=0.7, drift="sine",
                                period_s=0.2)
    assert sine.dead_frac_at(0.0) == pytest.approx(0.1)
    assert sine.dead_frac_at(0.1) == pytest.approx(0.7)  # half period: peak
    assert sine.dead_frac_at(0.2) == pytest.approx(0.1)  # full cycle returns
    with pytest.raises(ValueError, match="drift"):
        DiurnalDriftScenario(drift="linear").dead_frac_at(0.0)


def test_scenario_constructor_validation():
    with pytest.raises(ValueError, match="one arrival per image"):
        ListScenario(imgs=(1, 2), arrivals=(0.0,))
    with pytest.raises(ValueError, match="swap_fn"):
        HotSwapScenario(in_shape=SHAPE)
    # ListScenario orders by arrival regardless of construction order
    s = ListScenario(imgs=("b", "a"), arrivals=(2.0, 1.0))
    assert [r.img for r in s.requests()] == ["a", "b"]
    assert s.streams() == ("",)


def test_replay_scenario_validates_clock_and_streams(params):
    eng = _engine(params)
    other = _engine(params)  # its own SimClock: not shared
    with pytest.raises(ValueError, match="ONE shared"):
        replay_scenario({"a": eng, "b": other},
                        ListScenario(imgs=(), arrivals=()))
    with pytest.raises(ValueError, match="SimClock"):
        replay_scenario({"a": _Fake()},  # wall clock: not replayable
                        ListScenario(imgs=(), arrivals=()))
    scn = ListScenario(imgs=(synth_image(SHAPE, 1, 0),), arrivals=(0.0,),
                       stream="ghost")
    with pytest.raises(ValueError, match="ghost"):
        replay_scenario(eng, scn)


class _Fake:
    """Engine stand-in whose clock is the (non-Sim) wall clock."""

    def __init__(self):
        import time

        self.clock = time.monotonic


# ---------------------------------------------------------------------------
# replay driver: wrapper equivalence + bit-identical determinism
# ---------------------------------------------------------------------------


def test_replay_stream_is_thin_wrapper_over_replay_scenario(params):
    """The steady-rate stream is the degenerate ListScenario: both drivers
    must produce identical results AND identical telemetry."""
    imgs = [synth_image(SHAPE, 3, i) for i in range(8)]
    rate = 300.0
    e1, e2 = _engine(params), _engine(params)
    r1 = replay_stream(e1, imgs, rate_rps=rate)
    arrivals = tuple(i / rate for i in range(len(imgs)))
    r2 = replay_scenario(e2, ListScenario(imgs=tuple(imgs),
                                          arrivals=arrivals))[""]
    assert [r.id for r in r1] == [r.id for r in r2]
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert (a.t_arrival, a.t_formed, a.t_done) == \
            (b.t_arrival, b.t_formed, b.t_done)
    assert e1.stats()["telemetry"] == e2.stats()["telemetry"]


def test_seeded_replay_is_bit_identical_including_snapshot(params):
    """Two identical seeded replays on the deterministic service model are
    indistinguishable: logits bit-identical AND `snapshot()` == — what makes
    a BENCH_scenarios.json diff a regression signal instead of noise."""
    def run():
        eng = _engine(params)
        scn = DiurnalDriftScenario(in_shape=SHAPE, n_requests=16,
                                   rate_rps=200.0, dead_lo=0.5, dead_hi=0.0,
                                   drift="step", t_drift=0.04, seed=7)
        results = replay_scenario(eng, scn)[""]
        return results, eng.stats()

    r1, s1 = run()
    r2, s2 = run()
    assert [r.id for r in r1] == [r.id for r in r2]
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.logits, b.logits)
        assert a.latency_s == b.latency_s
    assert s1["telemetry"] == s2["telemetry"]
    assert s1["occ_ema"] == s2["occ_ema"]


# ---------------------------------------------------------------------------
# the regime contracts
# ---------------------------------------------------------------------------


def test_diurnal_drift_replans_within_k_batches_to_reference_plan(params):
    """The tentpole contract: an engine planned at the dead_lo regime whose
    traffic steps to dead_hi must (a) trigger a re-plan within K executed
    batches of the drift onset and (b) land on the SAME schedule
    `plan_network` picks when calibrated at the drifted occupancy."""
    eng = _engine(params)
    key_before = plan_key(0, eng.plan)
    scn = DiurnalDriftScenario(in_shape=SHAPE, n_requests=24, rate_rps=200.0,
                               dead_lo=0.5, dead_hi=0.0, drift="step",
                               t_drift=0.03, seed=5)
    replay_scenario(eng, scn)
    st = eng.stats()
    assert st["replans"] >= 1
    tel = st["telemetry"]
    triggers = [e for e in tel["replan_events"] if e["kind"] == "trigger"]
    swaps = [e for e in tel["replan_events"]
             if e["kind"] == "swap" and e["changed"]]
    assert triggers and swaps
    assert triggers[0]["delta"] > eng.replan_band
    # (a) within K batches: occ_timeline has one row per executed batch
    k = sum(1 for t, _ in tel["occ_timeline"]
            if scn.t_drift <= t <= triggers[0]["t"])
    assert 1 <= k <= 4, f"re-plan took {k} post-drift batches"
    # (b) the adopted schedule is the one planning at the drifted occupancy
    # would pick (and it really is a different schedule than dead_lo's)
    drifted_calib = jnp.stack([
        synth_image(SHAPE, scn.seed, i, scn.dead_hi) for i in range(20, 24)])
    ref = plan_network(params, drifted_calib, eng.graph,
                       occ_threshold=eng.plan.occ_threshold,
                       block_c=eng.plan.block_c, use_pallas=eng.use_pallas)
    assert plan_key(0, eng.plan) == plan_key(0, ref)
    assert plan_key(0, eng.plan) != key_before


def test_burst_never_strands_requests(params):
    """A burst queues several full buckets at once; every request must still
    be served exactly once, and its formation wait is bounded by the deadline
    plus the service time of the buckets executed between its arrival and its
    formation (the backlog it legitimately queued behind) — never by the next
    arrival (the stranding failure the drain-every-due-bucket loop prevents)."""
    eng = _engine(params)
    scn = PoissonBurstScenario(in_shape=SHAPE, n_requests=24, base_rps=50.0,
                               burst_rps=2000.0, burst_every_s=0.08,
                               burst_len_s=0.03, seed=11)
    results = replay_scenario(eng, scn)[""]
    assert sorted(r.id for r in results) == list(range(24))  # none lost/dup
    batch_times = sorted({r.t_formed for r in results})
    for r in results:
        backlog = sum(1 for t in batch_times if r.t_arrival < t < r.t_formed)
        bound = eng.batcher.deadline_s + backlog * SERVICE_S + 1e-9
        assert r.t_formed - r.t_arrival <= bound, (
            f"request {r.id} waited {r.t_formed - r.t_arrival:.4f}s "
            f"(bound {bound:.4f}s, backlog {backlog})")
    # the burst actually coalesced: at least one full bucket formed
    assert max(eng.metrics.bucket_counts) == eng.batcher.max_batch


def test_multi_tenant_shared_cache_never_cross_contaminates(params):
    """Two models interleaved over ONE PlanCache: compiles bounded by the
    distinct PlanKeys (warmup only — steady streams add none), and every
    tenant's logits are bit-identical to ITS OWN model's run_plan reference."""
    from repro.configs.lenet import LENET_REDUCED
    from repro.graph import init_graph

    clock = SimClock()
    cache = PlanCache(max_entries=32)
    eng_vgg = _engine(params, clock=clock, cache=cache)
    lenet_graph = LENET_REDUCED
    lenet_params = init_graph(jax.random.PRNGKey(1), lenet_graph)
    lenet_calib = jnp.stack([synth_image(lenet_graph.in_shape, 901, i, 0.5)
                             for i in range(2)])
    eng_lenet = Engine(lenet_params, graph=lenet_graph, calib=lenet_calib,
                       occ_threshold=0.9, block_c=8, max_batch=4,
                       deadline_s=0.005, clock=clock, cache=cache,
                       sim_service_s=SERVICE_S, ema_alpha=0.5,
                       replan_band=0.15, replan_cooldown=0)
    engines = {"vgg": eng_vgg, "lenet": eng_lenet}
    warm = sum(e.warmup() for e in engines.values())
    assert warm == cache.compiles == len(cache)  # all keys distinct: no alias
    scn = MultiTenantScenario(tenants=(
        ("vgg", TenantSpec(in_shape=SHAPE, n_requests=6, rate_rps=100.0,
                           dead_frac=0.5)),
        ("lenet", TenantSpec(in_shape=lenet_graph.in_shape, n_requests=6,
                             rate_rps=100.0, dead_frac=0.5))), seed=13)
    results = replay_scenario(engines, scn)
    assert cache.compiles == warm  # shared cache: zero stream compiles
    for stream, eng in engines.items():
        assert eng.stats()["replans"] == 0  # steady regime: no drift
        tenant_reqs = [r for r in scn.requests() if r.stream == stream]
        ref = np.asarray(run_plan(eng.plan, eng.params,
                                  jnp.stack([r.img for r in tenant_reqs])))
        got = {r.id: r.logits for r in results[stream]}
        assert sorted(got) == list(range(len(tenant_reqs)))
        for i in range(len(tenant_reqs)):  # ids are per-engine submission order
            np.testing.assert_array_equal(got[i], ref[i])


def test_hot_swap_under_load_is_atomic(params):
    """Mid-stream swap to a BSR-pruned variant: every request completed
    before the swap carries the OLD model's exact logits, every one after
    carries the NEW model's, no bucket mixes the two, and both variants'
    programs end up resident in one cache."""
    from repro.sparse_weights import prune_graph_params

    eng = _engine(params)
    plan_old, params_old = eng.plan, eng.params
    pruned, report = prune_graph_params(params, 0.3, eng.graph)
    assert report.density <= 0.5  # the swap is a genuinely different model
    plan_new = plan_network(pruned, jnp.stack(
        [synth_image(SHAPE, 900, i, 0.5) for i in range(2)]), eng.graph,
        occ_threshold=eng.plan.occ_threshold, block_c=eng.plan.block_c,
        use_pallas=eng.use_pallas)

    def swap(engines):
        engines[""].hot_swap(pruned, plan=plan_new)

    n = 16
    scn = HotSwapScenario(in_shape=SHAPE, n_requests=n, rate_rps=200.0,
                          t_swap=0.04, swap_fn=swap, seed=17)
    results = replay_scenario(eng, scn)[""]
    assert sorted(r.id for r in results) == list(range(n))
    st = eng.stats()
    assert st["hot_swaps"] == 1 and st["plan_bsr"] >= 1
    swap_t = [e for e in st["telemetry"]["replan_events"]
              if e["kind"] == "hot_swap"][0]["t"]
    imgs = jnp.stack([r.img for r in scn.requests()])  # id == arrival order
    ref_old = np.asarray(run_plan(plan_old, params_old, imgs))
    ref_new = np.asarray(run_plan(plan_new, pruned, imgs))
    pre = [r for r in results if r.t_done <= swap_t]
    post = [r for r in results if r.t_done > swap_t]
    assert pre and post, "t_swap must land mid-stream to test atomicity"
    for r in pre:
        np.testing.assert_array_equal(r.logits, ref_old[r.id])
    for r in post:
        np.testing.assert_array_equal(r.logits, ref_new[r.id])
    # both variants' programs coexist: the pruned plan's keys are new entries
    assert plan_key(0, plan_new) != plan_key(0, plan_old)


def test_hot_swap_before_first_batch_requires_calib(params):
    eng = _engine(params)
    with pytest.raises(ValueError, match="hot_swap"):
        eng.hot_swap(params)  # no executed batch yet: no recent calib
    calib = jnp.stack([synth_image(SHAPE, 900, i, 0.5) for i in range(2)])
    eng.hot_swap(params, calib=calib)
    assert eng.n_hot_swaps == 1
    assert eng.stats()["telemetry"]["replans"]["hot_swaps"] == 1
