"""Serving subsystem: batcher deadline contract, plan-cache compile counts,
engine-vs-run_plan exactness, occupancy-drift re-planning, autotune selection,
and the planner edge cases serving relies on (validation, occ_threshold=0,
block_c override, batch=1 occupancy)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vgg19_sparse import CNNConfig
from repro.kernels.ecr_conv.ops import channel_block_occupancy
from repro.models.cnn import init_cnn
from repro.pipeline import measure_occupancy, plan_network, run_plan
from repro.serving import (
    Engine,
    MicroBatcher,
    SimClock,
    autotune,
    bucket_sizes,
    plan_key,
    replay_stream,
)

TINY = CNNConfig(name="vgg-serve-tiny", in_channels=16, img_size=12,
                 plan=((8, 1), (16, 1)), n_classes=4)


@pytest.fixture(scope="module")
def params():
    return init_cnn(jax.random.PRNGKey(0), TINY)


def _img(seed, dead=8):
    """Single request image; `dead` trailing channels are zero. All test
    requests share one dead-channel band, so the shared-union compaction
    permutation is identical for ANY subset of them — the condition under
    which engine batching is bit-exact against the whole-batch reference."""
    x = np.array(jax.random.uniform(jax.random.PRNGKey(seed),
                                    (16, TINY.img_size, TINY.img_size)), np.float32)
    if dead:
        x[16 - dead:] = 0.0
    return jnp.asarray(x)


def _engine(params, **kw):
    kw.setdefault("calib", jnp.stack([_img(900), _img(901)]))
    kw.setdefault("occ_threshold", 0.9)
    kw.setdefault("block_c", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("deadline_s", 0.005)
    kw.setdefault("clock", SimClock())
    return Engine(params, TINY, **kw)


# ---------------------------------------------------------------------------
# batcher: buckets and the deadline contract (simulated clock)
# ---------------------------------------------------------------------------


def test_bucket_sizes():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)  # non-pow2 cap HONORED, not clamped
    assert bucket_sizes(1) == (1,)
    with pytest.raises(ValueError):
        bucket_sizes(0)


def test_batcher_honors_non_power_of_two_max_batch():
    """max_batch=6 used to be silently clamped to 4; the requested cap must
    now be a real bucket (a full 6-queue forms one 6-batch, not 4 + leftovers)."""
    clock = SimClock()
    b = MicroBatcher(max_batch=6, deadline_s=1.0, clock=clock)
    assert b.max_batch == 6 and b.buckets == (1, 2, 4, 6)
    for i in range(6):
        b.submit(i)
    batch = b.ready()  # full bucket dispatches immediately at the true cap
    assert batch is not None and batch.n_real == 6 and batch.bucket == 6
    assert b.pending() == 0
    b.submit(99)
    clock.advance(1.1)
    assert b.ready().bucket == 2  # pow2 buckets below the cap still serve


def test_batcher_align_device_slices():
    """align=N (sharded serving): executed buckets are N-multiples whose
    per-device slice keeps the min_bucket bit-exactness floor."""
    b = MicroBatcher(max_batch=8, deadline_s=1.0, clock=SimClock(), align=4)
    assert b.exec_buckets() == (8,)  # 8/4 = 2 >= min_bucket; 4/4 = 1 < floor
    assert b.bucket_for(1) == 8 and b.bucket_for(8) == 8
    b2 = MicroBatcher(max_batch=8, deadline_s=1.0, clock=SimClock(), align=2)
    assert b2.exec_buckets() == (4, 8)
    assert b2.bucket_for(3) == 4
    with pytest.raises(ValueError, match="multiple of"):
        MicroBatcher(max_batch=6, deadline_s=1.0, clock=SimClock(), align=4)
    # a full bucket that would leave shards below the min_bucket floor must
    # REFUSE, not silently clamp away the bit-exactness contract
    with pytest.raises(ValueError, match="floor"):
        MicroBatcher(max_batch=8, deadline_s=1.0, clock=SimClock(), align=8)
    b3 = MicroBatcher(max_batch=8, deadline_s=1.0, clock=SimClock(), align=8,
                      min_bucket=1)  # explicit opt-in to M=1 shards
    assert b3.exec_buckets() == (8,)


def test_batcher_full_bucket_dispatches_immediately():
    clock = SimClock()
    b = MicroBatcher(max_batch=4, deadline_s=1.0, clock=clock)
    for i in range(4):
        b.submit(i)
    batch = b.ready()  # no time has passed: full bucket, not the deadline
    assert batch is not None and batch.n_real == 4 and batch.bucket == 4
    assert b.pending() == 0


def test_batcher_never_exceeds_deadline_simulated_clock():
    """Drive a jittery arrival pattern; every request must be FORMED into a
    batch within deadline_s of its arrival, provided the driver polls by
    next_deadline() — the engine/replay_stream contract."""
    clock = SimClock()
    deadline = 0.010
    b = MicroBatcher(max_batch=4, deadline_s=deadline, clock=clock)
    arrivals = [0.0, 0.001, 0.002, 0.015, 0.0151, 0.04, 0.08, 0.0805, 0.081,
                0.0815, 0.0816, 0.3]
    formed = {}  # id -> (t_arrival, t_formed)
    i = 0
    while len(formed) < len(arrivals):
        t_arr = arrivals[i] if i < len(arrivals) else None
        t_dl = b.next_deadline()
        if t_arr is not None and (t_dl is None or t_arr <= t_dl):
            clock.set(t_arr)
            b.submit(i, now=t_arr)
            i += 1
        else:
            clock.set(t_dl)
        while True:
            batch = b.ready()
            if batch is None:
                break
            for r in batch.requests:
                formed[r.id] = (r.t_arrival, batch.t_formed)
    waits = [tf - ta for ta, tf in formed.values()]
    assert max(waits) <= deadline + 1e-12
    assert len(formed) == len(arrivals)


def test_batcher_pads_to_power_of_two_buckets():
    clock = SimClock()
    b = MicroBatcher(max_batch=8, deadline_s=0.01, clock=clock, min_bucket=1)
    for i in range(3):
        b.submit(i)
    clock.advance(0.011)
    batch = b.ready()
    assert batch.n_real == 3 and batch.bucket == 4  # ragged tail pads 3 -> 4
    b.submit(99)
    clock.advance(0.02)
    assert b.ready().bucket == 1  # min_bucket=1 admits the single bucket
    b2 = MicroBatcher(max_batch=8, deadline_s=0.01, clock=clock)  # default floor
    b2.submit(1)
    clock.advance(0.02)
    assert b2.ready().bucket == 2  # lone request pads to the 2-bucket


# ---------------------------------------------------------------------------
# engine: exactness against run_plan + compile counting
# ---------------------------------------------------------------------------


def test_engine_matches_run_plan_fp32_exact(params):
    """Acceptance: N single-image requests through the engine == run_plan on
    the same images, bit-for-bit, across ragged buckets (5 -> [4, 2-padded])."""
    eng = _engine(params)
    imgs = [_img(i) for i in range(5)]
    served = eng.serve(imgs)
    ref = np.asarray(run_plan(eng.plan, params, jnp.stack(imgs), TINY))
    assert served.dtype == np.float32
    assert np.array_equal(served, ref)
    assert eng.stats()["pad_samples"] > 0  # the ragged tail really was padded


def test_engine_poll_drains_burst_of_full_buckets(params):
    """A burst of 3x max_batch requests leaves three full buckets due AT
    ONCE; one poll() must drain them all (the old one-batch-per-poll loop
    stranded the rest until the next deadline poll, so a queued request
    could wait arbitrarily longer than deadline_s under load)."""
    eng = _engine(params)  # max_batch=4, SimClock
    imgs = [_img(7000 + i) for i in range(12)]
    for img in imgs:
        eng.submit(img)
    results = eng.poll()
    assert len(results) == 12  # every due full bucket served in this poll
    assert eng.batcher.pending() == 0
    assert sorted(r.id for r in results) == list(range(12))
    assert eng.stats()["batches"] == 3
    # and the burst's logits are still the whole-batch reference, per bucket
    ref = np.asarray(run_plan(eng.plan, params, jnp.stack(imgs), TINY))
    by_id = {r.id: r.logits for r in results}
    assert np.array_equal(np.stack([by_id[i] for i in range(12)]), ref)
    assert eng.poll() == []  # nothing left due


@pytest.mark.parametrize("n", [4, 3], ids=["full", "ragged"])
def test_engine_submit_keeps_a_private_host_copy(params, n):
    """submit queues a float32 host array of its own: overwriting the
    caller's buffers afterwards changes nothing that is served."""
    eng = _engine(params)  # max_batch=4
    imgs = [np.array(_img(5000 + i)) for i in range(n)]
    originals = [x.copy() for x in imgs]
    for x in imgs:
        eng.submit(x)
    for x, r in zip(imgs, eng.batcher._q):
        assert isinstance(r.img, np.ndarray) and r.img.dtype == np.float32
        assert not np.shares_memory(r.img, x)
        x[:] = 1e6  # the caller reuses its buffer
    served = {r.id: r.logits for r in eng.drain()}
    ref = np.asarray(run_plan(eng.plan, params, jnp.stack(originals), TINY))
    assert np.array_equal(np.stack([served[i] for i in range(n)]), ref)


@pytest.mark.parametrize("n", [4, 3], ids=["full", "ragged"])
def test_engine_batch_reaches_the_device_in_one_transfer(params, monkeypatch, n):
    """A batch is stacked on the host and handed to the device by exactly
    one jax.device_put (the padded bucket and n_valid together); nothing is
    stacked on the device."""
    eng = _engine(params)  # max_batch=4: n=3 pads one all-zero row
    eng.warmup()  # no tracing inside the counted batch
    imgs = [_img(5100 + i) for i in range(n)]
    for img in imgs:
        eng.submit(img)
    puts, real_put = [], jax.device_put

    def counting_put(x, *args, **kw):
        puts.append(x)
        return real_put(x, *args, **kw)

    def no_stack(*args, **kw):
        raise AssertionError("jnp.stack in batch assembly")

    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(jnp, "stack", no_stack)
    results = eng.drain()
    monkeypatch.undo()
    assert len(results) == n and eng.stats()["batches"] == 1
    assert len(puts) == 1
    host, n_valid = puts[0]
    assert isinstance(host, np.ndarray) and host.shape == (4, 16, 12, 12)
    assert np.array_equal(host[:n], np.stack(imgs)) and not host[n:].any()
    assert int(n_valid) == n


def test_engine_calib_recent_is_the_host_batch(params):
    """The last real batch stays on the host (no device slice a batch), and
    the paths that plan or profile on it still run."""
    eng = _engine(params, ema_alpha=0.5, replan_band=0.2, replan_cooldown=0)
    imgs = [_img(5200 + i, dead=0) for i in range(3)]
    eng.serve(imgs)
    calib = eng._calib_recent
    assert isinstance(calib, np.ndarray) and calib.shape == (3, 16, 12, 12)
    assert np.array_equal(calib, np.stack(imgs))
    eng.profile(iters=1, warmup=0)
    assert eng.stats()["telemetry"]["profile"] is not None
    for wave in range(2):  # dense traffic drifts from the sparse plan
        eng.serve([_img(5300 + wave * 10 + i, dead=0) for i in range(4)])
    assert eng.n_replans >= 1
    assert all(lp.impl == "dense" for lp in eng.plan.layers)


def test_engine_serve_empty_request_list(params):
    """serve([]) used to crash in np.stack on the empty result list; it must
    return an empty (0, n_classes) float32 array instead."""
    eng = _engine(params)
    out = eng.serve([])
    assert out.shape == (0, TINY.n_classes) and out.dtype == np.float32
    assert eng.stats()["batches"] == 0 and eng.stats()["requests"] == 0
    # and the engine still serves normally afterwards
    assert eng.serve([_img(0)]).shape == (1, TINY.n_classes)


def test_engine_non_power_of_two_max_batch_exact(params):
    """max_batch=6 end-to-end: the cap bucket compiles and stays bit-exact
    against the whole-batch reference."""
    eng = _engine(params, max_batch=6)
    imgs = [_img(7100 + i) for i in range(6)]
    served = eng.serve(imgs)
    ref = np.asarray(run_plan(eng.plan, params, jnp.stack(imgs), TINY))
    assert np.array_equal(served, ref)
    assert eng.stats()["batches"] == 1  # one full 6-bucket, no 4+2 split


def test_engine_exact_on_fully_dense_requests(params):
    """No dead channels at all: compaction is the identity for every batch
    composition, so exactness must hold here too (and the plan goes dense)."""
    eng = _engine(params, occ_threshold=0.5,
                  calib=jnp.stack([_img(900, dead=0), _img(901, dead=0)]))
    assert all(lp.impl == "dense" for lp in eng.plan.layers)
    imgs = [_img(i, dead=0) for i in range(3)]
    served = eng.serve(imgs)
    ref = np.asarray(run_plan(eng.plan, params, jnp.stack(imgs), TINY))
    assert np.array_equal(served, ref)


def test_plan_cache_compiles_each_key_exactly_once(params):
    eng = _engine(params)
    # one program per executable bucket (bucket 1 is floored away, see batcher)
    assert eng.warmup() == len(eng.batcher.exec_buckets())
    compiles = eng.cache.stats()["compiles"]
    for wave in range(3):  # repeat traffic over every bucket shape
        for n in (1, 2, 3, 4, 7):
            eng.serve([_img(1000 + wave * 10 + i) for i in range(n)])
    stats = eng.stats()
    assert stats["compiles"] == compiles  # the stream NEVER compiled
    assert stats["hits"] > 0 and stats["replans"] == 0


def test_plan_cache_lru_eviction_and_counters():
    """Boundedness regression: the cache must evict in LRU order (a hit
    refreshes recency), count every hit/miss/eviction, and recompile an
    evicted key on its next use — graphs x meshes x pruned densities
    multiply keys, so an unbounded cache is a serving memory leak."""
    from repro.serving import PlanCache, PlanKey

    def key(b):
        return PlanKey(bucket=b, block_c=8, occ_sig=(("conv", "dense"),))

    cache = PlanCache(max_entries=2)
    assert cache.get_or_compile(key(1), None, lambda: "exe1") == "exe1"
    assert cache.get_or_compile(key(2), None, lambda: "exe2") == "exe2"
    # hit on key(1) refreshes it: key(2) is now least-recently-used
    assert cache.get_or_compile(key(1), None, lambda: "BUG") == "exe1"
    assert cache.get_or_compile(key(3), None, lambda: "exe3") == "exe3"
    assert key(2) not in cache and key(1) in cache and key(3) in cache
    assert len(cache) == 2
    assert cache.stats() == {"entries": 2, "compiles": 3, "hits": 1,
                             "misses": 3, "evictions": 1}
    # the evicted key is a real miss again: build runs a second time
    assert cache.get_or_compile(key(2), None, lambda: "exe2b") == "exe2b"
    assert cache.stats()["compiles"] == 4 and cache.stats()["evictions"] == 2


def test_plan_key_distinguishes_schedule_not_occupancy(params):
    sparse = plan_network(params, jnp.stack([_img(0)]), TINY,
                          occ_threshold=0.9, block_c=8)
    sparse2 = plan_network(params, jnp.stack([_img(1)]), TINY,
                           occ_threshold=0.9, block_c=8)
    dense = plan_network(params, jnp.stack([_img(0, dead=0)]), TINY,
                         occ_threshold=0.9, block_c=8)
    assert plan_key(4, sparse) == plan_key(4, sparse2)  # same schedule: one program
    assert plan_key(4, sparse) != plan_key(4, dense)
    assert plan_key(4, sparse) != plan_key(2, sparse)


def test_plan_key_one_device_mesh_is_the_unsharded_key(params):
    """A 1-device mesh compiles the same program as no mesh at all, so the
    keys must collide (mesh_shape only appears at >= 2 devices; the sharded
    subprocess tests cover the distinct 2-/4-device keys)."""
    from repro.parallel import data_mesh

    plan = plan_network(params, jnp.stack([_img(0)]), TINY,
                        occ_threshold=0.9, block_c=8)
    assert plan_key(4, plan).mesh_shape == ()
    assert plan_key(4, plan, data_mesh(1)) == plan_key(4, plan)


# ---------------------------------------------------------------------------
# occupancy drift -> re-plan (hysteresis, atomic swap)
# ---------------------------------------------------------------------------


def test_engine_replans_on_occupancy_drift(params):
    """Plan on sparse calibration, then serve dense traffic: the observed
    occupancy EMA leaves the band and the engine re-plans to dense."""
    eng = _engine(params, ema_alpha=0.5, replan_band=0.2, replan_cooldown=0)
    assert any(lp.impl != "dense" for lp in eng.plan.layers)
    old_key = plan_key(0, eng.plan)
    for wave in range(3):
        eng.serve([_img(2000 + wave * 10 + i, dead=0) for i in range(4)])
    assert eng.n_replans >= 1
    assert plan_key(0, eng.plan) != old_key
    assert all(lp.impl == "dense" for lp in eng.plan.layers)


def test_engine_stable_traffic_never_replans(params):
    """Hysteresis: traffic matching the calibration stays inside the band."""
    eng = _engine(params, replan_band=0.2)
    for wave in range(3):
        eng.serve([_img(3000 + wave * 10 + i) for i in range(4)])
    assert eng.n_replans == 0


def test_engine_background_replan_swaps_atomically(params):
    eng = _engine(params, ema_alpha=0.5, replan_band=0.2, replan_cooldown=0,
                  replan_async=True)
    eng.serve([_img(4000 + i, dead=0) for i in range(4)])
    eng.join_replan()  # wait for the worker, then adopt at the swap point
    eng.serve([_img(4100 + i, dead=0) for i in range(4)])
    assert eng.n_replans >= 1
    assert all(lp.impl == "dense" for lp in eng.plan.layers)


def test_replay_stream_latency_accounting(params):
    eng = _engine(params, deadline_s=0.004)
    imgs = [_img(5000 + i) for i in range(6)]
    results = replay_stream(eng, imgs, rate_rps=500.0)
    assert len(results) == len(imgs)
    assert sorted(r.id for r in results) == list(range(6))
    for r in results:
        assert r.t_done >= r.t_arrival  # service time is charged to the clock
        assert np.isfinite(r.latency_s)


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------


def test_autotune_timing_and_model_modes(params):
    calib = jnp.stack([_img(0), _img(1)])
    res = autotune(params, calib, TINY, thresholds=(0.0, 0.9), block_cs=(8,),
                   iters=2, mode="time")
    assert not res.used_model
    assert len(res.candidates) == 2
    assert res.best.wall_us == min(c.wall_us for c in res.candidates)
    # model mode: deterministic fallback ranking; the sparse plan must model
    # faster than all-dense at 50% dead channels (skipped DMA + MACs)
    res_m = autotune(params, calib, TINY, thresholds=(0.0, 0.9), block_cs=(8,),
                     iters=1, mode="model")
    assert res_m.used_model
    by_th = {c.occ_threshold: c for c in res_m.candidates}
    assert by_th[0.9].model_us < by_th[0.0].model_us
    assert res_m.best.occ_threshold == 0.9
    # the tuned plan still executes correctly
    out = run_plan(res_m.plan, params, calib, TINY)
    ref = run_plan(plan_network(params, calib, TINY, occ_threshold=0.0), params,
                   calib, TINY)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-3, atol=1e-3)


def test_autotune_dedupes_identical_schedules(params):
    calib = jnp.stack([_img(0, dead=0)])  # dense input: every threshold agrees
    res = autotune(params, calib, TINY, thresholds=(0.0, 0.5, 0.75), block_cs=(8,),
                   iters=1, mode="time")
    walls = {c.wall_us for c in res.candidates}
    assert len(walls) == 1  # one timing shared across the deduped grid points


# ---------------------------------------------------------------------------
# planner edge cases serving relies on (satellites)
# ---------------------------------------------------------------------------


def test_run_plan_rejects_wrong_input_shape(params):
    plan = plan_network(params, jnp.stack([_img(0)]), TINY)
    bad = jnp.zeros((2, 16, 10, 10), jnp.float32)  # wrong H, W
    with pytest.raises(ValueError, match="calibrated for input shape"):
        run_plan(plan, params, bad, TINY)
    with pytest.raises(ValueError, match=r"\(C,H,W\)"):
        run_plan(plan, params, jnp.zeros((16, 12), jnp.float32), TINY)


def test_run_plan_rejects_mismatched_params(params):
    plan = plan_network(params, jnp.stack([_img(0)]), TINY)
    shallow = {"stages": [params["stages"][0]], "fc1": params["fc1"],
               "fc2": params["fc2"]}
    with pytest.raises(ValueError, match="silently truncate"):
        run_plan(plan, shallow, jnp.stack([_img(1)]), TINY)


def test_run_plan_rejects_negative_block_c(params):
    plan = plan_network(params, jnp.stack([_img(0)]), TINY)
    bad = plan.__class__(layers=plan.layers, occ_threshold=plan.occ_threshold,
                         block_c=-8)
    with pytest.raises(ValueError, match="block_c"):
        run_plan(bad, params, jnp.stack([_img(1)]), TINY)


def test_occ_threshold_zero_yields_all_dense_plan(params):
    """occ_threshold=0: only an exactly-zero-occupancy layer may go sparse, so
    any nonzero traffic plans fully dense — the serving escape hatch."""
    calib = jnp.stack([_img(0), _img(1)])  # sparse but nonzero
    plan = plan_network(params, calib, TINY, occ_threshold=0.0)
    assert all(lp.impl == "dense" for lp in plan.layers)
    assert plan.counts() == {"dense": len(plan.layers), "sparse": 0, "fused": 0,
                             "bsr": 0, "int8": 0}


def test_explicit_block_c_override_honored_end_to_end(params, monkeypatch):
    """block_c=8 at plan time must reach every Pallas call in run_plan."""
    import repro.kernels.conv_pool.ops as cp_ops
    import repro.kernels.ecr_conv.ops as ecr_ops

    plan = plan_network(params, jnp.stack([_img(0), _img(1)]), TINY,
                        occ_threshold=1.0, block_c=8)
    assert plan.block_c == 8
    assert all(lp.impl.endswith("_pallas") for lp in plan.layers)
    seen = []
    real_ecr, real_fused = ecr_ops.ecr_conv, cp_ops.fused_conv_pool

    def spy_ecr(x, w, stride=1, block_c=0, **kw):
        seen.append(("ecr", block_c))
        return real_ecr(x, w, stride=stride, block_c=block_c, **kw)

    def spy_fused(x, w, stride=1, pool=2, p_s=None, block_c=0, **kw):
        seen.append(("pecr", block_c))
        return real_fused(x, w, stride=stride, pool=pool, p_s=p_s,
                          block_c=block_c, **kw)

    monkeypatch.setattr(ecr_ops, "ecr_conv", spy_ecr)
    monkeypatch.setattr(cp_ops, "fused_conv_pool", spy_fused)
    run_plan(plan, params, jnp.stack([_img(2), _img(3)]), TINY)
    assert len(seen) == len(plan.layers)
    assert all(bc == 8 for _, bc in seen)


def test_measure_occupancy_batch1_equals_single_image_compacted():
    """measure_occupancy at batch=1 == the single-image post-compaction
    occupancy of DESIGN.md §2.2 (ceil(n_live/bc)/n_cb)."""
    for seed, sparsity_dead in ((0, 5), (1, 11), (2, 0)):
        x = np.array(jax.random.uniform(jax.random.PRNGKey(seed), (16, 9, 9)),
                     np.float32)
        if sparsity_dead:
            x[16 - sparsity_dead:] = 0.0
        x = jnp.asarray(x)
        batched = measure_occupancy(x[None], block_c=8)
        single = channel_block_occupancy(x, 8, compact=True)
        assert batched == pytest.approx(single)


# ---------------------------------------------------------------------------
# benchmark JSON emission (satellite)
# ---------------------------------------------------------------------------


def test_write_bench_json_roundtrip(tmp_path):
    from benchmarks._util import parse_csv_rows, write_bench_json

    rows = parse_csv_rows("name,us_per_call,derived\n"
                          "fig9/conv_1/s1,12.5,dense_us=40 occ=0.50\n"
                          "not a row\n"
                          "serve/rate20,100.0,throughput_rps=19.9 p50_ms=4.0\n")
    assert [r["name"] for r in rows] == ["fig9/conv_1/s1", "serve/rate20"]
    path = write_bench_json("unit", rows, str(tmp_path), extra={"points": [1]})
    data = json.loads(open(path).read())
    assert data["name"] == "unit" and data["points"] == [1]
    assert data["rows"][0]["us_per_call"] == 12.5


def test_serve_benchmark_emits_json(tmp_path):
    """End-to-end smoke of benchmarks/serve_vgg19.py at test scale: the JSON
    artifact must carry throughput/latency per rate point."""
    from benchmarks import serve_vgg19

    path = serve_vgg19.main(reduced=True, json_dir=str(tmp_path),
                            rates=(100.0,), n_requests=4)
    data = json.loads(open(path).read())
    assert data["name"] == "serve_vgg19"
    (point,) = data["points"]
    assert point["rate_rps"] == 100.0
    assert point["throughput_rps"] > 0
    assert point["p95_ms"] >= point["p50_ms"] > 0
    assert point["stream_compiles"] == 0  # steady-state serving never compiles
