"""The load generator: images from the seed, a closed loop that keeps its
clients busy, the window's requests by their send time."""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np
import pytest

from chipbench import drive, harness


@dataclass(frozen=True)
class Result:
    id: int
    logits: np.ndarray
    t_arrival: float
    t_done: float
    t_formed: float


class FakeEngine:
    """Answers every queued request at each poll once a batch is full or
    the oldest request's deadline has passed, after `service_s`."""

    def __init__(self, max_batch=8, service_s=0.0, deadline_s=0.002):
        self.q = []
        self.max_batch = max_batch
        self.service_s = service_s
        self.deadline_s = deadline_s
        self.n = 0
        self.most_queued = 0
        self.batcher = self

    def submit(self, img, now=None):
        self.q.append((self.n, time.monotonic() if now is None else now))
        self.n += 1
        self.most_queued = max(self.most_queued, len(self.q))
        return self.n - 1

    def pending(self):
        return len(self.q)

    def next_deadline(self):
        return self.q[0][1] + self.deadline_s if self.q else None

    def poll(self):
        now = time.monotonic()
        if not self.q or (len(self.q) < self.max_batch
                          and now < self.q[0][1] + self.deadline_s):
            return []
        formed = time.monotonic()
        time.sleep(self.service_s)
        done = time.monotonic()
        out = [Result(i, np.zeros(2), t, done, formed) for i, t in self.q]
        self.q = []
        return out


POOL = drive.image_pool((3, 4, 4), 5, 9, 0.5)


def test_image_pool_is_the_seeds_with_a_shared_dead_band():
    a, b = drive.image_pool((4, 3, 3), 6, 2**31 + 9, 0.5), \
        drive.image_pool((4, 3, 3), 6, 2**31 + 9, 0.5)
    assert np.array_equal(a, b) and a.dtype == np.float32
    assert not np.array_equal(a, drive.image_pool((4, 3, 3), 6, 10, 0.5))
    assert (a[:, 2:] == 0).all() and (a[:, :2] > 0).all()


def test_closed_loop_keeps_its_clients_busy():
    eng = FakeEngine(service_s=0.001)
    load = drive.Load(eng, POOL, {"kind": "closed", "in_flight": 32})
    t0 = time.monotonic()
    load.run_until(t0 + 0.1)
    done_first = len(load.rec.done)
    assert done_first >= 32 * 10
    assert eng.most_queued == 32  # every client has one request out
    load.run_until(t0 + 0.2)  # a second phase picks the clients up again
    assert len(load.rec.done) > done_first
    assert eng.most_queued == 32
    # images cycle through the pool in order
    assert load.rec.image[:7] == [0, 1, 2, 3, 4, 0, 1]


def test_finish_serves_what_is_queued_on_the_deadline():
    eng = FakeEngine(max_batch=64, deadline_s=0.02)
    load = drive.Load(eng, POOL, {"kind": "closed", "in_flight": 3})
    load.run_until(time.monotonic() + 0.005)  # three sent, none due yet
    assert eng.pending() == 3 and not load.rec.done
    load.finish()
    assert eng.pending() == 0 and sorted(load.rec.done) == [0, 1, 2]
    for i in range(3):
        assert load.rec.done[i] - load.rec.sent[i] >= 0.02 - 1e-3


def test_an_unknown_traffic_kind_is_refused():
    with pytest.raises(ValueError, match="unknown traffic kind"):
        drive.Load(FakeEngine(), POOL, {"kind": "poisson", "rate": 100.0})


def test_the_window_holds_the_requests_sent_inside_it():
    run = harness.Run(cell=None, seconds=1.0, peaks=None, t0=1.0, t1=2.0)
    rec = drive.Records()
    for t in (0.5, 1.0, 1.5, 1.999, 2.0, 2.5):
        i = rec.add(t, 0)
        rec.finish(i, Result(i, np.zeros(2), t, t + 0.3, t + 0.1))
    run.rec = rec
    assert run.window_requests() == [1, 2, 3]
    # answered 0.3 s after sending: 1.3 and 1.8 lie inside [1, 2]
    assert sorted(run.done_between(1.0, 2.0)) == [1, 2]
    # a batch is its (formed, done) pair: four of one image end in [1.3, 2.3]
    assert run.batches_between(1.3, 2.3) == [1, 1, 1, 1]
