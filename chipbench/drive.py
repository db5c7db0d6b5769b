"""The one load generator: reads a traffic mix's parameters and drives the
serving engine through `submit` / `poll` on the host's monotonic clock.

Mixes (`chipbench/traffic/<mix>.json`):
- `{"kind": "closed", "in_flight": N, ...}`: N clients with zero think time.
  Each completion sends that client's next request at once.
A mix also gives `buckets` (the bucket programs the cell warms),
`warm_sizes` (the batch sizes its request path is warmed at), `pool` (the
host images drawn from the seed) and `dead_frac` (the shared dead trailing
band of the image channels).

Every request is recorded with the time it was sent, the time its batch was
formed, the time its logits were on the host, and which image it sent.
"""
from __future__ import annotations

import time

import numpy as np


def image_pool(shape: tuple, n: int, seed: int, dead_frac: float) -> np.ndarray:
    """`n` host images of `shape`: uniform pixels with the trailing
    `int(C * dead_frac)` channels zero, the same band in every image."""
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 1])
    pool = rng.random((n,) + tuple(shape), dtype=np.float32)
    c = shape[0]
    n_dead = int(c * dead_frac)
    if n_dead:
        pool[:, c - n_dead:] = 0.0
    return pool


class Records:
    """Per-request stamps, in submission order."""

    def __init__(self):
        self.sent: list = []
        self.image: list = []
        self.formed: dict = {}
        self.done: dict = {}
        self.logits: dict = {}

    def add(self, sent: float, image: int) -> int:
        self.sent.append(sent)
        self.image.append(image)
        return len(self.sent) - 1

    def finish(self, idx: int, result) -> None:
        self.formed[idx] = result.t_formed
        self.done[idx] = result.t_done
        self.logits[idx] = result.logits


class Load:
    """Drives one engine with one mix. `run_until(t)` sends and serves until
    the clock reaches `t`; phases follow one another without a pause, and
    `finish()` serves what is still queued, with no new requests."""

    def __init__(self, engine, pool: np.ndarray, mix: dict, span=None):
        if mix["kind"] != "closed":
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
        self.engine = engine
        self.pool = pool
        self.clock = time.monotonic
        self.span = span if span is not None else _no_span
        self.rec = Records()
        self._rid = {}  # engine request id -> record index
        self._next_image = 0
        self._idle = int(mix["in_flight"])  # clients waiting to send

    # -- sending ---------------------------------------------------------

    def _send(self) -> None:
        with self.span("client.send"):
            i = self._next_image % len(self.pool)
            self._next_image += 1
            img = self.pool[i]
        idx = self.rec.add(self.clock(), i)
        with self.span("engine.submit"):
            rid = self.engine.submit(img)
        self._rid[rid] = idx

    def _collect(self, results) -> None:
        for r in results:
            self.rec.finish(self._rid.pop(r.id), r)

    def _poll(self) -> int:
        with self.span("engine.poll"):
            results = self.engine.poll()
        self._collect(results)
        return len(results)

    # -- phases ----------------------------------------------------------

    def run_until(self, t_stop: float) -> None:
        while self.clock() < t_stop:
            for _ in range(self._idle):
                self._send()
            self._idle = self._poll()

    def finish(self) -> None:
        """Serve every queued request on the batcher's own deadlines."""
        while self.engine.batcher.pending():
            deadline = self.engine.next_deadline()
            wait = deadline - self.clock()
            if wait > 0:
                with self.span("client.wait"):
                    time.sleep(wait)
            self._poll()
        self._idle = 0


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(name):  # noqa: ARG001
    return _NoSpan()
