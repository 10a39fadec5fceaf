"""The one traffic generator: drives ``DslrServer.submit`` from a mix's data file.

A traffic mix (``bench/traffic/<name>.json``) is data only:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  when its previous answer is in hand) or ``"open"`` (arrivals on a schedule
  at ``rate_per_s``, whatever the server does);
* ``slo``, ``deadline_ms``: what each request asks for;
* ``image_pool``: how many distinct seeded images the requests cycle through.

Open-loop arrivals form a Poisson process conditioned on its count: the
window holds ``round(rate * seconds)`` arrivals whose gaps are the
exponential distribution's quantiles, shuffled by the seed.  Every seed then
offers the same work, in another order, so seeds differ no more than the
process itself does.

Every request is timed on the benchmark's own clock, from the moment it was
due (open loop) or sent (closed loop) to the moment the client holds its
logits on the host.  Answers are collected in submission order, which is the
order a single-policy server completes them in.
"""
from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import List, Optional

import jax
import numpy as np

LATE_S = 60.0  # how long past the window's close an answer is waited for


@dataclasses.dataclass
class Request:
    index: int
    image: int  # index into the image pool
    due: float  # seconds after the window opened
    sent: Optional[float] = None
    done: Optional[float] = None
    logits: Optional[np.ndarray] = None
    outcome: str = "missing"  # ok | degraded | shed | error | missing
    error: str = ""


@dataclasses.dataclass
class Drive:
    requests: List[Request]
    seconds: float  # the measured window
    opened: float  # perf_counter at the window's opening

    @property
    def completed_in_window(self) -> List[Request]:
        return [r for r in self.requests if r.outcome == "ok" and r.done <= self.seconds]

    def latency_ms(self) -> List[float]:
        """Latency of every request due in the window; one that failed or
        never came counts as waiting until collection stopped."""
        cap = self.seconds + LATE_S
        out = []
        for r in self.requests:
            if r.due >= self.seconds:
                continue
            end = r.done if r.outcome == "ok" else cap
            out.append((end - r.due) * 1e3)
        return out

    def longest_gap_s(self) -> float:
        """The longest time inside the window in which no answer came."""
        done = sorted(r.done for r in self.requests if r.outcome == "ok" and r.done <= self.seconds)
        return float(max(np.diff([0.0] + done + [self.seconds])))

    def send_lateness_ms(self) -> List[float]:
        return [(r.sent - r.due) * 1e3 for r in self.requests if r.sent is not None]


def image_order(pool: int, seed: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 1]).permutation(pool)


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds after the window opens) of an open-loop mix."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)  # exponential quantiles
    gaps = np.random.default_rng([int(seed), 2]).permutation(gaps)
    start = np.cumsum(gaps) - gaps  # the first arrival opens the window
    return start * (seconds / gaps.sum())


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: an observed value, never interpolated."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


class _Client:
    """Sends and collects requests; keeps every record."""

    def __init__(self, server, traffic, images, seed, overloaded):
        self.server = server
        self.traffic = traffic
        self.images = images
        self.order = image_order(len(images), seed)
        self.overloaded = overloaded
        self.requests: List[Request] = []
        self.opened = 0.0

    def now(self) -> float:
        return time.perf_counter() - self.opened

    def send(self, due: float):
        r = Request(len(self.requests), int(self.order[len(self.requests) % len(self.order)]), due)
        self.requests.append(r)
        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.submit"):
            r.sent = self.now()
            try:
                return r, self.server.submit(
                    self.images[r.image],
                    slo=t["slo"],
                    deadline_ms=t.get("deadline_ms"),
                )
            except self.overloaded as e:
                r.outcome, r.error = "shed", str(e)
                return r, None

    def collect(self, r: Request, handle, deadline: float) -> None:
        if handle is None:
            return
        with jax.profiler.TraceAnnotation("bench.wait"):
            try:
                logits = np.asarray(handle.result(timeout=max(deadline - self.now(), 0.0)))
            except TimeoutError:
                return  # never came: stays "missing"
            except Exception as e:  # noqa: BLE001 — a failed request is a record, not a crash
                r.outcome, r.error, r.done = "error", repr(e), self.now()
                return
        r.done = self.now()
        r.logits = logits
        r.outcome = "degraded" if handle.degraded else "ok"


def drive(server, traffic: dict, images: np.ndarray, seconds: float, seed: int,
          overloaded=Exception, opened: Optional[float] = None) -> Drive:
    """Offer ``traffic`` to a started ``server`` for the window of ``seconds``
    that opened at ``opened`` (``time.perf_counter``; now by default);
    returns every request with its outcome, once each has answered or
    ``LATE_S`` has passed since the window closed."""
    client = _Client(server, traffic, images, seed, overloaded)
    client.opened = time.perf_counter() if opened is None else opened
    deadline = seconds + LATE_S
    if traffic["loop"] == "closed":
        _closed(client, int(traffic["clients"]), seconds, deadline)
    elif traffic["loop"] == "open":
        _open(client, arrivals(traffic, seconds, seed), deadline)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return Drive(client.requests, seconds, client.opened)


def _closed(client: _Client, clients: int, seconds: float, deadline: float) -> None:
    # one thread plays every client: answers come back in submission order,
    # so waiting on the oldest outstanding request is waiting on the first
    # client whose answer lands
    outstanding = [client.send(0.0) for _ in range(clients)]
    stopped = False
    while outstanding:
        r, handle = outstanding.pop(0)
        client.collect(r, handle, deadline)
        t = client.now()
        if t < seconds and r.outcome != "missing":
            outstanding.append(client.send(t))
        elif not stopped:
            # the callers have stopped: flush the last, partial wave instead
            # of leaving it to wait out its dwell deadline
            stopped = True
            try:
                client.server.drain(timeout=max(deadline - t, 0.0))
            except TimeoutError:
                pass  # what never came is recorded as missing


def _open(client: _Client, due: np.ndarray, deadline: float) -> None:
    sent: "queue.Queue" = queue.Queue()

    def collector():
        while True:
            item = sent.get()
            if item is None:
                return
            client.collect(*item, deadline)

    worker = threading.Thread(target=collector, name="bench-collector")
    worker.start()
    try:
        for t in due:
            wait = t - client.now()
            if wait > 0:
                time.sleep(wait)
            sent.put(client.send(float(t)))
        # the arrivals have ended: flush the last, partial wave instead of
        # leaving it to wait out its dwell deadline
        try:
            client.server.drain(timeout=max(deadline - client.now(), 0.0))
        except TimeoutError:
            pass  # what never came is recorded as missing
    finally:
        sent.put(None)
        worker.join()
