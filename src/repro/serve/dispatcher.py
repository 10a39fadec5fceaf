"""Background dispatcher: deadline-based continuous batching for DslrServer.

The synchronous ``DslrServer.flush`` made the submitting thread do the
compute, so one slow (``exact``-budget) request stalled every queued request
behind it.  This module owns the asynchronous request lifecycle instead — the
thread architecture of the MaxText MLPerf harness (worker loops draining
backpressure queues through per-bucket cached programs) applied to the DSLR
digit-plane engine:

  * **one daemon worker thread** drains the submit queue.  Submitting threads
    only validate + enqueue; all jax dispatch happens on the worker.
  * **deadline-based flush** — every queued request carries a dwell deadline
    (its SLO class's ``max_dwell_ms``, or a per-request ``deadline_ms``
    override).  A wave launches when the oldest deadline nears (so a request
    never waits past its dwell budget just to improve batching) or when a
    group fills the largest size bucket (no point waiting once the bucket is
    full).
  * **continuous batching across SLO classes** — waves group by
    ``(ExecutionPolicy, image shape)``, not by class name, so two tiers that
    resolve to the same policy share waves (and the same compiled program).
    Per-sample quantization scales keep every request's logits bitwise
    independent of whoever shares its wave.
  * **escalation queue** — a confidence-gated (adaptive-tier) wave's
    undecided tail re-enters the queue via ``requeue`` at its next cascade
    stage, ahead of later arrivals and with its original deadline, so
    escalations fold into the next wave of the same ``(slo, stage, shape)``
    group instead of restarting the lifecycle.
  * **admission control with load shedding** — ``submit`` projects the queue
    dwell this request would see (queue depth x an EWMA of the measured
    per-request service time) and raises :class:`ServerOverloaded` when the
    projection exceeds the request's own dwell budget, or when the queue hits
    the hard ``max_queue`` cap.  Shedding at submit time keeps the failure
    *fast and explicit* instead of a silently blown SLO.
  * **clean shutdown** — ``drain()`` forces every queued request out (ignoring
    deadlines) and blocks until in-flight waves complete; ``close()`` drains
    and joins the worker.  ``pause()``/``resume()`` hold wave launches while
    the queue keeps accepting (deterministic backpressure for tests).
  * **fault tolerance** — a failed dispatch no longer takes its wave down
    with it.  Transient failures retry with bounded exponential backoff
    under a per-request retry budget; once a request's budget is exhausted
    the wave is *bisected* so a single poisoned request is quarantined (only
    its handle errors) while its wave-mates complete — per-sample
    quantization scales guarantee the re-batched logits are bitwise
    identical to a fault-free run.  A dying worker thread (any non-fatal
    ``BaseException`` escaping the wave loop) requeues its in-flight wave
    and is restarted by the supervisor; ``KeyboardInterrupt``/``SystemExit``
    fail the wave's handles and propagate.  ``serve/faults.py`` injects
    exactly these failures deterministically for chaos runs.

Wave selection is deterministic: among launch-ready groups, the one whose
oldest request has the earliest deadline wins (ties broken by lowest request
id), and requests within a wave ride in arrival order — so a given submission
sequence always produces the same wave log.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation


class ServerOverloaded(RuntimeError):
    """Raised by ``submit`` when admission control projects that the request
    would dwell in the queue longer than its SLO budget allows (or the hard
    queue cap is hit).  The request was NOT enqueued.

    ``retry_after_s`` is the structured backoff hint: the EWMA projection's
    estimate of how long until an identical submission would clear admission
    (None when no service-time estimate exists yet).  Clients should sleep
    that long before retrying instead of hammering the door."""

    def __init__(self, message: str, retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


@dataclasses.dataclass
class QueuedRequest:
    """One admitted request waiting for (or riding) a wave.  ``group_key``
    is ``(policy, image shape)`` — the continuous-batching identity — or
    ``("adaptive", slo, stage, shape)`` for confidence-gated tiers, so an
    escalated request folds into the next wave of its *next* cascade stage,
    never back into a prefix wave it already ran; the dwell ``deadline_t``
    is monotonic-clock seconds.  ``stage_idx``/``digits_spent`` track the
    cascade position and the cumulative digit planes the request has
    executed (summed over conv layers, across every stage it attended).
    ``retries`` counts failed dispatch attempts charged against this request
    (the retry budget); ``brownout_k`` marks a brown-out-degraded request
    with the digit-prefix budget it was admitted at (None = full tier)."""

    request_id: int
    image: object  # jax.Array (H, W, C)
    slo: str
    anytime: Tuple[int, ...]
    handle: object  # ResultHandle (server side sets results)
    group_key: Tuple[object, ...]
    submit_t: float
    deadline_t: float
    stage_idx: int = 0
    digits_spent: int = 0
    retries: int = 0
    brownout_k: Optional[int] = None


class Dispatcher:
    """Daemon worker thread + deadline-batched submit queue.

    ``dispatch`` is the server's wave executor: it receives a list of
    :class:`QueuedRequest` sharing one ``group_key`` and must complete (or
    fail) every handle in it.  The dispatcher never touches jax itself,
    beyond the profiler spans around its waits (``dslr.dispatch.wait``,
    ``dslr.dispatch.backoff``), which record only while a trace runs.
    """

    def __init__(
        self,
        dispatch: Callable[[List[QueuedRequest]], None],
        max_wave: int,
        max_queue: Optional[int] = 256,
        margin_s: float = 1e-3,
        ema_alpha: float = 0.4,
        max_retries: int = 2,
        backoff_base_s: float = 0.005,
        backoff_cap_s: float = 0.1,
        fault_injector=None,
    ):
        if max_wave < 1:
            raise ValueError(f"max_wave must be >= 1, got {max_wave}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 or None, got {max_queue}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self._dispatch = dispatch
        self._max_wave = int(max_wave)
        self._max_queue = max_queue
        self._margin_s = float(margin_s)
        self._ema_alpha = float(ema_alpha)
        self._max_retries = int(max_retries)
        self._backoff_base_s = float(backoff_base_s)
        self._backoff_cap_s = float(backoff_cap_s)
        self._injector = fault_injector  # serve/faults.py FaultInjector or None
        self._cond = threading.Condition()
        self._pending: List[QueuedRequest] = []
        self._inflight = 0
        self._flush = False
        self._paused = False
        self._running = False
        self._closed = False
        self._thread: Optional[threading.Thread] = None
        self._service_ema_s: Optional[float] = None
        self._retries = 0  # failed dispatch attempts that were retried
        self._quarantined = 0  # requests isolated by bisection
        self._restarts = 0  # worker-thread resurrections

    # -- lifecycle -----------------------------------------------------------

    @property
    def running(self) -> bool:
        return self._running

    @property
    def closed(self) -> bool:
        return self._closed

    def start(self) -> None:
        with self._cond:
            if self._running:
                return
            if self._closed:
                raise RuntimeError("dispatcher already closed; build a new server")
            self._running = True
            self._thread = threading.Thread(
                target=self._worker, name="dslr-dispatcher", daemon=True
            )
            self._thread.start()

    def drain(self, timeout: Optional[float] = None) -> None:
        """Force every queued request out (deadlines ignored) and block until
        the queue is empty and no wave is in flight."""
        with self._cond:
            if not self._running:
                return
            self._flush = True
            self._cond.notify_all()
            if not self._cond.wait_for(
                lambda: not self._pending and self._inflight == 0, timeout
            ):
                raise TimeoutError(f"drain did not complete within {timeout} s")
            self._flush = False

    def close(self, timeout: Optional[float] = None) -> None:
        """Drain, then stop and join the worker.  Idempotent.  ``timeout``
        is a single budget split across the drain and the join — it used to
        be spent twice in full, so ``close(5)`` could block 10 s."""
        t0 = time.monotonic()
        self.drain(timeout)
        with self._cond:
            if not self._running:
                return
            self._running = False
            self._closed = True
            self._cond.notify_all()
            thread = self._thread
        if thread is not None:
            remaining = (
                None
                if timeout is None
                else max(timeout - (time.monotonic() - t0), 0.0)
            )
            thread.join(remaining)

    def pause(self) -> None:
        """Hold wave launches (the queue keeps accepting submissions)."""
        with self._cond:
            self._paused = True

    def resume(self) -> None:
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    # -- submission-side -----------------------------------------------------

    @property
    def service_estimate_s(self) -> Optional[float]:
        """EWMA of the measured per-request wave service time (None until the
        first wave completes) — the admission controller's rate model."""
        with self._cond:
            return self._service_ema_s

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._pending) + self._inflight

    def projected_dwell_s(self) -> Optional[float]:
        """The EWMA queue-dwell projection a request submitted now would
        see (depth x per-request service estimate); None until the first
        wave completes.  The brown-out controller's pressure signal."""
        with self._cond:
            if self._service_ema_s is None:
                return None
            return (len(self._pending) + self._inflight) * self._service_ema_s

    @property
    def retries(self) -> int:
        """Failed dispatch attempts that were retried (or bisected)."""
        with self._cond:
            return self._retries

    @property
    def quarantined(self) -> int:
        """Requests isolated by wave bisection (only their handles errored)."""
        with self._cond:
            return self._quarantined

    @property
    def restarts(self) -> int:
        """Worker-thread resurrections after a mid-wave death."""
        with self._cond:
            return self._restarts

    def submit(self, req: QueuedRequest, preadmitted: bool = False) -> None:
        """Admit one request or raise :class:`ServerOverloaded`.

        ``preadmitted=True`` skips the EWMA dwell projection (but never the
        hard ``max_queue`` cap): the server's brown-out controller already
        made the admission decision — possibly degrading the request to a
        digit-prefix policy — and the dispatcher must not second-guess it by
        shedding what the controller chose to serve."""
        with self._cond:
            if not self._running:
                raise RuntimeError("dispatcher is not running (start() the server)")
            est = self._service_ema_s
            if self._max_queue is not None and len(self._pending) >= self._max_queue:
                raise ServerOverloaded(
                    f"queue full: {len(self._pending)} pending >= max_queue="
                    f"{self._max_queue}; drain() or retry later",
                    retry_after_s=est,
                )
            budget_s = req.deadline_t - req.submit_t
            if est is not None and not preadmitted:
                projected_s = (len(self._pending) + self._inflight) * est
                if projected_s > budget_s:
                    raise ServerOverloaded(
                        f"projected queue dwell {projected_s * 1e3:.1f} ms exceeds "
                        f"the request's dwell budget {budget_s * 1e3:.1f} ms "
                        f"({len(self._pending)} queued + {self._inflight} in flight "
                        f"at ~{est * 1e3:.1f} ms/request); shed at admission",
                        retry_after_s=max(projected_s - budget_s, est),
                    )
            self._pending.append(req)
            self._cond.notify_all()

    def requeue(self, reqs: List[QueuedRequest]) -> None:
        """The escalation queue: fold a wave's undecided tail back into
        ``pending``, ahead of later arrivals and bypassing admission control
        — these requests were admitted once and keep their original
        deadlines, so earliest-deadline wave selection naturally prioritizes
        them (their group key moved to the next cascade stage, so they land
        in that stage's next wave).  Called from the dispatch callback while
        its wave is still counted in flight, which keeps ``drain``'s
        completion predicate (queue empty AND nothing in flight) airtight:
        the escalations are visible before the wave retires."""
        with self._cond:
            self._pending[:0] = reqs
            self._cond.notify_all()

    def cancel(self, request_id: int) -> bool:
        """Remove a not-yet-dispatched request.  False once its wave was
        taken (or it already completed)."""
        with self._cond:
            for i, req in enumerate(self._pending):
                if req.request_id == request_id:
                    del self._pending[i]
                    return True
            return False

    # -- worker loop ---------------------------------------------------------

    def _groups(self) -> Dict[Tuple[object, ...], List[QueuedRequest]]:
        groups: Dict[Tuple[object, ...], List[QueuedRequest]] = {}
        for req in self._pending:  # arrival order preserved within a group
            groups.setdefault(req.group_key, []).append(req)
        return groups

    def _take_wave(self, now: float) -> Optional[List[QueuedRequest]]:
        """The next launch-ready wave, or None.  Caller holds the lock."""
        force = self._flush or not self._running
        # a drain/shutdown flush overrides pause: drain() promises to force
        # every queued request out, and close() may wait with no timeout —
        # honoring pause here would deadlock a paused server's teardown
        if not self._pending or (self._paused and not force):
            return None
        best: Optional[List[QueuedRequest]] = None
        best_key: Optional[Tuple[float, int]] = None
        for reqs in self._groups().values():
            ready = (
                force
                or len(reqs) >= self._max_wave
                or min(r.deadline_t for r in reqs) - self._margin_s <= now
            )
            if not ready:
                continue
            key = (min(r.deadline_t for r in reqs), min(r.request_id for r in reqs))
            if best_key is None or key < best_key:
                best, best_key = reqs, key
        if best is None:
            return None
        wave = best[: self._max_wave]
        taken = {r.request_id for r in wave}
        self._pending = [r for r in self._pending if r.request_id not in taken]
        return wave

    def _wait_timeout(self, now: float) -> Optional[float]:
        if self._paused or not self._pending:
            return None  # sleep until notified
        nearest = min(r.deadline_t for r in self._pending)
        return max(nearest - self._margin_s - now, 0.0)

    def _worker(self) -> None:
        """The supervisor: resurrect the wave loop when it dies.  A fatal
        ``KeyboardInterrupt``/``SystemExit`` propagates (its wave's handles
        were already failed); any other escaping ``BaseException`` — a
        worker death — restarts the loop, whose dying wave requeued its
        unfinished requests before unwinding, so nothing is lost."""
        while True:
            try:
                self._run()
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException:  # noqa: BLE001 — supervision, not handling
                with self._cond:
                    if not self._running:
                        return
                    self._restarts += 1

    def _run(self) -> None:
        # ``dslr.dispatch.wait`` spans cover the worker between waves: taking
        # the next wave or waiting for one to fill, and the bookkeeping after
        # a wave.  Each wake-up opens one of its own as well, because a span
        # opened before a trace began records nothing.
        while True:
            with TraceAnnotation("dslr.dispatch.wait"), self._cond:
                wave = None
                while wave is None:
                    if not self._running and not self._pending:
                        self._cond.notify_all()
                        return
                    now = time.monotonic()
                    wave = self._take_wave(now)
                    if wave is None:
                        with TraceAnnotation("dslr.dispatch.wait"):
                            self._cond.wait(self._wait_timeout(now))
                self._inflight += len(wave)
            t0 = time.monotonic()
            try:
                self._run_wave(wave)
            except (KeyboardInterrupt, SystemExit) as e:
                # fatal: fail what's unfinished, then propagate — the old
                # blanket `except BaseException` swallowed these into handles
                # and kept serving
                for req in wave:
                    if not req.handle.done():
                        req.handle._set_error(e)
                raise
            except Exception as e:  # noqa: BLE001 — retry machinery bug
                for req in wave:
                    if not req.handle.done():
                        req.handle._set_error(e)
            except BaseException:
                # worker death mid-wave: hand the unfinished requests back to
                # the queue (front, original deadlines) BEFORE the in-flight
                # count drops below, so drain()'s "queue empty and nothing in
                # flight" predicate can never pass while they are in limbo;
                # the supervisor restarts the loop and re-serves them
                with self._cond:
                    self._pending[:0] = [r for r in wave if not r.handle.done()]
                raise
            finally:
                per_req = (time.monotonic() - t0) / len(wave)
                with TraceAnnotation("dslr.dispatch.wait"), self._cond:
                    self._inflight -= len(wave)
                    if self._service_ema_s is None:
                        self._service_ema_s = per_req
                    else:
                        a = self._ema_alpha
                        self._service_ema_s = a * per_req + (1 - a) * self._service_ema_s
                    self._cond.notify_all()

    def _run_wave(self, wave: List[QueuedRequest]) -> None:
        """Execute one wave with the full fault-tolerance ladder:

        retry      a failed dispatch retries with bounded exponential
                   backoff while every rider has retry budget left;
        bisect     once budgets are exhausted the wave splits in half and
                   each half re-dispatches independently (recursively), so
        quarantine a deterministic failure narrows to a single request —
                   only its handle errors, wave-mates complete normally.

        Per-sample quantization scales make re-batching bitwise invisible:
        a request's logits are identical whether it completes in the
        original wave, a retried wave, or a bisected half.  Fatal
        exceptions propagate to ``_run``; worker deaths unwind past it to
        the supervisor."""
        attempt = 0
        err: Optional[Exception] = None
        while True:
            live = [r for r in wave if not r.handle.done()]
            if not live:
                return
            try:
                if self._injector is not None:
                    self._injector.at_dispatch(
                        [r.request_id for r in live], attempt
                    )
                self._dispatch(live)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 — the retry ladder's input
                err = e
                live = [r for r in live if not r.handle.done()]
                if not live:
                    return
                for r in live:
                    r.retries += 1
                with self._cond:
                    self._retries += 1
            if max(r.retries for r in live) <= self._max_retries:
                with TraceAnnotation("dslr.dispatch.backoff", attempt=attempt):
                    time.sleep(
                        min(self._backoff_base_s * 2.0**attempt, self._backoff_cap_s)
                    )
                attempt += 1
                continue
            break
        if len(live) == 1:
            with self._cond:
                self._quarantined += 1
            live[0].handle._set_error(err)
            return
        # bisect with fresh retry budgets: the halves re-earn their retries,
        # so a clean wave-mate is only quarantined after max_retries + 1
        # *consecutive* transient hits on its own sub-wave (vanishingly
        # unlikely), while a deterministic poison still narrows to one
        # request — wave size strictly decreases, so the recursion costs at
        # most O(max_retries * log wave) extra dispatches
        for r in live:
            r.retries = 0
        mid = len(live) // 2
        self._run_wave(live[:mid])
        self._run_wave(live[mid:])
