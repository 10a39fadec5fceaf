"""Request-level serving runtime over the compiled DSLR engine.

``DslrEngine.serve`` is batch-level: the caller owns batching, and a
per-tensor activation scale couples whoever lands in the same batch.
``DslrServer`` is request-native and (once started) asynchronous:

  * ``submit(image, slo=..., anytime=..., deadline_ms=...)`` returns a
    Future-style ``ResultHandle`` immediately; a background dispatcher
    thread (serve/dispatcher.py) owns all compute.
  * Waves form by **continuous batching**: pending requests group by
    ``(ExecutionPolicy, image shape)`` — SLO classes that resolve to the
    same policy share waves — chunk to the largest configured size bucket,
    and zero-pad up to the smallest bucket that fits, so a mixed stream of
    ragged request counts touches only ``len(buckets) x len(policies)``
    compiled programs, ever.
  * **Deadline-based flush**: each request carries a dwell deadline (its SLO
    class's ``max_dwell_ms`` or a per-request ``deadline_ms`` override); a
    wave launches when the oldest deadline nears or a bucket fills, so a
    slow ``exact`` request can no longer stall a later ``fast`` one.
  * **Admission control**: ``submit`` raises ``ServerOverloaded`` when the
    projected queue dwell exceeds the request's budget (load shedding at the
    door, not a silently blown SLO).
  * Per-sample quantization scales (``ExecutionPolicy.per_sample_scales``,
    on by default here) make the batching *exact*: each request is quantized
    against its own amax, so its logits are bitwise identical to serving it
    alone — wave composition, bucket padding rows, and outlier wave-mates
    cannot perturb it.  Async and synchronous serving are therefore bitwise
    interchangeable.
  * SLO classes resolve to planner-solved per-layer digit budgets
    (serve/slo.py) — precision/latency as a per-request knob.
  * The **anytime channel**: a request may ask for ``k``-digit partial
    results.  MSDF evaluation makes a ``k``-plane prefix a valid
    bounded-error answer, so the server runs the cheap prefix-budget
    programs and reports, per partial, the top-1 class and a sound error
    bound versus the request's full-budget logits.
  * **Brown-out degradation**: when the dispatcher's EWMA dwell projection
    blows a tier's budget, the tier steps down a ladder of digit-prefix
    policies (halving budgets toward a floor) instead of shedding — the
    same MSDF anytime prefixes, served as the primary answer with
    ``digits_spent`` and a sound ``|degraded - full|`` bound on every
    degraded handle.  Recovery is hysteretic (a hold window plus a
    recovery fraction below the budget); only past the floor prefix does
    the tier shed, with a structured ``retry_after_s``.
  * **Output guardrails**: every wave's logits are checked finite and its
    anytime partials checked against their sound bounds; a suspect wave
    re-runs once (clears injected/transient corruption) and then falls
    back to the pure-jnp oracle path (``ExecutionPolicy.use_ref``), which
    is bitwise-coupled to the kernel — so even a guardrail-rerouted wave
    returns bit-identical logits.

Lifecycle: ``with DslrServer(engine) as server`` starts the dispatcher and
drains + joins it on exit; explicitly, ``start()`` / ``drain()`` /
``close()``.  A server that is never started keeps the deterministic
synchronous path: ``flush()`` drains the queue in the submitting thread and
``handle.result()`` flushes on demand — the reference the async path is
asserted bitwise against.

Profiling: the serving path opens ``jax.profiler.TraceAnnotation`` spans,
which record only while a profiler session runs — ``dslr.submit`` per
request (``request_id``), ``dslr.wave`` per wave (``rows``, ``bucket``,
``request_ids``) around its steps ``dslr.wave.assemble``, ``.launch``,
``.anytime``, ``.guard`` and ``.deliver``, and the dispatcher's
``dslr.dispatch.wait`` and ``dslr.dispatch.backoff``.  They put the host's
per-wave work on the device trace's clock.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import CancelledError
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.adaptive import (
    Cascade,
    CascadeCalibration,
    calibrate_thresholds,
    compile_cascade,
    prefix_policy,
)
from repro.core import cycle_model as cyc
from repro.models.engine import DslrEngine, conv_layers_for_graph
from repro.models.graph import ExecutionPolicy

from .dispatcher import Dispatcher, QueuedRequest, ServerOverloaded
from .faults import FaultInjector
from .slo import DEFAULT_SLOS, SloClass, resolve_policy, slo_table


class AnytimeResult(NamedTuple):
    """One ``k``-digit partial answer: the prefix-budget logits, their top-1
    class, and a conservative bound on ``max|partial - full|`` (worst-case
    Lipschitz composition of the per-layer anytime tails at the dispatch
    wave's calibrated activation scales — see ``DslrServer._anytime_bounds``
    for the derivation and its one approximation)."""

    budget: int
    logits: jax.Array  # (num_classes,)
    top1: int
    bound: float


class ResultHandle:
    """Future-style handle for one submitted request.

    ``result(timeout=None)`` blocks until the dispatcher completes the
    request (raising ``TimeoutError`` on expiry); on a never-started server
    it synchronously flushes the queue instead.  ``done()`` is a pure query
    — it never triggers compute.  ``cancel()`` withdraws a request that no
    wave has picked up yet; a cancelled handle's ``result()`` raises
    ``concurrent.futures.CancelledError``.
    """

    def __init__(self, server: "DslrServer", request_id: int, slo: str):
        self._server = server
        self.request_id = request_id
        self.slo = slo
        self._event = threading.Event()
        self._logits: Optional[jax.Array] = None
        self._partials: Tuple[AnytimeResult, ...] = ()
        self._error: Optional[BaseException] = None
        self._cancelled = False
        self.submit_time = time.monotonic()
        self.done_time: Optional[float] = None  # set at completion
        self.wave_seq: Optional[int] = None  # dispatch order (1-based)
        # adaptive (confidence-gated) tiers only, set at completion:
        # cumulative digit planes executed (summed over conv layers, across
        # every cascade stage attended) and the 0-based stage index whose
        # decision rule accepted the answer (last stage = ran full budget)
        self.digits_spent: Optional[int] = None
        self.decided_at_stage: Optional[int] = None
        # brown-out degradation (non-adaptive tiers under overload), set at
        # completion: ``degraded`` marks a request served a digit-prefix of
        # its tier, ``served_budget`` the prefix plane count k, and
        # ``brownout_bound`` a sound bound on max|degraded - tier-full|
        # logits (the anytime tail bound at k); ``digits_spent`` is then the
        # planes actually executed, summed over conv layers
        self.degraded = False
        self.served_budget: Optional[int] = None
        self.brownout_bound: Optional[float] = None

    def done(self) -> bool:
        """True once the request completed, errored, or was cancelled.
        Never dispatches anything (unlike the pre-async API, where the flush
        side-channel in ``result`` made ``done`` observable state mutate)."""
        return self._event.is_set()

    def cancel(self) -> bool:
        """Withdraw the request if no wave has picked it up yet.  Returns
        True when cancelled; False once dispatched (or already done)."""
        return self._server._cancel(self)

    def result(self, timeout: Optional[float] = None) -> jax.Array:
        """The request's logits (num_classes,) under its SLO's policy.
        Blocks up to ``timeout`` seconds (None = forever) on a started
        server; synchronously flushes a never-started server's queue."""
        if not self._event.is_set():
            if self._server.running:
                if not self._event.wait(timeout):
                    raise TimeoutError(
                        f"request {self.request_id} ({self.slo}) not done "
                        f"within {timeout} s"
                    )
            else:
                self._server.flush()
        if self._cancelled:
            raise CancelledError(f"request {self.request_id} was cancelled")
        if self._error is not None:
            raise self._error
        assert self._logits is not None
        return self._logits

    @property
    def top1(self) -> int:
        return int(jnp.argmax(self.result()))

    @property
    def partials(self) -> Tuple[AnytimeResult, ...]:
        """The anytime partial results (one per requested budget, ascending),
        available once the request has been dispatched."""
        self.result()
        return self._partials

    # -- completion (dispatcher / flush side) --------------------------------

    def _set_result(
        self,
        logits: jax.Array,
        partials: Tuple[AnytimeResult, ...],
        wave_seq: int,
        digits_spent: Optional[int] = None,
        decided_at_stage: Optional[int] = None,
    ) -> None:
        self._logits = logits
        self._partials = partials
        self.wave_seq = wave_seq
        self.digits_spent = digits_spent
        self.decided_at_stage = decided_at_stage
        self.done_time = time.monotonic()
        self._event.set()
        self._server._completed(self)

    def _set_error(self, error: BaseException) -> None:
        self._error = error
        self.done_time = time.monotonic()
        self._event.set()
        self._server._completed(self)

    def _set_cancelled(self) -> None:
        self._cancelled = True
        self.done_time = time.monotonic()
        self._event.set()


def _wave_span(chunk: List[QueuedRequest], bucket: int) -> TraceAnnotation:
    """The ``dslr.wave`` profiler span of one wave.  Its request ids (space
    separated) are formatted only while a trace records, so that a request's
    ``dslr.submit`` span can be joined to its wave at no cost otherwise."""
    if TraceAnnotation.is_enabled():
        ids = " ".join(str(r.request_id) for r in chunk)
        return TraceAnnotation("dslr.wave", rows=len(chunk), bucket=bucket, request_ids=ids)
    return TraceAnnotation("dslr.wave", rows=len(chunk), bucket=bucket)


def _assemble(chunk: List[QueuedRequest], bucket: int) -> jax.Array:
    """Stack a wave's images and zero-pad the batch up to its bucket."""
    with TraceAnnotation("dslr.wave.assemble"):
        xb = jnp.stack([r.image for r in chunk])
        if bucket > len(chunk):
            xb = jnp.pad(xb, ((0, bucket - len(chunk)), (0, 0), (0, 0), (0, 0)))
        return xb


class DslrServer:
    """Request-level serving runtime: background dispatcher with
    deadline-based continuous batching, one compiled program per (bucket,
    policy), SLO classes solved by the budget planner, per-sample
    quantization scales, anytime partial results.

    ``max_queue`` caps the dispatcher's submit queue (admission control's
    hard backstop); ``dispatch_margin_ms`` is how far before a dwell
    deadline a wave launches; ``default_dwell_ms`` is the dwell budget of
    explicit ``policies=`` tiers (named SLO classes carry their own).

    Fault tolerance: ``max_retries``/``backoff_base_s``/``backoff_cap_s``
    parameterize the dispatcher's wave retry -> bisect -> quarantine ladder;
    ``fault_injector`` (serve/faults.py) hooks seeded chaos at the dispatch
    boundary.  ``brownout=True`` (default) converts EWMA-projected overload
    on non-adaptive tiers into digit-prefix degradation down to
    ``brownout_floor`` planes, shedding only past the floor; recovery needs
    the projection under ``brownout_recover_fraction`` of the budget for at
    least ``brownout_hold_s`` (hysteresis, so the tier does not flap).
    ``brownout=False`` restores plain shedding.
    """

    def __init__(
        self,
        engine: DslrEngine,
        slos: Sequence[SloClass] = DEFAULT_SLOS,
        buckets: Sequence[int] = (1, 2, 4, 8),
        per_sample_scales: bool = True,
        policies: Optional[Dict[str, ExecutionPolicy]] = None,
        max_queue: Optional[int] = 256,
        dispatch_margin_ms: float = 1.0,
        default_dwell_ms: float = 200.0,
        fault_injector: Optional[FaultInjector] = None,
        max_retries: int = 2,
        backoff_base_s: float = 0.005,
        backoff_cap_s: float = 0.1,
        brownout: bool = True,
        brownout_floor: int = 2,
        brownout_recover_fraction: float = 0.5,
        brownout_hold_s: float = 0.05,
    ):
        if engine.policy.mode != "dslr_planes":
            raise ValueError(
                f"DslrServer needs a dslr_planes-mode engine, got {engine.policy.mode!r}"
            )
        buckets = tuple(int(b) for b in buckets)
        if not buckets or list(buckets) != sorted(set(buckets)) or buckets[0] < 1:
            raise ValueError(f"buckets must be ascending positive ints, got {buckets}")
        self.buckets = buckets
        self.slos = slo_table(slos)
        self._base_policy = dataclasses.replace(
            engine.policy, per_sample_scales=per_sample_scales
        )
        self._donor = engine  # weight donor: with_policy shares flat weights
        self._slo_policies: Dict[str, ExecutionPolicy] = {}
        self._default_dwell_ms = float(default_dwell_ms)
        for name, pol in (policies or {}).items():
            if name in self.slos:
                raise ValueError(f"explicit policy {name!r} shadows an SLO class")
            self._slo_policies[name] = dataclasses.replace(
                pol, per_sample_scales=per_sample_scales
            )
        # _lock guards policy resolution, the sync queue, stats, and the
        # completion log — submitters and the dispatcher thread share them
        self._lock = threading.RLock()
        self._queue: List[QueuedRequest] = []
        self._next_id = 0
        self._gains: Optional[Dict[str, float]] = None
        self._row_l1: Optional[Dict[str, float]] = None
        self._predicted_ms: Dict[str, float] = {}
        self._cascades: Dict[str, Cascade] = {}  # adaptive tier -> ladder
        self._calibrations: Dict[str, CascadeCalibration] = {}
        self._fault_injector = fault_injector
        if not 0.0 < brownout_recover_fraction <= 1.0:
            raise ValueError(
                f"brownout_recover_fraction={brownout_recover_fraction} "
                f"outside (0, 1]"
            )
        self._brownout = bool(brownout)
        self._brownout_floor = int(brownout_floor)
        self._brownout_recover = float(brownout_recover_fraction)
        self._brownout_hold_s = float(brownout_hold_s)
        # per-tier hysteretic degradation state: slo -> [ladder level,
        # monotonic time of the last level change]
        self._brownout_state: Dict[str, List[float]] = {}
        self._dispatcher = Dispatcher(
            dispatch=self._dispatch_wave,
            max_wave=buckets[-1],
            max_queue=max_queue,
            margin_s=float(dispatch_margin_ms) * 1e-3,
            max_retries=max_retries,
            backoff_base_s=backoff_base_s,
            backoff_cap_s=backoff_cap_s,
            fault_injector=fault_injector,
        )
        # every (bucket, policy) this server has dispatched — the program
        # cache keyspace (jax's jit cache holds the programs themselves)
        self.program_keys: Set[Tuple[int, ExecutionPolicy]] = set()
        self.stats = {
            "requests": 0,
            "dispatches": 0,
            "padded_rows": 0,
            "shed": 0,
            "cancelled": 0,
            "early_exits": 0,
            "escalated": 0,
            "degraded": 0,  # requests served a brown-out digit prefix
            "brownout_steps": 0,  # tier level escalations under pressure
            "guard_retries": 0,  # waves re-run by the output guardrails
            "oracle_waves": 0,  # waves rerouted to the jnp oracle path
        }
        self.wave_log: List[Tuple[int, ...]] = []  # request ids per wave
        self.completion_order: List[int] = []  # request ids as results land

    # -- lifecycle -----------------------------------------------------------

    @property
    def running(self) -> bool:
        """True while the background dispatcher thread is live."""
        return self._dispatcher.running

    def start(self) -> "DslrServer":
        """Start the background dispatcher (idempotent).  Until started, the
        server runs the synchronous path (``flush`` in the caller's thread)."""
        self._dispatcher.start()
        return self

    def drain(self, timeout: Optional[float] = None) -> None:
        """Force every queued request out (deadlines ignored) and block until
        all in-flight waves complete.  On a never-started server this is
        ``flush()``."""
        if self.running:
            self._dispatcher.drain(timeout)
        else:
            self.flush()

    def close(self, timeout: Optional[float] = None) -> None:
        """Drain, then stop and join the dispatcher.  A closed server rejects
        further submissions; build a new server to restart (engines and their
        compiled programs are reusable across servers)."""
        self._dispatcher.close(timeout)

    def __enter__(self) -> "DslrServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def pause(self) -> None:
        """Hold wave launches while the queue keeps accepting — deterministic
        backpressure (tests, maintenance windows)."""
        self._dispatcher.pause()

    def resume(self) -> None:
        self._dispatcher.resume()

    @property
    def service_estimate_s(self) -> Optional[float]:
        """The admission controller's EWMA of per-request service time."""
        return self._dispatcher.service_estimate_s

    @property
    def retries(self) -> int:
        """Failed wave dispatch attempts that were retried (or bisected)."""
        return self._dispatcher.retries

    @property
    def quarantined(self) -> int:
        """Requests isolated by wave bisection (only their handles errored)."""
        return self._dispatcher.quarantined

    @property
    def restarts(self) -> int:
        """Dispatcher worker-thread resurrections after a mid-wave death."""
        return self._dispatcher.restarts

    # -- policy / engine resolution -----------------------------------------

    def policy_for(self, slo: str) -> ExecutionPolicy:
        """The solved ExecutionPolicy of an SLO class (planner budgets for
        planned tiers, full precision for exact tiers).  Thread-safe: the
        planner solve runs at most once per tier."""
        with self._lock:
            if slo not in self._slo_policies:
                if slo not in self.slos:
                    have = sorted(set(self.slos) | set(self._slo_policies))
                    raise ValueError(f"unknown SLO class {slo!r} (have {have})")
                self._slo_policies[slo] = resolve_policy(
                    self._donor, self.slos[slo], self._base_policy
                )
            return self._slo_policies[slo]

    def _engine_for(self, policy: ExecutionPolicy) -> DslrEngine:
        # DslrEngine.with_policy is a thread-safe memo sharing the donor's
        # flattened weights, so concurrent lookups return one engine
        return self._donor.with_policy(policy)

    def _prefix_policy(self, policy: ExecutionPolicy, k: int) -> ExecutionPolicy:
        """The ``k``-plane prefix of a policy's budgets (the anytime
        channel's program) — shared with the adaptive cascade's stage
        policies, so an anytime partial at budget ``k`` and a cascade stage
        at budget ``k`` are literally the same compiled program."""
        return prefix_policy(policy, k)

    def _slo_class(self, slo: str) -> Optional[SloClass]:
        return self.slos.get(slo)

    def cascade_for(self, slo: str) -> Cascade:
        """The compiled escalation ladder of an adaptive SLO tier (built
        lazily, one per tier).  A ``decision="calibrated"`` tier needs a
        prior :meth:`calibrate` call — the measured thresholds are state the
        server cannot invent."""
        with self._lock:
            cascade = self._cascades.get(slo)
            if cascade is not None:
                return cascade
            cls = self._slo_class(slo)
            if cls is None or not cls.adaptive:
                raise ValueError(f"SLO class {slo!r} is not an adaptive tier")
            calibration = self._calibrations.get(slo)
            if cls.decision == "calibrated" and calibration is None:
                raise RuntimeError(
                    f"adaptive tier {slo!r} uses decision='calibrated' but no "
                    f"thresholds are calibrated yet; call "
                    f"server.calibrate({slo!r}, x_calib, ...) first (the "
                    f"default 'proven' decision rule needs no calibration)"
                )
            policy = self.policy_for(slo)
            cascade = compile_cascade(
                self._engine_for(policy),
                stages=cls.stages,
                calibration=calibration if cls.decision == "calibrated" else None,
            )
            self._cascades[slo] = cascade
            return cascade

    def calibrate(
        self,
        slo: str,
        x_calib: jax.Array,
        target_argmax_agreement: float = 1.0,
    ) -> CascadeCalibration:
        """Measure per-stage margin thresholds for a ``decision="calibrated"``
        adaptive tier on a calibration batch (B, H, W, C) — the *heuristic*
        exit mode: argmax agreement with the full-budget answer holds at the
        measured rate on the calibration distribution, not per-sample by
        construction (repro.adaptive.calibrate).  Replaces any previous
        calibration for the tier."""
        cls = self._slo_class(slo)
        if cls is None or not cls.adaptive:
            raise ValueError(f"SLO class {slo!r} is not an adaptive tier")
        if cls.decision != "calibrated":
            raise ValueError(
                f"adaptive tier {slo!r} uses the proven decision rule; "
                f"calibration only applies to decision='calibrated' tiers"
            )
        engine = self._engine_for(self.policy_for(slo))
        cal = calibrate_thresholds(
            engine,
            x_calib,
            stages=cls.stages,
            target_argmax_agreement=target_argmax_agreement,
        )
        with self._lock:
            self._calibrations[slo] = cal
            self._cascades.pop(slo, None)  # rebuild on next use
        return cal

    def dwell_budget_ms(self, slo: str) -> float:
        """The queue-dwell budget of a tier: its SLO class's ``max_dwell_ms``
        (explicit ``policies=`` tiers use the server's ``default_dwell_ms``)."""
        if slo in self.slos:
            return self.slos[slo].max_dwell_ms
        return self._default_dwell_ms

    def predicted_compute_ms(self, slo: str) -> float:
        """Planner-predicted compute time of one request under a tier's
        solved budgets: the Eq.-3 cycle count of every conv layer at its
        effective digit budget, at the accelerator clock.  The floor a
        ``deadline_ms`` override must clear — no dwell budget can beat the
        compute itself."""
        with self._lock:
            if slo not in self._predicted_ms:
                policy = self.policy_for(slo)
                dims = conv_layers_for_graph(self._donor.cfg, self._donor.graph)
                cycles = sum(
                    cyc.dslr_cycles(
                        dims[n.name],
                        precision=policy.budget_for(n.name) or policy.n_planes,
                    )
                    for n in self._donor.graph.conv_nodes
                )
                self._predicted_ms[slo] = cycles / cyc.FREQ_HZ * 1e3
            return self._predicted_ms[slo]

    # -- brown-out controller ------------------------------------------------

    def brownout_ladder(self, slo: str) -> Tuple[int, ...]:
        """The descending digit-prefix budgets a tier steps through under
        overload: the tier's maximum effective plane budget halved repeatedly
        down to the server's ``brownout_floor``.  Empty when the tier cannot
        degrade (its budget is already at/below the floor) — such a tier
        sheds immediately under overload, exactly like ``brownout=False``."""
        policy = self.policy_for(slo)
        if policy.layer_budgets:
            kmax = max(int(k) for _, k in policy.layer_budgets)
        elif policy.digit_budget is not None:
            kmax = int(policy.digit_budget)
        else:
            kmax = policy.n_planes
        cls = self._slo_class(slo)
        floor = (
            self._brownout_floor
            if cls is None or cls.brownout_floor is None
            else cls.brownout_floor
        )
        floor = max(1, min(floor, kmax))
        ladder: List[int] = []
        k = kmax
        while k > floor:
            k = max(floor, k // 2)
            ladder.append(k)
        return tuple(ladder)

    def brownout_level(self, slo: str) -> int:
        """The tier's current position on its brown-out ladder (0 = serving
        full budgets)."""
        with self._lock:
            st = self._brownout_state.get(slo)
            return 0 if st is None else int(st[0])

    def _brownout_admit(self, slo: str, budget_s: float) -> Optional[int]:
        """The brown-out admission decision for one non-adaptive request:
        returns the digit-prefix budget to serve it at (None = the tier's
        full policy), stepping the tier's ladder level up when the EWMA
        dwell projection blows ``budget_s`` and back down — hysteretically:
        only after the projection holds below ``brownout_recover_fraction x
        budget`` for ``brownout_hold_s`` — when pressure clears.  Past the
        floor prefix the request is shed with a structured
        ``retry_after_s``, the only shedding a brown-out tier does."""
        proj = self._dispatcher.projected_dwell_s()
        ladder = self.brownout_ladder(slo)
        now = time.monotonic()
        with self._lock:
            st = self._brownout_state.setdefault(slo, [0, -float("inf")])
            level = int(st[0])
            overloaded = proj is not None and proj > budget_s
            if overloaded:
                held = now - st[1] >= self._brownout_hold_s
                if level < len(ladder) and (level == 0 or held):
                    level += 1
                    st[0], st[1] = level, now
                    self.stats["brownout_steps"] += 1
                elif level >= len(ladder):
                    est = self._dispatcher.service_estimate_s
                    raise ServerOverloaded(
                        f"tier {slo!r} is past its brown-out floor "
                        f"(level {level}/{len(ladder)}, ladder {ladder}): "
                        f"projected dwell {proj * 1e3:.1f} ms still exceeds "
                        f"the {budget_s * 1e3:.1f} ms budget at the floor "
                        f"prefix; shed",
                        retry_after_s=max(proj - budget_s, est or proj),
                    )
            elif level > 0:
                recovered = proj is None or proj <= self._brownout_recover * budget_s
                if recovered and now - st[1] >= self._brownout_hold_s:
                    level -= 1
                    st[0], st[1] = level, now
            return ladder[level - 1] if level > 0 else None

    # -- submission ----------------------------------------------------------

    def submit(
        self,
        image: jax.Array,
        slo: str = "balanced",
        anytime: Sequence[int] = (),
        deadline_ms: Optional[float] = None,
    ) -> ResultHandle:
        """Enqueue one request.  ``image``: (H, W, C) float.  ``anytime``
        asks for k-digit partial results (MSDF prefix budgets) alongside the
        full answer.  ``deadline_ms`` overrides the SLO class's queue-dwell
        budget for this request; it must clear the tier's planner-predicted
        compute time.  Returns immediately.  On a started server the
        background dispatcher batches and executes (``submit`` raises
        ``ServerOverloaded`` when the projected queue dwell exceeds the
        budget); on a never-started server, ``handle.result()`` or an
        explicit ``flush()`` dispatches synchronously."""
        with TraceAnnotation("dslr.submit") as span:
            handle = self._submit(image, slo, anytime, deadline_ms)
            span.set_metadata(request_id=handle.request_id)
        return handle

    def _submit(
        self,
        image: jax.Array,
        slo: str,
        anytime: Sequence[int],
        deadline_ms: Optional[float],
    ) -> ResultHandle:
        if self._dispatcher.closed:
            raise RuntimeError("server is closed; build a new DslrServer")
        image = jnp.asarray(image, jnp.float32)
        if image.ndim != 3:
            raise ValueError(f"image must be (H, W, C), got shape {image.shape}")
        policy = self.policy_for(slo)  # validates the SLO name eagerly
        anytime = tuple(sorted(int(k) for k in anytime))
        cls = self._slo_class(slo)
        is_adaptive = cls is not None and cls.adaptive
        if is_adaptive:
            if anytime:
                raise ValueError(
                    f"anytime= and the adaptive tier {slo!r} are mutually "
                    f"exclusive: the cascade already serves the k-digit "
                    f"prefix answer the moment it is decided — submit to a "
                    f"non-adaptive tier for explicit partials"
                )
            self.cascade_for(slo)  # build/validate the ladder eagerly
        for k in anytime:
            if not 1 <= k <= policy.n_planes:
                raise ValueError(
                    f"anytime budget {k} outside [1, {policy.n_planes}]"
                )
        if deadline_ms is not None:
            floor_ms = self.predicted_compute_ms(slo)
            if deadline_ms < floor_ms:
                raise ValueError(
                    f"deadline_ms={deadline_ms} is below the {slo!r} tier's "
                    f"planner-predicted compute time {floor_ms:.4f} ms — no "
                    f"queue policy can meet it; raise the deadline or pick a "
                    f"faster SLO class"
                )
            dwell_ms = float(deadline_ms)
        else:
            dwell_ms = self.dwell_budget_ms(slo)
        # brown-out admission: under projected overload a non-adaptive tier
        # degrades to a digit-prefix policy instead of shedding (shedding
        # only past the floor prefix); the dispatcher then skips its own
        # projection check (preadmitted) — the controller already decided
        brownout_k: Optional[int] = None
        if self.running and self._brownout and not is_adaptive:
            try:
                brownout_k = self._brownout_admit(slo, dwell_ms * 1e-3)
            except ServerOverloaded:
                with self._lock:
                    self.stats["shed"] += 1
                raise
        wave_policy = policy
        if brownout_k is not None:
            wave_policy = self._prefix_policy(policy, brownout_k)
            if wave_policy == policy:  # prefix changes nothing: not degraded
                brownout_k = None
        with self._lock:
            request_id = self._next_id
            self._next_id += 1
        handle = ResultHandle(self, request_id, slo)
        # adaptive requests group by (tier, cascade stage, shape): every
        # stage is its own program, so stages never share a wave — and
        # adaptive waves never mix with plain waves of the same policy
        group_key = (
            ("adaptive", slo, 0, tuple(image.shape))
            if is_adaptive
            else (wave_policy, tuple(image.shape))
        )
        req = QueuedRequest(
            request_id=request_id,
            image=image,
            slo=slo,
            anytime=anytime,
            handle=handle,
            group_key=group_key,
            submit_t=handle.submit_time,
            deadline_t=handle.submit_time + dwell_ms * 1e-3,
            brownout_k=brownout_k,
        )
        if self.running:
            try:
                self._dispatcher.submit(
                    req, preadmitted=self._brownout and not is_adaptive
                )
            except ServerOverloaded:
                with self._lock:
                    self.stats["shed"] += 1
                raise
        else:
            with self._lock:
                self._queue.append(req)
        with self._lock:
            self.stats["requests"] += 1
        return handle

    def _cancel(self, handle: ResultHandle) -> bool:
        if handle.done():
            return False
        if self.running:
            removed = self._dispatcher.cancel(handle.request_id)
        else:
            with self._lock:
                n = len(self._queue)
                self._queue = [
                    r for r in self._queue if r.request_id != handle.request_id
                ]
                removed = len(self._queue) < n
        if removed:
            handle._set_cancelled()
            with self._lock:
                self.stats["cancelled"] += 1
        return removed

    def _completed(self, handle: ResultHandle) -> None:
        with self._lock:
            self.completion_order.append(handle.request_id)

    # -- dispatch ------------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def flush(self) -> None:
        """Synchronously drain the queue in the calling thread: group by
        (policy, image shape) in arrival order, chunk to the largest bucket,
        dispatch — looping until the queue stays empty, because an adaptive
        wave re-enqueues its undecided tail at the next cascade stage.  On a
        started server this delegates to ``drain()`` — the dispatcher owns
        the queue there."""
        if self.running:
            self.drain()
            return
        while True:
            with self._lock:
                queue, self._queue = self._queue, []
            if not queue:
                return
            groups: Dict[Tuple[object, ...], List[QueuedRequest]] = {}
            for r in queue:
                groups.setdefault(r.group_key, []).append(r)
            for reqs in groups.values():
                while reqs:
                    chunk, reqs = reqs[: self.buckets[-1]], reqs[self.buckets[-1]:]
                    self._dispatch_wave(chunk)

    def _dispatch_wave(self, chunk: List[QueuedRequest]) -> None:
        """Execute one wave (all requests share one group key).  Runs on the
        dispatcher thread (async) or the caller (sync flush), inside the
        wave's ``dslr.wave`` profiler span."""
        bucket = self._bucket_for(len(chunk))
        with _wave_span(chunk, bucket):
            if chunk[0].group_key[0] == "adaptive":
                self._dispatch_adaptive_wave(chunk, bucket)
            else:
                self._dispatch_plain_wave(chunk, bucket)

    def _dispatch_plain_wave(self, chunk: List[QueuedRequest], bucket: int) -> None:
        """One wave of a non-adaptive tier: every request shares the
        ``(policy, shape)`` group key and rides the same program."""
        policy = chunk[0].group_key[0]
        xb = _assemble(chunk, bucket)
        # anytime channel budgets: one prefix program per distinct requested
        # budget in this wave (per-sample scales make the grouping invisible
        # to each request's values)
        ks = sorted({k for r in chunk for k in r.anytime})
        wave_ids = tuple(r.request_id for r in chunk)
        logits, partials_by_k, bounds_by_k = self._guarded_wave(
            policy, xb, ks, wave_ids
        )

        # brown-out accounting, per degraded (tier, prefix k) in this wave:
        # a sound |degraded - tier-full| bound (the tier's anytime tail
        # bound at k — and at min(k_any, k) for each anytime partial, since
        # a prefix of the degraded policy IS a prefix of the tier policy)
        # plus the digit planes actually executed, summed over conv layers
        tier_bounds: Dict[Tuple[str, int], Dict[int, float]] = {}
        tier_digits: Dict[Tuple[str, int], int] = {}
        for tslo, kd in {
            (r.slo, r.brownout_k) for r in chunk if r.brownout_k is not None
        }:
            full_pol = self.policy_for(tslo)
            keffs = sorted(
                {
                    min(k, kd)
                    for r in chunk
                    if r.slo == tslo and r.brownout_k == kd
                    for k in r.anytime
                }
                | {kd}
            )
            tier_bounds[(tslo, kd)] = self._anytime_bounds(
                self._engine_for(full_pol), xb, keffs
            )
            tier_digits[(tslo, kd)] = sum(
                min(kd, full_pol.budget_for(n.name) or full_pol.n_planes)
                for n in self._donor.graph.conv_nodes
            )

        with TraceAnnotation("dslr.wave.deliver"):
            with self._lock:
                self.stats["dispatches"] += 1
                self.stats["padded_rows"] += bucket - len(chunk)
                self.stats["degraded"] += sum(
                    1 for r in chunk if r.brownout_k is not None
                )
                self.program_keys.add((bucket, policy))
                for k in ks:
                    pk = self._prefix_policy(policy, k)
                    if pk != policy:
                        self.program_keys.add((bucket, pk))
                self.wave_log.append(tuple(r.request_id for r in chunk))
                wave_seq = len(self.wave_log)

            for i, r in enumerate(chunk):
                partials = []
                for k in r.anytime:
                    if r.brownout_k is not None:
                        bound = tier_bounds[(r.slo, r.brownout_k)][
                            min(k, r.brownout_k)
                        ]
                    else:
                        bound = bounds_by_k[k]
                    partials.append(
                        AnytimeResult(
                            budget=k,
                            logits=partials_by_k[k][i],
                            top1=int(jnp.argmax(partials_by_k[k][i])),
                            bound=bound,
                        )
                    )
                if r.brownout_k is not None:
                    key = (r.slo, r.brownout_k)
                    r.handle.degraded = True
                    r.handle.served_budget = r.brownout_k
                    r.handle.brownout_bound = tier_bounds[key][r.brownout_k]
                    r.handle._set_result(
                        logits[i],
                        tuple(partials),
                        wave_seq,
                        digits_spent=tier_digits[key],
                    )
                else:
                    r.handle._set_result(logits[i], tuple(partials), wave_seq)

    def _guarded_wave(
        self,
        policy: ExecutionPolicy,
        xb: jax.Array,
        ks: Sequence[int],
        wave_ids: Tuple[int, ...],
    ) -> Tuple[jax.Array, Dict[int, jax.Array], Dict[int, float]]:
        """Execute one wave's full + anytime-prefix programs behind the
        output guardrails: logits (and partials) must be finite and every
        partial must respect its sound anytime bound.  A suspect wave
        re-runs once — injected/transient corruption clears, a deterministic
        miscomputation does not — and then reroutes to the pure-jnp oracle
        path (``ExecutionPolicy.use_ref``), which is bitwise-coupled to the
        kernel, so even a rerouted wave's logits match a healthy kernel's
        bit for bit."""
        inj = self._fault_injector
        engine = self._engine_for(policy)
        for attempt in range(2):
            with TraceAnnotation("dslr.wave.launch", attempt=attempt):
                logits = engine(xb)
            if inj is not None:
                logits = inj.corrupt_logits(logits, key=wave_ids + (attempt,))
            partials_by_k, bounds_by_k = self._anytime_partials(
                policy, xb, ks, logits
            )
            if self._wave_healthy(logits, partials_by_k, bounds_by_k):
                return logits, partials_by_k, bounds_by_k
            with self._lock:
                self.stats["guard_retries"] += 1
        # both kernel runs suspect: fall back to the trusted oracle (no
        # injection on this path — it models the known-good slow engine)
        oracle_policy = dataclasses.replace(policy, use_ref=True)
        with TraceAnnotation("dslr.wave.launch", attempt=2):
            logits = self._engine_for(oracle_policy)(xb)
        partials_by_k, bounds_by_k = self._anytime_partials(
            oracle_policy, xb, ks, logits
        )
        with self._lock:
            self.stats["oracle_waves"] += 1
        return logits, partials_by_k, bounds_by_k

    def _anytime_partials(
        self,
        policy: ExecutionPolicy,
        xb: jax.Array,
        ks: Sequence[int],
        logits: jax.Array,
    ) -> Tuple[Dict[int, jax.Array], Dict[int, float]]:
        """The anytime channel's per-budget prefix logits and sound bounds
        for one wave (empty dicts when no budgets were requested)."""
        partials_by_k: Dict[int, jax.Array] = {}
        bounds_by_k: Dict[int, float] = {}
        if not ks:
            return partials_by_k, bounds_by_k
        with TraceAnnotation("dslr.wave.anytime", budgets=" ".join(map(str, ks))):
            bounds_by_k = self._anytime_bounds(self._engine_for(policy), xb, ks)
            for k in ks:
                pk = self._prefix_policy(policy, k)
                if pk == policy:
                    partials_by_k[k] = logits
                    bounds_by_k[k] = 0.0
                else:
                    partials_by_k[k] = self._engine_for(pk)(xb)
        return partials_by_k, bounds_by_k

    def _wave_healthy(
        self,
        logits: jax.Array,
        partials_by_k: Dict[int, jax.Array],
        bounds_by_k: Dict[int, float],
    ) -> bool:
        """The output guardrails: finite logits/partials, and every anytime
        partial within its sound bound of the full answer (a violated bound
        is *proof* of a miscomputation — the bound is an upper bound by
        construction, so a healthy wave cannot trip it).  The first point
        where the host blocks on the wave's device work."""
        with TraceAnnotation("dslr.wave.guard"):
            if not bool(jnp.all(jnp.isfinite(logits))):
                return False
            for k, part in partials_by_k.items():
                if not bool(jnp.all(jnp.isfinite(part))):
                    return False
                bound = bounds_by_k.get(k)
                if bound is not None and bound > 0.0:
                    measured = float(jnp.max(jnp.abs(part - logits)))
                    if measured > bound:
                        return False
            return True

    def _dispatch_adaptive_wave(self, chunk: List[QueuedRequest], bucket: int) -> None:
        """One cascade-stage wave of a confidence-gated tier: run the stage
        program on the whole (bucket-padded) wave, complete the decided
        requests with the stage's logits, and escalate the undecided tail —
        group key bumped to the next stage — back through the dispatcher's
        escalation queue (sync path: back onto the flush queue).  Per-sample
        scales make the padding and the wave composition bitwise invisible
        to every request, so an escalated sample's final logits are
        independent of who shared any of its waves."""
        slo, stage_idx = chunk[0].slo, chunk[0].stage_idx
        cascade = self.cascade_for(slo)
        stage = cascade.stages[stage_idx]
        xb = _assemble(chunk, bucket)
        with TraceAnnotation("dslr.wave.launch", attempt=0):
            logits, amax = cascade.run_stage(stage, xb)
        n = len(chunk)
        with TraceAnnotation("dslr.wave.guard"):
            dec, _, _ = cascade.decide(
                stage, logits[:n], None if amax is None else amax[:, :n]
            )

        with TraceAnnotation("dslr.wave.deliver"):
            with self._lock:
                self.stats["dispatches"] += 1
                self.stats["padded_rows"] += bucket - n
                # a prefix-stage program is distinct from the plain program of
                # the same policy (it also returns the per-layer amax), so it
                # gets its own key; the final stage IS the plain program
                key = (
                    (bucket, stage.policy)
                    if stage.final
                    else (bucket, stage.policy, "stage")
                )
                self.program_keys.add(key)
                self.wave_log.append(tuple(r.request_id for r in chunk))
                wave_seq = len(self.wave_log)

            escalate: List[QueuedRequest] = []
            n_exits = 0
            for i, r in enumerate(chunk):
                r.digits_spent += stage.planes_cost
                if dec[i]:
                    n_exits += not stage.final
                    r.handle._set_result(
                        logits[i],
                        (),
                        wave_seq,
                        digits_spent=r.digits_spent,
                        decided_at_stage=stage.index,
                    )
                else:
                    r.stage_idx += 1
                    r.group_key = (
                        "adaptive", r.slo, r.stage_idx, tuple(r.image.shape)
                    )
                    escalate.append(r)
            with self._lock:
                self.stats["early_exits"] += n_exits
                self.stats["escalated"] += len(escalate)
            if escalate:
                if self.running:
                    self._dispatcher.requeue(escalate)
                else:
                    with self._lock:
                        self._queue.extend(escalate)

    # -- anytime error bounds --------------------------------------------------

    def _anytime_bounds(
        self, engine: DslrEngine, xb: jax.Array, ks: Sequence[int]
    ) -> Dict[int, float]:
        """Conservative bound on ``max|partial_k - full|`` per requested
        budget: each conv layer truncated below its policy budget
        contributes its anytime tail bound (2 * scale * 2**-k_eff *
        ||W_col||_1, at the wave's calibrated activation scale — an upper
        bound on any single sample's scale), amplified by the layer output's
        downstream worst-case Lipschitz gain (``engine.node_gains``), summed
        over layers.  One approximation: the calibration scales come from
        the full-budget forward, and truncation can in principle raise a
        downstream layer's input amax above that — a second-order effect,
        dwarfed in practice by the orders-of-magnitude slack of the
        worst-case gain composition (docs/NUMERICS.md measures probes far
        below Lipschitz; dominance over the measured error is asserted in
        tests and the serve benchmark)."""
        with self._lock:
            if self._gains is None:
                self._gains = engine.node_gains()
                self._row_l1 = {
                    n.name: float(
                        jnp.max(jnp.sum(jnp.abs(engine._weights[n.name][0]), axis=0))
                    )
                    for n in engine.graph.conv_nodes
                }
            gains, row_l1 = self._gains, self._row_l1
        scales = engine.calibration_scales(xb)
        pol = engine.policy
        out: Dict[int, float] = {}
        for k in ks:
            total = 0.0
            for node in engine.graph.conv_nodes:
                full = pol.budget_for(node.name) or pol.n_planes
                k_eff = min(int(k), full)
                if k_eff < full:
                    tail = 2.0 * scales[node.name] * 2.0 ** -k_eff
                    total += gains[node.name] * tail * row_l1[node.name]
            out[k] = total
        return out

    # -- warmup ----------------------------------------------------------------

    def warmup(
        self,
        image_shape: Tuple[int, int, int],
        slos: Optional[Sequence[str]] = None,
        buckets: Optional[Sequence[int]] = None,
        anytime: Sequence[int] = (),
    ) -> int:
        """Trace/compile every (bucket, SLO policy) program up front with
        zero images so steady-state latency percentiles exclude jit cost.
        ``anytime`` additionally warms the k-plane prefix programs that
        requests asking for those partial budgets will hit, and the eager
        calibration forward behind their error bounds; an adaptive tier
        warms every cascade stage program per bucket (a ``"calibrated"``
        tier must be calibrated first).  Returns the number of programs
        warmed (shared programs counted once)."""
        warmed: Set[Tuple] = set()
        if slos is None:
            slos = sorted(set(self.slos) | set(self._slo_policies))
        warm_buckets = tuple(buckets if buckets is not None else self.buckets)
        for slo in slos:
            policy = self.policy_for(slo)
            cls = self._slo_class(slo)
            if cls is not None and cls.adaptive:
                cascade = self.cascade_for(slo)
                for b in warm_buckets:
                    xb = jnp.zeros((b,) + tuple(image_shape), jnp.float32)
                    for stage in cascade.stages:
                        key = (
                            (b, stage.policy)
                            if stage.final
                            else (b, stage.policy, "stage")
                        )
                        if key in warmed:
                            continue
                        logits, _ = cascade.run_stage(stage, xb)
                        jax.block_until_ready(logits)
                        self.program_keys.add(key)
                        warmed.add(key)
                continue
            policies = {policy}
            policies.update(self._prefix_policy(policy, int(k)) for k in anytime)
            for pol in policies:
                engine = self._engine_for(pol)
                for b in warm_buckets:
                    key = (b, pol)
                    if key in warmed:
                        continue
                    xb = jnp.zeros((b,) + tuple(image_shape), jnp.float32)
                    jax.block_until_ready(engine(xb))
                    self.program_keys.add(key)
                    warmed.add(key)
            if anytime:
                # the anytime bounds' calibration forward runs op by op, and
                # its first run at an image shape compiles every one of them
                engine = self._engine_for(policy)
                for b in warm_buckets:
                    xb = jnp.zeros((b,) + tuple(image_shape), jnp.float32)
                    self._anytime_bounds(engine, xb, anytime)
        return len(warmed)
