"""Request-level CNN serving driver over ``repro.serve.DslrServer``.

    PYTHONPATH=src python -m repro.launch.serve_cnn --net resnet18 \
        --width 0.05 --requests 12 [--slo balanced | --mixed-slo] \
        [--buckets 1,2,4,8] [--qps 8] [--anytime 2,4] [--deadline-ms 500] \
        [--budget 4 | --per-layer-budgets ... | --plan-latency CYCLES | --plan-error BOUND]

The CNN analogue of launch/serve.py's transformer loop, driven through the
asynchronous request runtime: the server runs as a context manager
(``start``/``drain``/``close``), requests arrive one image at a time on an
open-loop paced stream (``--qps``; 0 = submit as fast as possible), the
background dispatcher forms waves by deadline-based continuous batching with
one compiled program per (bucket, policy), per-sample quantization scales
keep every request's result independent of its wave-mates, and SLO classes
map to planner-solved per-layer digit budgets (each carrying a queue-dwell
budget; ``--deadline-ms`` overrides it per request).  Requests the admission
controller sheds (``ServerOverloaded``) are counted and reported.
``--anytime`` additionally asks each request for k-digit partial results
(the MSDF prefix budgets) and prints their error bounds.  ``--slo adaptive``
routes traffic through the confidence-gated escalation cascade and reports
the digit planes each request actually paid and the stage it decided at.

Explicit budgets (``--budget`` / ``--per-layer-budgets``) or a planner
target (``--plan-latency`` / ``--plan-error``) install a single ``custom``
tier instead of the SLO classes.  All (bucket, policy) programs are warmed
up before the timed waves, so the latency percentiles exclude jit
trace/compile cost.

Fault tolerance: ``--chaos "seed=0,transient=0.1,nan=0.05,poison=3,die_at=2"``
hooks a deterministic ``FaultInjector`` at the dispatch boundary (transient
wave failures retry/bisect/quarantine, NaN outputs reroute through the
guardrails to the jnp oracle, worker deaths restart and requeue) and the run
reports retries / quarantined / restarts / guardrail counters.  Brown-out
degradation is on by default — overload steps tiers down a digit-prefix
ladder instead of shedding (``--no-brownout`` restores plain shedding,
``--brownout-floor`` sets the smallest prefix served) — and degraded
requests are reported with their ``digits_spent`` and sound error bound.

Exit status: nonzero when a request failed or the guardrail rerouted a wave
to the jnp oracle, unless ``--chaos`` injected the faults.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.compile_cache import use_compile_cache
from repro.models import common as cm
from repro.models.engine import compile_cnn
from repro.models.graph import CnnConfig, ExecutionPolicy, build_graph, graph_spec
from repro.serve import DslrServer, ServerOverloaded, injector_from_spec


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="resnet18", choices=("alexnet", "vgg16", "resnet18"))
    ap.add_argument("--width", type=float, default=0.05)
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--requests", type=int, default=12, help="total request count")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered request rate (0 = closed-loop: submit all at once)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request dwell deadline overriding the SLO class")
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="comma-separated batch-size buckets")
    ap.add_argument("--slo", default="balanced",
                    help="SLO class for all requests (fast|balanced|exact)")
    ap.add_argument("--mixed-slo", action="store_true",
                    help="round-robin fast/balanced/exact traffic")
    ap.add_argument("--anytime", default="",
                    help="comma-separated k-digit partial budgets per request")
    ap.add_argument("--per-tensor-scales", action="store_true",
                    help="disable per-sample quantization scales (couples batchmates)")
    ap.add_argument("--budget", type=int, default=None,
                    help="uniform digit budget (planes) — installs a 'custom' tier")
    ap.add_argument("--per-layer-budgets", default="",
                    help="comma-separated per-conv-layer budgets — 'custom' tier")
    ap.add_argument("--plan-latency", type=int, default=None, metavar="CYCLES",
                    help="solve per-layer budgets for an accelerator cycle target")
    ap.add_argument("--plan-error", type=float, default=None, metavar="BOUND",
                    help="solve per-layer budgets for a predicted output-error target")
    ap.add_argument("--plan-method", default="bound",
                    choices=("auto", "bound", "measured"),
                    help="planner frontier error model (default: analytic "
                         "bound — 'measured' probes every layer first)")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault injection spec, e.g. "
                         "'seed=0,transient=0.1,nan=0.05,poison=3,die_at=2'")
    ap.add_argument("--no-brownout", action="store_true",
                    help="shed under overload instead of degrading tiers "
                         "down the digit-prefix ladder")
    ap.add_argument("--brownout-floor", type=int, default=2,
                    help="smallest digit-prefix budget brown-out may serve")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # validate flag combinations BEFORE any engine is compiled: a conflicting
    # invocation must fail in milliseconds, not after a full compile
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.qps < 0:
        ap.error("--qps must be >= 0")
    planning = args.plan_latency is not None or args.plan_error is not None
    if planning and (args.per_layer_budgets or args.budget):
        ap.error("--plan-* and explicit budgets (--budget/--per-layer-budgets) "
                 "are mutually exclusive")
    if args.budget and args.per_layer_budgets:
        ap.error("--budget and --per-layer-budgets are mutually exclusive")
    return args


def main() -> None:
    args = parse_args()
    use_compile_cache()
    planning = args.plan_latency is not None or args.plan_error is not None
    custom = planning or bool(args.per_layer_budgets) or args.budget is not None

    cfg = CnnConfig(name=args.net, width=args.width)
    graph = build_graph(cfg)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(args.seed))

    t0 = time.perf_counter()
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    policies = {}
    if custom:
        policy = ExecutionPolicy(digit_budget=args.budget)
        if args.per_layer_budgets:
            budgets = [int(b) for b in args.per_layer_budgets.split(",")]
            policy = policy.with_layer_budgets(graph, budgets)
        if planning:
            calib = None
            if args.plan_method != "bound":
                calib = jnp.asarray(
                    np.random.default_rng(args.seed).standard_normal(
                        (1, args.img, args.img, 3)
                    ),
                    jnp.float32,
                )
            try:
                plan = engine.plan(
                    max_cycles=args.plan_latency, max_error=args.plan_error,
                    x=calib, method=args.plan_method,
                )
            except ValueError as e:
                raise SystemExit(f"--plan-*: {e}")
            print(plan.describe(), flush=True)
            policy = policy.with_plan(plan)
        policies["custom"] = policy

    buckets = tuple(int(b) for b in args.buckets.split(","))
    injector = injector_from_spec(args.chaos)
    server = DslrServer(
        engine,
        buckets=buckets,
        per_sample_scales=not args.per_tensor_scales,
        policies=policies,
        fault_injector=injector,
        brownout=not args.no_brownout,
        brownout_floor=args.brownout_floor,
    )
    build_ms = (time.perf_counter() - t0) * 1e3

    if custom:
        tiers = ["custom"]
    elif args.mixed_slo:
        tiers = sorted(server.slos)
    else:
        tiers = [args.slo]
    anytime = tuple(int(k) for k in args.anytime.split(",")) if args.anytime else ()
    # the adaptive cascade and the anytime channel are mutually exclusive on
    # one request (single early-but-exact answer vs a stream of bounded-error
    # prefixes), so adaptive-tier traffic drops the --anytime ask
    def tier_anytime(tier: str) -> tuple:
        cls = server.slos.get(tier)
        return () if (cls is not None and cls.adaptive) else anytime

    # warm every (bucket, tier) program — including the anytime prefix
    # programs requests will hit — so the percentiles below measure
    # steady-state dispatch, not jit trace/compile
    t0 = time.perf_counter()
    warmed = server.warmup((args.img, args.img, 3), slos=tiers, anytime=anytime)
    warm_ms = (time.perf_counter() - t0) * 1e3

    rng = np.random.default_rng(args.seed)
    imgs = rng.standard_normal((args.requests, args.img, args.img, 3))
    handles = []
    shed = 0
    gap_s = 1.0 / args.qps if args.qps else 0.0
    with server:  # start the dispatcher; drain + join on exit
        t0 = time.perf_counter()
        for i in range(args.requests):
            if gap_s:
                target = t0 + i * gap_s
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
            try:
                tier = tiers[i % len(tiers)]
                handles.append(
                    server.submit(
                        jnp.asarray(imgs[i], jnp.float32),
                        slo=tier,
                        anytime=tier_anytime(tier),
                        deadline_ms=args.deadline_ms,
                    )
                )
            except ServerOverloaded:
                shed += 1
        server.drain()
        total_s = time.perf_counter() - t0

    completed = [h for h in handles if h.done() and h._error is None]
    failed = [h for h in handles if h._error is not None]
    lat_ms = np.array([(h.done_time - h.submit_time) * 1e3 for h in handles])
    dev = jax.devices()[0]
    print(
        f"[serve_cnn] {args.net} width={args.width} requests={args.requests} "
        f"qps={args.qps or 'closed-loop'} buckets={buckets} on "
        f"{len(jax.devices())} {dev.platform} device(s) ({dev.device_kind}): "
        f"build {build_ms:.1f} ms, warmup {warmed} programs {warm_ms:.1f} ms, "
        f"p50 {np.percentile(lat_ms, 50):.1f} ms p99 {np.percentile(lat_ms, 99):.1f} ms, "
        f"throughput {len(handles) / max(total_s, 1e-9):.1f} img/s, shed {shed}",
        flush=True,
    )
    print(f"[serve_cnn] stats: {server.stats} programs={len(server.program_keys)} "
          f"waves={len(server.wave_log)}")
    if injector is not None or failed or server.retries:
        print(f"[serve_cnn] fault tolerance: completed {len(completed)}/"
              f"{len(handles)}, failed {len(failed)}, retries {server.retries}, "
              f"quarantined {server.quarantined}, worker restarts "
              f"{server.restarts}, guard retries {server.stats['guard_retries']}, "
              f"oracle waves {server.stats['oracle_waves']}"
              + (f", injected {injector.counters}" if injector is not None else ""))
    degraded = [h for h in completed if h.degraded]
    if degraded:
        spent = np.array([h.digits_spent for h in degraded])
        bounds = np.array([h.brownout_bound for h in degraded])
        print(f"[serve_cnn] brown-out: {len(degraded)} degraded request(s), "
              f"served budgets {sorted({h.served_budget for h in degraded})}, "
              f"digit planes spent mean {spent.mean():.1f}, "
              f"max bound {bounds.max():.3e}")
    for tier in tiers:
        pol = server.policy_for(tier)
        if pol.layer_budgets:
            shown = ",".join(str(k) for _, k in pol.layer_budgets)
        else:
            shown = str(pol.digit_budget or "full")
        print(f"[serve_cnn] tier {tier!r}: budgets={shown} "
              f"per_sample_scales={pol.per_sample_scales}")
    if anytime:
        h = next((h for h in completed if h.partials), None)
        if h is not None:
            parts = ", ".join(
                f"k={p.budget}: top1={p.top1} bound={p.bound:.3e}"
                for p in h.partials
            )
            print(f"[serve_cnn] anytime partials of first {h.slo!r} request: "
                  f"{parts}; final top1={h.top1}")
    decided = [h for h in handles if h.decided_at_stage is not None]
    if decided:
        spent = np.array([h.digits_spent for h in decided])
        stages = sorted({h.decided_at_stage for h in decided})
        dist = " ".join(
            f"stage{s}={sum(h.decided_at_stage == s for h in decided)}"
            for s in stages
        )
        print(f"[serve_cnn] adaptive: {len(decided)} request(s), digit planes "
              f"spent mean {spent.mean():.1f} min {spent.min()} max {spent.max()}; "
              f"decided at {dist}")
    # without --chaos nothing was injected: a failed request or a wave the
    # guardrail rerouted to the jnp oracle is a fault of the served path
    if injector is None and (failed or server.stats["oracle_waves"]):
        raise SystemExit(
            f"[serve_cnn] FAILED: {len(failed)} failed request(s), "
            f"{server.stats['oracle_waves']} wave(s) rerouted to the oracle"
        )


if __name__ == "__main__":
    main()
