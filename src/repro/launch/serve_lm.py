"""Request-level LM serving driver over ``repro.lm.DslrLmServer``.

    PYTHONPATH=src python -m repro.launch.serve_lm --arch qwen2-0.5b --smoke \
        --requests 8 --prompt-len 8 --gen 4 [--slo balanced | --mixed-slo] \
        [--buckets 1,2,4] [--qps 8] [--anytime 2,4] [--deadline-ms 500] \
        [--budget 4 | --plan-latency CYCLES | --plan-error BOUND]

The LM analogue of launch/serve_cnn.py: the server runs as a context
manager, token prompts arrive one request at a time on an open-loop paced
stream (``--qps``; 0 = submit as fast as possible), the background
dispatcher forms waves by deadline-based continuous batching — batched
prefill plus greedy KV-cache ``decode_step`` generation per wave — with one
compiled program per (bucket, policy), per-token-row quantization scales
keep every request's logits independent of its wave-mates, and SLO classes
map to planner-solved per-projection-site digit budgets.  ``--anytime``
additionally asks each request for k-digit-prefix last-position logits with
their calibrated error bounds.

Explicit budgets (``--budget``) or a planner target (``--plan-latency`` /
``--plan-error``) install a single ``custom`` tier instead of the SLO
classes.  All (bucket, policy) programs are warmed before the timed stream.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.lm import DslrLmServer, compile_lm
from repro.models import common as cm
from repro.models import transformer as tf
from repro.models.graph import ExecutionPolicy
from repro.serve import ServerOverloaded


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=configs.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8, help="total request count")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=4,
                    help="greedy continuation tokens per request")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="offered request rate (0 = closed-loop: submit all at once)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request dwell deadline overriding the SLO class")
    ap.add_argument("--buckets", default="1,2,4",
                    help="comma-separated batch-size buckets")
    ap.add_argument("--slo", default="balanced",
                    help="SLO class for all requests (fast|balanced|exact)")
    ap.add_argument("--mixed-slo", action="store_true",
                    help="round-robin fast/balanced/exact traffic")
    ap.add_argument("--anytime", default="",
                    help="comma-separated k-digit prefix budgets per request")
    ap.add_argument("--per-tensor-scales", action="store_true",
                    help="disable per-token-row quantization scales "
                         "(couples batchmates)")
    ap.add_argument("--budget", type=int, default=None,
                    help="uniform digit budget (planes) — installs a 'custom' tier")
    ap.add_argument("--plan-latency", type=int, default=None, metavar="CYCLES",
                    help="solve per-site budgets for an accelerator cycle target")
    ap.add_argument("--plan-error", type=float, default=None, metavar="BOUND",
                    help="solve per-site budgets for a predicted logit-error target")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # validate flag combinations BEFORE any engine is compiled
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.prompt_len < 1:
        ap.error("--prompt-len must be >= 1")
    if args.gen < 0:
        ap.error("--gen must be >= 0")
    if args.qps < 0:
        ap.error("--qps must be >= 0")
    planning = args.plan_latency is not None or args.plan_error is not None
    if planning and args.budget is not None:
        ap.error("--plan-* and --budget are mutually exclusive")
    return args


def main() -> None:
    args = parse_args()
    use_compile_cache()
    planning = args.plan_latency is not None or args.plan_error is not None
    custom = planning or args.budget is not None

    cfg = configs.get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    rng = np.random.default_rng(args.seed)
    params = cm.init_params(tf.model_spec(cfg), jax.random.PRNGKey(args.seed))

    t0 = time.perf_counter()
    engine = compile_lm(cfg, params, plan_tokens=args.prompt_len + args.gen)
    policies = {}
    if custom:
        policy = ExecutionPolicy(
            digit_budget=args.budget,
            per_sample_scales=not args.per_tensor_scales,
        )
        if planning:
            calib = jnp.asarray(
                rng.integers(0, cfg.vocab, size=(2, args.prompt_len)), jnp.int32
            )
            try:
                plan = engine.plan(
                    max_cycles=args.plan_latency, max_error=args.plan_error,
                    tokens=calib,
                )
            except ValueError as e:
                raise SystemExit(f"--plan-*: {e}")
            print(plan.describe(), flush=True)
            policy = policy.with_plan(plan)
        policies["custom"] = policy

    buckets = tuple(int(b) for b in args.buckets.split(","))
    server = DslrLmServer(
        engine,
        buckets=buckets,
        per_sample_scales=not args.per_tensor_scales,
        policies=policies,
    )
    build_ms = (time.perf_counter() - t0) * 1e3

    if custom:
        tiers = ["custom"]
    elif args.mixed_slo:
        tiers = sorted(server.slos)
    else:
        tiers = [args.slo]
    anytime = tuple(int(k) for k in args.anytime.split(",")) if args.anytime else ()

    t0 = time.perf_counter()
    warmed = server.warmup(
        args.prompt_len, gen=args.gen, slos=tiers, anytime=anytime
    )
    warm_ms = (time.perf_counter() - t0) * 1e3

    prompts = rng.integers(
        0, cfg.vocab, size=(args.requests, args.prompt_len)
    ).astype(np.int32)
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
                handles.append(
                    server.submit(
                        jnp.asarray(prompts[i]),
                        slo=tiers[i % len(tiers)],
                        anytime=anytime,
                        gen=args.gen,
                        deadline_ms=args.deadline_ms,
                    )
                )
            except ServerOverloaded:
                shed += 1
        server.drain()
        total_s = time.perf_counter() - t0

    lat_ms = np.array([(h.done_time - h.submit_time) * 1e3 for h in handles])
    tokens_out = sum(len(h.generated) for h in handles)
    n_dev = len(jax.devices())
    print(
        f"[serve_lm] {cfg.name}{' (smoke)' if args.smoke else ''} "
        f"requests={args.requests} prompt={args.prompt_len} gen={args.gen} "
        f"qps={args.qps or 'closed-loop'} buckets={buckets} on {n_dev} device(s): "
        f"build {build_ms:.1f} ms, warmup {warmed} programs {warm_ms:.1f} ms, "
        f"p50 {np.percentile(lat_ms, 50):.1f} ms p99 {np.percentile(lat_ms, 99):.1f} ms, "
        f"{tokens_out} tokens generated, "
        f"{tokens_out / max(total_s, 1e-9):.1f} tok/s, shed {shed}",
        flush=True,
    )
    print(f"[serve_lm] stats: {server.stats} programs={len(server.program_keys)} "
          f"waves={len(server.wave_log)}")
    for tier in tiers:
        pol = server.policy_for(tier)
        if pol.layer_budgets:
            ks = [k for _, k in pol.layer_budgets]
            shown = f"per-site min {min(ks)} max {max(ks)} mean {np.mean(ks):.1f}"
        else:
            shown = str(pol.digit_budget or "full")
        print(f"[serve_lm] tier {tier!r}: budgets={shown} "
              f"predicted {server.predicted_compute_ms(tier):.4f} ms "
              f"per_sample_scales={pol.per_sample_scales}")
    if handles:
        h = handles[0]
        print(f"[serve_lm] request 0: continuation {list(h.generated)}")
        if h.partials:
            parts = ", ".join(
                f"k={p.budget}: top1={p.top1} bound={p.bound:.3e}"
                for p in h.partials
            )
            print(f"[serve_lm] request 0 anytime partials: {parts}; "
                  f"final top1={h.top1}")


if __name__ == "__main__":
    main()
