"""Serve full-width ResNet-18 through ``DslrServer`` on one TPU chip, and check it.

    python chip_smoke.py [--seed N]

One process runs every phase in order and stops at the first fault:

1. **Device.**  JAX's first device must be a TPU.  On a host without one
   (or under ``JAX_PLATFORMS=cpu``) the script exits nonzero before building
   anything: there is no CPU fallback and no interpret mode here.
2. **Build and warm up.**  ResNet-18 at width 1.0 (the paper's network) with
   random weights from ``--seed``, compiled by ``compile_cnn`` and wrapped in
   ``DslrServer`` with the ``exact`` tier (every digit plane) and the
   ``balanced`` tier (planner-solved per-layer budgets), buckets (1, 4).
   ``warmup`` compiles every program for 224x224x3 images, the k=4 anytime
   prefix programs included.
3. **Serve.**  Eight seeded requests, alternating ``exact`` (asking for the
   k=4 anytime partial) and ``balanced``, through the running dispatcher.
   Every request must complete at its tier's full budget.
4. **Compiled program.**  The exact-tier bucket-4 program must hold one
   Mosaic kernel (``tpu_custom_call``) per conv and downsample node: no
   layer ran interpreted or through the jnp oracle.
5. **Correctness.**  No dispatcher retry, guardrail re-run or oracle reroute;
   finite logits and partials; exact-tier logits with the same top-1 as the
   same engine under ``ExecutionPolicy(use_ref=True)`` and within
   ``1e-2 x max|oracle|`` of it.  A one-ulp change in summation order can flip
   a quantized digit in the next layer, so bitwise agreement is printed, not
   required.  Agreement with a plain float32 forward (highest matmul
   precision) is printed too.

The p50 latency printed is a smoke figure from eight closed-loop requests,
not a benchmark.  The last line of standard output is the JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import common as cm  # noqa: E402
from repro.models.engine import compile_cnn  # noqa: E402
from repro.models.graph import CnnConfig, ExecutionPolicy, graph_spec  # noqa: E402
from repro.serve import DslrServer  # noqa: E402
from repro.serve.slo import DEFAULT_SLOS  # noqa: E402

TIERS = ("exact", "balanced")
BUCKETS = (1, 4)
IMAGE = (224, 224, 3)
N_REQUESTS = 8
ANYTIME = (4,)
ORACLE_TOL = 1e-2  # of max|oracle logit|


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def tpu_device():
    """The chip this run serves on; exits when JAX's first device is not one."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX's first device is {dev.platform!r} ({dev.device_kind}), not a TPU")
    return dev


def build(seed: int):
    cfg = CnnConfig(name="resnet18", width=1.0)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(seed))
    engine = compile_cnn(cfg, params, ExecutionPolicy())
    slos = tuple(s for s in DEFAULT_SLOS if s.name in TIERS)
    return engine, DslrServer(engine, slos=slos, buckets=BUCKETS)


def serve(server: DslrServer, images: np.ndarray, tiers):
    with server:
        handles = [
            server.submit(img, slo=t, anytime=ANYTIME if t == "exact" else ())
            for img, t in zip(images, tiers)
        ]
        server.drain()
    # result() raises a failed request's error
    logits = np.stack([np.asarray(h.result()) for h in handles])
    return handles, logits


def check_served(server: DslrServer, handles, logits: np.ndarray) -> None:
    degraded = [h.request_id for h in handles if h.degraded]
    if degraded:
        fail(f"requests {degraded} were served a brown-out digit prefix")
    faults = {
        "dispatcher retries": server.retries,
        "guard retries": server.stats["guard_retries"],
        "oracle waves": server.stats["oracle_waves"],
    }
    print(f"faults: {faults}")
    if any(faults.values()):
        fail(f"the served path needed a fallback: {faults}")
    partials = [p.logits for h in handles for p in h.partials]
    if not np.isfinite(logits).all() or not all(
        np.isfinite(np.asarray(p)).all() for p in partials
    ):
        fail("non-finite logits")


def check_kernels(engine, server: DslrServer) -> None:
    exact = engine.with_policy(server.policy_for("exact"))
    x = jnp.zeros((BUCKETS[-1],) + IMAGE, jnp.float32)
    n_kernels = exact.lower(x).compile().as_text().count("tpu_custom_call")
    n_convs = len(engine.graph.conv_nodes)
    print(f"compiled exact-tier bucket-{BUCKETS[-1]} program: {n_kernels} "
          f"tpu_custom_call for {n_convs} conv/downsample nodes")
    if n_kernels != n_convs:
        fail(f"{n_kernels} Mosaic kernels in the wave program, expected {n_convs}")


def check_against_oracle(engine, server, images, tiers, logits) -> None:
    idx = [i for i, t in enumerate(tiers) if t == "exact"]
    oracle_policy = dataclasses.replace(server.policy_for("exact"), use_ref=True)
    oracle = np.asarray(engine.with_policy(oracle_policy)(jnp.asarray(images[idx])))
    served = logits[idx]
    diff = float(np.abs(served - oracle).max())
    scale = float(np.abs(oracle).max())
    agree = int((served.argmax(1) == oracle.argmax(1)).sum())
    print(f"kernel vs jnp oracle (exact tier, {len(idx)} requests): max|diff| "
          f"{diff!r} = {diff / scale!r} of max|oracle| {scale!r}; bitwise equal: "
          f"{np.array_equal(served, oracle)}; top-1 agree {agree}/{len(idx)}")
    if agree != len(idx) or diff > ORACLE_TOL * scale:
        fail(f"kernel disagrees with the oracle beyond top-1 / {ORACLE_TOL} x max|oracle|")

    float_engine = engine.with_policy(ExecutionPolicy(mode="float"))
    for tier in TIERS:
        idx = [i for i, t in enumerate(tiers) if t == tier]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(float_engine(jnp.asarray(images[idx])))
        rel = float(np.abs(logits[idx] - ref).max() / np.abs(ref).max())
        agree = int((logits[idx].argmax(1) == ref.argmax(1)).sum())
        print(f"{tier} tier vs float32 reference: top-1 agree {agree}/{len(idx)}, "
              f"max relative logit error {rel!r}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and request images")
    args = ap.parse_args()
    dev = tpu_device()
    cache = use_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind}, {len(jax.devices())} chip(s); "
          f"compile cache {cache}")

    t0 = time.perf_counter()
    engine, server = build(args.seed)
    t1 = time.perf_counter()
    n_programs = server.warmup(IMAGE, slos=TIERS, anytime=ANYTIME)
    t2 = time.perf_counter()
    print(f"build: {t1 - t0:.1f} s")
    print(f"warmup: {t2 - t1:.1f} s, {n_programs} programs compiled")
    print(f"balanced tier budgets: {server.policy_for('balanced').layer_budgets}")

    rng = np.random.default_rng(args.seed)
    images = rng.standard_normal((N_REQUESTS,) + IMAGE).astype(np.float32)
    tiers = [TIERS[i % 2] for i in range(N_REQUESTS)]
    handles, logits = serve(server, images, tiers)
    t3 = time.perf_counter()
    lat_ms = [(h.done_time - h.submit_time) * 1e3 for h in handles]
    print(f"served {len(handles)} requests in {t3 - t2:.1f} s "
          f"({len(server.wave_log)} waves); p50 request latency "
          f"{np.percentile(lat_ms, 50):.1f} ms (smoke figure, not a benchmark)")

    check_served(server, handles, logits)
    check_kernels(engine, server)
    check_against_oracle(engine, server, images, tiers, logits)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"checks: {time.perf_counter() - t3:.1f} s")
    print(f"peak device memory: {'not reported' if peak is None else f'{peak} bytes'}")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))


if __name__ == "__main__":
    main()
