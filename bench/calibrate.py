"""Readings behind the correctness limits: the program over many seeds, and its
control, each in one process on the chip (set-up compiles once).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5 --digits 8,4
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --reference-precision DEFAULT

``--digits`` runs the configuration at each MSDF digit count: its own (the
program) and lower ones (the control, the program's own lower-precision
path).  ``--reference-precision`` instead puts the reference, computed at
that ``lax.Precision`` (``DEFAULT``: one bfloat16 pass), in the program's
place, over every image of each seed's pool, and reads the check's number
against the reference at ``HIGHEST``.  Prints one JSON line per run with the
numbers the check compared.  The benchmark's own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import check, harness, reference, weights  # noqa: E402


def reference_gap(config: dict, traffic: dict, seed: int, precision: str) -> float:
    """The check's widest relative logit gap of the reference at ``precision``
    against the reference at ``HIGHEST``, over the seed's image pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    params = weights.make(config, seed)
    images = harness.make_images(config, traffic, seed)
    low = jax.jit(lambda p, x: reference.forward(config, p, x, lax.Precision[precision]))
    want = check.reference_logits(config, params, images, list(range(len(images))))
    worst = 0.0
    for i in range(0, len(images), check.BLOCK):
        got = np.asarray(low(params, jnp.asarray(images[i:i + check.BLOCK])))
        for j, row in enumerate(got):
            ref = want[i + j]
            worst = max(worst, float(np.max(np.abs(row - ref)) / np.max(np.abs(ref))))
    return worst


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--digits", default="8", help="comma-separated MSDF digit counts")
    ap.add_argument("--reference-precision", default=None,
                    help="a lax.Precision name: read the reference at it instead of the program")
    args = ap.parse_args()
    spec = harness.load_benchmark()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    harness.require_chips(int(cell["chips"]))
    harness.configure_jax()
    if args.reference_precision is not None:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t = time.perf_counter()
            gap = reference_gap(config, traffic, seed, args.reference_precision)
            print(json.dumps({"reference_precision": args.reference_precision, "seed": seed,
                              "max_rel_logit_err": gap, "run_s": time.perf_counter() - t}),
                  flush=True)
        return
    for digits in [int(d) for d in args.digits.split(",")]:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t = time.perf_counter()
            res = harness.run_cell(
                cell, dict(config, n_digits=digits), traffic, seed, args.seconds, False, t,
                log=lambda line: print(line, file=sys.stderr, flush=True),
            )
            print(json.dumps({"digits": digits, "seed": seed, "correct": res["correct"],
                              "attempted": res["attempted"], "failed": res["failed"],
                              "check": res["check"], "run_s": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
