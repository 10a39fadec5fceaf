"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits nonzero, printing no result, when JAX's first device is not a TPU or it
finds fewer chips than the cell asks for.  The last lines of standard error
give each number the correctness check compared, beside its limit; the last
line of standard output is the result's JSON object.
"""
import time

T0 = time.perf_counter()  # process start: set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)  # import the benchmark as a package; bench/ itself shadows nothing

from bench import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="weights, images and arrivals")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report the per-layer metrics")
    args = ap.parse_args(argv)

    spec = harness.load_benchmark()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    harness.require_chips(int(cell["chips"]))
    harness.configure_jax()
    result = harness.run_cell(
        cell, config, traffic, args.seed, args.seconds, bool(args.trace), T0,
        metrics=harness.cell_metrics(spec, cell["name"], bool(args.trace)),
        log=lambda line: print(line, file=sys.stderr, flush=True),
    )
    for name, v in result["check"].items():
        print(f"check: {name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
