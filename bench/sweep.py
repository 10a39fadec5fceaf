"""Find the knee of an open-loop cell: offer its mix at several fixed rates,
one short window each, in one process on the chip.

    python3 bench/sweep.py --workload <cell> --rates 100,150,200 --seconds 20 --seed 7

The knee is the highest rate with no shed, degraded or failed request and no
growing backlog: the latency of the window's last third stays near its
first third's.  Run once when a cell is defined; the cell then offers a
fixed rate.  The benchmark's own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import generator, harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    spec = harness.load_benchmark()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    harness.require_chips(int(cell["chips"]))
    harness.configure_jax()
    for rate in [float(r) for r in args.rates.split(",")]:
        drives = []
        res = harness.run_cell(
            cell, config, dict(traffic, rate_per_s=rate), args.seed, args.seconds, False,
            time.perf_counter(), log=lambda line: print(line, file=sys.stderr, flush=True),
            on_drive=drives.append,
        )
        d = drives[0]
        thirds = []
        for k in range(3):
            lo, hi = k * args.seconds / 3, (k + 1) * args.seconds / 3
            lat = [(r.done - r.due) * 1e3 if r.outcome == "ok" else float("inf")
                   for r in d.requests if lo <= r.due < hi]
            thirds.append(generator.percentile(lat, 95) if lat else None)
        lat = d.latency_ms()
        print(json.dumps({
            "rate_per_s": rate, "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "p50_ms": generator.percentile(lat, 50),
            "p95_ms": generator.percentile(lat, 95), "p95_ms_by_third": thirds,
            "completed_per_s": len(d.completed_in_window) / args.seconds,
        }), flush=True)


if __name__ == "__main__":
    main()
