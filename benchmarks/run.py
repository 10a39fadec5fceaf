# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness:
    python -m benchmarks.run [--only <module>[,<module>...]] [--json <path>]

One module per paper table/figure:
  table2_synthesis   Table 2  (synthesis constants + critical-path model)
  table4_networks    Table 4 + Figs. 8-11 (durations, TOPS, TOPS/W, GOPS/mm2)
  table5_comparison  Table 5  (prior-work ratios, 45->65 nm scaling)
  fig2_pipeline      Fig. 2   (digit-level pipelining latency + sim timing)
  fig12_intensity    Fig. 12  (operational intensity)
  kernels_bench      TPU adaptation (Pallas MSDF matmul vs refs, CPU interpret)
  conv_bench         conv execution paths: float vs scan-serial vs digit-plane
  packed_bench       packed 2-bit digit interchange: traffic ratio, OI, skips
  engine_bench       compiled engine: build-once vs per-call weight prep
  planner_bench      budget planner: planned vs uniform budgets, equal cycles
  serve_bench        request-level server: mixed-SLO latency, scale decoupling
  serve_async_bench  async dispatcher: sustained-load p99 vs QPS, bitwise parity
  adaptive_bench     confidence-gated early exit: mean digits vs static plans
  pipeline_bench     cross-layer digit pipelining: traffic saved, cycle overlap
  lm_bench           digit-serial LM inference: token agreement/CE vs digits
  chaos_bench        fault-tolerant serving: availability/bitwise under chaos

``--only`` takes exact module names (comma-separated for several); an
unknown name is an error, not a silent no-op.  (It used to be a prefix
match, which made ``serve_bench`` impossible to run without also running
``serve_async_bench``.)  ``--json <path>`` (or env BENCH_JSON) writes every
emitted row to a JSON artifact — the per-PR perf trajectory CI uploads.
Env BENCH_FAST=1 shrinks kernel benchmarks to smoke size.
"""
from __future__ import annotations

import os
import sys
import traceback
from typing import List, Optional

MODULES = [
    "table2_synthesis",
    "table4_networks",
    "table5_comparison",
    "fig2_pipeline",
    "fig12_intensity",
    "kernels_bench",
    "conv_bench",
    "packed_bench",
    "engine_bench",
    "planner_bench",
    "serve_bench",
    "serve_async_bench",
    "adaptive_bench",
    "pipeline_bench",
    "lm_bench",
    "chaos_bench",
]


def flag_value(argv: List[str], flag: str) -> Optional[str]:
    """The token after ``flag`` in ``argv``, or None if absent.  A trailing
    flag with no operand is an error (it used to IndexError into a
    traceback when ``--only`` or ``--json`` was the last token)."""
    if flag not in argv:
        return None
    i = argv.index(flag)
    if i + 1 >= len(argv):
        raise ValueError(f"{flag} requires an argument")
    return argv[i + 1]


def select_modules(only: Optional[str]) -> List[str]:
    """Resolve ``--only``: exact module names, comma-separated, order as in
    MODULES.  Raises ValueError on an unknown name (a prefix that silently
    matched nothing — or too much, like ``serve`` catching both serve
    benches — was how CI steps quietly drifted)."""
    if only is None:
        return list(MODULES)
    wanted = {w.strip() for w in only.split(",") if w.strip()}
    unknown = sorted(wanted - set(MODULES))
    if unknown:
        raise ValueError(
            f"unknown --only module(s) {unknown}; available: {MODULES}"
        )
    return [m for m in MODULES if m in wanted]


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    try:
        only = flag_value(sys.argv, "--only")
        json_path = flag_value(sys.argv, "--json") or os.environ.get("BENCH_JSON")
        selected = select_modules(only)
    except ValueError as e:
        print(f"# {e}", file=sys.stderr)
        sys.exit(2)
    print("name,us_per_call,derived")
    failures = []
    for mod_name in selected:
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            mod.main()
        except Exception:  # keep the harness robust; report at the end
            failures.append(mod_name)
            traceback.print_exc()
    if json_path:
        from .common import write_json

        write_json(json_path)
        print(f"# wrote {json_path}", file=sys.stderr)
    if failures:
        print(f"# FAILED modules: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
