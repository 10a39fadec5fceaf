"""Runs one cell of ``BENCHMARK.json``: everything found by name, nothing by branch.

A cell names a configuration (``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and, through ``BENCHMARK.json``, the
metrics it reports; each metric is read by ``bench/metrics/<name>.py``.  A
later cell, mix or metric is new files and new entries, never an edit here.

One run: make the weights and images from the seed, build the server the
configuration describes, warm every program the mix will touch, offer the mix
for the measured window, wait for late answers, read the device's peak
memory, free the server, and compare every answer with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


# -- finding things by name ---------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: unknown {what} {name!r} (have {sorted(e['name'] for e in entries)})")


def load_config(spec: dict, name: str, root: Path = ROOT) -> dict:
    return json.loads((root / find(spec["configs"], name, "configuration")["file"]).read_text())


def load_traffic(name: str, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"bench: unknown traffic mix {name!r} (no {path.relative_to(root)})")
    return json.loads(path.read_text())


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: unknown metric {name!r} (no {path.relative_to(root)})")
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise ValueError(
            f"no peaks for device kind {kind!r} in bench/peaks.json (have {sorted(table)})")
    return table[kind]


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its per-layer ones when traced, else its
    end-to-end ones (an entry without ``workloads`` applies to every cell)."""
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


# -- the device -----------------------------------------------------------------


def require_chips(n: int):
    """The first ``n`` TPU chips; exits nonzero where JAX finds fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: JAX's first device is {devices[0].platform!r} "
            f"({devices[0].device_kind}), not a TPU; no result"
        )
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX finds {len(devices)}; no result")
    return devices[:n]


def configure_jax(root: Path = ROOT) -> None:
    """A persistent compilation cache at a fixed path inside the checkout, so
    that only a checkout's first run of a cell compiles; small programs are
    cached too, or every eager operation would compile again each run."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a cache that evicts one cell's programs for another's
    # recompiles them inside a later run's set-up
    jax.config.update("jax_compilation_cache_max_size", -1)


class GcPauses:
    """Python's garbage-collector pauses while entered, as (generation,
    seconds): a pause holds the GIL, so the server's threads and the
    generator all wait it out."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def longest(self):
        return max(self.pauses, key=lambda p: p[1], default=(None, 0.0))


class CompileCounter:
    """Counts the compilations JAX starts (persistent-cache loads included)."""

    _count = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if not cls._installed:
            from jax import monitoring

            def on_event(event, **kw):
                if event == "/jax/compilation_cache/compile_requests_use_cache":
                    cls._count += 1

            monitoring.register_event_listener(on_event)
            cls._installed = True

    @classmethod
    def count(cls) -> int:
        return cls._count


# -- one run ------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    config: dict
    traffic: dict
    peaks: Optional[dict]
    seconds: float  # the measured window
    setup_s: float
    drive: object  # generator.Drive
    span_s: float  # the window's first span_s seconds: traced (--trace 1) or all of it
    waves: List[int]  # requests in each wave launched in the span
    trace: object = None  # trace.Trace of the span in a traced run

    @property
    def answered_in_span(self) -> int:
        return sum(r.outcome == "ok" and r.done <= self.span_s for r in self.drive.requests)


def _program(root: Path) -> None:
    """Put the checkout's own ``src`` on the path: the system under test."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(1, src)
    import repro  # noqa: F401  (fails here, before any work, without the program)


def build_server(config: dict, params, root: Path = ROOT):
    _program(root)
    from repro.models.engine import compile_cnn
    from repro.models.graph import CnnConfig, ExecutionPolicy
    from repro.serve import DslrServer
    from repro.serve.slo import DEFAULT_SLOS

    cfg = CnnConfig(config["network"], float(config["width"]), int(config["num_classes"]))
    engine = compile_cnn(cfg, params, ExecutionPolicy(n_digits=int(config["n_digits"])))
    tiers = tuple(s for s in DEFAULT_SLOS if s.name == config["tier"])
    return DslrServer(engine, slos=tiers, buckets=tuple(config["buckets"]),
                      max_queue=int(config["max_queue"]), brownout=bool(config["brownout"]))


def make_images(config: dict, traffic: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0])
    return rng.standard_normal((int(traffic["image_pool"]),) + tuple(config["image"]), np.float32)


WARM_DEADLINE_MS = 60000.0
STEADY_WAVES = 4
TRACE_SECONDS = 5.0  # the traced part of a --trace 1 run's window


def warm(server, config: dict, traffic: dict, images: np.ndarray, phases=None) -> None:
    """Compile (or load) every program the mix can touch: the tier's bucket
    programs, and the server's per-wave host operations at every wave size
    (stacking, padding, row slicing), by serving one wave of each size, then
    a few full waves.

    Warm-up requests carry a long dwell budget: the first waves of a process
    run slow (each eager operation's first call), and the server's admission
    and brown-out control, fed by that service time, would otherwise step the
    tier down to a digit prefix and compile a degraded program the mix never
    asks for.  The full waves after them bring the control's service-time
    average to its steady value before the window opens."""
    server.warmup(tuple(config["image"]), slos=(config["tier"],))
    if phases is not None:
        phases.append(("warmup", time.perf_counter()))
    top = max(config["buckets"])
    for n in list(range(1, top + 1)) + [top] * STEADY_WAVES:
        handles = [
            server.submit(images[i % len(images)], slo=traffic["slo"],
                          deadline_ms=WARM_DEADLINE_MS)
            for i in range(n)
        ]
        server.drain()
        for h in handles:
            np.asarray(h.result())


def _window_marker(span_s: float, on_open, on_close):
    """A thread that holds the ``bench.window`` trace span for the first
    ``span_s`` seconds of the measured window (all of it in an untraced run)
    and calls ``on_open`` and ``on_close`` at its two ends."""
    import jax

    opened = threading.Event()
    box = {}

    def hold():
        with jax.profiler.TraceAnnotation("bench.window"):
            box["t"] = time.perf_counter()
            on_open()
            opened.set()
            time.sleep(max(box["t"] + span_s - time.perf_counter(), 0.0))
        on_close()

    thread = threading.Thread(target=hold, name="bench-window")
    thread.start()
    opened.wait()
    return thread, box["t"]


def _counters(server) -> dict:
    with server._lock:
        return {"waves": len(server.wave_log), "padded_rows": server.stats["padded_rows"]}


def run_cell(cell: dict, config: dict, traffic: dict, seed: int, seconds: float, trace: bool,
             t0: float, root: Path = ROOT, require_chip: bool = True, metrics: List[dict] = (),
             log=print, on_drive=None) -> dict:
    """One run of a cell; returns the result line's object."""
    import jax

    from bench import check, generator, weights

    devices = require_chips(int(cell["chips"])) if require_chip else jax.devices()[:1]
    dev = devices[0]
    peaks = peaks_for(dev.device_kind, root) if require_chip else None
    CompileCounter.install()
    limit = config.get("limits", {}).get("max_rel_logit_err")

    phases = [("start", time.perf_counter())]
    params = jax.block_until_ready(weights.make(config, seed))
    phases.append(("weights", time.perf_counter()))
    server = build_server(config, params, root)
    from repro.serve.dispatcher import ServerOverloaded
    phases.append(("build", time.perf_counter()))
    images = make_images(config, traffic, seed)
    phases.append(("images", time.perf_counter()))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    reduced = None
    server.start()
    try:
        warm(server, config, traffic, images, phases)
        phases.append(("warm waves", time.perf_counter()))
        if trace:
            # no Python tracer, and host events only at level 1 (the benchmark's
            # own annotations): deeper host tracing slows the dispatcher's
            # host work and so inflates the idle share it is meant to read
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span_s = min(seconds, TRACE_SECONDS) if trace else seconds
        snap = {}

        def close_span():
            snap["close"] = _counters(server)
            if trace:  # the rest of the window runs untraced
                jax.profiler.stop_trace()

        compiles0 = CompileCounter.count()
        marker, opened = _window_marker(
            span_s, on_open=lambda: snap.__setitem__("open", _counters(server)),
            on_close=close_span)
        setup_s = opened - t0
        with GcPauses() as gc_pauses:
            drive = generator.drive(server, traffic, images, seconds, seed,
                                    overloaded=ServerOverloaded, opened=opened)
        compiles = CompileCounter.count() - compiles0
        marker.join()
        waves = [len(w) for w in server.wave_log[snap["open"]["waves"]:snap["close"]["waves"]]]
        padded = snap["close"]["padded_rows"] - snap["open"]["padded_rows"]
        stats = dict(server.stats)
    finally:
        server.close()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if trace:
        from bench import trace as trace_mod

        reduced = trace_mod.load(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
    del server
    gc.collect()

    t_ref = time.perf_counter()
    verdict = check.compare(config, params, images, drive.requests, limit)
    t_ref = time.perf_counter() - t_ref

    if on_drive is not None:
        on_drive(drive)
    run = Run(config, traffic, peaks, seconds, setup_s, drive, span_s, waves, reduced)
    values = {}
    for m in metrics:
        v = metric_reader(m["name"], root)(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    due = [r for r in drive.requests if r.due < seconds]
    outcomes = {k: sum(r.outcome == k for r in due)
                for k in ("ok", "degraded", "shed", "error", "missing")}
    late = drive.send_lateness_ms()
    log("bench: set-up phases (s): " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip([("process", t0)] + phases, phases)))
    log(f"bench: device {dev.platform} {dev.device_kind}; setup_s {setup_s!r}; window {seconds} s; "
        f"{len(waves)} waves in its first {span_s} s, sizes {sorted(set(waves))}, "
        f"padded rows {padded}")
    log(f"bench: requests due in the window {len(due)}: {outcomes}; completed in the window "
        f"{len(drive.completed_in_window)}; compiles in the window {compiles}")
    gen, pause = gc_pauses.longest()
    log(f"bench: stalls: longest gap between answers in the window {drive.longest_gap_s()!r} s; "
        f"{len(gc_pauses.pauses)} collector pauses, longest {pause!r} s (generation {gen})")
    log(f"bench: generator lateness p99 {generator.percentile(late, 99) if late else 0.0!r} ms; "
        f"server stats {stats}; reference check {t_ref!r} s over {verdict.compared} answers")

    result = {
        "correct": verdict.correct,
        "attempted": len(due),
        "failed": len(due) - outcomes["ok"],
        "metrics": values,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced.busy_s()
        result["device"]["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10), "idle_gaps": reduced.idle_gaps(10)}
    result["check"] = {
        k: {kk: _finite(vv) for kk, vv in v.items()} for k, v in verdict.numbers().items()}
    return result


def _finite(v):
    """JSON has no infinity: a non-finite reading prints as the largest float."""
    if isinstance(v, float) and not math.isfinite(v):
        return sys.float_info.max
    return v
