"""The serving path's profiler spans, on the CPU.

Three waves of a tiny AlexNet served through a started ``DslrServer`` inside
a profiler session (host events at level 1, as the benchmark traces them):
each wave leaves one ``dslr.wave`` span whose ``rows`` and ``request_ids``
stats name its riders, with its ``assemble``, ``launch``, ``guard`` and
``deliver`` steps inside it in that order; the dispatcher's wait for the
first wave to fill shows as ``dslr.dispatch.wait``; every request leaves a
``dslr.submit`` span with its ``request_id``.  Without a session, serving
records nothing and raises nothing.
"""
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.models import common as cm
from repro.models.engine import compile_cnn
from repro.models.graph import CnnConfig, ExecutionPolicy, graph_spec
from repro.serve import DslrServer, SloClass

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

WAVE_SIZES = (2, 2, 1)  # two full waves of bucket 2, then one flushed by drain
STEPS = ["dslr.wave.assemble", "dslr.wave.launch", "dslr.wave.guard", "dslr.wave.deliver"]
SLOS = (SloClass("exact", None, max_dwell_ms=60000.0),)


@pytest.fixture(scope="module")
def alexnet():
    cfg = CnnConfig(name="alexnet", width=0.02, num_classes=4)
    params = cm.init_params(graph_spec(cfg), jax.random.PRNGKey(0))
    return compile_cnn(cfg, params, ExecutionPolicy())


def images(n, img=12):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.standard_normal((img, img, 3)), jnp.float32) for _ in range(n)]


def serve(server, imgs):
    """One wave per entry of ``WAVE_SIZES``: a full bucket launches as soon
    as it fills, the last wave when ``drain`` forces it out."""
    handles, it = [], iter(imgs)
    for n in WAVE_SIZES:
        wave = [server.submit(next(it), slo="exact") for _ in range(n)]
        server.drain(timeout=300)
        handles.append(wave)
    for wave in handles:
        for h in wave:
            assert np.isfinite(np.asarray(h.result(timeout=300))).all()
    return handles


def host_events(profile):
    """Every ``dslr.*`` event of the host planes: (name, start, end, stats)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dslr."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns), dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(alexnet, tmp_path_factory):
    """Warm the programs untraced, then serve the three waves inside a
    profiler session."""
    imgs = images(sum(WAVE_SIZES))
    with DslrServer(alexnet, slos=SLOS, buckets=(1, 2)) as server:
        serve(server, imgs)  # compiles both bucket programs
    server = DslrServer(alexnet, slos=SLOS, buckets=(1, 2))
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with TraceAnnotation("bench.window"):
            server.start()
            handles = serve(server, imgs)
    finally:
        jax.profiler.stop_trace()
        server.close()
    profile = ProfileData.from_file(glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0])
    return server, handles, profile


def test_each_wave_has_one_span_with_its_steps_in_order(traced):
    server, handles, profile = traced
    events = host_events(profile)
    waves = [e for e in events if e[0] == "dslr.wave"]
    assert len(waves) == len(WAVE_SIZES)
    for (_, s, e, stats), riders, logged in zip(waves, handles, server.wave_log):
        assert stats["rows"] == len(riders)
        assert stats["bucket"] == server._bucket_for(len(riders))
        ids = [int(i) for i in str(stats["request_ids"]).split()]
        assert ids == [h.request_id for h in riders] == list(logged)
        inside = [n for n, cs, ce, _ in events if n.startswith("dslr.wave.") and s <= cs
                  and ce <= e]
        assert inside == STEPS


def test_wave_spans_survive_the_benchmarks_reduction(traced):
    """``bench.trace.reduce`` keeps the wave spans, on the trace's clock."""
    _, _, profile = traced
    reduced = trace.reduce(profile.planes)
    waves = [(s, e) for n, s, e in reduced.host if n == "dslr.wave"]
    assert len(waves) == len(WAVE_SIZES)
    assert all(reduced.window[0] <= s < e <= reduced.window[1] for s, e in waves)


def test_dispatcher_waits_before_the_first_wave(traced):
    _, _, profile = traced
    events = host_events(profile)
    first = min(s for n, s, _, _ in events if n == "dslr.wave")
    assert any(n == "dslr.dispatch.wait" and e <= first for n, _, e, _ in events)


def test_each_request_has_a_submit_span(traced):
    _, handles, profile = traced
    ids = [st["request_id"] for n, _, _, st in host_events(profile) if n == "dslr.submit"]
    assert ids == [h.request_id for wave in handles for h in wave]


def test_no_session_records_nothing_and_raises_nothing(alexnet, tmp_path):
    assert not TraceAnnotation.is_enabled()
    with DslrServer(alexnet, slos=SLOS, buckets=(1, 2)) as server:
        handles = serve(server, images(sum(WAVE_SIZES)))
    assert [len(w) for w in server.wave_log] == list(WAVE_SIZES)
    assert [h.wave_seq for wave in handles for h in wave] == [1, 1, 2, 2, 3]
    # a session opened afterwards holds none of those spans
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    assert host_events(ProfileData.from_file(path)) == []
