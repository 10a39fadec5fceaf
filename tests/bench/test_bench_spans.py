"""The dispatcher's metrics on a synthesised trace: the device's idle time
inside each wave span, and the dispatcher's wait between waves.

Windows are [100, 1000] us.  Waves: A [50, 150] and E [950, 1050] cross the
window's edges; B [200, 400], C [500, 700] and D [800, 900] lie inside it."""
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace

US = 1000  # the synthetic trace counts in microseconds


def ev(name, start, dur):
    return NS(name=name, start_ns=start * US, duration_ns=dur * US)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


WAVES = [("A", 50, 100), ("B", 200, 200), ("C", 500, 200), ("D", 800, 100), ("E", 950, 100)]


def planes(waits=(), waves=WAVES):
    ops = [
        ev("fusion.1", 250, 100),  # B: busy 100 of 200, idle 100
        ev("fusion.2", 450, 110),  # starts in the gap before C, busy 60 of C
        ev("dslr_conv2d_planes_packed_mxu.3", 600, 50),  # C: busy 110 of 200, idle 90
        ev("fusion.3", 800, 100),  # D: busy throughout, idle 0
    ]
    host = [ev("bench.window", 100, 900), ev("bench.wait", 100, 900)]
    host += [ev("dslr.wave", s, d) for _, s, d in waves]
    host += [ev("dslr.dispatch.wait", s, d) for s, d in waits]
    return [plane("/device:TPU:0", **{"XLA Ops": ops}), plane("/host:CPU", python=host)]


def read(name, planes_):
    return harness.metric_reader(name)(NS(trace=trace.reduce(planes_)))


@pytest.mark.parametrize("name", ["wave_idle_ms.throughput", "wave_idle_ms.latency"])
def test_wave_idle_is_the_median_idle_inside_whole_waves(name):
    # B 100, C 90, D 0 us: the 50 us gap between B and C lies outside every
    # wave, and A and E, which cross the window's edges, do not count
    assert read(name, planes()) == pytest.approx(0.090)


def test_wave_idle_counts_only_waves_inside_the_window():
    edges = [w for w in WAVES if w[0] in "AE"]
    assert read("wave_idle_ms.throughput", planes(waves=edges)) is None
    assert read("wave_idle_ms.throughput", planes(waves=WAVES[:2])) == pytest.approx(0.100)


def test_fill_wait_sums_the_waits_between_a_wave_and_the_previous_one():
    # B -> C: between B's end (400) and C's start (500), a wait from 350
    # (counts from 400), one that touches it (to 480) and one that runs into
    # C (counts to 500): 100 us.  C -> D: a 20 us wait, shorter than the
    # trace keeps, reads 0.  A -> B and D -> E do not count: A starts before
    # the window and E ends after it
    waits = [(350, 60), (410, 70), (480, 50), (720, 20), (900, 50)]
    assert read("fill_wait_ms.latency", planes(waits)) == pytest.approx((100 + 0) / 2 * 1e-3)


def test_fill_wait_without_wait_spans_reads_zero():
    assert read("fill_wait_ms.latency", planes()) == 0.0


@pytest.mark.parametrize(
    "name", ["wave_idle_ms.throughput", "wave_idle_ms.latency", "fill_wait_ms.latency"])
def test_untraced_or_unannotated_runs_read_nothing(name):
    assert harness.metric_reader(name)(NS(trace=None)) is None
    # a program that opens no wave spans (the trace still has its window)
    assert read(name, planes(waves=[])) is None
