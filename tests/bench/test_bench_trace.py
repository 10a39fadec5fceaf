"""The trace reducer on a synthesised trace: interval union, idle share,
kernel matching, clipping to the measured window, and idle-gap naming."""
from types import SimpleNamespace as NS

import pytest

from bench import trace


US = 1000  # the synthetic trace counts in microseconds


def ev(name, start, dur):
    return NS(name=name, start_ns=start * US, duration_ns=dur * US)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def planes():
    ops = [
        ev("%fusion.1 = f32[8] fusion(f32[8] %p)", 100, 100),  # 100-200
        ev('%dslr_conv2d_planes_packed_mxu.3 = f32[8,128] custom-call(s8[8] %copy.1), '
           'custom_call_target="tpu_custom_call"', 150, 150),  # 150-300, overlaps
        # consumes the kernel's output: not a kernel itself
        ev("%fusion.2 = f32[8] fusion(f32[8,128] %dslr_conv2d_planes_packed_mxu.3)", 500, 100),
        ev("copy.9", 0, 50),  # before the window: dropped
        ev("fusion.3", 950, 100),  # clipped to 950-1000
    ]
    modules = [ev("jit__jit_execute", 100, 500)]  # same time at module level: ignored
    host = [
        ev("bench.window", 80, 920),  # window 80-1000
        ev("PjitFunction(_jit_execute)", 300, 200),  # overlaps the 300-500 gap fully
        ev("bench.wait", 250, 700),  # outer span over the same gap
        ev("ParseArguments", 310, 10),  # too short to name a gap
    ]
    return [
        plane("/device:TPU:0", **{"XLA Ops": ops, "XLA Modules": modules}),
        plane("/host:CPU", python=host),
    ]


def test_union_merges_overlaps():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.merge([(5, 20), (0, 10)]) == [(0, 20)]


def test_busy_idle_and_kernel_time():
    t = trace.reduce(planes())
    assert t.window == (80 * US, 1000 * US)
    assert t.window_s == pytest.approx(920e-6)
    busy = (300 - 100) + (600 - 500) + (1000 - 950)
    assert t.busy_s() == pytest.approx(busy * 1e-6)
    assert t.idle_share() == pytest.approx(1 - busy / 920)
    assert t.kernel_s() == pytest.approx(150e-6)
    assert [o.kernel for o in t.device_ops[0]] == [False, True, False, False]


def test_top_ops_and_idle_gaps():
    t = trace.reduce(planes())
    assert t.top_ops(2)[0] == ("dslr_conv2d_planes_packed_mxu.3", pytest.approx(150e-6))
    gaps = t.idle_gaps(10)
    # longest first; each named by the host event overlapping it most, the
    # innermost one where two overlap it alike
    assert [g[0] for g in gaps] == ["bench.wait", "PjitFunction(_jit_execute)", "no host event"]
    assert [g[1] for g in gaps] == pytest.approx([350e-6, 200e-6, 20e-6])
    assert sum(g[1] for g in gaps) == pytest.approx(t.window_s - t.busy_s())


def test_op_names_are_hlo_instruction_names():
    assert trace.op_name("%fusion.12 = f32[8] fusion(f32[8] %p)") == "fusion.12"
    assert trace.op_name("copy-start") == "copy-start"


def test_no_window_span_is_an_error():
    p = [plane("/device:TPU:0", **{"XLA Ops": [ev("fusion", 0, 10)]})]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(p)


def test_no_device_plane_reads_nothing():
    t = trace.reduce([plane("/host:CPU", python=[ev("bench.window", 0, 100)])])
    assert t.busy_s() == 0.0 and t.idle_share() is None and t.idle_gaps() == []
