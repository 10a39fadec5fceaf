"""Reduce a profiler trace (``.xplane.pb``) to the intervals the metrics read.

Device planes are those named ``/device:TPU:<n>``; on each, the line of XLA
operations holds one event per operation that ran (``XLA Ops`` where the
plane has such a line — the others repeat the same time at module or step
level).  Host planes keep the spans the benchmark itself opened
(``bench.window``, ``bench.submit``, ``bench.wait``) and the runtime's own
events, which name what the host was doing while the device idled.

Everything is clipped to the ``bench.window`` span, the measured window on
the trace's clock.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# the digit-plane conv kernels: Mosaic custom calls whose HLO instruction is
# named after the jitted wrapper around the pallas_call
KERNEL = re.compile(r"^dslr_conv2d")
HOST_MIN_NS = 50_000  # host events shorter than this name no idle gap

Interval = Tuple[int, int]  # (start_ns, end_ns)


@dataclasses.dataclass
class Op:
    name: str
    start: int
    end: int
    kernel: bool


@dataclasses.dataclass
class Trace:
    window: Interval
    device_ops: Dict[int, List[Op]]  # chip -> ops inside the window
    host: List[Tuple[str, int, int]]  # (name, start, end) of host events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        return sum(
            union_ns([(o.start, o.end) for o in ops]) for ops in self.device_ops.values()
        ) * 1e-9 / len(self.device_ops)

    def kernel_s(self) -> float:
        """Device time of the conv kernels' events, averaged over chips."""
        if not self.device_ops:
            return 0.0
        return sum(
            union_ns([(o.start, o.end) for o in ops if o.kernel])
            for ops in self.device_ops.values()
        ) * 1e-9 / len(self.device_ops)

    def idle_share(self) -> Optional[float]:
        if not self.device_ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The device operations that took the most time, by HLO instruction
        (a loop and the operations of its body each count their own time)."""
        total: Dict[str, int] = {}
        for ops in self.device_ops.values():
            for o in ops:
                total[o.name] = total.get(o.name, 0) + o.end - o.start
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ns * 1e-9 / len(self.device_ops)) for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of chip 0, each named by the host event that
        overlaps it most (the innermost, where several nest)."""
        if not self.device_ops:
            return []
        chip = min(self.device_ops)
        busy = merge([(o.start, o.end) for o in self.device_ops[chip]])
        gaps, t = [], self.window[0]
        for s, e in busy + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            out.append((self._host_label(s, e), (e - s) * 1e-9))
        return out

    def _host_label(self, s: int, e: int) -> str:
        best, best_key = "no host event", None
        for name, hs, he in self.host:
            overlap = min(e, he) - max(s, hs)
            if overlap <= 0 or name == WINDOW_SPAN:
                continue
            key = (overlap, -(he - hs))  # most overlap, then the innermost
            if best_key is None or key > best_key:
                best, best_key = name, key
        return best


def union_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in merge(intervals))


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(text: str) -> str:
    """The HLO instruction's name from an event named by its text
    (``%fusion.12 = f32[...] fusion(...)`` gives ``fusion.12``)."""
    return text.partition(" = ")[0].lstrip("%")


def reduce(planes) -> Trace:
    """``planes``: the ``planes`` of a ``jax.profiler.ProfileData`` (or any
    objects with the same ``name``/``lines``/``events`` fields)."""
    window: Optional[Interval] = None
    host: List[Tuple[str, int, int]] = []
    raw: Dict[int, List[Op]] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        if m:
            chip = int(m.group(1))
            names = {ln.name for ln in lines}
            for ln in lines:
                if OPS_LINE in names and ln.name != OPS_LINE:
                    continue
                for ev in ln.events:
                    start = int(ev.start_ns)
                    name = op_name(ev.name)
                    raw.setdefault(chip, []).append(
                        Op(name, start, start + int(ev.duration_ns), bool(KERNEL.match(name)))
                    )
        elif plane.name.startswith("/host"):
            for ln in lines:
                for ev in ln.events:
                    dur = int(ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = (int(ev.start_ns), int(ev.start_ns) + dur)
                    elif dur >= HOST_MIN_NS:
                        host.append((ev.name, int(ev.start_ns), int(ev.start_ns) + dur))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = window
    device = {
        chip: [
            Op(o.name, max(o.start, w0), min(o.end, w1), o.kernel)
            for o in ops if o.end > w0 and o.start < w1
        ]
        for chip, ops in raw.items()
    }
    host = [(n, s, e) for n, s, e in host if e > w0 and s < w1]
    return Trace(window, device, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes)
