"""How long the dispatcher waits for a wave to fill: for each ``dslr.wave``
span inside the traced window whose previous wave also started inside it,
the time between the previous wave's end and this wave's start covered by
``dslr.dispatch.wait`` spans (``serve/dispatcher.py``; 0 where none of
50 us or more was kept); the median over those waves, in ms.  Nothing to
read where the program opens no such span."""
import statistics

from bench import trace

WAVE, WAIT = "dslr.wave", "dslr.dispatch.wait"


def read(run):
    t = run.trace
    if t is None:
        return None
    w0, w1 = t.window
    waves = sorted((s, e) for name, s, e in t.host if name == WAVE)
    waits = [(s, e) for name, s, e in t.host if name == WAIT]
    fills = [
        trace.union_ns((max(ws, pe), min(we, s)) for ws, we in waits if we > pe and ws < s)
        for (ps, pe), (s, e) in zip(waves, waves[1:])
        if ps >= w0 and e <= w1
    ]
    return statistics.median(fills) * 1e-6 if fills else None
