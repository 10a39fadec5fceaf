"""Device time lost to the server's own per-wave host work: for each
``dslr.wave`` span (``serve/server.py``) that starts and ends inside the
traced window, the time inside it in which chip 0 ran no operation; the
median over those waves, in ms.  Nothing to read where the program opens no
such span."""
import statistics

from bench import trace

WAVE = "dslr.wave"


def read(run):
    t = run.trace
    if t is None or not t.device_ops:
        return None
    w0, w1 = t.window
    busy = trace.merge([(o.start, o.end) for o in t.device_ops[min(t.device_ops)]])
    idle = [
        (e - s) - trace.union_ns((max(bs, s), min(be, e)) for bs, be in busy if be > s and bs < e)
        for name, s, e in t.host
        if name == WAVE and w0 <= s and e <= w1
    ]
    return statistics.median(idle) * 1e-6 if idle else None
