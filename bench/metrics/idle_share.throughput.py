"""Share of the traced window in which no operation ran on the device
(closed-loop throughput cells)."""


def read(run):
    share = None if run.trace is None else run.trace.idle_share()
    return None if share is None else 100.0 * share
