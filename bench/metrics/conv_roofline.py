"""The conv kernels' share of their roofline: the least time the chip could
take for the conv layers of the images answered in the traced span (per
layer, operations over the int8 peak or bytes over HBM bandwidth, whichever
is larger; ``bench/work.py``), over the kernels' device time there."""
from bench import work


def read(run):
    t = run.trace
    if t is None or run.peaks is None or t.kernel_s() <= 0 or run.answered_in_span == 0:
        return None
    least = work.conv_min_seconds(run.config, run.answered_in_span, len(run.waves), run.peaks)
    return 100.0 * least / t.kernel_s()
