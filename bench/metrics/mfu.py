"""The whole model's share of the chip's peak: operations of every image
answered in the traced span (convs and head, ``bench/work.py``) over the
span times the int8 peak, the highest matmul rate the chip has."""
from bench import work


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ops = run.answered_in_span * work.model_ops(run.config)
    return 100.0 * ops / (run.trace.window_s * run.peaks["int8_ops_per_s"])
