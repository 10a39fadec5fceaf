"""Seconds from process start to the first timed request: weights, images,
server build and warm-up (a checkout's first run compiles as well)."""


def read(run):
    return run.setup_s
