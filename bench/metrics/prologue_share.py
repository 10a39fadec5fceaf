"""Share of the device's busy time spent outside the digit-plane conv
kernels: quantize, recode, pack and im2col fusions, pooling, residual adds,
the head and the server's per-wave operations."""


def read(run):
    t = run.trace
    if t is None:
        return None
    busy, kernel = t.busy_s(), t.kernel_s()
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * (busy - kernel) / busy
