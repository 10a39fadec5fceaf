"""Median latency over every request due in the window, from its due time to
the client holding its logits; a failed or unanswered request counts as
waiting until collection stopped."""
from bench.generator import percentile


def read(run):
    lat = run.drive.latency_ms()
    return percentile(lat, 50) if lat else None
