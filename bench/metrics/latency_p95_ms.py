"""95th percentile (nearest rank) of the same latencies as ``latency_p50_ms``."""
from bench.generator import percentile


def read(run):
    lat = run.drive.latency_ms()
    return percentile(lat, 95) if lat else None
