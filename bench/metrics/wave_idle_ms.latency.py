"""``wave_idle_ms.throughput``'s reading in the open-loop cells, where each
wave's host work adds to the latency of every request riding it."""
from bench.harness import metric_reader

read = metric_reader("wave_idle_ms.throughput")
