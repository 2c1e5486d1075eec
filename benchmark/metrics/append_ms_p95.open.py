"""Deli role topic I/O out: 95th percentile of the program's
`deli.append` spans (the fenced durable append to the deltas topic)."""

from benchmark.metrics._spans import p95_ms


def read(run):
    return p95_ms(run, "deli.append")
