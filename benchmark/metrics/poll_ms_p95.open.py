"""Deli role topic I/O in: 95th percentile of the program's `deli.poll`
spans (the raw-topic read and per-batch processing of one step) over
the steps that moved records."""

from benchmark.metrics._spans import p95_ms


def read(run):
    return p95_ms(run, "deli.poll", moved_only=True)
