"""Deli role checkpoints: mean wall time (ms) of the checkpoint flushes
in the interval (`deli.checkpoint.flush`: the lock, fence check, write,
fsync and replace that the role's writer thread runs beside the pump).
None where the program flushes no checkpoint off its pump."""

from benchmark.metrics._spans import named, spans


def read(run):
    recs = spans(run)
    if recs is None:
        return None
    ms = [1000.0 * (s[2] - s[1]) for s in named(recs, "deli.checkpoint.flush")]
    return sum(ms) / len(ms) if ms else None
