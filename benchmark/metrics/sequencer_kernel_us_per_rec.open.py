"""Sequencer kernel: device time of the sequencer program per record
the traced pumps sequenced."""

from benchmark.metrics._deli_pumps import traced_work


def read(run):
    ts, work = run.trace_summary, traced_work(run)
    if ts is None or not work or not work[0]:
        return None
    s = ts.program_seconds("sequence_batch_grouped")
    return 1e6 * s / work[0] if s > 0 else None
