"""Sequencer kernel: least time for the bytes the traced pumps had to
move (roofline.sequencer_bytes) at the chip's memory bandwidth, over
the sequencer program's device time."""

from benchmark.metrics._deli_pumps import traced_work
from benchmark import roofline


def read(run):
    ts, work = run.trace_summary, traced_work(run)
    if ts is None or not work or not work[0]:
        return None
    s = ts.program_seconds("sequence_batch_grouped")
    if s <= 0:
        return None
    nbytes = roofline.sequencer_bytes(work[0], work[1],
                                      int(run.config["clients"]))
    return roofline.roofline_pct(nbytes, s, run.device["kind"])
