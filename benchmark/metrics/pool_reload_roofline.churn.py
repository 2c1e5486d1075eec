"""Sequencer pool residency: least time for the bytes the traced
reloads had to move (roofline_pool.pool_load_bytes, the rows the
`deli.pool.prepare` spans loaded) at the chip's memory bandwidth, over
the reload program's device time."""

from benchmark import roofline, roofline_pool
from benchmark.metrics._spans import named, spans


def read(run):
    ts, recs = run.trace_summary, spans(run)
    if ts is None or recs is None:
        return None
    rows = sum(s[5] or 0 for s in named(recs, "deli.pool.prepare"))
    s = ts.program_seconds("seq_pool_load")
    if not rows or s <= 0:
        return None
    nbytes = roofline_pool.pool_load_bytes(rows,
                                           int(run.facts["pool_clients"]))
    return roofline.roofline_pct(nbytes, s, run.device["kind"])
