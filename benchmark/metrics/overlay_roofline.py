"""Overlay kernel: least time for the bytes one log's replay must move
(roofline.overlay_bytes) at the chip's memory bandwidth, over the
device time of one log's fused replay program."""

from benchmark import roofline


def read(run):
    ts, n = run.trace_summary, run.facts.get("logs_traced")
    nbytes = run.facts.get("overlay_bytes")
    if ts is None or not n or not nbytes:
        return None
    s = ts.program_seconds("replay_fused")
    if s <= 0:
        return None
    return roofline.roofline_pct(nbytes, s / n, run.device["kind"])
