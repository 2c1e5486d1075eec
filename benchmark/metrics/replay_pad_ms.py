"""Replay engine host side: time per traced log padding the host
columns for upload (the program's `overlay.pad` spans)."""

from benchmark.metrics._spans import named, spans


def read(run):
    recs, n = spans(run), run.facts.get("logs_traced")
    if recs is None or not n:
        return None
    pad = named(recs, "overlay.pad")
    if not pad:
        return None
    return 1000.0 * sum(s[2] - s[1] for s in pad) / n
