"""Sequencer pool residency: host time of the pool's flush before each
sequencer call (the program's `deli.pool.prepare` spans: building the
reloaded rows and the reload program's dispatch) per record the traced
steps moved."""

from benchmark.metrics._spans import named, records, spans


def read(run):
    recs = spans(run)
    if recs is None:
        return None
    prep = named(recs, "deli.pool.prepare")
    n = records(recs)
    if not prep or not n:
        return None
    return 1e6 * sum(s[2] - s[1] for s in prep) / n
