"""Deli role host pack/emit: host time of the batch flush outside the
sequencer call (`deli.flush` less its `deli.kernel` spans: planning,
packing, pool prepare and emission) per record the traced steps
moved."""

from benchmark.metrics._spans import named, records, spans


def read(run):
    recs = spans(run)
    if recs is None:
        return None
    n = records(recs)
    if not n:
        return None
    flush = sum(s[2] - s[1] for s in named(recs, "deli.flush"))
    kernel = sum(s[2] - s[1] for s in named(recs, "deli.kernel"))
    return 1e6 * (flush - kernel) / n
