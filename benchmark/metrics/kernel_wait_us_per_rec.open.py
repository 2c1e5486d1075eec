"""Sequencer kernel dispatch: host time blocked in the sequencer call
(the program's `deli.kernel` spans, through the verdict fetch) per
record the traced steps moved; the device's own time per record is
`sequencer_kernel_us_per_rec.open`."""

from benchmark.metrics._spans import named, records, spans


def read(run):
    recs = spans(run)
    if recs is None:
        return None
    n = records(recs)
    if not n:
        return None
    return 1e6 * sum(s[2] - s[1] for s in named(recs, "deli.kernel")) / n
