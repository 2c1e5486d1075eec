"""Raw topic queue: 95th percentile over the records the traced steps
moved of (start of the `deli.poll` span that read the record - the
record's due time). Records map to polls through the steps' input
offsets; a generator running late adds to the wait
(`gen_late_p95_ms.open` reports that part beside it)."""

from bisect import bisect_left

import numpy as np

from benchmark.harness import quantile
from benchmark.metrics._spans import interval, named, spans


def read(run):
    recs, pumps = spans(run), run.facts.get("pumps")
    if recs is None or not pumps:
        return None
    iv = interval(run)
    polls = sorted(s[1] for s in named(recs, "deli.poll") if s[5])
    sched, base = run.facts["sched"], run.facts["n_joins"]
    t_window = run.facts["window"][0]
    waits = []
    for ts, dt, moved, off0, off1 in pumps:
        if not moved or not (iv[0] <= ts < iv[1]):
            continue
        k = bisect_left(polls, ts)
        if k == len(polls) or polls[k] > ts + dt:
            return None  # a step's poll is missing from the log
        lo, hi = max(off0 - base, 0), max(off1 - base, 0)
        waits.append(polls[k] - (t_window + sched.due[lo:hi]))
    if not waits:
        return None
    return 1000.0 * quantile(np.concatenate(waits), 0.95)
