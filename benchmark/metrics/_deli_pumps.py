"""Shared by the sequencer readers: what the pumps inside the traced
interval sequenced."""

import numpy as np


def traced_work(run):
    """(records, touched-doc count summed over pumps) of the pumps that
    started inside the traced interval, or None."""
    iv, pumps = run.trace_interval, run.facts.get("pumps")
    if not iv or not pumps:
        return None
    sched, base = run.facts["sched"], run.facts["n_joins"]
    records = touched = 0
    for t, _, moved, off0, off1 in pumps:
        if not (iv[0] <= t < iv[1]) or not moved:
            continue
        lo, hi = max(off0 - base, 0), max(off1 - base, 0)
        records += off1 - off0
        touched += len(np.unique(sched.doc[lo:hi]))
    return records, touched
