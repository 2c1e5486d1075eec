"""Plain reference of the deli's stamps for an open-loop schedule.

Semantics (routerlicious deli `ticket`): per document, every accepted
message takes the next sequence number; a join admits its client at the
head before its own stamp; the MSN after a stamp is the minimum refSeq
over the connected clients, never decreasing. The schedule's refSeqs
never fall below the MSN nor pass the head and its clientSeqs count up
by one, so no op is nacked.

All clients join every document first, in document-major order, so
client c's join is the doc's c-th message: seq c, MSN 0, refSeq c-1.

`control=True` is the comparison's control: a sequencer that takes the
MSN from the sender's own refSeq instead of the minimum over connected
clients (the shortcut that skips the min-reduce).
"""

from __future__ import annotations

import numpy as np


def expected_ops(sched, control: bool = False):
    """(seq, msn) int64[N] of every op of `sched`, in schedule order."""
    n_clients = sched.n_clients
    doc = sched.doc
    n = doc.shape[0]
    order = np.argsort(doc, kind="stable")
    ds = doc[order]
    starts = np.flatnonzero(np.concatenate([[True], ds[1:] != ds[:-1]]))
    ends = np.concatenate([starts[1:], [n]])
    seq = np.empty(n, np.int64)
    msn = np.empty(n, np.int64)
    join_refs = np.arange(n_clients, dtype=np.int64)
    for lo, hi in zip(starts, ends):
        idx = order[lo:hi]
        k = hi - lo
        seq[idx] = n_clients + 1 + np.arange(k)
        refs = sched.ref[idx].astype(np.int64)
        if control:
            msn[idx] = np.maximum.accumulate(refs)
            continue
        table = np.tile(join_refs, (k, 1))
        table[np.arange(k), sched.client[idx] - 1] = refs
        # Each client's refSeq never decreases and starts at its join,
        # so its latest refSeq is the running maximum down the column.
        table = np.maximum.accumulate(table, axis=0)
        msn[idx] = table.min(axis=1)
    return seq, msn


def expected_joins(n_clients: int):
    """(seq, msn) of client c's join (index c-1) in every document."""
    return np.arange(1, n_clients + 1, dtype=np.int64), \
        np.zeros(n_clients, np.int64)
