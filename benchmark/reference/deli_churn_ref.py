"""Plain reference of the deli's stamps for a partition whose documents
are created, joined, left and edited in one raw log.

Semantics (routerlicious deli `ticket`), per document, each message in
log order:

- a join admits its client at the head (its refSeq is the head before
  its own stamp) and takes the next sequence number;
- a leave of a connected client removes it and takes the next sequence
  number; a leave of an unknown client stamps nothing;
- an op from a connected client whose refSeq lies in [MSN, head] and
  whose clientSeq is one past its last takes the next sequence number
  and sets the client's refSeq; any other op is nacked;
- after every stamp the MSN is the least refSeq over the clients still
  connected (the head when none is), never decreasing.

`stamps` runs one scalar sequencer per document over that document's
records; nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

OP, JOIN, LEAVE = 0, 1, 2


def stamps(kind, doc, client, cseq, ref):
    """(seq, msn, nacked) int64/int64/bool arrays, one entry per record
    of the log given as columns; seq is 0 where nothing was stamped."""
    kind = np.asarray(kind)
    doc = np.asarray(doc, np.int64)
    n = doc.shape[0]
    seq_o = np.zeros(n, np.int64)
    msn_o = np.zeros(n, np.int64)
    nack_o = np.zeros(n, bool)
    order = np.argsort(doc, kind="stable")
    ds = doc[order]
    starts = np.flatnonzero(np.concatenate([[True], ds[1:] != ds[:-1]])) \
        if n else np.zeros(0, np.int64)
    ends = np.concatenate([starts[1:], [n]]).astype(np.int64)
    kinds, clients = kind.tolist(), np.asarray(client).tolist()
    cseqs, refs = np.asarray(cseq).tolist(), np.asarray(ref).tolist()
    order_l = order.tolist()
    for lo, hi in zip(starts.tolist(), ends.tolist()):
        head = msn = 0
        conn = {}  # client -> [refSeq, clientSeq]
        for i in order_l[lo:hi]:
            k, c = kinds[i], clients[i]
            if k == JOIN:
                conn[c] = [head, 0]
            elif k == LEAVE:
                if conn.pop(c, None) is None:
                    msn_o[i] = msn
                    continue
            else:
                st = conn.get(c)
                r = refs[i]
                if st is None or r < msn or r > head \
                        or cseqs[i] != st[1] + 1:
                    nack_o[i] = True
                    msn_o[i] = msn
                    continue
                st[0], st[1] = r, cseqs[i]
            head += 1
            low = min(v[0] for v in conn.values()) if conn else head
            msn = max(msn, low)
            seq_o[i] = head
            msn_o[i] = msn
    return seq_o, msn_o, nack_o
