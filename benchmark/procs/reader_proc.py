"""Deltas reader: a process of its own, without JAX, that tails the
deltas topic as downstream roles do and notes when it read each
sequenced record.

    python3 reader_proc.py <deltas topic> <out.npz> <expected records> \
        <stop file>

It wakes on the topic's doorbell, stamps every poll with one
`time.monotonic()` reading, and exits once it has read the expected
number of records or the stop file appears. The output holds, per
record read: kind, type code, document number, client, clientSeq,
seq, MSN and read time.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


def main() -> None:
    path, out, expect, stop = sys.argv[1], sys.argv[2], int(sys.argv[3]), \
        sys.argv[4]
    from fluidframework_tpu.server.columnar_log import (
        make_tail_reader,
        make_topic,
    )
    from fluidframework_tpu.server.queue import TopicDoorbell

    reader = make_tail_reader(make_topic(path, "columnar"), 0)
    bell = TopicDoorbell(path)
    cols = {k: [] for k in ("kind", "type", "doc", "client", "cseq", "seq",
                            "msn", "t")}
    doc_ids = {}
    n = 0
    stopping = False
    while True:
        units = reader.poll_batches(None)
        now = time.monotonic()
        for unit in units:
            if unit[0] != "batch":
                continue  # a stray decoded record: nothing sequenced
            b = unit[2]
            ids = np.array([doc_ids.setdefault(d, int(d[3:]) if
                                               d.startswith("doc") else -1)
                            for d in b.docs], np.int64)
            cols["kind"].append(np.array(b.kind))
            cols["type"].append(np.array(b.type_code))
            cols["doc"].append(ids[b.doc_idx] if b.n else
                               np.zeros(0, np.int64))
            cols["client"].append(np.array(b.client))
            cols["cseq"].append(np.array(b.client_seq))
            cols["seq"].append(np.array(b.seq))
            cols["msn"].append(np.array(b.msn))
            cols["t"].append(np.full(b.n, now))
            n += b.n
        if n >= expect or (stopping and not units):
            break
        if not units:
            stopping = stopping or os.path.exists(stop)
            bell.wait(0.05)
    bell.close()
    np.savez(out, **{k: (np.concatenate(v) if v else np.zeros(0))
                     for k, v in cols.items()})


if __name__ == "__main__":
    main()
