"""Open-loop load generator: a process of its own, without JAX.

    python3 gen_proc.py <spec.json>

The spec names the raw topic, the configuration, the traffic mix, the
seed and the window length. The process builds the window's schedule
(`benchmark.traffic.open_loop`) and every frame up front, prints READY,
reads the window's start (a `time.monotonic()` value) from stdin, then
appends one columnar frame every `frame_ms`: all ops due since the
previous frame. It never waits on the system. On exit it writes how
late each append finished against its frame's due time.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark import traffic as gen  # noqa: E402


def frames(sched, frame_s: float):
    """[(due_s, lo, hi)]: frame k carries the ops due in
    [k * frame_s, (k + 1) * frame_s) and is due at its end."""
    k = np.floor(sched.due / frame_s).astype(np.int64)
    bounds = np.flatnonzero(np.diff(k)) + 1
    los = np.concatenate([[0], bounds])
    his = np.concatenate([bounds, [len(k)]])
    return [((int(k[lo]) + 1) * frame_s, int(lo), int(hi))
            for lo, hi in zip(los, his) if hi > lo]


def build(sched, lo: int, hi: int, heap: bytes, off):
    from fluidframework_tpu.protocol import record_batch as rb

    uniq, inv = np.unique(sched.doc[lo:hi], return_inverse=True)
    n = hi - lo
    zeros = np.zeros(n, np.int64)
    base = int(off[lo])
    return rb.ColumnarRecords(
        [f"doc{d}" for d in uniq.tolist()],
        np.full(n, rb.K_RAW_OP, np.uint8), np.full(n, 255, np.uint8),
        inv.astype(np.int32), sched.client[lo:hi], sched.cseq[lo:hi],
        sched.ref[lo:hi], zeros, zeros, np.full(n, -1, np.int64),
        (off[lo:hi + 1] - base).astype(np.uint32),
        heap[base:int(off[hi])],
    )


def main() -> None:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    from fluidframework_tpu.server.columnar_log import make_topic

    sched = gen.open_loop(spec["config"], spec["traffic"], spec["seed"],
                          spec["seconds"])
    heap, off = gen.contents_heap(sched)
    frame_s = float(spec["traffic"]["frame_ms"]) / 1000.0
    plan = [(due, build(sched, lo, hi, heap, off))
            for due, lo, hi in frames(sched, frame_s)]
    raw = make_topic(spec["raw"], "columnar")
    print("READY", flush=True)
    t0 = float(sys.stdin.readline())
    late = np.zeros(len(plan))
    for i, (due, recs) in enumerate(plan):
        wait = t0 + due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        raw.append_many([recs])
        late[i] = time.monotonic() - (t0 + due)
    np.save(spec["out"], late)


if __name__ == "__main__":
    main()
