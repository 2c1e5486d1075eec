"""Open-loop load generator of the churning-partition cells: a process
of its own, without JAX.

    python3 churn_gen_proc.py <spec.json>

The spec names the raw topic, the configuration, the traffic mix, the
seed and the window length. The process builds the window's records
(`drivers.deli_churn.schedule`) and every frame up front, prints READY,
reads the window's start (a `time.monotonic()` value) from stdin, then
appends one columnar frame every `frame_ms`: every record whose event
is due since the previous frame, a new document's joins together. It
never waits on the system. On exit it writes how late each append
finished against its frame's due time.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from benchmark import traffic as gen  # noqa: E402
from benchmark.drivers import deli_churn  # noqa: E402
from benchmark.procs.gen_proc import frames  # noqa: E402


def build(sched, lo: int, hi: int, heap: bytes, off):
    base = int(off[lo])
    return deli_churn.frame(
        sched.kind[lo:hi], sched.doc[lo:hi], sched.client[lo:hi],
        sched.cseq[lo:hi], sched.ref[lo:hi], heap[base:int(off[hi])],
        (off[lo:hi + 1] - base).astype(np.uint32))


def main() -> None:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    from fluidframework_tpu.server.columnar_log import make_topic

    sched = deli_churn.schedule(spec["config"], spec["traffic"],
                                spec["seed"], spec["seconds"])
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
