#!/usr/bin/env python3
"""The comparisons' controls at a cell's own size: the reference with
one guarantee broken, put in the program's place, must come out not
correct.

    python3 benchmark/controls.py --workload replay-lagged --seeds 1,2,3
    python3 benchmark/controls.py --workload sequence-open --seeds 1,2,3 \\
        --seconds 10

- replay cells: the reference replay with each annotation's range
  resolved at the head instead of at the op's own refSeq (the shortcut
  that skips the perspective walk for ops that change no length);
  reading: `chars_mismatched` against the reference.
- deli cells: a sequencer that takes the MSN from the sender's own
  refSeq instead of the minimum over connected clients; its stamps go
  through the run's own comparison as if the deli had emitted them;
  readings: every count the run checks.

One JSON line per seed. No chip is needed: nothing here runs the
program's device path.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark import traffic as gen  # noqa: E402


def replay_control(cfg, trf, seed) -> dict:
    from benchmark.drivers import replay
    from benchmark.reference import string_ref

    log = gen.lagged_log(cfg, trf, seed)
    initial = int(cfg["initial_chars"])
    text, props = string_ref.replay(log, initial)
    ctext, cprops = string_ref.replay(log, initial, control=True)
    return {"chars_mismatched": replay.mismatched_chars(ctext, cprops, text,
                                                        props)}


def deli_rows(sched, seq, msn, n_docs: int, n_clients: int) -> dict:
    """The reader's rows for a deltas topic holding every join with the
    reference stamps, then every op with the given stamps."""
    from fluidframework_tpu.protocol import record_batch as rb

    from benchmark.reference import deli_ref

    jseq, jmsn = deli_ref.expected_joins(n_clients)
    nj, n = n_docs * n_clients, len(sched.doc)
    cat = np.concatenate
    return {
        "kind": np.full(nj + n, rb.K_SEQ_OP),
        "type": cat([np.full(nj, rb._TYPE_CODE["join"]),
                     np.full(n, rb._TYPE_CODE["op"])]),
        "doc": cat([np.repeat(np.arange(n_docs), n_clients), sched.doc]),
        "client": cat([np.tile(np.arange(1, n_clients + 1), n_docs),
                       sched.client]),
        "cseq": cat([np.zeros(nj, np.int64), sched.cseq]),
        "seq": cat([np.tile(jseq, n_docs), seq]),
        "msn": cat([np.tile(jmsn, n_docs), msn]),
        "t": np.zeros(nj + n),
    }


def deli_control(cfg, trf, seed, seconds) -> dict:
    from benchmark.drivers import deli
    from benchmark.reference import deli_ref

    n_docs, n_clients = int(cfg["docs"]), int(cfg["clients"])
    sched = gen.open_loop(cfg, trf, seed, seconds)
    sound = deli_rows(sched, *deli_ref.expected_ops(sched), n_docs,
                      n_clients)
    control = deli_rows(sched, *deli_ref.expected_ops(sched, control=True),
                        n_docs, n_clients)
    assert all(v == 0 for v in deli.compare(sched, sound, n_docs,
                                            n_clients)[0].values())
    return deli.compare(sched, control, n_docs, n_clients)[0]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    spec = harness.bench_spec()
    cell, cfg, trf = harness.find_cell(spec, args.workload)
    seconds = args.seconds or float(spec["run_seconds"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cfg["system"] == "replay":
            readings = replay_control(cfg, trf, seed)
        else:
            readings = deli_control(cfg, trf, seed, seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": readings,
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
