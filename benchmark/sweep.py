#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop cell: the knee its traffic file's
rate is set from (0.8 x the highest rate sustained).

    python3 benchmark/sweep.py --workload sequence-open \\
        --rates 6000,8000,10000 --seconds 15 --seed 3001

Each rate runs in a process of its own (one process per chip), with the
cell's traffic mix at that rate; a rate may be listed more than once.
A run is sustained when the deli drained the window's checkpoint
backlog: the median op waited at most 100 ms and at the close no more
than a quarter second of arrivals was unread. The p95 cannot tell: it
is the checkpoint stall itself at every rate, and by thirds of the
window it falls in a phased 10 s window and grows in a 30 s one even
well below the knee, as the stalls lengthen. The median op arrives
outside a stall, so it waits the steady pump latency (30-60 ms at
5,000-8,000 ops/s on a v5e) unless the deli is still working off the
stall's backlog; 100 ms is about twice that. A quarter second of
arrivals at the close is a few pumps' worth. The knee is the highest
rate whose runs, and every lower rate's, were all sustained. One JSON
line per run is printed, then a table, then the knee and 0.8 x knee.
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(args) -> None:
    import time

    t_start = time.perf_counter()
    from benchmark import harness

    spec = harness.bench_spec()
    cell, config, traffic = harness.find_cell(spec, args.workload)
    traffic["rate_per_s"] = float(args.rate)
    run = harness.Run(cell, config, traffic, args.seed, args.seconds, False,
                      t_start=t_start, spec=spec)
    try:
        harness.driver_for(config).run(run)
        res = run.result()
        res["diag"] = run.facts.get("diag")
        print(json.dumps(res), flush=True)
    finally:
        run.cleanup()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate", type=float)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=3001)
    args = ap.parse_args()
    if args.rate is not None:
        return one(args)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--rate", str(rate), "--seconds",
             str(args.seconds), "--seed", str(args.seed + i)],
            capture_output=True, text=True)
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() \
            else "{}"
        print(line, flush=True)
        if out.returncode:
            print(out.stderr[-3000:], file=sys.stderr)
        rows.append((rate, json.loads(line)))
    print("rate  correct  p50_ms  p95_ms  p95_thirds_ms  backlog  rec/s")
    for rate, r in rows:
        d = r.get("diag") or {}
        m = r.get("metrics", {})
        print(rate, r.get("correct"), d.get("p50_ms"), d.get("p95_ms"),
              d.get("p95_thirds_ms"), d.get("backlog_at_close"),
              m.get("sequenced_records_per_s", {}).get("value"))
    knee = knee_rate(rows)
    print(json.dumps({"knee": knee, "rate": int(0.8 * knee // 100 * 100)}))


def sustained(rate: float, res: dict) -> bool:
    """The window's checkpoint backlog drained: the median op waited at
    most 100 ms, and at the close no more than 0.25 s of arrivals were
    still unread."""
    d = res.get("diag") or {}
    return bool(res.get("correct")) and d.get("p50_ms", 1e9) <= 100.0 \
        and d.get("backlog_at_close", 1e9) <= 0.25 * rate


def knee_rate(rows) -> float:
    """The highest rate at which every run, and every run at each lower
    rate, was sustained (0 when none was)."""
    knee = 0.0
    for rate in sorted({r for r, _ in rows}):
        if not all(sustained(rate, res) for r, res in rows if r == rate):
            break
        knee = rate
    return knee


if __name__ == "__main__":
    main()
