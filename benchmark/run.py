#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

`--trace 0` reports the cell's end-to-end metrics; `--trace 1` records
a profiler trace of part of the window and reports the per-layer
metrics instead. Both check what the timed path produced against the
benchmark's own reference. The run fails, and prints no result, off a
TPU or with fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run = None
    try:
        spec = harness.bench_spec()
        cell, config, traffic = harness.find_cell(spec, args.workload)
        run = harness.Run(cell, config, traffic, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START, spec=spec)
        harness.driver_for(config).run(run)
        run.finish()
    except harness.BenchError as exc:
        sys.exit(f"benchmark: {exc}")
    finally:
        if run is not None:
            run.cleanup()


if __name__ == "__main__":
    main()
