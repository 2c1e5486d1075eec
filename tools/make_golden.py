"""Record the golden final-state digest for the standard replay stream.

Verification chain (each link independently tested):

1. The scalar Python oracle (core/mergetree.py — slow, obviously
   correct) replays a PREFIX of the stream directly. The oracle is
   O(doc) per op, so a full 1M-op replay is infeasible (hours); the
   prefix grounds the chain in the oracle.
2. The scan engine (ops/mergetree_kernel.py — the lax.scan XLA
   kernel, an implementation independent of the pallas kernel) must
   match the oracle bit-for-bit on that prefix, then replays the FULL
   stream to produce the recorded digest.
3. `chip_smoke.py` requires the pallas engine's full-stream digest to
   equal the recorded digest (GOLDEN.json), closing the round-1 gap
   where identity was only gated on a 20k prefix.

The stream is deterministic (seeded); params ride the file and are
checked before the digest is trusted.

Usage: python tools/make_golden.py [n_ops] [oracle_prefix]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fluidframework_tpu.testing.digest import state_digest  # noqa: E402


def main() -> None:
    n_ops = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_prefix = int(sys.argv[2]) if len(sys.argv) > 2 else 50_000
    n_clients, seed, initial_len = 1024, 7, 64

    from fluidframework_tpu.core.columnar_replay import ColumnarReplica
    from fluidframework_tpu.core.mergetree import replay_passive
    from fluidframework_tpu.testing.synthetic import generate_stream

    stream = generate_stream(
        n_ops, n_clients=n_clients, seed=seed, initial_len=initial_len
    )

    # 1. oracle on the prefix
    prefix_stream = generate_stream(
        n_prefix, n_clients=n_clients, seed=seed, initial_len=initial_len
    )
    t0 = time.perf_counter()
    oracle = replay_passive(
        prefix_stream.as_messages(),
        initial="".join(map(chr, prefix_stream.text[:initial_len])),
    )
    t_oracle = time.perf_counter() - t0
    oracle_digest = state_digest(oracle.annotated_spans())

    # 2. scan engine: prefix must match the oracle, then the full run
    pre = ColumnarReplica(prefix_stream, initial_len=initial_len, engine="scan")
    pre.replay()
    pre.check_errors()
    if state_digest(pre.annotated_spans()) != oracle_digest:
        print("FATAL: scan engine diverges from oracle on prefix",
              file=sys.stderr)
        sys.exit(1)

    t0 = time.perf_counter()
    full = ColumnarReplica(stream, initial_len=initial_len, engine="scan")
    full.replay()
    full.check_errors()
    t_scan = time.perf_counter() - t0
    digest = state_digest(full.annotated_spans())

    out = {
        "params": {
            "n_ops": n_ops, "n_clients": n_clients, "seed": seed,
            "initial_len": initial_len,
        },
        "digest": digest,
        "chain": {
            "oracle_prefix_ops": n_prefix,
            "oracle_prefix_digest": oracle_digest,
            "oracle_seconds": round(t_oracle, 1),
            "full_engine": "scan",
            "scan_seconds": round(t_scan, 1),
        },
        "final_len": len(full.get_text()),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "GOLDEN.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
