"""Record GOLDEN.json for the LAGGED headline stream (round 4).

Verification chain (each link independently farm-tested):

1. The scalar Python oracle (core/mergetree.py — slow, obviously
   correct) replays a PREFIX of the stream; its digest must equal the
   native engine's digest at the same point. This grounds the chain
   in the oracle.
2. The native C++ engine (native/hostmerge.cpp — oracle-exact
   semantics, differentially farm-gated by tests/test_native_engine.py
   and tests/test_lagged_stream.py) replays the FULL stream, recording
   staged digests every `stage` ops and the final digest — the
   recorded ground truth. This closes the round-3 gap where oracle
   grounding stopped at 300k: the native chain covers all stages.
3. An independent engine's stage log (numpy overlay from
   tools/overlay_golden-style runs, or the pure oracle extending past
   its prefix) can be merged via --merge-log to cross-check stages
   from a second implementation family.
4. `chip_smoke.py` requires the pallas overlay engine's full-stream
   digest to equal the recorded digest (the north-star bit-identity
   contract).

Usage: python tools/lagged_golden.py [n_ops] [oracle_prefix]
       python tools/lagged_golden.py --merge-log LOG TAG
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fluidframework_tpu.testing.digest import state_digest  # noqa: E402

GOLDEN = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "GOLDEN.json",
)
STAGE = 100_000


def _native_replay(stream, initial_len, checkpoints):
    """Replay `stream` through the native engine, returning
    {op_index: digest} at each checkpoint index."""
    from fluidframework_tpu.core.native_engine import NativeMergeEngine

    eng = NativeMergeEngine(local_client_id=-3)
    eng.load("".join(map(chr, stream.text[:initial_len])))
    marks = sorted(set(checkpoints))
    out = {}
    t0 = time.perf_counter()
    for i, msg in enumerate(stream.as_messages()):
        eng.apply_sequenced(msg)
        if (i + 1) % 997 == 0:
            eng.pack_settled()
        if marks and i + 1 == marks[0]:
            marks.pop(0)
            out[i + 1] = state_digest(eng.annotated_spans())
            print(
                f"[native] {i + 1}/{len(stream)} ops, "
                f"{time.perf_counter() - t0:.0f}s, "
                f"digest {out[i + 1][:16]}...",
                flush=True,
            )
    return out


def merge_log(path: str, tag: str) -> None:
    """Merge an independent engine's stage log (lines like
    '[tag] N/M ops, Ss, digest HEX...') into GOLDEN.json, verifying
    against the native chain where stages overlap."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    pat = re.compile(r"\[(\w[\w-]*)\] (\d+)/\d+ ops, \d+s, digest ([0-9a-f]+)")
    stages = {}
    with open(path) as f:
        for line in f:
            m = pat.search(line)
            if m:
                stages[m.group(2)] = m.group(3)
    native = golden["chain"]["native_stage_digests"]
    verified = []
    for k, d in sorted(stages.items(), key=lambda kv: int(kv[0])):
        if k in native:
            full = native[k]
            assert full.startswith(d) or d.startswith(full[: len(d)]), (
                f"stage {k}: {tag} digest {d[:16]} != native {full[:16]}"
            )
            verified.append(int(k))
    golden["chain"][f"{tag}_stage_digests"] = stages
    golden["chain"][f"{tag}_stages_verified_vs_native"] = sorted(verified)
    with open(GOLDEN, "w") as f:
        json.dump(golden, f, indent=1)
    print(f"merged {len(stages)} {tag} stages; {len(verified)} verified "
          "against the native chain")


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--merge-log":
        merge_log(sys.argv[2], sys.argv[3])
        return
    n_ops = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_prefix = int(sys.argv[2]) if len(sys.argv) > 2 else 20_000
    n_clients, seed, initial_len, window = 1024, 7, 64, 1024

    from fluidframework_tpu.core.mergetree import replay_passive
    from fluidframework_tpu.testing.synthetic import generate_lagged_stream

    cache = os.path.join(os.path.dirname(GOLDEN), ".stream_cache")
    stream = generate_lagged_stream(
        n_ops, n_clients=n_clients, seed=seed, window=window,
        initial_len=initial_len, cache_dir=cache,
    )

    # 1. oracle grounding on the prefix
    t0 = time.perf_counter()
    oracle = replay_passive(
        (m for i, m in zip(range(n_prefix), stream.as_messages())),
        initial="".join(map(chr, stream.text[:initial_len])),
    )
    t_oracle = time.perf_counter() - t0
    oracle_digest = state_digest(oracle.annotated_spans())
    print(f"[oracle] {n_prefix} ops in {t_oracle:.0f}s, "
          f"digest {oracle_digest[:16]}...", flush=True)

    # 2. native full replay with stages
    checkpoints = [n_prefix] + [
        s for s in range(STAGE, n_ops + 1, STAGE)
    ] + [n_ops]
    t0 = time.perf_counter()
    native = _native_replay(stream, initial_len, checkpoints)
    t_native = time.perf_counter() - t0

    assert native[n_prefix] == oracle_digest, (
        "native/oracle divergence on the prefix — do not record"
    )

    golden = {
        "params": {
            "n_ops": n_ops, "n_clients": n_clients, "seed": seed,
            "initial_len": initial_len, "lagged": True,
            "window": window,
        },
        "digest": native[n_ops],
        "chain": {
            "oracle_prefix_ops": n_prefix,
            "oracle_prefix_digest": oracle_digest,
            "oracle_seconds": round(t_oracle, 1),
            "full_engine": "native-cpp",
            "native_seconds": round(t_native, 1),
            "native_stage_digests": {
                str(k): v for k, v in sorted(native.items())
                if k % STAGE == 0 or k == n_ops
            },
        },
    }
    with open(GOLDEN, "w") as f:
        json.dump(golden, f, indent=1)
    print(f"GOLDEN.json recorded: {native[n_ops][:16]}... "
          f"(native {t_native:.0f}s, oracle prefix {n_prefix})")


if __name__ == "__main__":
    main()
