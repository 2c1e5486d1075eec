#!/usr/bin/env python3
"""Smoke test of the served path on TPU chips, through its entry points.

    python chip_smoke.py              # one chip: farm, replay, serve, summary
    python chip_smoke.py --chips 4    # four chips: the multi-chip path only

One process per chip: the `farm` phase runs before this process
imports JAX (its kernel-deli child owns the chip while it runs and has
exited before the next phase); every later phase runs in this process.

- farm     a supervised farm (`ServiceSupervisor(deli_impl="kernel")`,
           driven by the chaos harness with no faults) sequences 16
           docs' records; the deli child's heartbeat must report a
           `tpu` device, and the stream must converge to the in-process
           golden.
- replay   the north-star stream (1,000,000 lagged ops, 1,024 clients,
           seed 7) through the compiled overlay engine; a 20k prefix is
           gated against the scalar oracle and the full-stream digest
           against GOLDEN.json.
- serve    ContainerRuntime clients edit one SharedString concurrently
           over `LocalServer(deli_impl="kernel")`, converge, summarize,
           and cold-boot through `catchup`; then the kernel deli pool
           at BASELINE config 5 width (10,000 docs x 64 clients) must
           stamp bit-identically to the scalar deli.
- summary  `SummarizerRole` folds 64 docs x 1,500 ops through compiled
           overlay-pallas kernels; its manifests (content-addressed
           canonical rows) must equal the kernel fold's, and a cold
           `read_catchup` boot must equal a full replay of the log.

`--chips 4` runs the kernel deli sharded over four chips against the
one-chip run, and `__graft_entry__._dryrun_impl(4)` with compiled
kernels, in this one process.

Lines before the last are per-phase diagnostics (host-clock compile
and run seconds, digests) — not device metrics. Any failure exits
non-zero; off a TPU the script exits non-zero at its first platform
check. The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from fluidframework_tpu.utils.devices import (  # noqa: E402  (no jax)
    compile_seconds,
    track_compiles,
)

REPLAY_OPS, REPLAY_CLIENTS, GATE_OPS = 1_000_000, 1024, 20_000
SEED, COLLAB_WINDOW, INITIAL_LEN = 7, 1024, 64
GEOMETRY = {"chunk": 256, "window": 2048, "n_removers": 24}
POOL_DOCS, POOL_CLIENTS = 10_000, 64
SUMMARY_DOCS, SUMMARY_OPS_PER_DOC = 64, 1500


class SmokeFailure(Exception):
    """A phase's result was wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(f"chip_smoke {phase}: {json.dumps(fields, sort_keys=True)}",
          flush=True)


# ---------------------------------------------------------------- farm


def phase_farm(work: str) -> None:
    """Runs before this process imports JAX."""
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    t0 = time.perf_counter()
    shared = os.path.join(work, "farm")
    res = run_chaos(ChaosConfig(
        seed=21, faults=(), n_docs=16, n_clients=4, ops_per_client=8,
        deli_impl="kernel", batch=64, shared_dir=shared,
        timeout_s=900.0,
    ))
    deli = res.devices.get("deli") or {}
    with open(os.path.join(shared, "hb", "deli.json")) as f:
        hb = json.load(f)
    report("farm", seconds=time.perf_counter() - t0,
           converged=res.converged, digest=res.digest,
           restarts=res.restarts, devices=res.devices,
           deli_compile_s=hb.get("compile_s"))
    check(deli.get("platform") == "tpu",
          f"the kernel deli child ran on {deli!r}, not a tpu")
    check(res.converged, f"farm did not converge: {res.detail}")
    check(not any(res.restarts.values()),
          f"farm children restarted without faults: {res.restarts}")
    # The chip must be free before this process takes it.
    pid = hb["pid"]
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return
    raise SmokeFailure(f"the deli child (pid {pid}) outlived the farm")


# -------------------------------------------------------------- replay


def gen_stream(n_ops: int, n_clients: int):
    """The north-star lagged op stream (seed 7), memoized under
    ``<repo>/.stream_cache``."""
    from fluidframework_tpu.testing.synthetic import generate_lagged_stream

    return generate_lagged_stream(
        n_ops, n_clients=n_clients, seed=SEED, window=COLLAB_WINDOW,
        initial_len=INITIAL_LEN,
        cache_dir=os.path.join(REPO, ".stream_cache"),
    )


def make_replica(stream, chunk: int, window: int, n_removers: int):
    """A compiled (never interpreted) overlay replay replica."""
    from fluidframework_tpu.core.overlay_replay import OverlayDeviceReplica

    return OverlayDeviceReplica(
        stream, initial_len=INITIAL_LEN, chunk_size=chunk, window=window,
        n_removers=n_removers,
    )


def gate_prefix(gate_stream, **geometry) -> None:
    """Replay `gate_stream` through the overlay engine and the scalar
    oracle; fails unless the final texts are identical."""
    from fluidframework_tpu.core.mergetree import replay_passive

    gate = make_replica(gate_stream, **geometry)
    # The incremental per-chunk path: the fused executable is
    # shape-specialized to the main stream.
    gate.replay(limit_chunks=gate.n_chunks)
    gate.check_errors()
    oracle = replay_passive(
        gate_stream.as_messages(),
        initial="".join(map(chr, gate_stream.text[:INITIAL_LEN])),
    )
    check(gate.get_text() == oracle.get_text(),
          f"kernel/oracle divergence on the {len(gate_stream.seq)}-op "
          f"gate prefix")


def golden_check(replica, n_ops: int, n_clients: int):
    """Compare `replica`'s full-stream final-state digest with
    GOLDEN.json. Returns (digest, producer) on a match, None when the
    golden was recorded for other stream params; fails on a
    mismatch."""
    from fluidframework_tpu.testing.digest import state_digest

    with open(os.path.join(REPO, "GOLDEN.json")) as f:
        golden = json.load(f)
    params = {
        "n_ops": n_ops, "n_clients": n_clients, "seed": SEED,
        "initial_len": INITIAL_LEN, "lagged": True,
        "window": COLLAB_WINDOW,
    }
    if golden.get("params") != params:
        return None
    producer = golden.get("chain", {}).get("full_engine", "?")
    d = state_digest(replica.annotated_spans())
    check(d == golden["digest"],
          f"full-stream final state {d[:16]} diverges from the recorded "
          f"{producer}-produced digest {golden['digest'][:16]}")
    return d, producer


def phase_replay() -> None:
    t0 = time.perf_counter()
    stream = gen_stream(REPLAY_OPS, REPLAY_CLIENTS)
    gate = gen_stream(GATE_OPS, REPLAY_CLIENTS)
    gen_s = time.perf_counter() - t0

    c0, t0 = compile_seconds(), time.perf_counter()
    gate_prefix(gate, **GEOMETRY)
    gate_s = time.perf_counter() - t0
    gate_compile = compile_seconds() - c0

    c0, t0 = compile_seconds(), time.perf_counter()
    first = make_replica(stream, **GEOMETRY)
    first.replay()
    first.check_errors()
    first_s = time.perf_counter() - t0
    replay_compile = compile_seconds() - c0

    replica = make_replica(stream, **GEOMETRY)
    replica.prepare()
    t0 = time.perf_counter()
    replica.replay()
    replica.check_errors()  # a fetch: the device has finished
    run_s = time.perf_counter() - t0

    match = golden_check(replica, REPLAY_OPS, REPLAY_CLIENTS)
    check(match is not None, "GOLDEN.json does not cover this stream")
    report("replay", ops=REPLAY_OPS, generate_s=gen_s,
           gate_ops=GATE_OPS, gate_s=gate_s, gate_compile_s=gate_compile,
           first_replay_s=first_s, compile_s=replay_compile,
           run_s=run_s, digest=match[0], golden=True)


# --------------------------------------------------------------- serve


def _concurrent_string_edits() -> dict:
    from fluidframework_tpu.dds import MapFactory, StringFactory
    from fluidframework_tpu.drivers import LocalDriver
    from fluidframework_tpu.loader import Loader
    from fluidframework_tpu.runtime import ChannelRegistry
    from fluidframework_tpu.server import LocalServer
    from fluidframework_tpu.server.summarizer import summarize_document

    registry = ChannelRegistry([MapFactory(), StringFactory()])
    server = LocalServer(deli_impl="kernel")
    loader = Loader(LocalDriver(server), registry)

    def text(c):
        return c.runtime.get_datastore("default").get_channel("s")

    first = loader.create_detached()
    first.runtime.create_datastore("default").create_channel(
        "s", StringFactory.type_name
    )
    text(first).insert_text(0, "served on one chip")
    doc = first.attach()
    clients = [first] + [loader.resolve(doc) for _ in range(3)]
    for rnd in range(6):
        # Every client edits before any flushes: each op is made
        # without seeing the others' (concurrent refSeqs).
        for i, c in enumerate(clients):
            s = text(c)
            s.insert_text((rnd * 7 + i * 5) % (len(s.get_text()) + 1),
                          f"<{i}.{rnd}>")
            if rnd % 2 and len(s.get_text()) > 8:
                s.remove_text(i, i + 2)
        for c in clients:
            c.flush()
    texts = [text(c).get_text() for c in clients]
    check(all(t == texts[0] for t in texts),
          f"SharedString clients diverged: {texts}")
    _, base = summarize_document(server, registry, doc)
    cu = server.catchup(doc)
    check(cu["summarySeq"] == base > 0, f"catchup missed the summary: "
          f"{cu['summarySeq']} vs {base}")
    cold = loader.resolve(doc, connect=False)
    check(text(cold).get_text() == texts[0],
          "summary + tail cold boot diverged from the live clients")
    return {"clients": len(clients), "chars": len(texts[0]),
            "summary_seq": base, "tail_ops": len(cu["ops"])}


def _pool_workload(work: str):
    """BASELINE config 5's raw topic (10,000 docs x 64 clients, one
    join + one op each) as a columnar log; returns (path, records)."""
    from fluidframework_tpu.server.columnar_log import make_topic
    from fluidframework_tpu.testing.pipeline import (
        build_pipeline_workload,
    )

    os.makedirs(work)
    records = build_pipeline_workload(POOL_DOCS, POOL_CLIENTS, 1)
    raw_path = os.path.join(work, "rawdeltas.jsonl")
    raw = make_topic(raw_path, "columnar")
    for lo in range(0, len(records), 16384):
        raw.append_many(records[lo:lo + 16384])
    return raw_path, len(records)


def _pool_identity(work: str) -> dict:
    from fluidframework_tpu.testing.pipeline import (
        read_canonical,
        run_pipeline,
    )

    raw_path, n_records = _pool_workload(work)
    c0 = compile_seconds()
    kern = run_pipeline("kernel", raw_path, work, batch=16384,
                        log_format="columnar")
    kernel_compile = compile_seconds() - c0
    scal = run_pipeline("scalar", raw_path, work, batch=16384,
                        log_format="columnar")
    a, b = read_canonical(kern["out_path"]), read_canonical(scal["out_path"])
    check(len(a) == n_records, f"kernel deli emitted {len(a)} of "
          f"{n_records} records")
    check(a == b, f"kernel vs scalar stamps/nacks/MSNs differ on "
          f"{sum(x != y for x, y in zip(a, b))} records")
    return {"docs": POOL_DOCS, "clients": POOL_CLIENTS,
            "records": n_records, "kernel_s": kern["seconds"],
            "kernel_compile_s": kernel_compile,
            "scalar_s": scal["seconds"], "bit_identical": True}


def phase_serve(work: str) -> None:
    c0, t0 = compile_seconds(), time.perf_counter()
    edits = _concurrent_string_edits()
    report("serve.runtime", seconds=time.perf_counter() - t0,
           compile_s=compile_seconds() - c0, **edits)
    pool = _pool_identity(os.path.join(work, "pool"))
    report("serve.deli_pool", **pool)


# ------------------------------------------------------------- summary


def phase_summary(work: str) -> None:
    from fluidframework_tpu.server.columnar_log import make_topic
    from fluidframework_tpu.server.summarizer import (
        SummaryReplica,
        open_summary_store,
        read_catchup,
    )
    from fluidframework_tpu.testing.pipeline import (
        build_mergetree_stream,
        drive_summarizer,
    )

    streams = [
        build_mergetree_stream(SUMMARY_OPS_PER_DOC, seed=300 + d,
                               doc=f"doc{d}")
        for d in range(SUMMARY_DOCS)
    ]
    # Docs interleave record by record, as one deltas topic carries them.
    log = [recs[i] for i in range(max(map(len, streams)))
           for recs in streams if i < len(recs)]
    runs = {}
    for backend in ("kernel", "overlay"):
        shared = os.path.join(work, backend)
        os.makedirs(os.path.join(shared, "topics"))
        make_topic(os.path.join(shared, "topics", "deltas.jsonl"),
                   "columnar").append_many(log)
        c0 = compile_seconds()
        runs[backend] = drive_summarizer(
            shared, "columnar", summary_ops=256,
            fold_backend=backend, fold_interpret=False,
        )
        runs[backend]["compile_s"] = compile_seconds() - c0
        check(runs[backend]["fold_backend"] == backend,
              f"summarizer folded with {runs[backend]['fold_backend']}")

    def key(run):
        return [(m["doc"], m["seq"], m["handle"], m["count"])
                for m in run["manifests"]]

    check(len(key(runs["kernel"])) >= SUMMARY_DOCS,
          f"only {len(key(runs['kernel']))} summaries emitted")
    check(key(runs["overlay"]) == key(runs["kernel"]),
          "overlay and kernel folds emitted different canonical rows")

    shared = os.path.join(work, "overlay")
    store = open_summary_store(shared)
    for recs in streams:
        doc = recs[0]["doc"]
        cu = read_catchup(shared, doc, "columnar", store=store)
        check(cu["blob"] is not None, f"{doc} has no summary")
        boot = SummaryReplica(cu["blob"])
        boot.apply_records(cu["ops"])
        cold = SummaryReplica(None)
        cold.apply_records(recs)
        check(boot.state_digest() == cold.state_digest(),
              f"{doc}: summary + tail boot != full replay")
    report("summary", docs=SUMMARY_DOCS, ops_per_doc=SUMMARY_OPS_PER_DOC,
           manifests=len(key(runs["kernel"])),
           kernel_s=runs["kernel"]["seconds"],
           kernel_compile_s=runs["kernel"]["compile_s"],
           overlay_s=runs["overlay"]["seconds"],
           overlay_compile_s=runs["overlay"]["compile_s"],
           bit_identical=True, cold_boot_identical=True)


# ----------------------------------------------------------- four chips


def phase_multichip(work: str) -> None:
    import __graft_entry__
    from fluidframework_tpu.parallel.mesh import shared_docs_mesh
    from fluidframework_tpu.testing.pipeline import (
        read_canonical,
        run_pipeline,
    )

    mesh = shared_docs_mesh(4)
    check(all(d.platform == "tpu" for d in mesh.devices.flat),
          f"mesh devices are not all TPUs: {mesh.devices}")
    raw_path, n_records = _pool_workload(work)
    runs = {}
    for n, out in ((1, "one"), (4, "four")):
        out_dir = os.path.join(work, out)
        os.makedirs(out_dir)
        c0 = compile_seconds()
        runs[n] = run_pipeline("kernel", raw_path, out_dir, batch=16384,
                               log_format="columnar",
                               deli_devices=n if n > 1 else None)
        runs[n]["compile_s"] = compile_seconds() - c0
    a, b = (read_canonical(runs[n]["out_path"]) for n in (1, 4))
    check(len(a) == n_records, f"1-chip deli emitted {len(a)} of "
          f"{n_records} records")
    check(a == b, "4-chip sharded deli differs from the 1-chip deli on "
          f"{sum(x != y for x, y in zip(a, b))} records")
    report("multichip.deli", docs=POOL_DOCS, clients=POOL_CLIENTS,
           records=n_records, one_chip_s=runs[1]["seconds"],
           one_chip_compile_s=runs[1]["compile_s"],
           four_chip_s=runs[4]["seconds"],
           four_chip_compile_s=runs[4]["compile_s"],
           mesh=[str(d) for d in mesh.devices.flat], bit_identical=True)
    c0, t0 = compile_seconds(), time.perf_counter()
    __graft_entry__._dryrun_impl(4, interpret=False)
    report("multichip.dryrun", seconds=time.perf_counter() - t0,
           compile_s=compile_seconds() - c0, digests_identical=True)


def require_tpus(n: int):
    """The default backend's devices, after checking that the first
    `n` are TPUs; exits non-zero with a message otherwise."""
    import jax

    devs = jax.devices()
    if len(devs) < n or any(d.platform != "tpu" for d in devs[:n]):
        sys.exit(f"chip_smoke: FAIL: needs {n} TPU device(s), found "
                 f"{len(devs)} {devs[0].platform!r} "
                 f"({devs[0].device_kind})")
    return devs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path (4 chips)")
    args = ap.parse_args(argv)

    from fluidframework_tpu.utils.devices import cpu_pinned, \
        use_compile_cache

    if cpu_pinned():
        sys.exit("chip_smoke: FAIL: JAX_PLATFORMS pins the CPU; this "
                 "smoke test runs on a TPU only")
    cache = use_compile_cache()
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    t_start = time.perf_counter()
    try:
        if args.chips == 1:
            phase_farm(work)
        devs = require_tpus(args.chips)  # the first JAX import
        track_compiles()
        report("device", platform=devs[0].platform,
               kind=devs[0].device_kind, count=len(devs),
               compile_cache=cache)
        if args.chips == 1:
            phase_replay()
            phase_serve(os.path.join(work, "serve"))
            phase_summary(os.path.join(work, "summary"))
        else:
            phase_multichip(os.path.join(work, "multichip"))
        report("total", seconds=time.perf_counter() - t_start,
               compile_s=compile_seconds())
    except SmokeFailure as exc:
        sys.exit(f"chip_smoke: FAIL: {exc}")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
