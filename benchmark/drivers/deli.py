"""Driver of the deli-partition cells (configuration "system": "deli").

The system under test is the served ordering loop: the program's
`server.deli_kernel.KernelDeliRole` driven through `_Role.step` (poll
the raw topic, pack, run the sequencer kernel, emit, fenced append to
the deltas topic, checkpoint cadence), in this process, which holds
the chip. Two helper processes without JAX surround it:

- the load generator (`procs/gen_proc.py`) appends the window's ops to
  the raw topic in columnar frames on a fixed schedule (open loop);
- the reader (`procs/reader_proc.py`) tails the deltas topic as
  downstream roles do and notes when each sequenced record arrives.

Set-up joins every client to every document through the deli itself.
A record's latency runs from its due time in the schedule to the
reader's read; one never read, or nacked, counts as failed.

Correctness, after the window: every record in the deltas topic is
compared with the benchmark's reference (`reference/deli_ref.py`) —
seq, MSN and verdict of every join and op, each exactly once.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

DRAIN_SECONDS = 60.0
PROCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "procs")


def _join_frames(n_docs: int, n_clients: int, per_frame: int):
    """Columnar join frames: every client of every doc, doc-major."""
    from fluidframework_tpu.protocol import record_batch as rb

    total = n_docs * n_clients
    for lo in range(0, total, per_frame):
        hi = min(total, lo + per_frame)
        idx = np.arange(lo, hi)
        docs = idx // n_clients
        uniq, inv = np.unique(docs, return_inverse=True)
        n = hi - lo
        zeros = np.zeros(n, np.int64)
        yield rb.ColumnarRecords(
            [f"doc{d}" for d in uniq.tolist()],
            np.full(n, rb.K_RAW_JOIN, np.uint8), np.full(n, 255, np.uint8),
            inv.astype(np.int32), idx % n_clients + 1, zeros, zeros, zeros,
            zeros, np.full(n, -1, np.int64), np.zeros(n + 1, np.uint32),
            b"")


def _stop(proc, timeout: float = 10.0) -> None:
    if proc is None or proc.poll() is not None:
        return
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def compare(sched, rows, n_docs: int, n_clients: int, control=None):
    """Counts of every way the deltas topic departs from the reference:
    ops and joins missing, duplicated, foreign or mis-stamped, and
    nacks. `rows` is the reader's output; `control`, when given, is a
    (seq, msn) pair that stands in for the reference."""
    from benchmark.reference import deli_ref
    from fluidframework_tpu.protocol import record_batch as rb

    types = rb._TYPE_CODE
    kind, typ = rows["kind"].astype(np.int64), rows["type"].astype(np.int64)
    is_seq = kind == rb.K_SEQ_OP
    ops = is_seq & (typ == types["op"])
    joins = is_seq & (typ == types["join"])
    out = {"nacks": int(np.count_nonzero(kind == rb.K_NACK)),
           "records_foreign": int(np.count_nonzero(~(ops | joins
                                                     | (kind == rb.K_NACK))))}

    # Ops, keyed by (doc, client, clientSeq).
    n = len(sched.doc)
    width = int(sched.cseq.max()) + 1 if n else 1
    key_s = (sched.doc * n_clients + sched.client - 1) * width + sched.cseq
    order = np.argsort(key_s)
    keys_sorted = key_s[order]
    doc, cl, cs = (rows[k][ops].astype(np.int64)
                   for k in ("doc", "client", "cseq"))
    key_r = (doc * n_clients + cl - 1) * width + cs
    pos = np.searchsorted(keys_sorted, key_r)
    found = (pos < n) & (keys_sorted[np.minimum(pos, max(n - 1, 0))]
                         == key_r) if n else np.zeros(len(key_r), bool)
    idx = order[pos[found]]
    hits = np.bincount(idx, minlength=n)
    seq_e, msn_e = control if control is not None else \
        deli_ref.expected_ops(sched)
    seq_r = rows["seq"][ops].astype(np.int64)[found]
    msn_r = rows["msn"][ops].astype(np.int64)[found]
    out["records_foreign"] += int(np.count_nonzero(~found))
    out["records_missing"] = int(np.count_nonzero(hits == 0))
    out["records_duplicated"] = int(np.count_nonzero(hits > 1))
    out["stamps_mismatched"] = int(np.count_nonzero(
        (seq_r != seq_e[idx]) | (msn_r != msn_e[idx])))

    # Joins, keyed by (doc, client).
    jseq, jmsn = deli_ref.expected_joins(n_clients)
    jd = rows["doc"][joins].astype(np.int64)
    jc = rows["client"][joins].astype(np.int64)
    ok = (jd >= 0) & (jd < n_docs) & (jc >= 1) & (jc <= n_clients)
    jhits = np.bincount(jd[ok] * n_clients + jc[ok] - 1,
                        minlength=n_docs * n_clients)
    jbad = np.count_nonzero(~ok) + np.count_nonzero(jhits != 1)
    jbad += np.count_nonzero(
        (rows["seq"][joins][ok] != jseq[jc[ok] - 1])
        | (rows["msn"][joins][ok] != jmsn[jc[ok] - 1]))
    out["joins_mismatched"] = int(jbad)
    return out, ops, found, idx


def run(run) -> None:
    from benchmark import traffic as gen
    from benchmark.harness import log, quantile

    cfg, trf = run.config, run.traffic
    jax = run.start_jax()
    n_docs, n_clients = int(cfg["docs"]), int(cfg["clients"])
    with run.part("schedule"):
        sched = gen.open_loop(cfg, trf, run.seed, run.seconds)
    n_ops, n_joins = len(sched.doc), n_docs * n_clients
    shared = os.path.join(run.work, "farm")
    topics = os.path.join(shared, "topics")
    os.makedirs(topics)
    raw_path = os.path.join(topics, "rawdeltas.jsonl")
    deltas_path = os.path.join(topics, "deltas.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    spec_path = os.path.join(run.work, "gen.json")
    late_path = os.path.join(run.work, "late.npy")
    rows_path = os.path.join(run.work, "rows.npz")
    stop_path = os.path.join(run.work, "stop")
    with open(spec_path, "w") as f:
        json.dump({"raw": raw_path, "config": cfg, "traffic": trf,
                   "seed": run.seed, "seconds": run.seconds,
                   "out": late_path}, f)
    gen_p = reader_p = None
    try:
        gen_p = subprocess.Popen(
            [sys.executable, os.path.join(PROCS, "gen_proc.py"), spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)
        with run.part("import"):
            from fluidframework_tpu.ops import sequencer_kernel as sk
            from fluidframework_tpu.server.columnar_log import make_topic
            from fluidframework_tpu.server.deli_kernel import KernelDeliRole
        role = KernelDeliRole(shared, owner="bench-deli", ttl_s=3600.0,
                              batch=int(cfg["batch"]),
                              log_format="columnar")
        reader_p = subprocess.Popen(
            [sys.executable, os.path.join(PROCS, "reader_proc.py"),
             deltas_path, rows_path, str(n_joins + n_ops), stop_path],
            env=env)
        with run.part("joins"):
            raw = make_topic(raw_path, "columnar")
            for frame in _join_frames(n_docs, n_clients, int(cfg["batch"])):
                raw.append_many([frame])
            while role.offset < n_joins:
                role.step()
        with run.part("warmup"):
            # The sequencer program at every batch width a pump can
            # take ([D, B], B a power of two from 8 to the column cap).
            pool = role.core.pool
            b = 8
            while b <= role.core.max_cols:
                state = sk.make_state(pool.n_docs, pool.n_clients)
                z = np.zeros((pool.n_docs, b), np.int32)
                batch = sk.SeqBatch(kind=z + sk.SUB_PAD, client=z,
                                    client_seq=z, ref_seq=z)
                out = sk.sequence_batch_grouped(
                    state, jax.tree_util.tree_map(jax.numpy.asarray, batch),
                    jax.numpy.asarray(z + sk.NO_GROUP), True)
                jax.block_until_ready(out)
                b *= 2
        with run.part("generator"):
            if gen_p.stdout.readline().strip() != "READY":
                raise RuntimeError("the load generator did not start")
        with run.part("checkpoint"):
            # Phase the role's checkpoint cadence to the window: after a
            # checkpoint that took s, the role's storm guard holds the
            # next one back for s / ckpt_duty. Waiting that long makes
            # the window's first pump write the next checkpoint, so every
            # window holds one checkpoint stall, at its start.
            t = time.perf_counter()
            role.checkpoint()
            time.sleep((time.perf_counter() - t) / role.ckpt_duty + 0.1)

        # The role's own checkpoint histogram, as its /metrics exports it.
        ckpt_hist = role.metrics.histogram("checkpoint_ms", role=role.name)
        t0_mono = time.monotonic() + 0.05
        gen_p.stdin.write(f"{t0_mono!r}\n")
        gen_p.stdin.flush()
        time.sleep(max(0.0, t0_mono - time.monotonic()))
        t0 = run.open_window()
        mono_off = t0_mono - t0  # perf_counter -> monotonic
        ckpt0 = getattr(ckpt_hist, "sum", None)
        n_ckpt0 = getattr(ckpt_hist, "count", 0)
        run.trace_start()
        pumps = []
        end = t0 + run.seconds
        while True:
            off0 = role.offset
            with run.span("deli.step"):
                ts = time.perf_counter()
                moved = role.step()
            te = time.perf_counter()
            pumps.append((ts, te - ts, moved, off0, role.offset))
            if te >= end:
                break
        run.close_window(end)
        run.trace_stop()  # the whole window: stopping costs seconds
        if ckpt0 is not None:
            run.facts["checkpoint_ms"] = ckpt_hist.sum - ckpt0
            run.facts["checkpoints"] = ckpt_hist.count - n_ckpt0
        deadline = time.perf_counter() + DRAIN_SECONDS
        while role.offset < n_joins + n_ops and \
                time.perf_counter() < deadline:
            role.step()
        given_up = time.monotonic()
        run.memory_peak()
        _stop(gen_p, timeout=DRAIN_SECONDS)
        open(stop_path, "w").close()
        _stop(reader_p, timeout=DRAIN_SECONDS)
    finally:
        for p in (gen_p, reader_p):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
    del role

    rows = dict(np.load(rows_path))
    counts, ops, found, idx = compare(sched, rows, n_docs, n_clients)
    for name in ("records_missing", "records_duplicated", "records_foreign",
                 "nacks", "stamps_mismatched", "joins_mismatched"):
        run.check(name, counts[name], 0)

    # Latency of every op: due -> read. An op never read counts as read
    # when the drain gave up, later than any op that was.
    t_read = np.full(n_ops, np.inf)
    np.minimum.at(t_read, idx, rows["t"][ops][found])
    unread = ~np.isfinite(t_read)
    t_read[unread] = max(given_up, float(np.max(rows["t"], initial=0.0)))
    lat_ms = (t_read - (t0_mono + sched.due)) * 1000.0

    run.attempted = n_ops
    run.failed = int(np.count_nonzero(unread)) + counts["nacks"]
    run.e2e["seq_latency_p95_ms"] = quantile(lat_ms, 0.95)
    close_mono = end + mono_off
    run.e2e["sequenced_records_per_s"] = float(
        np.count_nonzero(t_read <= close_mono)) / run.seconds
    # Whether the rate is sustained: the backlog at the close, and p95
    # by thirds of the window (by due time).
    thirds = [quantile(lat_ms[(sched.due >= run.seconds * i / 3)
                              & (sched.due < run.seconds * (i + 1) / 3)],
                       0.95) for i in range(3)]
    due_by_close = int(np.count_nonzero(sched.due <= run.seconds))
    run.facts["diag"] = {
        "rate": sched.rate, "p50_ms": quantile(lat_ms, 0.5),
        "p95_ms": run.e2e["seq_latency_p95_ms"],
        "p99_ms": quantile(lat_ms, 0.99), "p95_thirds_ms": thirds,
        "backlog_at_close": due_by_close - int(np.count_nonzero(
            t_read <= close_mono)),
        "steps": len(pumps), "checkpoints": run.facts.get("checkpoints")}
    log(f"diag {json.dumps(run.facts['diag'])}")

    run.facts.update(pumps=pumps, sched=sched, n_joins=n_joins,
                     window=(t0, end))
    if os.path.exists(late_path):
        run.facts["late_s"] = np.load(late_path)
    run.reduce_trace()
