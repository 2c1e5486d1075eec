"""Deli and summarizer drivers shared by the tests and `chip_smoke.py`.

Each driver runs a role's datapath in this process, the way the
supervised farm's child runs it, minus lease upkeep:

- `build_pipeline_workload` writes a deterministic raw-topic stream
  (joins, then ops, round-robin across documents);
- `run_pipeline` drains a raw topic through one deli variant into a
  deltas topic, under the farm's checkpoint cadence;
- `read_canonical` and `_canonical_by_doc` put deltas topics in the
  form two runs are compared in;
- `build_mergetree_stream` and `drive_summarizer` feed a sequenced
  merge-tree stream through `SummarizerRole`;
- `_run_latency_variant` drives the supervised farm open-loop with
  wire traces on and checks its spans and quantiles.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional


def build_pipeline_workload(n_docs: int, n_clients: int,
                            ops_per_client: int, seed: int = 5,
                            doc_names: Optional[List[str]] = None
                            ) -> List[dict]:
    """Deterministic raw-topic stream, round-robin across docs (every
    pump carries many documents — the data-parallel axis the kernel
    batches over). Each client's join rides immediately before its
    first op, so any prefix of the stream carries the same join:op mix
    as the whole. `doc_names` overrides the default ``doc{d}`` naming
    (e.g. partition-balanced names from `spread_doc_names`)."""
    import random

    rng = random.Random(seed)
    docs = doc_names if doc_names is not None else [
        f"doc{d}" for d in range(n_docs)
    ]
    recs: List[dict] = []
    for i in range(ops_per_client):
        for c in range(1, n_clients + 1):
            for doc in docs:
                if i == 0:
                    recs.append({"kind": "join", "doc": doc, "client": c})
                recs.append({
                    "kind": "op", "doc": doc, "client": c,
                    "clientSeq": i + 1, "refSeq": 0,
                    "contents": {"v": rng.randint(0, 999), "i": i},
                })
    return recs


def _make_role(impl: str, scratch: str, log_format: str = "json",
               deli_devices: Optional[int] = None):
    if impl == "kernel":
        from ..server.deli_kernel import KernelDeliRole

        return KernelDeliRole(scratch, owner=f"pipeline-{impl}",
                              ttl_s=3600.0, log_format=log_format,
                              deli_devices=deli_devices)
    from ..server.supervisor import DeliRole

    return DeliRole(scratch, owner=f"pipeline-{impl}", ttl_s=3600.0,
                    log_format=log_format)


def run_pipeline(impl: str, raw_path: str, out_dir: str,
                 batch: int = 8192, log_format: str = "json",
                 deli_devices: Optional[int] = None) -> dict:
    """Drive one deli variant raw-topic-in → deltas-topic-out, with
    the farm's time/byte checkpoint cadence (`_Role.maybe_checkpoint`).

    `log_format` selects the topic wire form for BOTH ends: "json"
    (JSONL lines) or "columnar" (binary record batches — the kernel
    role then ingests raw `RecordBatch` frames and passes op contents
    through as pre-encoded blobs, zero per-record JSON on the wire).

    Returns {"seconds", "out_path"}."""
    from ..server.columnar_log import make_tail_reader, make_topic

    raw = make_topic(raw_path, log_format)
    out_path = os.path.join(out_dir, f"deltas-{impl}-{log_format}.jsonl")
    for stale in (out_path, out_path + ".fence", out_path + ".clen"):
        if os.path.exists(stale):
            os.remove(stale)
    deltas = make_topic(out_path, log_format)
    role = _make_role(impl, os.path.join(out_dir, f"scratch-{impl}"),
                      log_format, deli_devices)
    # No lease loop here: bind a fence so fenced checkpoint writes work.
    role.fence = 1
    reader = make_tail_reader(raw)
    # The kernel role's columnar fast path: whole RecordBatch frames.
    use_batches = role.ingest_batches and hasattr(reader, "poll_batches")
    t0 = time.perf_counter()
    while True:
        out: List[dict] = []
        if use_batches:
            units = reader.poll_batches(batch)
            if not units:
                break
            for u in units:
                if u[0] == "batch":
                    role.process_batch(u[1], u[2], out)
                else:
                    role.process(u[1], u[2], out)
        else:
            entries = reader.poll(batch)
            if not entries:
                break
            for line_idx, rec in entries:
                role.process(line_idx, rec, out)
        role.flush_batch(out)
        role._ckpt_pending_bytes += deltas.append_many(out)
        role.offset = reader.next_line
        role._ckpt_dirty = True
        role.maybe_checkpoint()
    role.wait_checkpoint()
    return {"seconds": time.perf_counter() - t0, "out_path": out_path}


def read_canonical(path: str) -> List[dict]:
    # ColumnarFileTopic reads BOTH wire forms (JSON lines and binary
    # frames), so one reader canonicalizes every variant's output.
    from ..server.columnar_log import ColumnarFileTopic

    return [
        {k: v for k, v in r.items() if k != "reason"}
        for r in ColumnarFileTopic(path).read_from(0)
    ]


def _canonical_by_doc(paths: List[str]) -> Dict[str, List[dict]]:
    """Merged per-doc, seq-sorted canonical streams across partition
    output topics — the form sharded and single-partition runs are
    compared in (a doc lives in exactly one partition, so per-doc
    streams merge without interleaving questions)."""
    per_doc: Dict[str, List[dict]] = {}
    for path in paths:
        for rec in read_canonical(path):
            if rec.get("kind") == "op":
                # inOff/inSrc are per-partition transport bookkeeping
                # (input line offsets differ across shardings, and a
                # ranged successor tags absorbed records with their
                # source) — the same exclusion canonical_record
                # applies.
                per_doc.setdefault(rec["doc"], []).append(
                    {k: v for k, v in rec.items()
                     if k not in ("inOff", "inSrc")}
                )
    for v in per_doc.values():
        v.sort(key=lambda r: r["seq"])
    return per_doc


def build_mergetree_stream(n_ops: int, n_clients: int = 4,
                           seed: int = 10, doc: str = "doc0",
                           window: int = 64,
                           target_len: int = 400) -> List[dict]:
    """A deterministic SEQUENCED deltas stream of merge-tree wire ops:
    joins, then `n_ops` sequential insert/remove/annotate ops whose
    positions are valid at their refSeq (= seq-1) perspective, with
    the msn trailing by `window` (so summaries stay window-bounded)
    and document length hovering around `target_len` (so per-op kernel
    cost — O(live rows) — is flat and the log-length axis isolates
    replay cost, the thing summaries remove). A PREFIX of the stream
    is itself a valid stream, so one build serves every swept log
    length."""
    import random
    import string

    rng = random.Random(seed)
    recs: List[dict] = []
    seq = 0
    for c in range(1, n_clients + 1):
        seq += 1
        recs.append({"kind": "op", "doc": doc, "seq": seq, "msn": 0,
                     "client": c, "clientSeq": 0, "refSeq": seq - 1,
                     "type": "join", "contents": c})
    length = 0
    cseq = {c: 0 for c in range(1, n_clients + 1)}
    for _ in range(n_ops):
        c = rng.randint(1, n_clients)
        seq += 1
        cseq[c] += 1
        msn = max(0, seq - window)
        r = rng.random()
        p_ins = 0.45 if length < target_len else 0.25
        if length == 0 or r < p_ins:
            pos = rng.randint(0, length)
            text = "".join(
                rng.choices(string.ascii_lowercase, k=rng.randint(1, 6))
            )
            contents: dict = {"type": 0, "pos1": pos, "seg": text}
            length += len(text)
        elif r < p_ins + 0.35:
            a = rng.randint(0, length - 1)
            b = min(length, a + rng.randint(1, 6))
            contents = {"type": 1, "pos1": a, "pos2": b}
            length -= b - a
        else:
            a = rng.randint(0, length - 1)
            b = min(length, a + rng.randint(1, 8))
            contents = {"type": 2, "pos1": a, "pos2": b,
                        "props": {rng.choice(["bold", "color", "size"]):
                                  rng.choice([1, 2, "x", None])}}
        recs.append({"kind": "op", "doc": doc, "seq": seq, "msn": msn,
                     "client": c, "clientSeq": cseq[c],
                     "refSeq": seq - 1, "type": "op",
                     "contents": contents})
    return recs


def drive_summarizer(shared: str, log_format: str,
                      summary_ops: int, batch: int = 4096,
                      **role_kw) -> dict:
    """Run the summarizer ROLE datapath (deltas → summaries + blobs)
    to quiescence over an already-written deltas topic — the exact
    fold/emit path the supervised child runs, minus lease upkeep (the
    `run_pipeline` pattern). `role_kw` reaches `SummarizerRole`
    (e.g. ``fold_backend``); the emitted manifests come back under
    ``"manifests"``."""
    from ..server.columnar_log import make_tail_reader, make_topic
    from ..server.summarizer import SummarizerRole

    deltas = make_topic(
        os.path.join(shared, "topics", "deltas.jsonl"), log_format
    )
    role = SummarizerRole(shared, owner="pipeline-summ", ttl_s=3600.0,
                          log_format=log_format,
                          summary_ops=summary_ops, **role_kw)
    role.fence = 1
    reader = make_tail_reader(deltas)
    # The counter is process-global (shared registry labels): report
    # THIS run's delta, not the cumulative across swept lengths.
    summ0 = int(role._m_summaries.value)
    n = 0
    manifests: List[dict] = []
    t0 = time.perf_counter()
    while True:
        entries = reader.poll(batch)
        if not entries:
            break
        out: List[dict] = []
        for line_idx, rec in entries:
            role.process(line_idx, rec, out)
        role.flush_batch(out)
        if out:
            role.out_topic.append_many(out, fence=1, owner="pipeline-summ")
            manifests.extend(out)
        role.offset = reader.next_line
        n += len(entries)
    return {"seconds": time.perf_counter() - t0, "records": n,
            "summaries": int(role._m_summaries.value) - summ0,
            "fold_backend": role.fold_backend(),
            "manifests": manifests}


def _exact_quantile(sorted_vals: List[float], q: float) -> float:
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * (len(sorted_vals) - 1)))]


def _span_quantiles(samples: List[float]) -> dict:
    s = sorted(samples)
    return {"count": len(s),
            "mean": round(sum(s) / len(s), 3),
            "p50": round(_exact_quantile(s, 0.5), 3),
            "p95": round(_exact_quantile(s, 0.95), 3),
            "p99": round(_exact_quantile(s, 0.99), 3)}


def _run_latency_variant(shared: str, rate_hz: float, duration_s: float,
                         n_docs: int, n_clients: int, ttl_s: float,
                         timeout_s: float) -> dict:
    """One open-loop run against the supervised farm: fixed-rate
    submits (never waiting on completion — OPEN loop, so a backlogged
    pipeline shows up as latency, not as a silently slower load), wire
    traces and doorbells on, spans read back off the broadcast/durable
    tails."""
    from ..server.queue import SharedFileTopic, TailReader
    from ..server.supervisor import ServiceSupervisor
    from ..utils import metrics as _metrics

    sup = ServiceSupervisor(
        shared, roles=("deli", "scriptorium", "broadcaster"),
        ttl_s=ttl_s,
        child_env={"FLUID_TRACE_WIRE": "1", "FLUID_DOORBELL": "1"},
        # Heartbeat throttle: a trace-mode registry snapshot per record
        # is pure tail latency, and liveness only needs ~Hz.
        hb_interval_s=0.1,
    ).start()
    try:
        raw = SharedFileTopic(os.path.join(shared, "topics",
                                           "rawdeltas.jsonl"))
        bc_reader = TailReader(SharedFileTopic(
            os.path.join(shared, "topics", "broadcast.jsonl")))
        dur_reader = TailReader(SharedFileTopic(
            os.path.join(shared, "topics", "durable.jsonl")))
        docs = [f"doc{d}" for d in range(n_docs)]
        raw.append_many([
            {"kind": "join", "doc": doc, "client": c}
            for doc in docs for c in range(1, n_clients + 1)
        ])
        # Warm: every join sequenced and broadcast (the pipeline is
        # live end to end before the timed window opens).
        want_joins = n_docs * n_clients
        bcast: List[dict] = []
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            sup.poll_once()
            bcast.extend(v for _, v in bc_reader.poll())
            if sum(1 for r in bcast
                   if isinstance(r, dict) and r.get("kind") == "op"
                   ) >= want_joins:
                break
            time.sleep(0.01)
        else:
            raise AssertionError(
                f"farm never came live: {len(bcast)} broadcast records "
                f"for {want_joins} joins"
            )
        bcast.clear()
        # Steady open-loop window, after a short PACED lead-in at the
        # same rate (cold paths — first checkpoint, instrument
        # creation, first bell registration — are start-up cost, not
        # steady-state SLO; the lead-in ops are still span-verified
        # below, just excluded from the quantiles).
        lead_in = 8
        total = max(16, int(rate_hz * duration_s)) + lead_in
        cseq = {(d, c): 0 for d in docs for c in range(1, n_clients + 1)}
        keys = sorted(cseq)
        t0 = time.perf_counter()
        last_sup = 0.0
        lead_keys = set()
        for i in range(total):
            tick = t0 + i / rate_hz
            while True:
                now = time.perf_counter()
                if now >= tick:
                    break
                # Drain tails while waiting so the driver never
                # bursts; the spans themselves come from wire stamps,
                # not from observation time.
                bcast.extend(v for _, v in bc_reader.poll())
                if now - last_sup > 0.2:
                    sup.poll_once()
                    last_sup = now
                time.sleep(min(0.002, tick - now))
            d, c = keys[i % len(keys)]
            cseq[(d, c)] += 1
            if i < lead_in:
                lead_keys.add((d, c, cseq[(d, c)]))
            raw.append_many([{
                "kind": "op", "doc": d, "client": c,
                "clientSeq": cseq[(d, c)], "refSeq": 0,
                "contents": {"i": i}, "tr_sub": time.time(),
            }])
        # Drain: every submitted op must reach broadcast.
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            bcast.extend(v for _, v in bc_reader.poll())
            n_ops = sum(1 for r in bcast
                        if isinstance(r, dict) and r.get("kind") == "op"
                        and "sub" in (r.get("tr") or {}))
            if n_ops >= total:
                break
            sup.poll_once()
            time.sleep(0.005)
        durable = [v for _, v in dur_reader.poll()]
        # Children heartbeat on a throttle (hb_interval_s): give every
        # role one post-drain heartbeat so the collected snapshots
        # include the final ops.
        time.sleep(0.35)
        metrics_reg = sup.collect_metrics()
        slow_ops = sup.child_slow_ops()
    finally:
        sup.stop()

    # ----- trace/quantile correctness assertions (run on EVERY host)
    ops = [r for r in bcast if isinstance(r, dict)
           and r.get("kind") == "op" and "sub" in (r.get("tr") or {})]
    seen = [(r["doc"], r["client"], r["clientSeq"]) for r in ops]
    assert len(seen) == len(set(seen)), "duplicate ops in broadcast"
    assert len(seen) == total, (
        f"open-loop drain incomplete: {len(seen)}/{total} ops reached "
        f"broadcast within {timeout_s}s"
    )
    sub_stamp, sub_bc = [], []
    all_sub_stamp, all_sub_bc = [], []
    for r in ops:
        tr = r["tr"]
        assert tr["sub"] <= tr["stamp"] <= tr["bc"], (
            f"non-monotone span {tr}"
        )
        ss = (tr["stamp"] - tr["sub"]) * 1000.0
        all_sub_stamp.append(ss)
        all_sub_bc.append((tr["bc"] - tr["sub"]) * 1000.0)
        if (r["doc"], r["client"], r["clientSeq"]) in lead_keys:
            continue  # verified, but start-up cost: no quantile
        sub_stamp.append(ss)
        sub_bc.append((tr["bc"] - tr["sub"]) * 1000.0)
    assert len(sub_bc) == total - len(lead_keys)
    for r in durable:
        tr = r.get("tr") if isinstance(r, dict) else None
        if isinstance(tr, dict) and "dur" in tr and "stamp" in tr:
            assert tr["stamp"] <= tr["dur"], f"non-monotone span {tr}"
    # The child-reported histogram must be exactly the driver-side
    # distribution: the deli stamps tr["stamp"] and observes its
    # submit_to_stamp histogram from ONE clock read, so rebuilding the
    # histogram from the wire spans must reproduce the child's bucket
    # counts — the end-to-end proof that quantile estimates summarize
    # the real per-op traces.
    reb = _metrics.MetricsRegistry()
    h_local = reb.histogram("op_stage_ms", stage="submit_to_stamp")
    for v in all_sub_stamp:  # the child saw the lead-in ops too
        h_local.observe(v)
    h_child = metrics_reg.histogram("op_stage_ms", stage="submit_to_stamp")
    assert h_child.counts == h_local.counts and \
        h_child.count == h_local.count, (
            "child-reported submit_to_stamp histogram diverges from "
            "the wire spans"
        )
    # Bucket-interpolated estimate lands in the same (or adjacent,
    # for an exact-bound landing) bucket as the exact sample quantile.
    from bisect import bisect_left
    snap_h = [h for h in metrics_reg.snapshot()["histograms"]
              if h["name"] == "op_stage_ms"
              and h["labels"].get("stage") == "submit_to_broadcast"]
    if snap_h:
        est = _metrics.histogram_quantile(snap_h[0], 0.99)
        exact = _exact_quantile(sorted(all_sub_bc), 0.99)
        bounds = snap_h[0]["buckets"]
        if est != float("inf"):
            assert abs(bisect_left(bounds, est)
                       - bisect_left(bounds, exact)) <= 1, (
                f"interpolated p99 {est} not in the exact p99 "
                f"{exact}'s bucket"
            )
    return {
        "records": total,
        "lead_in": lead_in,
        "rate_hz": rate_hz,
        "submit_to_stamp_ms": _span_quantiles(sub_stamp),
        "submit_to_broadcast_ms": _span_quantiles(sub_bc),
        "slow_ops": slow_ops[:5],
    }
