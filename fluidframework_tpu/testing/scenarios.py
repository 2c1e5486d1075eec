"""Traffic-profile scenario layer: skewed, bursty, REAL-shaped load.

Even load over many docs is not what real Fluid traffic looks like
(SURVEY §S0: production load is dominated by joins and reads, and
alfred exists precisely to absorb storms). This module composes
OPEN-LOOP scenario primitives on top of the supervised farm and the
`testing.pipeline` drivers so the skewed shapes are first-class,
tested workloads:

- **hot-doc storm** (`run_hotdoc_storm`) — one viral document with
  thousands of writers plus a cold background mix, driven open-loop
  through the supervised farm. Stresses the sequencer's per-doc
  client table (the kernel deli's `[D, C]` pool COLUMN axis) and the
  MSN math of a huge collaborator set; reports hot-vs-cold
  submit→broadcast quantiles separately, because a storm's tail and
  the background's tail are different SLOs.
- **reconnect stampede** (`run_reconnect_stampede`) — a simulated
  network partition heals and thousands of sessions catch up
  SIMULTANEOUSLY through the summary path (`summarizer.read_catchup`
  + `SummaryReplica` boot, PR 10): the read-amplification burst a
  real outage recovery produces. Every session must land the
  identical manifest/blob/tail, and summary+tail boots must stay
  bit-identical to a cold full-log replay.
- **read-mostly swarm** (`run_read_swarm`) — 100k+ subscribed
  sessions fanning out through `FarmReadServer`'s doorbell-woken
  pusher (a handful of them as REAL TCP sessions over the framed
  wire protocol, the rest as in-proc subscriber sessions — scaled
  honestly, with a LOUD skip on the throughput evidence below the
  100k-session/core bar). A session that misses one record fails the
  run: fan-out cannot pass by dropping work.
- **tenant-skewed mix** (`run_tenant_mix`) — a zipf-shaped tenant mix
  riding the PR 12 ingress token buckets: one hot tenant over its
  rate budget must be throttled (visible 429 nacks billed to IT and
  only it) while the cold tenants' traffic flows untouched, and the
  throttled tail retries to exactly-once convergence.

The scenario CONTRACT (every primitive, every scale):

- **Open loop** — load is offered on a fixed schedule (or all at
  once, for the stampede/swarm) and NEVER waits on completion; a
  backlogged pipeline shows up as latency, not as a silently gentler
  load.
- **`/slo` quantiles** — each run returns an `slo` body
  (`utils.metrics.slo_summary` form: per-stage `op_stage_ms`
  histograms reduced to count/mean/p50/p95/p99, plus the `ingress_*`
  admission counters where a front door is in play).
- **Slow-op evidence** — each run returns `slow_ops`, the flight-
  recorder spans of its slowest operations (farm scenarios from the
  broadcaster-fed process recorder via the supervisor's merged
  `/traces` channel; read-side scenarios from a scenario-scoped
  recorder fed with per-session spans).
- **Convergence digest** — each run ends in a digest gate proving no
  work was dropped: exactly-once keys + contiguous seqs for write
  scenarios, single-valued catch-up signatures / complete per-session
  delivery for read scenarios. A scenario cannot pass by shedding
  its own load.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import shutil
import socket
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from .chaos import sequence_integrity, stream_digest
from .pipeline import _span_quantiles

__all__ = [
    "run_hotdoc_storm",
    "run_read_swarm",
    "run_reconnect_stampede",
    "run_tenant_mix",
    "run_week_of_traffic",
]


def _slo_p99(slo: dict, stage: str) -> Optional[float]:
    """The p99 of one `op_stage_ms` stage out of an /slo body."""
    for h in slo.get("histograms", ()):
        if h["name"] == "op_stage_ms" and \
                h.get("labels", {}).get("stage") == stage:
            return h.get("p99")
    return None


def _slowest(recorder, top: int = 5) -> List[dict]:
    """The recorder's spans slowest-first (the /traces convention)."""
    return sorted(recorder.snapshot(),
                  key=lambda s: -float(s.get("e2e_ms", 0.0)))[:top]


def _fresh_metrics():
    """(registry, recorder, restore_fn): scenario-scoped metrics + a
    scenario-scoped flight recorder, swapped in process-wide so role
    code constructed inside the scenario feeds THEM, not the
    process's shared instruments."""
    from ..utils import metrics as M

    reg = M.MetricsRegistry()
    # Fast-arming rolling gate: a scaled-down scenario has tens of
    # observations, and the production defaults (arm at 32, refresh
    # every 32) would leave the evidence buffer empty — same policy,
    # shorter warm-up.
    rec = M.FlightRecorder(min_samples=8)
    rec.RECALC_EVERY = 8
    prev_reg = M.set_registry(reg)
    prev_rec = M.set_flight_recorder(rec)

    def restore():
        M.set_registry(prev_reg)
        M.set_flight_recorder(prev_rec)

    return reg, rec, restore


# ---------------------------------------------------------------------------
# hot-doc storm
# ---------------------------------------------------------------------------


def run_hotdoc_storm(n_writers: int = 2000, cold_docs: int = 32,
                     cold_clients: int = 2, rate_hz: float = 300.0,
                     duration_s: float = 4.0, hot_fraction: float = 0.9,
                     deli_impl: str = "scalar", log_format: str = "json",
                     ttl_s: float = 0.75, timeout_s: float = 120.0,
                     seed: int = 13,
                     hb_timeout_s: Optional[float] = None,
                     work_dir: Optional[str] = None) -> dict:
    """One viral document, `n_writers` writers, a cold background mix
    — open-loop through the supervised farm (fused durable+broadcast
    hop, wire traces on). The hot doc concentrates `hot_fraction` of
    the offered ops on ONE per-doc client table, which is exactly the
    axis even load never stresses: the kernel deli's `[D, C]` pool
    must widen its client-column axis for one row, and the MSN is a
    min over thousands of collaborators instead of a handful.

    Gates (always): every offered op broadcast exactly once, seqs
    contiguous per doc, spans monotone, /slo quantiles present,
    slow-op spans recorded. Returns hot/cold/combined quantiles —
    the storm's tail and the background's tail are separate numbers."""
    scratch = work_dir or tempfile.mkdtemp(
        prefix="storm-",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None,
    )
    try:
        return _hotdoc_storm_run(
            scratch, n_writers, cold_docs, cold_clients, rate_hz,
            duration_s, hot_fraction, deli_impl, log_format, ttl_s,
            timeout_s, seed, hb_timeout_s,
        )
    finally:
        # Unconditional (failure paths too): the scratch lives on
        # tmpfs, and a run that failed its gates must not leave a
        # 2000-writer run's topics pinned in RAM.
        if work_dir is None:
            shutil.rmtree(scratch, ignore_errors=True)


def _hotdoc_storm_run(scratch: str, n_writers: int, cold_docs: int,
                      cold_clients: int, rate_hz: float,
                      duration_s: float, hot_fraction: float,
                      deli_impl: str, log_format: str, ttl_s: float,
                      timeout_s: float, seed: int,
                      hb_timeout_s: Optional[float] = None) -> dict:
    from ..server.supervisor import ServiceSupervisor

    rng = random.Random(seed)
    sup = ServiceSupervisor(
        scratch, roles=("deli", "scriptorium", "broadcaster"),
        ttl_s=ttl_s, fused_hop=True, deli_impl=deli_impl,
        log_format=log_format,
        # The WEDGE bar (chaos kills still surface via process exit):
        # a kernel deli compiling its first full-width [D, C, B] pump
        # on a small host is silent for tens of seconds — killing it
        # mid-compile restarts the same compile forever.
        heartbeat_timeout_s=hb_timeout_s if hb_timeout_s else 2.0,
        # FLUID_TRACE_SLOW_MS=0: the children's flight recorders keep
        # every span (ring-bounded) instead of waiting for the rolling
        # p99 to arm — a short scaled run must still produce /traces
        # evidence.
        child_env={"FLUID_TRACE_WIRE": "1", "FLUID_DOORBELL": "1",
                   "FLUID_TRACE_SLOW_MS": "0"},
        hb_interval_s=0.1,
    ).start()
    try:
        # Topics in the FARM's wire format: a columnar run feeds
        # binary record-batch frames and tails the broadcast leg with
        # the frame-aware reader (SharedFileTopic would write JSONL
        # into a columnar pipeline and parse none of its output).
        from ..server.columnar_log import make_tail_reader, make_topic

        raw = make_topic(
            os.path.join(scratch, "topics", "rawdeltas.jsonl"),
            log_format,
        )
        bc_reader = make_tail_reader(make_topic(
            os.path.join(scratch, "topics", "broadcast.jsonl"),
            log_format,
        ))
        hot_doc = "hotdoc"
        colds = [(f"cold{d}", c) for d in range(cold_docs)
                 for c in range(1, cold_clients + 1)]
        joins = [{"kind": "join", "doc": hot_doc, "client": w}
                 for w in range(1, n_writers + 1)]
        joins += [{"kind": "join", "doc": d, "client": c}
                  for d, c in colds]
        for lo in range(0, len(joins), 4096):
            raw.append_many(joins[lo:lo + 4096])
        # Warm: the whole collaborator set joined and broadcast before
        # the timed window opens (the storm measures steady state, not
        # the connect burst — that burst is the stampede's job).
        want = len(joins)
        bcast: List[dict] = []
        # Running counters folded at append time — a full rescan of
        # the accumulated list per poll tick would be O(n²) over the
        # run (the swarm's crossing-counter rule, applied here).
        n_op = 0        # broadcast records with kind == "op"
        n_traced = 0    # ...that carry the tr.sub submit stamp

        def take() -> None:
            nonlocal n_op, n_traced
            for _, v in bc_reader.poll():
                bcast.append(v)
                if isinstance(v, dict) and v.get("kind") == "op":
                    n_op += 1
                    if "sub" in (v.get("tr") or {}):
                        n_traced += 1

        deadline = time.time() + timeout_s
        while time.time() < deadline:
            sup.poll_once()
            take()
            if n_op >= want:
                break
            time.sleep(0.01)
        else:
            raise AssertionError(
                f"storm farm never came live: {len(bcast)} broadcast "
                f"records for {want} joins"
            )
        # (The join prefix stays in `bcast`: the integrity gate below
        # checks seqs 1..N per doc, and a doc's stream starts with its
        # joins. The op-drain condition keys on tr.sub, which joins
        # never carry, so nothing double-counts.)
        # Open-loop storm: fixed-rate offered load, hot_fraction of
        # picks landing on the viral doc. The feeder NEVER waits on
        # completion — while pacing it only drains tails and polls
        # the supervisor, so a backlogged pipeline reads as latency.
        total = max(128, int(rate_hz * duration_s))
        hot_cseq = {w: 0 for w in range(1, n_writers + 1)}
        cold_cseq = {k: 0 for k in colds}
        hot_sent = 0
        behind_ticks = 0
        t0 = time.perf_counter()
        last_sup = 0.0
        for i in range(total):
            tick = t0 + i / rate_hz
            now = time.perf_counter()
            if now > tick + 1.0 / rate_hz:
                behind_ticks += 1
            while True:
                now = time.perf_counter()
                if now >= tick:
                    break
                take()
                if now - last_sup > 0.2:
                    sup.poll_once()
                    last_sup = now
                time.sleep(min(0.002, tick - now))
            if rng.random() < hot_fraction:
                w = 1 + (hot_sent % n_writers)
                hot_sent += 1
                hot_cseq[w] += 1
                doc, client, cseq = hot_doc, w, hot_cseq[w]
            else:
                k = colds[i % len(colds)]
                cold_cseq[k] += 1
                doc, client, cseq = k[0], k[1], cold_cseq[k]
            raw.append_many([{
                "kind": "op", "doc": doc, "client": client,
                "clientSeq": cseq, "refSeq": 0,
                "contents": {"i": i}, "tr_sub": time.time(),
            }])
        feed_wall_s = time.perf_counter() - t0
        # Drain: every offered op must reach broadcast (bounded).
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            take()
            if n_traced >= total:
                break
            sup.poll_once()
            time.sleep(0.005)
        time.sleep(0.35)  # one post-drain throttled heartbeat
        slo = _collect_slo(sup)
        slow_ops = sup.child_slow_ops()
    finally:
        sup.stop()

    ops = [r for r in bcast if isinstance(r, dict)
           and r.get("kind") == "op" and "sub" in (r.get("tr") or {})]
    keys = [(r["doc"], r["client"], r["clientSeq"]) for r in ops]
    assert len(keys) == len(set(keys)), "duplicate ops in broadcast"
    assert len(keys) == total, (
        f"storm dropped work: {len(keys)}/{total} offered ops reached "
        f"broadcast within {timeout_s}s"
    )
    all_ops = [r for r in bcast if isinstance(r, dict)
               and r.get("kind") == "op"]  # joins + ops: seqs 1..N
    dups, skips = sequence_integrity(all_ops)
    assert dups == 0 and skips == 0, (
        f"storm stream integrity violated: dups={dups} skips={skips}"
    )
    hot_ms, cold_ms = [], []
    for r in ops:
        tr = r["tr"]
        assert tr["sub"] <= tr["stamp"] <= tr["bc"], \
            f"non-monotone span {tr}"
        ms = (tr["bc"] - tr["sub"]) * 1000.0
        (hot_ms if r["doc"] == hot_doc else cold_ms).append(ms)
    assert hot_ms, "storm produced no hot-doc ops"
    combined = _span_quantiles(hot_ms + cold_ms)
    assert _slo_p99(slo, "submit_to_broadcast") is not None, (
        "storm /slo carries no submit_to_broadcast quantiles"
    )
    assert slow_ops, "storm recorded no slow-op spans"
    return {
        "scenario": "hotdoc_storm",
        "open_loop": True,
        "records": total,
        "writers": n_writers,
        "hot_ops": len(hot_ms),
        "cold_ops": len(cold_ms),
        "hot_fraction": hot_fraction,
        "rate_hz": rate_hz,
        "feed_wall_s": round(feed_wall_s, 3),
        "behind_ticks": behind_ticks,
        "hot_submit_to_broadcast_ms": _span_quantiles(hot_ms),
        "cold_submit_to_broadcast_ms": (
            _span_quantiles(cold_ms) if cold_ms else None
        ),
        "submit_to_broadcast_ms": combined,
        "scenario_p99_ms": combined["p99"],
        "digest": stream_digest(all_ops),
        "slo": slo,
        "slow_ops": slow_ops[:5],
        "gate": ("exactly-once + contiguous seqs + monotone spans; "
                 "slo + slow-op evidence present"),
    }


def _collect_slo(sup) -> dict:
    """The farm's /slo body off the supervisor's merged child
    heartbeats (exactly what `monitor.MetricsServer` would serve)."""
    from ..utils.metrics import slo_summary

    return slo_summary(sup.collect_metrics().snapshot())


# ---------------------------------------------------------------------------
# reconnect stampede
# ---------------------------------------------------------------------------


def _drive_ranged_summarizers(shared: str, log_format: str,
                              summary_ops: int, topo: dict) -> int:
    """Drive one RANGED summarizer per live topology range to
    quiescence over already-written ``deltas-{rid}`` topics — the
    per-range elastic summary surface (`drive_summarizer`'s fabric
    twin; the supervised form is `ShardWorker(elastic=True,
    summarize=True)`). Returns the manifest count."""
    from ..server.columnar_log import make_tail_reader, make_topic
    from ..server.shard_fabric import ranged_role_class
    from ..server.summarizer import SummarizerRole

    emitted = 0
    for entry in topo["ranges"]:
        cls = ranged_role_class(SummarizerRole, entry, topo["epoch"])
        role = cls(shared, owner=f"stampede-{entry['rid']}",
                   ttl_s=3600.0, log_format=log_format,
                   summary_ops=summary_ops)
        role.fence = 1
        reader = make_tail_reader(make_topic(
            os.path.join(shared, "topics",
                         f"{role.in_topic_name}.jsonl"),
            log_format,
        ))
        while True:
            entries = reader.poll(4096)
            if not entries:
                break
            out: List[dict] = []
            for line_idx, rec in entries:
                role.process(line_idx, rec, out)
            role.flush_batch(out)
            if out:
                role.out_topic.append_many(out, fence=1,
                                           owner=role.owner)
                emitted += len(out)
            role.offset = reader.next_line
    return emitted


def run_reconnect_stampede(n_sessions: int = 2000, log_len: int = 20000,
                           n_clients: int = 4, summary_ops: int = 1000,
                           boot_checks: int = 3, threads: int = 16,
                           log_format: str = "json",
                           elastic_ranges: int = 0,
                           work_dir: Optional[str] = None) -> dict:
    """A partition heals: `n_sessions` clients that were offline for
    the whole log catch up SIMULTANEOUSLY through the summary path.
    Each session pays the real server-side work (`read_catchup`:
    manifest lookup + blob fetch + O(tail) backward scan) against ONE
    shared `SummaryIndex`/store — the read-amplification burst of an
    outage recovery, started behind a barrier so the stampede is
    genuinely concurrent.

    Gates (always): `boot_checks` full `SummaryReplica` boots
    bit-identical to a cold full-log replay (the PR 10 contract under
    stampede conditions), and every session's catch-up SIGNATURE
    (manifest seq/handle, tail key range) single-valued — a stampede
    cannot pass by handing different clients different states.

    `elastic_ranges` >= 2 runs the PER-RANGE elastic summary variant
    (PR 13 follow-up b over PR 14's elastic summarizer): the stream
    splits into hash-range ``deltas-{rid}`` topics, one RANGED
    summarizer serves each range, and every stampeding session reads
    through the MERGED `SummaryIndex` over the per-range
    ``summaries-{rid}`` topics — the same single-signature gate must
    hold across the fabric-shaped surface, plus a background doc per
    other range proving the merged index resolves them all."""
    from ..server.columnar_log import make_topic
    from ..server.queue import RangeLeaseStore, range_for_doc
    from ..server.summarizer import (
        SummaryIndex,
        SummaryReplica,
        open_summary_store,
        read_catchup,
    )
    from .pipeline import drive_summarizer, build_mergetree_stream

    scratch = work_dir or tempfile.mkdtemp(prefix="stampede-")
    reg, recorder, restore = _fresh_metrics()
    elastic = int(elastic_ranges) >= 2
    try:
        summary_ops = max(16, min(int(summary_ops), log_len // 4))
        stream = build_mergetree_stream(log_len, n_clients=n_clients)
        os.makedirs(os.path.join(scratch, "topics"), exist_ok=True)
        hot_deltas_topic = "deltas"
        if elastic:
            topo = RangeLeaseStore(scratch, "stampede").ensure_topology(
                int(elastic_ranges)
            )
            # The hot doc lands in ITS range's topic; one background
            # doc per OTHER range keeps every summaries-{rid} topic
            # live, so the merged index demonstrably resolves across
            # the whole per-range surface.
            hot_rid = range_for_doc(topo, "doc0")["rid"]
            hot_deltas_topic = f"deltas-{hot_rid}"
            by_topic: Dict[str, List[dict]] = {hot_deltas_topic: stream}
            bg_digests: Dict[str, str] = {}
            bg_i = 0
            for entry in topo["ranges"]:
                if entry["rid"] == hot_rid:
                    continue
                # Find a doc hashing into this range (bounded probe).
                doc = None
                for k in range(10000):
                    cand = f"bg{bg_i}-{k}"
                    if range_for_doc(topo, cand)["rid"] == entry["rid"]:
                        doc = cand
                        break
                if doc is None:
                    continue
                bg_i += 1
                bg = build_mergetree_stream(
                    max(64, summary_ops * 2), n_clients=2,
                    seed=90 + bg_i, doc=doc,
                )
                by_topic.setdefault(
                    f"deltas-{entry['rid']}", []
                ).extend(bg)
                cold_bg = SummaryReplica(None)
                cold_bg.apply_records(bg)
                bg_digests[doc] = cold_bg.state_digest()
            for tname, recs in by_topic.items():
                t = make_topic(
                    os.path.join(scratch, "topics", f"{tname}.jsonl"),
                    log_format,
                )
                for lo in range(0, len(recs), 16384):
                    t.append_many(recs[lo:lo + 16384])
            _drive_ranged_summarizers(scratch, log_format,
                                      summary_ops, topo)
            store = open_summary_store(scratch)
            index = SummaryIndex(scratch, log_format, topics=[
                f"summaries-{e['rid']}" for e in topo["ranges"]
            ])
        else:
            deltas = make_topic(
                os.path.join(scratch, "topics", "deltas.jsonl"),
                log_format,
            )
            for lo in range(0, len(stream), 16384):
                deltas.append_many(stream[lo:lo + 16384])
            drive_summarizer(scratch, log_format, summary_ops)
            store = open_summary_store(scratch)
            index = SummaryIndex(scratch, log_format)

        # Boot-equivalence gate (+ jit warm-up for the boot path).
        cold = SummaryReplica(None)
        cold.apply_records(stream)
        cold_digest = cold.state_digest()
        for _ in range(max(1, boot_checks)):
            cu = read_catchup(scratch, "doc0", log_format,
                              index=index, store=store,
                              deltas_topic=hot_deltas_topic)
            assert cu["manifest"] is not None, "no summary emitted"
            boot = SummaryReplica(cu["blob"])
            boot.apply_records(cu["ops"])
            assert boot.state_digest() == cold_digest, (
                "summary+tail boot diverged from cold replay under "
                "stampede conditions"
            )
        if elastic:
            # The merged per-range surface resolves EVERY range's
            # docs, not just the hot one.
            for doc, want in bg_digests.items():
                rid = range_for_doc(topo, doc)["rid"]
                cu = read_catchup(scratch, doc, log_format,
                                  index=index, store=store,
                                  deltas_topic=f"deltas-{rid}")
                assert cu["manifest"] is not None, (
                    f"merged index missed {doc}'s range summary"
                )
                boot = SummaryReplica(cu["blob"])
                boot.apply_records(cu["ops"])
                assert boot.state_digest() == want, (
                    f"per-range boot diverged for {doc}"
                )

        # The stampede proper: all sessions released at once.
        h_catchup = reg.histogram("op_stage_ms", stage="read_catchup")
        lat_ms: List[float] = [0.0] * n_sessions
        sigs: List[Optional[str]] = [None] * n_sessions
        errors: List[str] = []
        barrier = threading.Barrier(min(threads, n_sessions) + 1)
        next_session = [0]
        lock = threading.Lock()

        def session_sig(cu: dict) -> str:
            man = cu["manifest"]
            ops = cu["ops"]
            payload = json.dumps([
                man["seq"], man["handle"], len(ops),
                ops[0]["seq"] if ops else None,
                ops[-1]["seq"] if ops else None,
            ])
            return hashlib.sha256(payload.encode()).hexdigest()

        def worker():
            try:
                barrier.wait(timeout=60)
            except threading.BrokenBarrierError:
                return
            while True:
                with lock:
                    i = next_session[0]
                    if i >= n_sessions:
                        return
                    next_session[0] = i + 1
                try:
                    t0 = time.perf_counter()
                    cu = read_catchup(scratch, "doc0", log_format,
                                      index=index, store=store,
                                      deltas_topic=hot_deltas_topic)
                    ms = (time.perf_counter() - t0) * 1000.0
                    lat_ms[i] = ms
                    sigs[i] = session_sig(cu)
                    h_catchup.observe(ms)
                    if recorder.note(ms):
                        recorder.add(ms, {"session": i,
                                          "stage": "read_catchup"})
                except Exception as exc:  # surfaced as a gate failure
                    with lock:
                        errors.append(f"session {i}: {exc!r}")
                    return

        pool = [threading.Thread(target=worker, daemon=True)
                for _ in range(min(threads, n_sessions))]
        for t in pool:
            t.start()
        t0 = time.perf_counter()
        barrier.wait(timeout=60)  # the partition heals HERE
        for t in pool:
            t.join(timeout=600)
        wall = time.perf_counter() - t0
        assert not errors, f"stampede sessions failed: {errors[:3]}"
        assert all(s is not None for s in sigs), "sessions incomplete"
        assert len(set(sigs)) == 1, (
            f"stampede diverged: {len(set(sigs))} distinct catch-up "
            f"signatures across {n_sessions} sessions"
        )
        from ..utils.metrics import slo_summary

        slo = slo_summary(reg.snapshot())
        q = _span_quantiles(lat_ms)
        return {
            "scenario": "reconnect_stampede",
            "open_loop": True,  # all sessions offered at once
            "sessions": n_sessions,
            "log_len": log_len,
            "elastic_ranges": int(elastic_ranges) if elastic else 0,
            "summary_seq": cu["manifest"]["seq"],
            "tail_ops": len(cu["ops"]),
            "wall_s": round(wall, 3),
            "catchups_per_sec": round(n_sessions / wall, 1),
            "catchup_ms": q,
            "scenario_p99_ms": q["p99"],
            "boots_bit_identical": True,
            "digest": sigs[0],
            "slo": slo,
            "slow_ops": _slowest(recorder),
            "gate": ("summary+tail boots == cold replay; one catch-up "
                     "signature across every session"),
        }
    finally:
        restore()
        if work_dir is None:
            shutil.rmtree(scratch, ignore_errors=True)


# ---------------------------------------------------------------------------
# read-mostly swarm
# ---------------------------------------------------------------------------


def run_read_swarm(n_sessions: int = 100_000, n_docs: int = 4,
                   n_records: int = 64, n_tcp: int = 8,
                   feed_batch: int = 32, session_bar: int = 100_000,
                   min_cores: int = 4, timeout_s: float = 180.0,
                   work_dir: Optional[str] = None) -> dict:
    """`n_sessions` subscribed read sessions fanning out through
    `FarmReadServer` — the joins-and-reads shape production traffic
    actually has. `n_tcp` of them are REAL TCP sessions over the
    framed wire protocol (subscribe + live push, per-record latency
    observed against the append stamp); the rest are in-proc
    subscriber sessions on the same doorbell-woken pusher, which is
    the honest way to reach 100k sessions on one box without
    measuring the kernel's fd table instead of the fan-out path.

    Convergence gate (always): EVERY session — TCP and in-proc —
    receives its doc's `n_records` records exactly, and the TCP
    sessions' streams are seq-contiguous; a swarm cannot pass by
    dropping a subscriber. The throughput evidence (deliveries/s) is
    recorded-not-gated below the `session_bar`/`min_cores` honesty
    bar, with a LOUD skip naming why."""
    from ..server.framing import read_frame, write_frame
    from ..server.queue import SharedFileTopic
    from ..server.socket_service import FarmReadServer

    scratch = work_dir or tempfile.mkdtemp(
        prefix="swarm-",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None,
    )
    reg, recorder, restore = _fresh_metrics()
    n_tcp = min(n_tcp, n_sessions)
    srv = None
    tcp_socks: List[socket.socket] = []
    torn_down = [False]

    def _teardown():
        # Idempotent: runs inline on the happy path (server down
        # before the asserts) AND from the finally, so a failed
        # subscribe or a timed-out fan-out can never leak the server
        # thread / sockets past the scratch rmtree.
        if torn_down[0]:
            return
        torn_down[0] = True
        for s in tcp_socks:
            try:
                s.close()
            except OSError:
                pass
        if srv is not None:
            srv.stop()

    try:
        topic = SharedFileTopic(
            os.path.join(scratch, "topics", "broadcast.jsonl")
        )
        srv = FarmReadServer(scratch).start()
        h_push = reg.histogram("op_stage_ms", stage="broadcast_to_push")
        docs = [f"doc{d}" for d in range(n_docs)]
        n_light = n_sessions - n_tcp
        counts = [0] * n_light
        done_light = threading.Event()
        # Crossing counter, not an all() scan: at 100k sessions an
        # O(sessions) completion check per delivery callback would be
        # O(sessions²) and the swarm would measure the checker. The
        # pusher delivers from ONE thread, so the decrement is
        # race-free by construction.
        pending = [n_light]

        def light_session(i: int):
            def fn(recs):
                before = counts[i]
                counts[i] = before + sum(
                    1 for r in recs if r.get("kind") == "op"
                )
                if before < n_records <= counts[i]:
                    pending[0] -= 1
                    if pending[0] == 0:
                        done_light.set()
            return fn

        for i in range(n_light):
            srv.pusher.subscribe(docs[i % n_docs], light_session(i))
        if not n_light:
            done_light.set()

        # Real TCP sessions: framed subscribe + push-reader threads.
        tcp_counts = [0] * n_tcp
        tcp_seq_ok = [True] * n_tcp
        tcp_threads: List[threading.Thread] = []
        tcp_done = threading.Event()

        def tcp_reader(i: int, rf):
            last = 0
            while tcp_counts[i] < n_records:
                try:
                    frame = read_frame(rf)
                except (OSError, ValueError, ConnectionError):
                    return
                if frame is None:
                    return
                if frame.get("event") != "recs":
                    continue
                now = time.time()
                for r in frame["recs"]:
                    if r.get("kind") != "op":
                        continue
                    tcp_counts[i] += 1
                    if int(r["seq"]) != last + 1:
                        tcp_seq_ok[i] = False
                    last = int(r["seq"])
                    ts = r.get("ts")
                    if isinstance(ts, (int, float)):
                        ms = (now - ts) * 1000.0
                        h_push.observe(ms)
                        if recorder.note(ms):
                            recorder.add(ms, {
                                "session": f"tcp{i}",
                                "doc": r.get("doc"),
                                "seq": r.get("seq"),
                                "stage": "broadcast_to_push",
                            })
            if all(c >= n_records for c in tcp_counts):
                tcp_done.set()

        for i in range(n_tcp):
            s = socket.create_connection((srv.host, srv.port),
                                         timeout=30)
            tcp_socks.append(s)
            wf, rf = s.makefile("wb"), s.makefile("rb")
            write_frame(wf, {"id": 1, "cmd": "subscribe",
                             "docId": docs[i % n_docs]})
            resp = read_frame(rf)
            assert resp and "result" in resp, f"subscribe failed: {resp}"
            th = threading.Thread(target=tcp_reader, args=(i, rf),
                                  daemon=True)
            th.start()
            tcp_threads.append(th)
        if not n_tcp:
            tcp_done.set()

        # Feed: n_records per doc, batched, append-stamped so the TCP
        # sessions measure broadcast→push latency off the wire.
        t0 = time.perf_counter()
        for lo in range(0, n_records, feed_batch):
            hi = min(n_records, lo + feed_batch)
            for doc in docs:
                topic.append_many([
                    {"kind": "op", "doc": doc, "seq": s + 1, "msn": 0,
                     "client": 1, "clientSeq": s + 1, "refSeq": 0,
                     "type": "op", "contents": {"i": s},
                     "ts": time.time()}
                    for s in range(lo, hi)
                ])
        ok = done_light.wait(timeout=timeout_s) and \
            tcp_done.wait(timeout=timeout_s)
        wall = time.perf_counter() - t0
        _teardown()
        assert ok, (
            f"swarm fan-out incomplete within {timeout_s}s: slowest "
            f"in-proc session {min(counts) if counts else n_records}"
            f"/{n_records}, tcp {tcp_counts}"
        )
        assert all(c == n_records for c in counts), (
            "an in-proc session saw duplicated records"
        )
        assert all(c == n_records for c in tcp_counts) and \
            all(tcp_seq_ok), (
                f"tcp sessions incomplete or out of order: "
                f"{tcp_counts} seq_ok={tcp_seq_ok}"
            )
        from ..utils.metrics import slo_summary

        slo = slo_summary(reg.snapshot())
        total = n_sessions * n_records
        p99 = _slo_p99(slo, "broadcast_to_push")
        result: Dict[str, Any] = {
            "scenario": "read_swarm",
            "open_loop": True,  # feed never waits on delivery
            "sessions": n_sessions,
            "tcp_sessions": n_tcp,
            "docs": n_docs,
            "records_per_doc": n_records,
            "deliveries": total,
            "wall_s": round(wall, 3),
            "deliveries_per_sec": round(total / wall, 1),
            "push_ms": slo,
            "scenario_p99_ms": p99,
            "digest": hashlib.sha256(json.dumps(
                [n_sessions, n_records, sorted(set(counts)),
                 tcp_counts]).encode()).hexdigest(),
            "slo": slo,
            "slow_ops": _slowest(recorder),
            "gate": ("every session delivered exactly n_records; tcp "
                     "streams seq-contiguous"),
        }
        cores = os.cpu_count() or 1
        if n_sessions < session_bar or cores < min_cores:
            why = (f"{n_sessions} sessions < the {session_bar}-session "
                   f"bar" if n_sessions < session_bar
                   else f"host has {cores} cores < {min_cores}")
            result["skipped"] = (
                f"swarm throughput recorded-not-gated: {why}; the "
                f"fan-out convergence gate ran on every session"
            )
            import sys

            print(f"SKIP read_swarm throughput evidence: "
                  f"{result['skipped']}", file=sys.stderr)
        return result
    finally:
        _teardown()  # failure paths: server down BEFORE the rmtree
        restore()
        if work_dir is None:
            shutil.rmtree(scratch, ignore_errors=True)


# ---------------------------------------------------------------------------
# tenant-skewed mix
# ---------------------------------------------------------------------------


def run_tenant_mix(n_tenants: int = 8, records: int = 4000,
                   hot_share: float = 0.7, rate_hz: float = 400.0,
                   rate_limit: float = 150.0, n_partitions: int = 2,
                   log_format: str = "json", timeout_s: float = 120.0,
                   seed: int = 17,
                   work_dir: Optional[str] = None) -> dict:
    """A zipf-shaped tenant mix through the PR 12 front door: tenant
    ``t0`` offers `hot_share` of a `rate_hz` open-loop stream — well
    over the per-tenant `rate_limit` token bucket — while the cold
    tenants split the rest, each far under it. The bucket must bill
    the hot tenant and ONLY the hot tenant: visible 429 rate-nacks on
    t0's docs, zero on anyone else's, and the throttled tail retried
    to exactly-once convergence (the real client contract).

    Wire traces are ON, so the run also proves the `admit_to_stamp`
    stage end-to-end: the front door stamps ``tr_adm``, the deli folds
    it into the trace dict and observes the stage, and the /slo body
    carries both the quantiles and the `ingress_*` refusal counters."""
    from ..server.columnar_log import make_topic
    from ..server.ingress import IngressRole, write_tenants
    from ..server.riddler import sign_token
    from ..server.supervisor import DeliRole, partitioned_role_class
    from ..utils.metrics import slo_summary

    scratch = work_dir or tempfile.mkdtemp(prefix="tenant-mix-")
    reg, recorder, restore = _fresh_metrics()
    prev_trace = os.environ.get("FLUID_TRACE_WIRE")
    os.environ["FLUID_TRACE_WIRE"] = "1"
    rng = random.Random(seed)
    try:
        tenants = {f"t{i}": f"mix-key-{i}" for i in range(n_tenants)}
        write_tenants(scratch, tenants)
        # One doc per tenant (names spread across partitions by the
        # consistent hash as-is; skew is the POINT here, not balance).
        docs = {t: f"{t}-doc" for t in tenants}
        doc_tenant = {d: t for t, d in docs.items()}
        tokens = {
            t: sign_token(k, t, docs[t], ["doc:write"],
                          lifetime_s=24 * 3600.0)
            for t, k in tenants.items()
        }
        ing = IngressRole(
            scratch, "mix-ingress", ttl_s=3600.0, batch=8192,
            log_format=log_format, n_partitions=n_partitions,
            rate_limit=rate_limit,
            # Half-second bucket depth: the default 2x-rate burst
            # would absorb a whole scaled run before the hot tenant
            # ever hit the sustained limit the scenario is about.
            rate_burst=max(1.0, rate_limit / 2.0),
        )
        delis = [
            partitioned_role_class(DeliRole, k)(
                scratch, f"mix-deli-p{k}", ttl_s=3600.0, batch=8192,
                log_format=log_format,
            )
            for k in range(n_partitions)
        ] if n_partitions > 1 else [
            DeliRole(scratch, "mix-deli", ttl_s=3600.0, batch=8192,
                     log_format=log_format)
        ]
        ing_topic = make_topic(
            os.path.join(scratch, "topics", "ingress.jsonl"), log_format
        )
        nacks_topic = make_topic(
            os.path.join(scratch, "topics", "nacks.jsonl"), log_format
        )
        # Sessions open first (the alfred connection shape): ops then
        # ride bare and inherit their (doc, client) session.
        ing_topic.append_many([
            {"kind": "auth", "doc": docs[t], "client": 1, "tenant": t,
             "token": tokens[t]}
            for t in tenants
        ])
        # Joins ride the front door too (session-authed, one bucket
        # token each): a client must be in the doc's collaborator set
        # before its first op or the deli nacks the whole stream.
        ing_topic.append_many([
            {"kind": "join", "doc": docs[t], "client": 1}
            for t in tenants
        ])
        while ing.step() > 0:
            pass

        # The offered mix: hot_share of picks on t0, the rest spread
        # over the cold tenants — contiguous clientSeq per tenant.
        cold = [t for t in tenants if t != "t0"]
        cseq = {t: 0 for t in tenants}
        plan: List[dict] = []
        for i in range(records):
            t = "t0" if rng.random() < hot_share else \
                cold[i % len(cold)]
            cseq[t] += 1
            plan.append({"kind": "op", "doc": docs[t], "client": 1,
                         "clientSeq": cseq[t], "refSeq": 0,
                         "contents": {"i": i}})
        offered = {t: cseq[t] for t in tenants}

        def pump():
            ing.step()
            for d in delis:
                d.step()

        # Open-loop feed at rate_hz (small batches so the bucket sees
        # a stream, not one burst); the feeder never waits on
        # sequencing — it pumps the roles only while pacing.
        t0 = time.perf_counter()
        step = max(1, int(rate_hz / 50))  # ~50 appends/s
        i = 0
        while i < len(plan):
            tick = t0 + i / rate_hz
            while time.perf_counter() < tick:
                pump()
                time.sleep(0.001)
            ing_topic.append_many(plan[i:i + step])
            i += step
            pump()
        feed_wall_s = time.perf_counter() - t0

        # Retry-and-converge: resubmit each nacked client-tail (both
        # ingress throttle nacks and any deli order nacks a gate flip
        # let through) until every offered op is sequenced once.
        deltas = [
            make_topic(os.path.join(
                scratch, "topics",
                f"deltas-p{k}.jsonl" if n_partitions > 1
                else "deltas.jsonl",
            ), log_format)
            for k in range(max(1, n_partitions))
        ]

        # Incremental drains (TailReader cursors, never a from-zero
        # re-read per pass — a from-zero scan would be O(records²)
        # over the retry window): `ops`/`every`/`seen` accumulate, and
        # nack triggers (ingress throttles AND deli order-nacks, which
        # land on the deltas topics) collect into pending_tails as
        # they arrive.
        from ..server.columnar_log import make_tail_reader

        seq_readers = [make_tail_reader(t, 0) for t in deltas]
        nack_reader = make_tail_reader(nacks_topic, 0)
        ops: List[dict] = []
        every: List[dict] = []
        seen: set = set()
        pending_tails: Dict[str, int] = {}

        def note_nack(r: Any) -> None:
            if isinstance(r, dict) and r.get("kind") == "nack" \
                    and r.get("doc") in doc_tenant:
                c = int(r.get("clientSeq") or 0)
                d = r["doc"]
                pending_tails[d] = min(pending_tails.get(d, c), c)

        def drain() -> None:
            for rd in seq_readers:
                for _i, r in rd.poll():
                    if not isinstance(r, dict):
                        continue
                    if r.get("kind") == "op":
                        every.append(r)
                        if r.get("type") == "op":
                            ops.append(r)
                            seen.add((r["doc"], r["clientSeq"]))
                    else:
                        note_nack(r)
            for _i, r in nack_reader.poll():
                note_nack(r)

        retries = 0
        last_retry = 0.0
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            pump()
            drain()
            if len(ops) >= records:
                break
            if time.time() - last_retry < 0.1:
                # Pace the resubmissions to the bucket's refill, or
                # every pass re-offers the whole throttled tail and
                # the nack log measures the retry loop, not the mix.
                time.sleep(0.002)
                continue
            last_retry = time.time()
            # Nacked tails: lowest nacked clientSeq per doc since the
            # last retry, resubmit everything unsequenced from there
            # (per-doc = per-tenant here). A re-throttled resubmit
            # produces fresh nacks, which re-trigger the next pass.
            tails, pending_tails = pending_tails, {}
            batch = [p for p in plan
                     if p["doc"] in tails
                     and p["clientSeq"] >= tails[p["doc"]]
                     and (p["doc"], p["clientSeq"]) not in seen]
            if batch:
                retries += len(batch)
                ing_topic.append_many(batch)
            time.sleep(0.002)

        # Convergence digest: every offered op exactly once, contents
        # intact, per-doc seqs contiguous.
        keys = [(r["doc"], r["clientSeq"]) for r in ops]
        assert len(ops) == records and len(set(keys)) == records, (
            f"tenant mix did not converge exactly-once: {len(ops)} "
            f"ops, {len(set(keys))} unique of {records}"
        )
        want = {(p["doc"], p["clientSeq"]):
                p["contents"] for p in plan}
        for r in ops:
            assert want[(r["doc"], r["clientSeq"])] == r["contents"], (
                f"contents corrupted for {r['doc']}#{r['clientSeq']}"
            )
        dups, skips = sequence_integrity(every)
        assert dups == 0 and skips == 0
        # Throttle taxonomy: rate nacks exist and bill ONLY t0.
        rate_nacks: Dict[str, int] = {}
        for r in nacks_topic.read_from(0):
            if isinstance(r, dict) and r.get("kind") == "nack" and \
                    str(r.get("reason", "")).startswith("rate:"):
                t = doc_tenant.get(r.get("doc"), "?")
                rate_nacks[t] = rate_nacks.get(t, 0) + 1
        assert rate_nacks.get("t0"), (
            "hot tenant was never throttled — the mix exercised no "
            "token bucket"
        )
        assert set(rate_nacks) == {"t0"}, (
            f"cold tenants were throttled too: {rate_nacks} (the "
            f"bucket must bill the hot tenant only)"
        )
        # Admission-stage evidence: adm stamps rode the wire and the
        # deli observed admit_to_stamp; feed the slowest admissions to
        # the scenario recorder as its slow-op spans.
        adm_ms: List[float] = []
        for r in ops:
            tr = r.get("tr")
            if isinstance(tr, dict) and "adm" in tr and "stamp" in tr:
                assert tr["adm"] <= tr["stamp"], f"adm > stamp: {tr}"
                ms = (tr["stamp"] - tr["adm"]) * 1000.0
                adm_ms.append(ms)
                if recorder.note(ms):
                    recorder.add(ms, {
                        "doc": r.get("doc"), "seq": r.get("seq"),
                        "stage": "admit_to_stamp",
                    })
        assert adm_ms, "no admit_to_stamp spans rode the wire"
        slo = slo_summary(reg.snapshot())
        assert _slo_p99(slo, "admit_to_stamp") is not None, (
            "/slo carries no admit_to_stamp quantiles"
        )
        assert any(c["name"] == "ingress_nacks_total"
                   for c in slo.get("counters", ())), (
            "/slo carries no ingress refusal counters"
        )
        q = _span_quantiles(adm_ms)
        return {
            "scenario": "tenant_mix",
            "open_loop": True,
            "records": records,
            "tenants": n_tenants,
            "hot_share": hot_share,
            "offered_per_tenant": offered,
            "rate_hz": rate_hz,
            "rate_limit": rate_limit,
            "feed_wall_s": round(feed_wall_s, 3),
            "throttle_nacks": rate_nacks,
            "retries": retries,
            "admit_to_stamp_ms": q,
            "scenario_p99_ms": q["p99"],
            "digest": stream_digest(ops),
            "slo": slo,
            "slow_ops": _slowest(recorder),
            "gate": ("exactly-once after retries; rate nacks bill the "
                     "hot tenant only; admit_to_stamp + ingress "
                     "counters in /slo"),
        }
    finally:
        if prev_trace is None:
            os.environ.pop("FLUID_TRACE_WIRE", None)
        else:
            os.environ["FLUID_TRACE_WIRE"] = prev_trace
        restore()
        if work_dir is None:
            shutil.rmtree(scratch, ignore_errors=True)


# ---------------------------------------------------------------------------
# week-of-traffic churn (the retention plane's gate)
# ---------------------------------------------------------------------------


def run_week_of_traffic(cycles: int = 4, hot_writers: int = 12,
                        cold_docs: int = 2, cold_clients: int = 2,
                        ops_per_writer: int = 30,
                        summary_ops: int = 64, rate_hz: float = 500.0,
                        stampede_sessions: int = 16,
                        swarm_sessions: int = 48,
                        deli_impl: str = "scalar",
                        retention: bool = True,
                        keep_tail: int = 256,
                        hwm_slack: float = 1.35,
                        timeout_s: float = 300.0,
                        work_dir: Optional[str] = None) -> dict:
    """The MIXED week-of-traffic shape (ROADMAP 4 follow-up (c)):
    storm + stampede + swarm CONCURRENTLY, compressed into `cycles`
    generations of churning collaborators — and the retention plane's
    churn gate (ROADMAP 3 / ISSUE 14 acceptance).

    Each cycle, a FRESH band of writers joins (one viral hot doc takes
    most of the load, a cold background mix the rest), streams
    bounded merge-tree edits open-loop at `rate_hz`, and LEAVES — the
    collab window closes, so summaries settle to state-sized blobs.
    While the cycle streams, `swarm_sessions` subscribed read sessions
    ride the broadcast push (every one must see every record of its
    doc), and a `stampede_sessions`-strong reconnect burst hits the
    summary catch-up path mid-run (one signature across the burst).

    With `retention=True` the farm runs the SIXTH role
    (`server.retention.RetentionRole`, columnar log, fused
    durable+broadcast hop): deltas/rawdeltas/durable/broadcast all
    truncate behind the summary epoch and unreferenced castore blobs
    sweep. The gate:

    - **bounded disk** — the on-disk high-water mark (op logs +
      castore) stops growing after the first retention cycle:
      ``max(usage[2:]) <= hwm_slack * usage[1]``;
    - **bit-identity** — a LIVE client's accumulated stream, a COLD
      boot from the newest summary + tail, and a LONG-OFFLINE
      reconnector (last saw cycle 0; its op gap is partially
      reclaimed, so it must REBOOT from the summary, not replay)
      all converge to one `state_digest` per doc, with zero
      duplicate/skipped seqs.

    Returns the per-cycle usage table and ``retention_disk_mb`` (the
    steady-state high-water mark)."""
    if retention and cycles < 3:
        raise ValueError(
            "retention=True needs cycles >= 3: the bounded-disk gate "
            "compares the high-water mark of cycles AFTER the first "
            "retention cycle against cycle 1 — with fewer cycles "
            "there is nothing to compare and the gate is vacuous"
        )
    from ..server.columnar_log import make_tail_reader, make_topic
    from ..server.retention import disk_usage
    from ..server.socket_service import FarmReadServer
    from ..server.summarizer import (
        SummaryIndex,
        SummaryReplica,
        open_summary_store,
        read_catchup,
    )
    from ..server.supervisor import ServiceSupervisor, canonical_record

    scratch = work_dir or tempfile.mkdtemp(
        prefix="week-",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None,
    )
    log_format = "columnar"
    srv = None
    sup = None
    try:
        sup = ServiceSupervisor(
            scratch,
            roles=("deli", "scriptorium", "broadcaster", "scribe",
                   "summarizer"),
            fused_hop=True, deli_impl=deli_impl,
            log_format=log_format, summary_ops=summary_ops,
            retention=retention, ttl_s=0.75, hb_interval_s=0.1,
            # A loaded CI box stalls children past the default 2s
            # staleness bar without any real fault; a spurious restart
            # mid-cycle stalls retention and races the disk sample.
            # Restart-on-crash still works — this only widens the
            # wedged-child detector.
            heartbeat_timeout_s=6.0,
            retention_env={
                "FLUID_RETENTION_INTERVAL": "0.25",
                "FLUID_RETENTION_MIN_BYTES": "4096",
                # Spare tail: live pushers/readers are structurally
                # ahead of every cut (scaled with the workload — it
                # must stay well under one cycle's record count or
                # nothing ever qualifies).
                "FLUID_RETENTION_KEEP_TAIL": str(int(keep_tail)),
                "FLUID_RETENTION_GRACE": "1.0",
                # Every growth surface, metadata included: the op
                # logs, plus the manifest topic (superseded manifests
                # beyond the keep depth) and the retention topic's own
                # commit history (only the newest commit per topic is
                # ever read again).
                "FLUID_RETENTION_TOPICS":
                    "deltas,rawdeltas,durable,broadcast,"
                    "summaries,retention",
            } if retention else None,
        ).start()
        raw = make_topic(
            os.path.join(scratch, "topics", "rawdeltas.jsonl"),
            log_format,
        )
        broadcast = make_topic(
            os.path.join(scratch, "topics", "broadcast.jsonl"),
            log_format,
        )
        retention_topic = make_topic(
            os.path.join(scratch, "topics", "retention.jsonl"),
            log_format,
        ) if retention else None
        docs = ["hotdoc"] + [f"cold{i}" for i in range(cold_docs)]
        # The LIVE client: an incremental broadcast tail accumulated
        # across the whole run (per-doc canonical records) — what any
        # connected session would have seen.
        bc_reader = make_tail_reader(broadcast, 0)
        live: Dict[str, List[dict]] = {d: [] for d in docs}

        def drain_live() -> None:
            for _i, r in bc_reader.poll():
                if isinstance(r, dict) and r.get("kind") == "op" \
                        and r.get("doc") in live:
                    live[r["doc"]].append(canonical_record(r))

        # The SWARM: in-proc subscribed sessions on the farm's read
        # front end (same doorbell-woken pusher real TCP rides).
        srv = FarmReadServer(scratch, log_format=log_format)
        srv.start()
        swarm_counts = [0] * swarm_sessions
        swarm_docs = [docs[i % len(docs)] for i in range(swarm_sessions)]

        def swarm_session(i: int):
            def fn(recs):
                swarm_counts[i] += sum(
                    1 for r in recs if r.get("kind") == "op"
                )
            return fn

        for i in range(swarm_sessions):
            srv.pusher.subscribe(swarm_docs[i], swarm_session(i))

        # Feeder model: feed order == sequence order (one feeder, one
        # raw topic), so refSeq can track the head exactly and the
        # text length model is exact — bounded merge-tree docs whose
        # canonical rows (and therefore blobs) stay O(state).
        head = {d: 0 for d in docs}  # per-doc fed-record count == seq
        text_len = {d: 0 for d in docs}

        def reader_lag() -> int:
            """How far the slowest UNTRACKED broadcast reader (the
            live tail, the swarm's pusher) trails the fed head, in
            records. Retention spares only `keep_tail` records behind
            its scan head for these readers — no checkpoint tracks
            them — and a reader lapped past a cut silently resumes at
            the truncation base (records between are gone, failing
            the convergence gates minutes later and doc-load-
            dependent). Joins/leaves sequence as records too, so the
            fed head is directly comparable to delivered counts."""
            total = sum(head.values())
            live_lag = total - sum(len(live[d]) for d in docs)
            swarm_lag = max(
                (head[swarm_docs[i]] - swarm_counts[i]
                 for i in range(swarm_sessions)),
                default=0,
            )
            return max(live_lag, swarm_lag)

        def feed(recs: List[dict]) -> None:
            # Backpressure (retention runs only): pace the feed so no
            # untracked reader falls further behind than HALF the
            # keep_tail spare — the cut can then never lap a live
            # reader by construction, however asymmetrically a loaded
            # host schedules the parent's reader threads against the
            # retention child. Bounded wait: a wedged farm surfaces
            # as the cycle-drain assertion, not a silent hang here.
            if retention:
                limit = time.time() + 30.0
                while reader_lag() > keep_tail // 2 and \
                        time.time() < limit:
                    pump(0.002)
            for r in recs:
                head[r["doc"]] += 1
            raw.append_many(recs)

        def mt_op(doc: str, i: int) -> dict:
            if text_len[doc] >= 120:
                k = 60
                text_len[doc] -= k
                return {"type": 1, "pos1": 0, "pos2": k}
            seg = f"w{i % 97:02d}"
            text_len[doc] += len(seg)
            return {"type": 0, "pos1": 0, "seg": seg}

        def pump(dt: float = 0.0) -> None:
            sup.poll_once()
            drain_live()
            if dt:
                time.sleep(dt)

        usage: List[int] = []
        stampede_sigs: List[set] = []
        reconnect_seen = 0  # the long-offline client's last seq (hot)
        truncs_seen = 0
        activity_seen = 0  # retention records-ever-appended high water
        for cycle in range(cycles):
            deadline = time.time() + timeout_s
            base_id = 1000 * (cycle + 1)
            hot = [base_id + w for w in range(hot_writers)]
            colds = [(f"cold{d}", base_id + w)
                     for d in range(cold_docs)
                     for w in range(cold_clients)]
            feed([{"kind": "join", "doc": "hotdoc", "client": c,
                   "refSeq": head["hotdoc"]} for c in hot])
            feed([{"kind": "join", "doc": d, "client": c,
                   "refSeq": head[d]} for d, c in colds])
            # Open-loop-paced edit stream: hot writers round-robin on
            # the viral doc, cold writers on the background docs.
            plan: List[tuple] = []
            for i in range(ops_per_writer):
                for w in hot:
                    plan.append(("hotdoc", w, i + 1))
                for d, c in colds:
                    plan.append((d, c, i + 1))
            t0 = time.perf_counter()
            for j, (doc, client, cseq) in enumerate(plan):
                tick = t0 + j / rate_hz
                while time.perf_counter() < tick:
                    pump(0.001)
                feed([{"kind": "op", "doc": doc, "client": client,
                       "clientSeq": cseq, "refSeq": head[doc],
                       "contents": mt_op(doc, j)}])
                if j % 16 == 0:
                    pump()
            # Churn: the whole generation LEAVES — the collab window
            # closes behind it, summaries settle, blobs stay bounded.
            feed([{"kind": "leave", "doc": "hotdoc", "client": c}
                  for c in hot])
            feed([{"kind": "leave", "doc": d, "client": c}
                  for d, c in colds])
            # Every record of the cycle must reach the live tail
            # (joins/leaves sequence too, so the target is the head).
            while time.time() < deadline:
                pump(0.005)
                if all(len(live[d]) >= head[d] for d in docs):
                    break
            else:
                raise AssertionError(
                    f"cycle {cycle} never drained: "
                    f"{ {d: len(live[d]) for d in docs} } of "
                    f"{ {d: head[d] for d in docs} }"
                )
            if cycle == 0:
                # The long-offline reconnector saw exactly cycle 0.
                reconnect_seen = max(
                    int(r["seq"]) for r in live["hotdoc"]
                )
            # Mid-run reconnect STAMPEDE through the summary path
            # (after cycle 1 a summary provably exists). Quiesce the
            # summarizer first — a manifest landing MID-burst would
            # legitimately split the signatures.
            if cycle >= 1:
                from ..server.queue import FencedCheckpointStore

                ck = FencedCheckpointStore(
                    os.path.join(scratch, "checkpoints")
                )

                def summ_offset() -> int:
                    env = ck.load("summarizer")
                    try:
                        return int(((env or {}).get("state") or {})
                                   .get("offset", 0))
                    except (TypeError, ValueError):
                        return 0

                total = sum(head.values())
                while summ_offset() < total and \
                        time.time() < deadline:
                    pump(0.02)
                idx = SummaryIndex(scratch, log_format)
                store = open_summary_store(scratch)
                idx.poll()
                last_man = idx.nearest("hotdoc")
                stable_t = time.time()
                while time.time() - stable_t < 0.8 and \
                        time.time() < deadline:
                    pump(0.05)
                    idx.poll()
                    cur = idx.nearest("hotdoc")
                    if (cur or {}).get("handle") != \
                            (last_man or {}).get("handle"):
                        last_man, stable_t = cur, time.time()
                sigs: List[Optional[str]] = [None] * stampede_sessions
                errs: List[str] = []

                def catchup_session(i: int) -> None:
                    try:
                        cu = read_catchup(scratch, "hotdoc", log_format,
                                          index=idx, store=store)
                        man = cu["manifest"]
                        sigs[i] = json.dumps([
                            man["seq"] if man else None,
                            man["handle"] if man else None,
                            len(cu["ops"]),
                        ])
                    except Exception as exc:  # gate failure, surfaced
                        errs.append(repr(exc))

                pool = [threading.Thread(target=catchup_session,
                                         args=(i,), daemon=True)
                        for i in range(stampede_sessions)]
                for t in pool:
                    t.start()
                for t in pool:
                    t.join(timeout=120)
                assert not errs, f"stampede failed: {errs[:3]}"
                assert all(s is not None for s in sigs)
                stampede_sigs.append(set(sigs))
                assert len(stampede_sigs[-1]) == 1, (
                    f"stampede diverged in cycle {cycle}: "
                    f"{stampede_sigs[-1]}"
                )
            # Let the retention plane SETTLE before sampling disk:
            # wait for the truncate-commit stream to go quiet (~4
            # retention intervals with nothing new — the reclaimable
            # prefix is cut incrementally, so breaking on the first
            # commit would race the rest), then one GC grace beat.
            if retention:
                # Progress target first: rawdeltas reclaims up to the
                # deli's checkpoint (= the head), so its base reaching
                # head - keep_tail (frame-granular slack) proves the
                # plane worked through THIS cycle — a restarted child
                # mid-cycle just makes the wait longer, not the sample
                # wrong.
                # (margin: keep_tail spare + frame granularity + the
                # min-reclaim-bytes hysteresis, in records)
                target = max(0, sum(head.values()) - 2 * keep_tail - 256)
                wait_until = time.time() + 60.0
                while time.time() < wait_until:
                    pump(0.01)
                    if raw.base_offsets()[0] >= target:
                        break
                # Then commit quiescence: the reclaimable prefix cuts
                # incrementally, so sample only once the commit stream
                # goes quiet. Activity is RECORDS EVER APPENDED
                # (base + visible) — the retention topic prunes its
                # own commit history, so a visible-commit count can
                # DROP below a prior cycle's and freeze the fast
                # break; records-ever-appended is monotone under
                # self-pruning.
                last_n = -1
                stable_t = time.time()
                wait_until = time.time() + 45.0
                while time.time() < wait_until:
                    pump(0.01)
                    recs = retention_topic.read_from(0)
                    n = retention_topic.base_offsets()[0] + len(recs)
                    # Visible commits only bound the stat from below
                    # after a self-prune; the newest commit per topic
                    # always survives, so the gate stays nonzero.
                    truncs_seen = max(truncs_seen, sum(
                        1 for r in recs
                        if isinstance(r, dict)
                        and r.get("kind") == "truncate"
                    ))
                    if n != last_n:
                        last_n, stable_t = n, time.time()
                    elif time.time() - stable_t >= 1.0 and \
                            n > activity_seen:
                        break
                    elif time.time() - stable_t >= 6.0:
                        break  # nothing reclaimable this cycle
                activity_seen = max(activity_seen, last_n)
                time.sleep(1.2)  # one GC grace beat
                pump()
            usage.append(disk_usage(scratch)["total_bytes"])
        # ------------------------------------------------ final gates
        # Swarm completeness: every subscribed session saw every op of
        # its doc (subscriptions predate the first record).
        for i in range(swarm_sessions):
            # Joins/leaves sequence as kind=="op" records too, so each
            # session's complete view is its doc's HEAD count.
            want = head[swarm_docs[i]]
            got = swarm_counts[i]
            lim = time.time() + 30.0
            while got < want and time.time() < lim:
                pump(0.01)
                got = swarm_counts[i]
            assert got == want, (
                f"swarm session {i} ({swarm_docs[i]}): {got}/{want} "
                f"records delivered"
            )
        # Sequence integrity + tri-view bit-identity per doc.
        dups, skips = sequence_integrity(
            [r for d in docs for r in live[d]]
        )
        assert dups == 0 and skips == 0, f"dups={dups} skips={skips}"
        store = open_summary_store(scratch)
        digests: Dict[str, str] = {}
        for d in docs:
            cu = read_catchup(scratch, d, log_format, store=store)
            assert cu["manifest"] is not None, f"no summary for {d}"
            boot = SummaryReplica(cu["blob"])
            boot.apply_records(cu["ops"])
            live_rep = SummaryReplica(None)
            live_rep.apply_records(live[d])
            assert boot.state_digest() == live_rep.state_digest(), (
                f"cold-from-summary boot diverged from the live "
                f"client on {d}"
            )
            digests[d] = boot.state_digest()
        # The long-offline reconnector: its gap is (partially)
        # reclaimed, so the farm MUST answer with a summary reboot —
        # newest manifest past its last seen seq — not a gap replay.
        recon = srv.catchup("hotdoc", from_seq=reconnect_seen)
        assert recon["rebase"] and recon["blob"] is not None, (
            "long-offline reconnect did not reboot from a summary"
        )
        assert recon["manifest"]["seq"] > reconnect_seen
        rboot = SummaryReplica(recon["blob"])
        rboot.apply_records(recon["ops"])
        assert rboot.state_digest() == digests["hotdoc"], (
            "reconnector diverged after summary reboot"
        )
        # Bounded disk: the high-water mark stops growing after the
        # first retention cycle.
        result: Dict[str, Any] = {
            "scenario": "week_of_traffic",
            "open_loop": True,
            "cycles": cycles,
            "records": sum(head.values()),
            "hot_writers_per_cycle": hot_writers,
            "swarm_sessions": swarm_sessions,
            "stampede_sessions": stampede_sessions,
            "retention": retention,
            "disk_bytes_per_cycle": usage,
            "truncations": truncs_seen,
            "digest": hashlib.sha256(json.dumps(
                sorted(digests.items())).encode()).hexdigest(),
            "gate": ("disk hwm bounded after first retention cycle; "
                     "live == cold-from-summary == reconnector "
                     "bit-identical; swarm complete; zero dup/skip"),
        }
        if retention:
            assert truncs_seen > 0, "retention never truncated"
            hwm = max(usage[1:])
            result["retention_disk_mb"] = round(hwm / 1e6, 3)
            result["unit"] = "MB"
            assert max(usage[2:]) <= \
                hwm_slack * usage[1], (
                    f"disk high-water mark kept growing after the "
                    f"first retention cycle: {usage} "
                    f"(slack {hwm_slack})"
                )
        else:
            result["disk_mb_unbounded"] = round(max(usage) / 1e6, 3)
        return result
    finally:
        if srv is not None:
            srv.stop()
        if sup is not None:
            sup.stop()
        if work_dir is None:
            shutil.rmtree(scratch, ignore_errors=True)
