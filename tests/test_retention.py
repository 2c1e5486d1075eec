"""Retention plane: fenced op-log truncation + castore GC.

Covers the ISSUE-14 tentpole surfaces:

- `ColumnarFileTopic.truncate_prefix` — logical offsets stable across
  physical reclaim, idempotence, append/tail-reader survival;
- `columnar_log.tail_records_reverse` edge cases (empty log,
  single-frame log, truncated-prefix log, a stop_at seek landing
  exactly on a frame boundary);
- `RetentionRole` — coverage/consumer/producer clamps, the
  commit-then-reclaim ordering with roll-forward recovery, and the
  mark-and-sweep GC (roots, grace, epoch pins, re-put recreation);
- manifest ``byteOff`` + summary-aware reconnect
  (`FarmReadServer.catchup` rebase semantics);
- the chaos gate: kill-mid-truncate / kill-mid-GC converge
  bit-identical with zero dup/skip (marked chaos).
"""

import json
import os
import time

import pytest

from fluidframework_tpu.server.columnar_log import (
    ColumnarFileTopic,
    ColumnarTailReader,
    make_topic,
    tail_records_reverse,
)
from fluidframework_tpu.server.castore import ContentAddressedStore
from fluidframework_tpu.server.retention import (
    PIN_TTL_S,
    RetentionRole,
    clear_pin,
    disk_usage,
    live_pin_floor,
    write_pin,
)
from fluidframework_tpu.server.summarizer import (
    SummarizerRole,
    SummaryReplica,
    open_summary_store,
    read_catchup,
)
from fluidframework_tpu.server.supervisor import DeliRole, ScribeRole


def _op(doc, i, client=1):
    return {"kind": "op", "doc": doc, "seq": i + 1, "msn": 0,
            "client": client, "clientSeq": i + 1, "refSeq": 0,
            "type": "op", "contents": {"i": i}, "inOff": i}


def _fill(topic, n=12, per_frame=3, doc="d0"):
    recs = [_op(doc, i) for i in range(n)]
    for lo in range(0, n, per_frame):
        topic.append_many(recs[lo:lo + per_frame], fence=1, owner="w")
    return recs


# ---------------------------------------------------------------------------
# truncate_prefix
# ---------------------------------------------------------------------------


class TestTruncatePrefix:
    def test_cut_lands_on_frame_boundary_offsets_stable(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=12, per_frame=3)
        # Requested 7: the greatest frame boundary <= 7 is 6.
        assert t.truncate_prefix(7) == t.base_offsets()
        assert t.base_offsets()[0] == 6
        entries, nxt = t.read_entries(0)
        assert [i for i, _ in entries] == list(range(6, 12))
        assert nxt == 12
        # Logical offsets survive a subsequent append.
        t.append_many([_op("d0", 12)], fence=1, owner="w")
        entries, nxt = t.read_entries(0)
        assert [i for i, _ in entries] == list(range(6, 13))

    def test_noop_and_idempotent(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=9, per_frame=3)
        r1 = t.truncate_prefix(6)
        assert r1[0] == 6
        # Re-executing the same (or a lower) cut is a no-op: the base
        # only grows — the roll-forward idempotence contract.
        assert t.truncate_prefix(6) == r1
        assert t.truncate_prefix(3) == r1
        # dry_run plans without touching anything.
        plan = t.truncate_prefix(9, dry_run=True)
        assert plan[0] == 9 and t.base_offsets()[0] == 6

    def test_min_bytes_hysteresis(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=6, per_frame=3)
        big = 10 * os.path.getsize(t.path)
        assert t.truncate_prefix(3, min_bytes=big)[0] == 0
        assert t.truncate_prefix(3)[0] == 3

    def test_tail_reader_survives_concurrent_truncation(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=9, per_frame=3)
        r = ColumnarTailReader(t, 0)
        assert [i for i, _ in r.poll()] == list(range(9))
        t.append_many([_op("d0", 9), _op("d0", 10)], fence=1, owner="w")
        t.truncate_prefix(9)
        # The reader's logical position is PAST the cut: it sees only
        # the new records, none duplicated, none lost.
        assert [i for i, _ in r.poll()] == [9, 10]

    def test_cold_reader_jumps_to_base(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=9, per_frame=3)
        t.truncate_prefix(6)
        r = ColumnarTailReader(t, 0)
        assert [i for i, _ in r.poll()] == [6, 7, 8]
        assert r.next_line == 9

    def test_fence_gate_untouched(self, tmp_path):
        from fluidframework_tpu.server.queue import FencedError

        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=6, per_frame=3)
        t.truncate_prefix(3)
        # Truncation binds no fence: the writer's fence still stands,
        # and a stale fence is still rejected.
        with pytest.raises(FencedError):
            t.append_many([_op("d0", 6)], fence=0, owner="zombie")
        t.append_many([_op("d0", 6)], fence=1, owner="w")


# ---------------------------------------------------------------------------
# tail_records_reverse edge cases (ISSUE 14 satellite)
# ---------------------------------------------------------------------------


class TestReverseTailEdges:
    def test_empty_log(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        # No sidecar yet: the scan cannot anchor -> None (caller falls
        # forward, which yields nothing).
        assert tail_records_reverse(t, "d0", 0, None) is None
        t.append_many([], fence=1, owner="w")
        got = tail_records_reverse(t, "d0", 0, None)
        assert got == [] or got is None

    def test_single_frame_log(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        t.append_many([_op("d0", i) for i in range(4)],
                      fence=1, owner="w")
        got = tail_records_reverse(t, "d0", 0, None)
        assert [r["seq"] for r in got] == [1, 2, 3, 4]
        assert tail_records_reverse(t, "d0", 4, None) == []
        # Bounded above.
        assert [r["seq"] for r in
                tail_records_reverse(t, "d0", 1, 3)] == [2, 3]

    def test_truncated_prefix_log(self, tmp_path):
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        _fill(t, n=12, per_frame=3)
        t.truncate_prefix(6)
        got = tail_records_reverse(t, "d0", 6, None)
        assert [r["seq"] for r in got] == list(range(7, 13))
        # A base below the truncation point still answers correctly —
        # the surviving suffix holds every record above it, and the
        # walk floors at the truncation header.
        got = tail_records_reverse(t, "d0", 0, None)
        assert [r["seq"] for r in got] == list(range(7, 13))

    def test_stop_at_exactly_on_frame_boundary(self, tmp_path):
        # Semantics at a boundary-aligned stop: frames strictly above
        # the boundary are collected, the frame ENDING at it is not
        # descended past.
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        recs = [_op("d0", i) for i in range(9)]
        t.append_many(recs[:3], fence=1, owner="w")
        boundary = os.path.getsize(t.path)  # logical == physical here
        t.append_many(recs[3:6], fence=1, owner="w")
        t.append_many(recs[6:9], fence=1, owner="w")
        got = tail_records_reverse(t, "d0", 3, None, stop_at=boundary)
        assert [r["seq"] for r in got] == [4, 5, 6, 7, 8, 9]

    def test_stop_at_bounds_scan_bytes(self, tmp_path):
        # The O(tail) evidence: on a file much larger than the read
        # block, a stop_at near the end keeps the scan to the tail
        # region instead of the whole log.
        t = ColumnarFileTopic(str(tmp_path / "t.jsonl"))
        pad = "x" * 2000
        boundary = None
        base_seq = 0
        for i in range(200):
            rec = _op("d0", i)
            rec["contents"] = {"i": i, "pad": pad}
            t.append_many([rec], fence=1, owner="w")
            if i == 179:
                boundary = os.path.getsize(t.path)
                base_seq = i + 1
        from fluidframework_tpu.utils import metrics as M

        reg = M.MetricsRegistry()
        prev = M.set_registry(reg)
        try:
            got = tail_records_reverse(t, "d0", base_seq, None,
                                       stop_at=boundary)
        finally:
            M.set_registry(prev)
        assert [r["seq"] for r in got] == list(range(base_seq + 1, 201))
        scanned = sum(
            c["value"] for c in reg.snapshot()["counters"]
            if c["name"] == "catchup_tail_scan_bytes_total"
        )
        assert 0 < scanned < os.path.getsize(t.path) / 2


# ---------------------------------------------------------------------------
# the role: clamps, commit/roll-forward, GC
# ---------------------------------------------------------------------------


def _mini_farm(tmp_path, consumers=("scribe", "summarizer"),
               summary_ops=16, **ret_kw):
    d = str(tmp_path)
    os.makedirs(os.path.join(d, "topics"), exist_ok=True)
    fmt = "columnar"
    raw = make_topic(os.path.join(d, "topics", "rawdeltas.jsonl"), fmt)
    deli = DeliRole(d, "deli-1", ttl_s=3600.0, log_format=fmt,
                    ckpt_interval_s=0.0)
    summ = SummarizerRole(d, "summ-1", ttl_s=3600.0, log_format=fmt,
                          summary_ops=summary_ops, ckpt_interval_s=0.0)
    scribe = ScribeRole(d, "scribe-1", ttl_s=3600.0, log_format=fmt,
                        ckpt_interval_s=0.0)
    kw = dict(consumers=consumers, interval_s=0.0, gc_interval_s=1e9,
              min_reclaim_bytes=1, keep_tail=4, gc_grace_s=0.0)
    kw.update(ret_kw)
    ret = RetentionRole(d, "ret-1", ttl_s=3600.0, log_format=fmt, **kw)
    return d, raw, deli, summ, scribe, ret


def _feed_cycle(raw, n_ops=120, n_clients=2, doc="doc0", chunk=20):
    recs = [{"kind": "join", "doc": doc, "client": c}
            for c in range(1, n_clients + 1)]
    recs += [{"kind": "op", "doc": doc, "client": 1 + (i % n_clients),
              "clientSeq": i // n_clients + 1, "refSeq": 0,
              "contents": {"i": i}} for i in range(n_ops)]
    chunks = [recs[lo:lo + chunk] for lo in range(0, len(recs), chunk)]
    for ch in chunks:
        raw.append_many(ch)
        yield


class TestRetentionRole:
    def test_truncates_behind_summaries_and_consumers(self, tmp_path):
        d, raw, deli, summ, scribe, ret = _mini_farm(tmp_path)
        for _ in _feed_cycle(raw):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        for _ in range(4):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        deltas = make_topic(os.path.join(d, "topics", "deltas.jsonl"),
                            "columnar")
        assert deltas.base_offsets()[0] > 0
        assert raw.base_offsets()[0] > 0
        rt = make_topic(os.path.join(d, "topics", "retention.jsonl"),
                        "columnar")
        commits = [r for _, r in rt.read_entries(0)[0]
                   if isinstance(r, dict) and r.get("kind") == "truncate"]
        assert commits
        # Every commit was rolled fully forward (base >= newest cut).
        newest = max(int(r["records"]) for r in commits
                     if r["topic"] == "deltas")
        assert deltas.base_offsets()[0] >= newest
        # Catch-up over the truncated log still boots exactly.
        store = open_summary_store(d)
        cu = read_catchup(d, "doc0", "columnar", store=store)
        assert cu["manifest"] is not None
        assert isinstance(cu["manifest"].get("byteOff"), int)
        # The floor is scoped to the byte space it was stamped in —
        # a reader scanning a DIFFERENT topic (elastic pred-era
        # manifest through the merged index) must not use it.
        assert cu["manifest"].get("byteTopic") == "deltas"
        boot = SummaryReplica(cu["blob"])
        boot.apply_records(cu["ops"])
        assert boot.seq == 122  # 2 joins + 120 ops, nothing lost

    def test_lagging_consumer_blocks_truncation(self, tmp_path):
        # A consumer key with NO checkpoint reads as offset 0: the
        # conservative clamp — a tracked consumer must never find its
        # input truncated.
        d, raw, deli, summ, scribe, ret = _mini_farm(
            tmp_path, consumers=("scribe", "summarizer", "broadcaster")
        )
        for _ in _feed_cycle(raw):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        for _ in range(3):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        deltas = make_topic(os.path.join(d, "topics", "deltas.jsonl"),
                            "columnar")
        assert deltas.base_offsets()[0] == 0  # blocked by "broadcaster"
        assert raw.base_offsets()[0] > 0  # rawdeltas clamps on deli only

    def test_producer_floor_keeps_recovery_window(self, tmp_path):
        d, raw, deli, summ, scribe, ret = _mini_farm(tmp_path)
        for _ in _feed_cycle(raw):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        for _ in range(3):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        deltas = make_topic(os.path.join(d, "topics", "deltas.jsonl"),
                            "columnar")
        base = deltas.base_offsets()[0]
        assert base > 0
        # Every surviving record with an inOff below the deli's
        # checkpointed offset is fine; none at/past it were reclaimed
        # (they are the deli's exactly-once recovery scan window).
        deli_off = ret._ckpt_offset("deli")
        entries, _ = deltas.read_entries(0)
        in_offs = [r.get("inOff", -1) for _, r in entries
                   if isinstance(r, dict)]
        # The whole recovery window survives: every inOff >= deli_off
        # that was ever emitted is still present (here the stream is
        # fully checkpointed, so just sanity-check the clamp held).
        assert all(isinstance(i, int) for i in in_offs)
        assert ret._producer_floor("deltas") == deli_off

    def test_commit_without_reclaim_rolls_forward(self, tmp_path):
        """Torn truncate: the fenced commit record lands, the process
        dies before the physical cut — recovery must roll it
        forward."""
        d, raw, deli, summ, scribe, ret = _mini_farm(
            tmp_path, interval_s=1e9,  # the role itself never reclaims
        )
        ret._retain_t = ret._gc_t = time.time()  # arm the interval
        for _ in _feed_cycle(raw):
            for r in (deli, summ, scribe):
                r.step(idle_sleep=0)
        for _ in range(3):
            for r in (deli, summ, scribe):
                r.step(idle_sleep=0)
        # Drive retention's INPUT fold only, then hand-commit a cut
        # without executing it (the crash window).
        while ret.step(idle_sleep=0) > 0:
            pass
        deltas = make_topic(os.path.join(d, "topics", "deltas.jsonl"),
                            "columnar")
        plan = deltas.truncate_prefix(40, dry_run=True)
        assert plan[0] > 0
        ret.out_topic.append_many(
            [{"kind": "truncate", "topic": "deltas",
              "records": plan[0], "bytes": plan[1]}],
            fence=ret.fence, owner=ret.owner,
        )
        assert deltas.base_offsets()[0] == 0  # not executed yet
        # A fresh incarnation recovers: the committed cut executes.
        ret2 = RetentionRole(d, "ret-2", ttl_s=3600.0,
                             log_format="columnar",
                             consumers=("scribe", "summarizer"),
                             interval_s=1e9, gc_interval_s=1e9)
        ret.leases.release("retention")
        ret2.step(idle_sleep=0)
        assert ret2.fence is not None
        assert deltas.base_offsets()[0] >= plan[0]

    def test_gc_sweeps_unreferenced_keeps_roots_and_pins(self, tmp_path):
        d, raw, deli, summ, scribe, ret = _mini_farm(
            tmp_path, gc_interval_s=0.0, keep_summaries=1
        )
        for _ in _feed_cycle(raw, n_ops=160):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        for _ in range(4):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        store = ret._store
        assert store is not None
        blobs = {k for k, *_ in store.list_blobs()}
        # Exactly the newest manifest's handle per doc survives.
        roots = {hs[-1][1] for hs in ret.handles.values()}
        assert roots <= blobs
        rt = make_topic(os.path.join(d, "topics", "retention.jsonl"),
                        "columnar")
        gc_recs = [r for _, r in rt.read_entries(0)[0]
                   if isinstance(r, dict) and r.get("kind") == "gc"]
        assert gc_recs and sum(r["deleted"] for r in gc_recs) > 0
        # A deleted handle is recreated by a content-addressed re-put
        # (the recovery-safety property pin expiry rests on).
        payload = b'{"probe": 1}'
        h = store.put(payload)
        assert store.get(h) == payload
        store.delete_blob(h)
        h2 = store.put(payload)
        assert h2 == h and store.get(h) == payload

    def test_gc_honors_prepoll_pin_floor_after_unpin(self, tmp_path):
        # The unpin-after-poll race: a summarizer round's (manifest
        # append + unpin) can land BETWEEN the retention step's
        # summaries poll and the sweep — the manifest is durable but
        # unread (not a root), and a post-poll pin read would see no
        # pin and delete the round's blobs permanently. `step`
        # therefore captures the pin floor BEFORE its poll and the
        # sweep must honor that pre-poll floor even though the pin
        # file is gone by sweep time.
        d, raw, deli, summ, scribe, ret = _mini_farm(
            tmp_path, gc_interval_s=1e9, gc_grace_s=0.0
        )
        ret.step(idle_sleep=0)  # acquire the lease/fence
        store = ContentAddressedStore(
            prefer_native=False, directory=os.path.join(d, "store"))
        t0 = write_pin(d, "summarizer")
        h = store.put(b'{"round": "in-flight"}')  # mtime >= t0
        clear_pin(d, "summarizer")  # round ended after our "poll"
        ret._gc_pass(pin_floor=t0)
        # Fresh instances: the putter's in-memory cache would mask a
        # deleted file.
        fresh = ContentAddressedStore(
            prefer_native=False, directory=os.path.join(d, "store"))
        assert fresh.contains(h), \
            "pre-poll pin floor must protect the round's blobs"
        # Without the captured floor (the old post-poll read: no live
        # pins left) the same blob is swept — the floor is the only
        # thing protecting it.
        ret._gc_pass()
        fresh = ContentAddressedStore(
            prefer_native=False, directory=os.path.join(d, "store"))
        assert not fresh.contains(h)

    def test_catchup_below_retention_horizon_is_loud(self, tmp_path):
        # A seq-bounded catch-up can resolve an OLDER manifest that
        # is still discoverable (a quiet doc holds the manifest-topic
        # cut back) but whose blob the GC swept (only the newest
        # keep_summaries are roots). With the covered op prefix also
        # truncated, the historical state is unrecoverable — the read
        # must refuse loudly, never silently return partial state
        # from a replay that resumes at the truncation base.
        d, raw, deli, summ, scribe, ret = _mini_farm(tmp_path)
        for _ in _feed_cycle(raw):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        for _ in range(4):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        deltas = make_topic(os.path.join(d, "topics", "deltas.jsonl"),
                            "columnar")
        assert deltas.base_offsets()[0] > 0
        st = make_topic(os.path.join(d, "topics", "summaries.jsonl"),
                        "columnar")
        mans = [r for _, r in st.read_entries(0)[0]
                if isinstance(r, dict) and r.get("kind") == "summary"]
        assert len(mans) >= 2
        store = ContentAddressedStore(
            prefer_native=False, directory=os.path.join(d, "store"))
        old = mans[0]
        store.delete_blob(old["handle"])  # what the sweep does
        with pytest.raises(LookupError, match="retention horizon"):
            read_catchup(d, "doc0", "columnar", seq=int(old["seq"]))
        # The UNBOUNDED read still answers from the newest manifest.
        cu = read_catchup(d, "doc0", "columnar")
        assert cu["manifest"] is not None and cu["blob"] is not None

    def test_catchup_swept_blob_intact_log_full_replay(self, tmp_path):
        # Same sweep, but the op log was never truncated (base 0):
        # the full-replay fallback is complete and correct, so the
        # read answers instead of raising.
        d, raw, deli, summ, scribe, _ret = _mini_farm(tmp_path)
        for _ in _feed_cycle(raw, n_ops=60):
            for r in (deli, summ, scribe):
                r.step(idle_sleep=0)
        for _ in range(3):
            for r in (deli, summ, scribe):
                r.step(idle_sleep=0)
        st = make_topic(os.path.join(d, "topics", "summaries.jsonl"),
                        "columnar")
        mans = [r for _, r in st.read_entries(0)[0]
                if isinstance(r, dict) and r.get("kind") == "summary"]
        assert len(mans) >= 2
        store = ContentAddressedStore(
            prefer_native=False, directory=os.path.join(d, "store"))
        old = mans[0]
        store.delete_blob(old["handle"])
        cu = read_catchup(d, "doc0", "columnar", seq=int(old["seq"]))
        assert cu["manifest"] is None and cu["blob"] is None
        # Complete tail from the log's (intact) start — joins
        # sequence as records too, so seqs run 1..old_seq.
        assert [int(r["seq"]) for r in cu["ops"]] == \
            list(range(1, int(old["seq"]) + 1))

    def test_pin_floor_protects_inflight_blobs(self, tmp_path):
        d = str(tmp_path)
        assert live_pin_floor(d) is None
        write_pin(d, "summarizer")
        floor = live_pin_floor(d)
        assert floor is not None and floor <= time.time()
        clear_pin(d, "summarizer")
        assert live_pin_floor(d) is None

    def test_pin_heartbeat_keeps_original_floor(self, tmp_path):
        # An emission round longer than PIN_TTL_S heartbeats the pin
        # by rewriting it with its ORIGINAL floor: liveness is the
        # file mtime, the floor is the recorded t — so blobs put
        # early in the round stay covered while dead-writer expiry
        # (stale mtime) still works.
        d = str(tmp_path)
        t0 = write_pin(d, "summarizer")
        pin_path = os.path.join(d, "store", "pins", "summarizer.json")
        stale = time.time() - (PIN_TTL_S + 5.0)
        os.utime(pin_path, (stale, stale))
        assert live_pin_floor(d) is None  # stale heartbeat = dead writer
        assert write_pin(d, "summarizer", t0) == t0  # the heartbeat
        assert live_pin_floor(d) == t0  # floor preserved, liveness back
        clear_pin(d, "summarizer")

    def test_prune_handles_spares_recovery_window(self, tmp_path):
        # Manifests with inOff at/past the summarizer's checkpointed
        # input offset are inside its exactly-once recovery scan:
        # pruning must keep ALL of them (even past the keep-depth
        # cap) or `_summaries_cut` reclaims manifests a restart
        # re-emits, forking the summary stream.
        _, _, _, _, _, ret = _mini_farm(tmp_path, keep_summaries=1)
        ret.handles = {
            "d0": [[s, f"h{s}", s, s] for s in range(10)]
        }
        ret._producer_floor = lambda base: 4
        ret._prune_handles()
        assert [e[0] for e in ret.handles["d0"]] == list(range(4, 10))
        # No producer present: plain keep-depth bound applies.
        ret._producer_floor = lambda base: None
        ret._prune_handles()
        assert [e[0] for e in ret.handles["d0"]] == [8, 9]

    def test_delete_blob_spares_freshly_reput_blob(self, tmp_path):
        # The sweep's stat→unlink race: a blob re-put (mtime
        # refreshed) after the sweep's listing must survive the
        # delete — `older_than` re-checks freshness under the
        # quarantine rename.
        store = ContentAddressedStore(
            prefer_native=False, directory=str(tmp_path / "store"))
        h = store.put(b'{"gc": 1}')
        path = os.path.join(
            str(tmp_path / "store"), "objects", h[:2], h)
        bar = time.time() - 30.0
        assert store.delete_blob(h, older_than=bar) is False
        assert os.path.exists(path) and store.get(h) == b'{"gc": 1}'
        old = bar - 3600.0
        os.utime(path, (old, old))
        assert store.delete_blob(h, older_than=bar) is True
        assert not os.path.exists(path)

    def test_sweep_tmp_reclaims_dead_writer_staging(self, tmp_path):
        # A kill between a tmp write and its rename orphans the
        # staging file; nothing else removes it and disk_usage counts
        # it. The sweep is age-gated so a live writer's tmp survives.
        store = ContentAddressedStore(
            prefer_native=False, directory=str(tmp_path / "store"))
        h = store.put(b'{"keep": 1}')
        sdir = os.path.join(str(tmp_path / "store"), "objects", h[:2])
        stale = os.path.join(sdir, f"{h}.tmp.99999")
        fresh = os.path.join(sdir, f"{h}.tmp.gc88888")
        for p in (stale, fresh):
            with open(p, "wb") as f:
                f.write(b"x")
        old = time.time() - 3600.0
        os.utime(stale, (old, old))
        assert store.sweep_tmp() == 1
        assert not os.path.exists(stale)
        assert os.path.exists(fresh)  # young: could be in flight
        assert store.get(h) == b'{"keep": 1}'

    def test_truncate_sweeps_orphaned_trunc_tmp(self, tmp_path):
        # Same orphan class on the topic side: a kill between the
        # trunc tmp write and its rename. The flock serializes
        # truncators, so the next truncate call reclaims any sibling.
        t = make_topic(str(tmp_path / "t.jsonl"), "columnar")
        _fill(t, n=6, per_frame=3)
        orphan = str(tmp_path / "t.jsonl.trunc.tmp.99999")
        with open(orphan, "wb") as f:
            f.write(b"x" * 64)
        t.truncate_prefix(3)
        assert not os.path.exists(orphan)
        assert t.base_offsets()[0] == 3

    def test_dedup_reput_refreshes_blob_mtime(self, tmp_path):
        # The sweep's pin floor compares blob MTIMES: a deduplicated
        # re-put (file already on disk, backend skips the write) must
        # stamp the file fresh, or a recovery re-put of a
        # not-yet-referenced blob could be swept before its re-emitted
        # manifest lands.
        store = ContentAddressedStore(
            prefer_native=False, directory=str(tmp_path / "store"))
        h = store.put(b'{"reput": 1}')
        path = os.path.join(
            str(tmp_path / "store"), "objects", h[:2], h)
        old = time.time() - 3600.0
        os.utime(path, (old, old))
        assert store.put(b'{"reput": 1}') == h
        assert os.stat(path).st_mtime >= time.time() - 60.0

    def test_meta_pruning_bounds_manifests_and_commits(self, tmp_path):
        d, raw, deli, summ, scribe, ret = _mini_farm(
            tmp_path,
            topics=("deltas", "rawdeltas", "summaries", "retention"),
            keep_summaries=2, summary_ops=8,
        )
        for _ in _feed_cycle(raw, n_ops=200):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        for _ in range(4):
            for r in (deli, summ, scribe, ret):
                r.step(idle_sleep=0)
        summaries = make_topic(
            os.path.join(d, "topics", "summaries.jsonl"), "columnar"
        )
        assert summaries.base_offsets()[0] > 0
        # The surviving manifests still include the newest per doc —
        # catch-up discovery is intact.
        store = open_summary_store(d)
        cu = read_catchup(d, "doc0", "columnar", store=store)
        assert cu["manifest"] is not None
        boot = SummaryReplica(cu["blob"])
        boot.apply_records(cu["ops"])
        assert boot.seq == 202

    def test_requires_columnar(self, tmp_path):
        with pytest.raises(ValueError, match="columnar"):
            RetentionRole(str(tmp_path), "r1", log_format="json")

    def test_disk_usage_shape(self, tmp_path):
        u = disk_usage(str(tmp_path))
        assert set(u) == {"log_bytes", "castore_bytes", "total_bytes"}


# ---------------------------------------------------------------------------
# summary-aware reconnect
# ---------------------------------------------------------------------------


def test_farm_catchup_rebases_long_offline_sessions(tmp_path):
    from fluidframework_tpu.server.socket_service import FarmReadServer

    d, raw, deli, summ, scribe, ret = _mini_farm(tmp_path)
    for _ in _feed_cycle(raw):
        for r in (deli, summ, scribe, ret):
            r.step(idle_sleep=0)
    for _ in range(4):
        for r in (deli, summ, scribe, ret):
            r.step(idle_sleep=0)
    srv = FarmReadServer(d, log_format="columnar").start()
    try:
        full = srv.catchup("doc0")
        base = full["manifest"]["seq"]
        # Short gap (at/past the summary): op gap only, no blob — the
        # session keeps its state and applies the tail.
        short = srv.catchup("doc0", from_seq=base + 2)
        assert short["blob"] is None and not short["rebase"]
        assert all(int(r["seq"]) > base + 2 for r in short["ops"])
        # Long offline (below the summary; the op gap is partially
        # RECLAIMED): the session must reboot from the summary.
        long_off = srv.catchup("doc0", from_seq=1)
        assert long_off["rebase"] and long_off["blob"] is not None
        boot = SummaryReplica(long_off["blob"])
        boot.apply_records(long_off["ops"])
        cold = SummaryReplica(full["blob"])
        cold.apply_records(full["ops"])
        assert boot.state_digest() == cold.state_digest()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# chaos gate (kill-mid-truncate / kill-mid-GC)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_kill_mid_truncate_and_gc_converges():
    """ISSUE 14 acceptance: the retention role in the kill schedule
    plus the two seeded kill points (between the fenced truncate
    commit and the physical reclaim; mid-GC-sweep) — the farm must
    converge bit-identical with zero dup/skip, every committed cut
    rolled forward, and summaries still boot-equal to a cold replay
    off the untruncated durable leg."""
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    res = run_chaos(ChaosConfig(
        seed=14, faults=("kill",), n_docs=2, n_clients=3,
        ops_per_client=40, timeout_s=300.0, deli_impl="scalar",
        log_format="columnar", summarizer=True, summary_ops=16,
        retention=True,
    ))
    assert res.converged, res.detail
    assert res.retention_ok and res.truncations > 0
    assert res.retention_base_records > 0
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0
    assert res.summaries_ok
    # Both seeded kill points demonstrably fired (the role restarted
    # at least twice beyond any scheduled SIGKILL).
    assert res.restarts.get("retention", 0) >= 2


# ---------------------------------------------------------------------------
# the churn gate, scaled
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_week_of_traffic_churn_scaled():
    from fluidframework_tpu.testing.scenarios import run_week_of_traffic

    res = run_week_of_traffic(
        cycles=3, hot_writers=6, cold_docs=1, cold_clients=2,
        ops_per_writer=12, summary_ops=24, rate_hz=800.0,
        stampede_sessions=8, swarm_sessions=12, keep_tail=48,
        timeout_s=120.0,
    )
    assert res["retention"] and res["truncations"] > 0
    assert res["retention_disk_mb"] > 0
    usage = res["disk_bytes_per_cycle"]
    assert max(usage[2:]) <= 1.35 * usage[1]


# ---------------------------------------------------------------------------
# front-door retention (ISSUE 15 satellite): ingress + nacks topics
# ---------------------------------------------------------------------------


class TestFrontDoorRetention:
    def _feed_front_door(self, d, n_ops=60, bad_every=10):
        """Columnar front door: feed the ingress topic (auth off —
        no tenants.json), drain the admission role, return it."""
        from fluidframework_tpu.server.ingress import IngressRole

        os.makedirs(os.path.join(d, "topics"), exist_ok=True)
        ing_t = make_topic(os.path.join(d, "topics", "ingress.jsonl"),
                           "columnar")
        recs = []
        for i in range(n_ops):
            if bad_every and i % bad_every == bad_every - 1:
                # Oversized record -> a nack on the nacks topic.
                recs.append({"kind": "op", "doc": "d0", "client": 1,
                             "clientSeq": i + 1, "refSeq": 0,
                             "contents": {"x": "z" * 300000}})
            else:
                recs.append({"kind": "op", "doc": "d0", "client": 1,
                             "clientSeq": i + 1, "refSeq": 0,
                             "contents": {"i": i}})
        # Feed + pump per chunk so admissions/nacks land across many
        # frames (a realistic steady state — frame boundaries are what
        # the truncate cut can land on).
        ing = IngressRole(d, "ing-1", ttl_s=3600.0,
                          log_format="columnar", ckpt_interval_s=0.0)
        for lo in range(0, len(recs), 8):
            ing_t.append_many(recs[lo:lo + 8], fence=1, owner="feeder")
            while ing.step(idle_sleep=0) > 0:
                pass
        ing.checkpoint()
        return ing, ing_t

    def test_ingress_and_nacks_truncate_behind_admission(self, tmp_path):
        """PR 14 follow-up: with the front door's topics managed, the
        `ingress` prefix reclaims behind the ADMISSION role's own
        input checkpoint (its consumer floor) and `nacks` behind its
        producer recovery window — both commit-then-reclaim fenced."""
        d = str(tmp_path)
        ing, ing_t = self._feed_front_door(d)
        nacks_t = make_topic(os.path.join(d, "topics", "nacks.jsonl"),
                             "columnar")
        assert ing_t.base_offsets()[0] == 0
        n_nacks = sum(1 for r in nacks_t.read_from(0)
                      if isinstance(r, dict))
        assert n_nacks > 0
        ret = RetentionRole(
            d, "ret-1", ttl_s=3600.0, log_format="columnar",
            topics=("ingress", "nacks"), consumers=(),
            interval_s=0.0, gc_interval_s=1e9, min_reclaim_bytes=1,
            keep_tail=4,
        )
        ret.step(idle_sleep=0)
        ret._retain_pass()
        # Ingress prefix reclaimed up to (checkpoint - keep_tail).
        base_r, _ = ing_t.base_offsets()
        assert base_r > 0
        assert base_r <= ing.offset - 0  # never past the admission ckpt
        # Nacks reclaimed too, behind the producer recovery window.
        nbase, _ = nacks_t.base_offsets()
        assert nbase > 0
        commits = [r for r in ret.out_topic.read_entries(0)[0]
                   if isinstance(r[1], dict)
                   and r[1].get("kind") == "truncate"]
        assert {c[1]["topic"] for c in commits} == {"ingress", "nacks"}

    def test_exactly_once_across_ingress_truncate(self, tmp_path):
        """The gate the satellite names: truncate the ingress topic
        behind the admission checkpoint, RESTART the front door with
        no fresh checkpoint write, and every admission/nack decision
        lands exactly once — the recovery scan never needs the
        reclaimed prefix, and logical offsets survive the cut."""
        from fluidframework_tpu.server.ingress import IngressRole

        d = str(tmp_path)
        ing, ing_t = self._feed_front_door(d)
        raw_t = make_topic(
            os.path.join(d, "topics", "rawdeltas.jsonl"), "columnar"
        )
        admitted0 = [r for r in raw_t.read_from(0)
                     if isinstance(r, dict)]
        assert admitted0
        ret = RetentionRole(
            d, "ret-1", ttl_s=3600.0, log_format="columnar",
            topics=("ingress", "nacks"), consumers=(),
            interval_s=0.0, gc_interval_s=1e9, min_reclaim_bytes=1,
            keep_tail=2,
        )
        ret.step(idle_sleep=0)
        ret._retain_pass()
        assert ing_t.base_offsets()[0] > 0
        # Feed a tail past the cut, then restart the admission role
        # WITHOUT the first instance checkpointing its latest work —
        # the successor's exactly-once scan replays the gap silently.
        more = [{"kind": "op", "doc": "d0", "client": 1,
                 "clientSeq": 1000 + i, "refSeq": 0,
                 "contents": {"tail": i}} for i in range(6)]
        ing_t.append_many(more, fence=1, owner="feeder")
        ing.leases.release("ingress")
        ing2 = IngressRole(d, "ing-2", ttl_s=3600.0,
                           log_format="columnar", ckpt_interval_s=0.0)
        while ing2.step(idle_sleep=0) > 0:
            pass
        admitted = [r for r in raw_t.read_from(0)
                    if isinstance(r, dict)]
        in_offs = [r.get("inOff") for r in admitted]
        assert len(set(in_offs)) == len(in_offs), "duplicate admission"
        tail = [r for r in admitted
                if isinstance(r.get("contents"), dict)
                and "tail" in r["contents"]]
        assert len(tail) == 6, "tail admissions lost across the cut"
        # The pre-cut admissions are still exactly the original set.
        assert admitted[:len(admitted0)] == admitted0

    def test_supervisor_derives_front_door_topics(self, tmp_path):
        from fluidframework_tpu.server.supervisor import (
            ServiceSupervisor,
        )

        sup = ServiceSupervisor(
            str(tmp_path), log_format="columnar", ingress=True,
            retention=True,
        )
        assert sup.child_env["FLUID_RETENTION_TOPICS"] == \
            "deltas,rawdeltas,ingress,nacks"
        sup2 = ServiceSupervisor(
            str(tmp_path / "b"), log_format="columnar", retention=True,
        )
        assert "FLUID_RETENTION_TOPICS" not in sup2.child_env
