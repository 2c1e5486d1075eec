"""Columnar emission end-to-end (ISSUE 11): record-batch codec v2
(nested boxcar blobs), the pre-columnized emit path
(`ColumnarRecords` / `encode_columns` / the kernel deli's verdict →
column emission), and the fused durable+broadcast hop
(`ScriptoriumBroadcasterRole`) — plus the columnar backward tail scan
summary catch-up rides."""

from __future__ import annotations

import json
import os
import random

import numpy as np
import pytest

from fluidframework_tpu.protocol import record_batch as rb
from fluidframework_tpu.server.columnar_log import (
    ColumnarFileTopic,
    make_topic,
    tail_records_reverse,
)
from fluidframework_tpu.server.deli_kernel import KernelDeliRole
from fluidframework_tpu.server.supervisor import (
    FUSED_PIPELINE_ROLES,
    BroadcasterRole,
    DeliRole,
    ScriptoriumBroadcasterRole,
    ScriptoriumRole,
    fused_roles,
)
from fluidframework_tpu.utils import metrics as M


# ---------------------------------------------------------------------------
# codec v2
# ---------------------------------------------------------------------------


def _random_records(rng: random.Random, n: int):
    recs = []
    for i in range(n):
        r = rng.random()
        doc = f"doc{rng.randrange(4)}"
        if r < 0.35:
            recs.append({"kind": "op", "doc": doc,
                         "client": rng.randrange(5),
                         "clientSeq": i, "refSeq": 0,
                         "contents": {"i": i, "s": "x" * rng.randrange(6)}})
        elif r < 0.55:
            ops = [{"clientSeq": i + k, "refSeq": 0,
                    "contents": [i, k, {"nested": True}]}
                   for k in range(rng.randrange(0, 4))]
            recs.append({"kind": "boxcar", "doc": doc,
                         "client": rng.randrange(5), "ops": ops})
        elif r < 0.7:
            recs.append({"kind": "op", "doc": doc, "seq": i + 1,
                         "msn": i // 2, "client": 1, "clientSeq": i,
                         "refSeq": 0, "type": "op", "contents": None,
                         "inOff": i})
        elif r < 0.8:
            recs.append({"kind": "nack", "doc": doc, "client": 2,
                         "clientSeq": i, "code": 422,
                         "reason": "out of order", "inOff": i})
        elif r < 0.9:
            recs.append({"kind": rng.choice(["join", "leave"]),
                         "doc": doc, "client": rng.randrange(5)})
        else:
            recs.append({"arbitrary": [i, None, {"deep": "value"}]})
    return recs


@pytest.mark.parametrize("version", [1, 2])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_v2_roundtrip_property(version, seed):
    """Both frame revs round-trip arbitrary streams to identical plain
    values — nested/empty boxcars included — and stamp their version
    byte per frame."""
    rng = random.Random(seed)
    recs = _random_records(rng, 120)
    frame = rb.encode_batch(recs, fence=9, owner="t", version=version)
    batch, end, n = rb.decode_batch(frame)
    assert (n, end) == (len(recs), len(frame))
    assert batch.version == version
    assert batch.records() == recs


def test_v2_boxcar_nested_offsets_pass_through():
    """A v2 boxcar's per-op ints read as values and its contents slice
    out as RAW blob handles (no once-per-boxcar JSON decode); v1 keeps
    the decoded-values contract."""
    box = {"kind": "boxcar", "doc": "d", "client": 3, "ops": [
        {"clientSeq": 5, "refSeq": 1, "contents": {"a": [1, 2]}},
        {"clientSeq": 6, "refSeq": 1, "contents": "text"},
    ]}
    b2, _, _ = rb.decode_batch(rb.encode_batch([box], version=2))
    ops = b2.boxcar(0)
    assert [(c, r) for c, r, _ in ops] == [(5, 1), (6, 1)]
    assert all(isinstance(v, rb.JsonBlob) for _, _, v in ops)
    assert ops[0][2].raw == b'{"a":[1,2]}'  # raw bytes, untouched
    b1, _, _ = rb.decode_batch(rb.encode_batch([box], version=1))
    assert [v for _, _, v in b1.boxcar(0)] == [{"a": [1, 2]}, "text"]
    # decoded record form is version-independent
    assert b1.record(0) == b2.record(0) == box


def test_v1_v2_mixed_stream_one_file(tmp_path):
    """v1 and v2 frames (and JSON lines) coexist in one topic file —
    the no-migration upgrade path: offsets stable, records identical.
    The v1 frames are written raw (a v1-era file's on-disk form)."""
    rng = random.Random(3)
    recs = _random_records(rng, 90)
    path = str(tmp_path / "mixed.jsonl")
    t = ColumnarFileTopic(path)
    with open(path, "ab") as f:  # the v1-era prefix
        f.write(rb.encode_batch(recs[:30], version=1))
    t.append_many(recs[30:60])  # current writer: v2 frames
    with open(path, "ab") as f:
        f.write(json.dumps(recs[60]).encode() + b"\n")
    t.append_many(recs[61:])
    entries, nxt = t.read_entries(0)
    assert nxt == len(recs)
    assert [v for _, v in entries] == recs


def test_v2_crc_corruption_skips_but_counts(tmp_path):
    """CRC/torn rules hold on v2 frames: a corrupt frame skips whole
    but keeps its record slots; a torn v2 tail is invisible until
    complete."""
    path = str(tmp_path / "t.jsonl")
    t = ColumnarFileTopic(path)
    recs = _random_records(random.Random(4), 40)
    t.append_many(recs[:20])
    size_1 = os.path.getsize(path)
    t.append_many(recs[20:])
    # flip a payload byte inside the SECOND frame
    with open(path, "r+b") as f:
        f.seek(size_1 + rb.HEADER.size + 10)
        b0 = f.read(1)
        f.seek(size_1 + rb.HEADER.size + 10)
        f.write(bytes([b0[0] ^ 0xFF]))
    entries, nxt = t.read_entries(0)
    assert [v for _, v in entries] == recs[:20]
    assert nxt == len(recs)  # skipped frame still counts its slots
    # torn tail: append a clipped v2 frame; readers must not consume it
    frame = rb.encode_batch(recs[:5], version=2)
    with open(path, "ab") as f:
        f.write(frame[:len(frame) // 2])
    entries2, nxt2 = t.read_entries(0)
    assert nxt2 == nxt and [v for _, v in entries2] == recs[:20]


def test_classify_hoist_matches_per_record_classification():
    """The homogeneous-run hoist must classify EXACTLY like per-record
    `_classify` — including runs broken by value-level failures (a
    non-i64 client mid-run) — and produce byte-stable frames."""
    rng = random.Random(7)
    recs = _random_records(rng, 400)
    # adversarial same-key-set value breaks inside runs
    for i in range(0, 390, 13):
        bad = dict(recs[i])
        if bad.get("kind") == "op" and "clientSeq" in bad \
                and "seq" not in bad:
            bad["client"] = 1 << 70  # same keys, not i64 -> generic
            recs.insert(i + 1, bad)
    frame = rb.encode_batch(recs)
    batch, _, _ = rb.decode_batch(frame)
    assert batch.kind.tolist() == [rb._classify(r) for r in recs]
    assert batch.records() == recs
    assert rb.encode_batch(recs) == frame  # deterministic


# ---------------------------------------------------------------------------
# ColumnarRecords / encode_columns
# ---------------------------------------------------------------------------


def test_columnar_records_splice_and_passthrough():
    seqs = [{"kind": "op", "doc": f"d{i % 2}", "seq": i + 1, "msn": 0,
             "client": 1, "clientSeq": i, "refSeq": 0, "type": "op",
             "contents": {"i": i}, "inOff": i} for i in range(10)]
    src, _, _ = rb.decode_batch(rb.encode_batch(seqs))
    cr = rb.ColumnarRecords.from_batch(
        src, np.arange(3, 8), np.arange(103, 108)
    )
    assert len(cr) == 5
    assert cr.record(0) == {**seqs[3], "inOff": 103}
    assert rb.count_records([seqs[0], cr, seqs[9]]) == 7
    out, _, n = rb.decode_batch(
        rb.encode_batch([seqs[0], cr, seqs[9]])
    )
    assert n == 7
    assert out.records() == [seqs[0]] + [
        {**seqs[i], "inOff": 100 + i} for i in range(3, 8)
    ] + [seqs[9]]
    # non-contiguous row gather (the fused role's nack-splitting path)
    cr2 = rb.ColumnarRecords.from_batch(
        src, np.array([1, 2, 6, 9]), np.array([1, 2, 6, 9])
    )
    assert [r["seq"] for r in cr2.records()] == [2, 3, 7, 10]
    # encode_columns counts its records
    reg = M.get_registry()
    c = reg.counter("codec_encode_columns_total", codec="columnar")
    before = c.value
    rb.encode_columns([cr, cr2])
    assert c.value - before == 9


def test_columnar_records_reject_boxcars():
    box = {"kind": "boxcar", "doc": "d", "client": 1,
           "ops": [{"clientSeq": 1, "refSeq": 0, "contents": None}]}
    src, _, _ = rb.decode_batch(rb.encode_batch([box]))
    with pytest.raises(ValueError):
        rb.ColumnarRecords.from_batch(src, np.array([0]), np.array([0]))


def test_mask_runs():
    assert rb.mask_runs(np.array([], bool)) == []
    assert rb.mask_runs(np.array([1, 1, 0, 0, 0, 1])) == [
        (1, 0, 2), (0, 2, 5), (1, 5, 6)
    ]
    assert rb.mask_runs(np.array([True])) == [(True, 0, 1)]


# ---------------------------------------------------------------------------
# kernel columnar emission differential
# ---------------------------------------------------------------------------


def _boxcar_heavy_workload(seed=11, n_docs=3, n_clients=3, n=260):
    rng = random.Random(seed)
    recs = []
    for d in range(n_docs):
        for c in range(1, n_clients + 1):
            recs.append({"kind": "join", "doc": f"doc{d}", "client": c})
    cs = {}
    for i in range(n):
        d = rng.randrange(n_docs)
        c = rng.randrange(1, n_clients + 1)
        k = cs.setdefault((d, c), 0) + 1
        if rng.random() < 0.3:
            ops = []
            for _ in range(rng.randint(2, 4)):
                ops.append({"clientSeq": k, "refSeq": 0,
                            "contents": {"i": i}})
                k += 1
            cs[(d, c)] = k - 1
            recs.append({"kind": "boxcar", "doc": f"doc{d}",
                         "client": c, "ops": ops})
        else:
            cs[(d, c)] = k
            recs.append({"kind": "op", "doc": f"doc{d}", "client": c,
                         "clientSeq": k, "refSeq": 0,
                         "contents": {"i": i}})
    # riders: resubmission (silent dedup), unknown-client nack,
    # out-of-order nack, duplicate join, leave, nacked boxcar tail
    recs.append(recs[n_docs * n_clients])
    recs.append({"kind": "op", "doc": "doc0", "client": 99,
                 "clientSeq": 1, "refSeq": 0, "contents": None})
    recs.append({"kind": "op", "doc": "doc1", "client": 1,
                 "clientSeq": 999, "refSeq": 0, "contents": None})
    recs.append({"kind": "join", "doc": "doc0", "client": 1})
    recs.append({"kind": "leave", "doc": "doc2", "client": 2})
    k31 = cs.get((2, 1), 0)
    recs.append({"kind": "boxcar", "doc": "doc2", "client": 1,
                 "ops": [{"clientSeq": k31 + 1, "refSeq": 0,
                          "contents": 1},
                         {"clientSeq": 999, "refSeq": 0, "contents": 2},
                         {"clientSeq": k31 + 3, "refSeq": 0,
                          "contents": 3}]})
    return recs


def _drive_role(cls, shared, log_format, owner):
    raw = make_topic(os.path.join(shared, "topics", "rawdeltas.jsonl"),
                     log_format)
    recs = _boxcar_heavy_workload()
    for lo in range(0, len(recs), 48):
        raw.append_many(recs[lo:lo + 48])
    role = cls(str(shared), owner=owner, ttl_s=3600.0,
               log_format=log_format, batch=64)
    idle = 0
    while idle < 3:
        idle = 0 if role.step(idle_sleep=0.001) else idle + 1
    out = make_topic(os.path.join(shared, "topics", "deltas.jsonl"),
                     log_format)
    return out.read_from(0)


def test_kernel_columnar_emit_matches_scalar_boxcar_heavy(tmp_path):
    """THE emission differential: the kernel role's pre-columnized
    emit (verdict arrays → ColumnarRecords → one spliced frame) must
    write the byte-identical canonical stream the scalar dict-path
    oracle writes — boxcars, nacks, dedup, join/leave churn and all —
    and every emitted record must actually ride `encode_columns`."""
    reg = M.get_registry()
    c = reg.counter("codec_encode_columns_total", codec="columnar")
    a = _drive_role(DeliRole, str(tmp_path / "s"), "columnar", "s")
    before = c.value
    b = _drive_role(KernelDeliRole, str(tmp_path / "k"), "columnar", "k")
    assert a == b  # reason text included: same mirror-order rule
    assert c.value - before >= len(b)


def test_kernel_emit_trace_mode_falls_back_to_dicts(tmp_path):
    """Wire tracing adds a side "tr" key (generic schema) — the role
    must take the dict path and still produce the same canonical
    stream."""
    a = _drive_role(DeliRole, str(tmp_path / "s"), "columnar", "s")
    os.environ["FLUID_TRACE_WIRE"] = "1"
    try:
        b = _drive_role(KernelDeliRole, str(tmp_path / "k"),
                        "columnar", "k")
    finally:
        del os.environ["FLUID_TRACE_WIRE"]
    strip = lambda rs: [  # noqa: E731
        {k: v for k, v in r.items() if k != "tr"} for r in rs
    ]
    assert strip(a) == strip(b)


def test_kernel_columnar_emit_v1_ingest(tmp_path):
    """A v1-era raw topic (JSON boxcar blobs) feeds the same kernel
    emission: migration needs no drained topics."""
    shared = tmp_path / "k1"
    os.makedirs(shared / "topics")
    raw_path = str(shared / "topics" / "rawdeltas.jsonl")
    recs = _boxcar_heavy_workload()
    with open(raw_path, "ab") as f:  # v1 frames, written raw
        for lo in range(0, len(recs), 48):
            f.write(rb.encode_batch(recs[lo:lo + 48], version=1))
    role = KernelDeliRole(str(shared), owner="k1", ttl_s=3600.0,
                          log_format="columnar", batch=64)
    idle = 0
    while idle < 3:
        idle = 0 if role.step(idle_sleep=0.001) else idle + 1
    got = make_topic(str(shared / "topics" / "deltas.jsonl"),
                     "columnar").read_from(0)
    want = _drive_role(DeliRole, str(tmp_path / "s"), "columnar", "s")
    assert got == want


# ---------------------------------------------------------------------------
# fused durable+broadcast hop
# ---------------------------------------------------------------------------


def _drive_downstream(shared, roles, log_format, crash_step=None,
                      deli_batch=512):
    deli = KernelDeliRole(str(shared), owner="d", ttl_s=3600.0,
                          log_format=log_format, batch=deli_batch)
    idle = 0
    while idle < 3:
        idle = 0 if deli.step(idle_sleep=0.001) else idle + 1
    steps = 0
    for r in roles:
        idle = 0
        while idle < 3:
            moved = r.step(idle_sleep=0.001)
            steps += 1
            if crash_step is not None and steps == crash_step:
                # crash: drop the consumer mid-stream; a successor
                # takes over (the lapsed-lease handoff, instant here)
                r.leases.release(r.name)
                r = type(r)(str(shared), owner="successor",
                            ttl_s=3600.0, log_format=log_format,
                            batch=r.batch)
                crash_step = None
                idle = 0
                continue
            idle = 0 if moved else idle + 1
    dur = make_topic(os.path.join(shared, "topics", "durable.jsonl"),
                     log_format).read_from(0)
    bc = make_topic(os.path.join(shared, "topics", "broadcast.jsonl"),
                    log_format).read_from(0)
    return dur, bc


def _stage_raw(shared, log_format):
    raw = make_topic(os.path.join(shared, "topics", "rawdeltas.jsonl"),
                     log_format)
    recs = _boxcar_heavy_workload()
    for lo in range(0, len(recs), 48):
        raw.append_many(recs[lo:lo + 48])


@pytest.mark.parametrize("log_format", ["json", "columnar"])
def test_fused_hop_matches_split_pair(log_format, tmp_path):
    """The fused consumer must write EXACTLY the split pair's durable
    and broadcast streams (nacks broadcast-only), on both wire
    forms."""
    s1 = str(tmp_path / "split")
    _stage_raw(s1, log_format)
    d1, b1 = _drive_downstream(s1, [
        ScriptoriumRole(s1, owner="s", ttl_s=3600.0,
                        log_format=log_format, batch=37),
        BroadcasterRole(s1, owner="b", ttl_s=3600.0,
                        log_format=log_format, batch=37),
    ], log_format)
    s2 = str(tmp_path / "fused")
    _stage_raw(s2, log_format)
    d2, b2 = _drive_downstream(s2, [
        ScriptoriumBroadcasterRole(s2, owner="f", ttl_s=3600.0,
                                   log_format=log_format, batch=37),
    ], log_format)
    assert d1 == d2
    assert b1 == b2
    assert any(r.get("kind") == "nack" for r in b1)
    assert not any(r.get("kind") == "nack" for r in d1)


def _topic_fsyncs(shared, log_format, make_roles):
    """Topic fsyncs of one `_drive_downstream` run."""
    reg = M.MetricsRegistry()
    prev = M.set_registry(reg)
    try:
        _stage_raw(shared, log_format)
        # Small deli pumps: one deltas append per raw frame, so the hop
        # consumers append several times on either wire form.
        _drive_downstream(shared, make_roles(shared), log_format,
                          deli_batch=48)
    finally:
        M.set_registry(prev)
    return reg.counter("topic_fsyncs_total", kind="topic").value


@pytest.mark.parametrize("log_format", ["json", "columnar"])
def test_fused_hop_fsyncs_fewer_than_split_pair(log_format, tmp_path):
    """Over the same workload the fused consumer fsyncs the hop pair's
    topics at most two thirds as often as the split pair: its
    broadcast leg appends unfsynced. Counted, not timed."""
    def split(s):
        return [ScriptoriumRole(s, owner="s", ttl_s=3600.0,
                                log_format=log_format, batch=37),
                BroadcasterRole(s, owner="b", ttl_s=3600.0,
                                log_format=log_format, batch=37)]

    def fused(s):
        return [ScriptoriumBroadcasterRole(s, owner="f", ttl_s=3600.0,
                                           log_format=log_format,
                                           batch=37)]

    deli = _topic_fsyncs(str(tmp_path / "deli"), log_format,
                         lambda s: [])
    split_hop = _topic_fsyncs(str(tmp_path / "split"), log_format,
                              split) - deli
    fused_hop = _topic_fsyncs(str(tmp_path / "fused"), log_format,
                              fused) - deli
    assert fused_hop > 0
    assert split_hop >= 1.5 * fused_hop, (split_hop, fused_hop)


@pytest.mark.parametrize("log_format", ["json", "columnar"])
def test_fused_hop_crash_recovers_both_legs_exactly_once(
        log_format, tmp_path):
    """A fused consumer killed mid-stream (checkpoint behind its
    appends, broadcast leg unfsynced) must resume with zero dup/skip
    on BOTH topics — the two-topic generalization of the inOff
    recovery contract."""
    s1 = str(tmp_path / "ref")
    _stage_raw(s1, log_format)
    d1, b1 = _drive_downstream(s1, [
        ScriptoriumBroadcasterRole(s1, owner="f", ttl_s=3600.0,
                                   log_format=log_format, batch=37),
    ], log_format)
    s2 = str(tmp_path / "crash")
    _stage_raw(s2, log_format)
    d2, b2 = _drive_downstream(s2, [
        ScriptoriumBroadcasterRole(s2, owner="f", ttl_s=3600.0,
                                   log_format=log_format, batch=37),
    ], log_format, crash_step=3)
    assert d1 == d2
    assert b1 == b2


def test_fused_roles_helper():
    assert FUSED_PIPELINE_ROLES == (
        "deli", "scriptorium_broadcaster", "scribe"
    )
    assert fused_roles(("deli", "scriptorium", "scribe", "broadcaster",
                        "summarizer")) == (
        "deli", "scriptorium_broadcaster", "scribe", "summarizer"
    )


@pytest.mark.chaos
def test_chaos_fused_hop_kill_torn_converges():
    """The acceptance gate: kill+torn chaos on the FUSED farm (kernel
    deli, columnar topics, boxcars) converges bit-identical to the
    scalar golden with zero dup/skip — the unfsynced broadcast leg
    regenerates exactly-once through recovery."""
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    res = run_chaos(ChaosConfig(
        seed=17, faults=("kill", "torn"), n_docs=2, n_clients=3,
        ops_per_client=24, timeout_s=240.0, fused_hop=True,
        deli_impl="kernel", log_format="columnar", boxcar_rate=0.3,
    ))
    assert res.converged, res.detail
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0


def test_chaos_rejects_fused_hop_on_fabric():
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    with pytest.raises(ValueError, match="fused_hop"):
        run_chaos(ChaosConfig(faults=("kill",), n_partitions=2,
                              fused_hop=True))


def test_sidecar_only_advances_over_fsynced_data(tmp_path, monkeypatch):
    """The file-global sidecar invariant (review finding): a FRESH
    topic instance's empty append (a successor's fence bind) scanning
    over a dead writer's never-fsynced frames must fsync the data
    BEFORE the sidecar names it — the local `_unsynced` flag cannot
    see another process's unsynced appends."""
    path = str(tmp_path / "t.jsonl")
    w1 = ColumnarFileTopic(path)
    w1.append_many([_seq_op("A", 1)])
    w1.append_many([_seq_op("A", 2)], fsync=False)  # dies unsynced
    clen_before = json.load(open(path + ".clen"))["len"]
    fsyncs = []
    from fluidframework_tpu.server import columnar_log as cl

    real = cl.fsync_file
    monkeypatch.setattr(cl, "fsync_file",
                        lambda f, kind="topic": (fsyncs.append(kind),
                                                 real(f, kind)))
    w2 = ColumnarFileTopic(path)  # the successor (fresh instance)
    w2.append_many([], fence=1, owner="succ")  # fence bind
    clen_after = json.load(open(path + ".clen"))["len"]
    assert clen_after > clen_before  # sidecar did advance...
    assert "topic" in fsyncs  # ...but only after a data fsync


# ---------------------------------------------------------------------------
# columnar backward tail scan (summary catch-up)
# ---------------------------------------------------------------------------


def _seq_op(doc, seq):
    return {"kind": "op", "doc": doc, "seq": seq, "msn": 0,
            "client": 1, "clientSeq": seq, "refSeq": 0, "type": "op",
            "contents": {"s": seq}, "inOff": seq}


def _grow_log(topic, frames, per_frame=20, start=(0, 0)):
    sa, sb = start
    for i in range(frames):
        batch = []
        for j in range(per_frame):
            if (i + j) % 2 == 0:
                sa += 1
                batch.append(_seq_op("A", sa))
            else:
                sb += 1
                batch.append(_seq_op("B", sb))
        topic.append_many(batch)
    return sa, sb


def test_reverse_tail_matches_forward(tmp_path):
    t = ColumnarFileTopic(str(tmp_path / "d.jsonl"))
    sa, sb = _grow_log(t, 60)
    ops = tail_records_reverse(t, "A", sa - 15, None)
    assert ops is not None
    assert [r["seq"] for r in ops] == list(range(sa - 14, sa + 1))
    fwd = [r for _, r in t.read_entries(0)[0]
           if r.get("doc") == "B" and r.get("kind") == "op"]
    assert tail_records_reverse(t, "B", 0, None) == fwd
    # upto bound
    assert [r["seq"] for r in
            tail_records_reverse(t, "A", sa - 10, sa - 5)] == \
        list(range(sa - 9, sa - 4))


def test_reverse_tail_flat_in_log_length(tmp_path):
    """The satellite's flat-join-cost claim, measured: the bytes a
    reverse catch-up scans stay ~CONSTANT as the log grows 4x (the
    forward skip grows linearly)."""
    reg = M.get_registry()
    c = reg.counter("catchup_tail_scan_bytes_total",
                    mode="reverse-columnar")

    def scanned(frames):
        t = ColumnarFileTopic(str(tmp_path / f"d{frames}.jsonl"))
        sa, _ = _grow_log(t, frames)
        before = c.value
        ops = tail_records_reverse(t, "A", sa - 10, None)
        assert ops is not None and len(ops) == 10
        return c.value - before

    small, big = scanned(100), scanned(400)
    assert big <= small * 2, (small, big)  # flat, not linear


def test_reverse_tail_torn_and_stale_sidecar(tmp_path):
    t = ColumnarFileTopic(str(tmp_path / "d.jsonl"))
    sa, _ = _grow_log(t, 30)
    want = tail_records_reverse(t, "A", sa - 12, None)
    with open(t.path, "ab") as f:
        f.write(b"FRB1torn-in-flight")
    assert tail_records_reverse(t, "A", sa - 12, None) == want
    # stale-LOW sidecar (crash before the sidecar update): the forward
    # suffix parse covers the gap
    data = open(t.path, "rb").read()
    _, end, _ = rb.decode_batch(data, 0)
    with open(t.path + ".clen", "w") as f:
        json.dump({"len": end}, f)
    assert tail_records_reverse(ColumnarFileTopic(t.path), "A",
                                sa - 12, None) == want
    # no sidecar at all: anchorless -> None (caller falls forward)
    os.remove(t.path + ".clen")
    assert tail_records_reverse(ColumnarFileTopic(t.path), "A", 0,
                                None) is None


def test_reverse_tail_json_prefix_falls_forward_not_misparse(tmp_path):
    """A JSON-era prefix breaks the backward frame chain: the scan
    must either stop cleanly above it (base reached) or return None —
    never fabricate records."""
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        for s in range(1, 6):
            f.write(json.dumps(_seq_op("A", s)) + "\n")
    t = ColumnarFileTopic(path)
    sa, _ = _grow_log(t, 10, start=(5, 0))
    # base above the JSON era: chain stops inside the frame region
    ops = tail_records_reverse(t, "A", sa - 5, None)
    assert ops is not None
    assert [r["seq"] for r in ops] == list(range(sa - 4, sa + 1))
    # base inside the JSON era: cannot anchor -> fall forward
    assert tail_records_reverse(t, "A", 0, None) is None


@pytest.mark.parametrize("log_format", ["json", "columnar"])
def test_read_catchup_reverse_equivalence(log_format, tmp_path):
    """`read_catchup` returns the same tail through the reverse scan
    as through the forward skip, at both log formats."""
    from fluidframework_tpu.server.summarizer import read_catchup

    shared = str(tmp_path)
    os.makedirs(os.path.join(shared, "topics"), exist_ok=True)
    t = make_topic(os.path.join(shared, "topics", "deltas.jsonl"),
                   log_format)
    n = 300
    ops = [_seq_op("A", s + 1) for s in range(n)]
    for lo in range(0, n, 25):
        t.append_many(ops[lo:lo + 25])
    base_seq, base_off = 240, 239

    class _Idx:
        def poll(self):
            pass

        def nearest(self, doc, seq):
            return {"doc": doc, "seq": base_seq, "off": base_off,
                    "handle": "h", "count": base_seq,
                    "form": "ops"}

    class _Store:
        def get(self, h):
            return json.dumps({"form": "ops", "records": []}).encode()

    cu = read_catchup(shared, "A", log_format, index=_Idx(),
                      store=_Store())
    assert [r["seq"] for r in cu["ops"]] == list(range(241, n + 1))
