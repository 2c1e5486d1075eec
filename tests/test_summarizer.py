"""Summary service (server/summarizer.py): the merge-tree summarizer
role, snapshot catch-up, and the exactly-once/no-fork contracts.

The core claim under test: **summary(seq=k) + tail replay is
bit-identical to full replay** (document-state digests), for seeded
workloads across engines (merge-tree kernel fold vs generic ops form)
and both log formats, including restarts mid-stream and a torn
manifest append — and restarts can never fork a summary (the canonical
serialized form is a pure function of the op prefix, so re-emitted
blobs are byte- and handle-identical)."""

from __future__ import annotations

import json
import os
import time

import pytest

from fluidframework_tpu.protocol.mergetree_ops import op_to_json
from fluidframework_tpu.server.columnar_log import (
    make_tail_reader,
    make_topic,
)
from fluidframework_tpu.server.summarizer import (
    SummarizerRole,
    SummaryIndex,
    SummaryReplica,
    open_summary_store,
    read_catchup,
)
from fluidframework_tpu.testing.pipeline import build_mergetree_stream
from fluidframework_tpu.testing.farm import FarmConfig, run_sharedstring_farm


def wire_records(doc, stream):
    """Farm SequencedMessages -> deltas-topic wire records."""
    recs = []
    for m in stream:
        contents = m.contents
        if hasattr(contents, "__dataclass_fields__"):
            contents = op_to_json(contents)
        recs.append({
            "kind": "op", "doc": doc, "seq": m.sequence_number,
            "msn": m.minimum_sequence_number, "client": m.client_id,
            "clientSeq": m.client_seq, "refSeq": m.ref_seq,
            "type": m.type.value, "contents": contents,
        })
    return recs


def farm_records(doc="doc0", seed=7, rounds=10):
    res = run_sharedstring_farm(FarmConfig(
        num_clients=3, rounds=rounds, ops_per_client_per_round=4,
        seed=seed, multi_key_annotates=True, initial_text="",
    ))
    return wire_records(doc, res.stream), res.final_text


def generic_records(doc, n_ops=60, n_clients=3, seed=1):
    """Sequenced records with opaque contents (the ops-form engine)."""
    import random

    rng = random.Random(seed)
    recs = []
    seq = 0
    for c in range(1, n_clients + 1):
        seq += 1
        recs.append({"kind": "op", "doc": doc, "seq": seq, "msn": 0,
                     "client": c, "clientSeq": 0, "refSeq": seq - 1,
                     "type": "join", "contents": c})
    cseq = {c: 0 for c in range(1, n_clients + 1)}
    for i in range(n_ops):
        c = rng.randint(1, n_clients)
        seq += 1
        cseq[c] += 1
        recs.append({"kind": "op", "doc": doc, "seq": seq,
                     "msn": max(0, seq - 8), "client": c,
                     "clientSeq": cseq[c], "refSeq": seq - 1,
                     "type": "op",
                     "contents": {"v": rng.randint(0, 999), "i": i}})
    return recs


def drive_direct(shared, records, summary_ops=32, log_format="json",
                 batch=512, append_first=True):
    """Run the role datapath (no lease loop) to quiescence — the
    `run_pipeline` pattern."""
    deltas = make_topic(
        os.path.join(shared, "topics", "deltas.jsonl"), log_format
    )
    if append_first:
        deltas.append_many(records)
    role = SummarizerRole(shared, owner="direct", ttl_s=3600.0,
                          log_format=log_format,
                          summary_ops=summary_ops)
    role.fence = 1
    reader = make_tail_reader(deltas)
    while True:
        entries = reader.poll(batch)
        if not entries:
            break
        out = []
        for li, rec in entries:
            role.process(li, rec, out)
        role.flush_batch(out)
        if out:
            role.out_topic.append_many(out, fence=1, owner="direct")
        role.offset = reader.next_line
    return role


def run_stepped(shared, summary_ops=16, owner="g1", max_steps=500,
                until_offset=None, log_format="json", **kw):
    """Run the role through the REAL `step()` machinery (lease, fenced
    append, checkpoint, recovery) until the input is drained or
    `max_steps` pass."""
    role = SummarizerRole(shared, owner=owner, ttl_s=2.0, batch=64,
                          ckpt_interval_s=0.0, log_format=log_format,
                          summary_ops=summary_ops, **kw)
    for _ in range(max_steps):
        role.step(idle_sleep=0.01)
        if until_offset is not None and role.offset >= until_offset:
            break
    return role


def manifests_of(shared, log_format="json", name="summaries"):
    topic = make_topic(
        os.path.join(shared, "topics", f"{name}.jsonl"), log_format
    )
    return [r for r in topic.read_from(0)
            if isinstance(r, dict) and r.get("kind") == "summary"]


def assert_all_boots_equal(shared, doc, records, log_format="json"):
    """EVERY manifest's summary + tail must equal the cold replay."""
    store = open_summary_store(shared)
    cold = SummaryReplica(None)
    cold.apply_records(records)
    idx = SummaryIndex(shared, log_format)
    idx.poll()
    mans = idx.manifests.get(doc, [])
    assert mans, "no summaries emitted"
    for m in mans:
        blob = json.loads(store.get(m["handle"]).decode())
        rep = SummaryReplica(blob)
        rep.apply_records([r for r in records if r["seq"] > m["seq"]])
        assert rep.state_digest() == cold.state_digest(), (
            f"boot from summary seq={m['seq']} diverges"
        )
    return mans, cold


# ---------------------------------------------------------------------------
# differential: summary + tail == full replay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("log_format", ["json", "columnar"])
def test_mergetree_summary_tail_equals_full_replay(tmp_path, log_format):
    records, final_text = farm_records()
    drive_direct(str(tmp_path), records, summary_ops=32,
                 log_format=log_format)
    mans, cold = assert_all_boots_equal(
        str(tmp_path), "doc0", records, log_format
    )
    assert all(m["form"] == "mergetree" for m in mans)
    assert cold.get_text() == final_text
    # read_catchup end-to-end: nearest summary + tail off the topic.
    cu = read_catchup(str(tmp_path), "doc0", log_format,
                      store=open_summary_store(str(tmp_path)))
    rep = SummaryReplica(cu["blob"])
    rep.apply_records(cu["ops"])
    assert rep.state_digest() == cold.state_digest()
    assert rep.get_text() == final_text
    # The tail is the post-summary suffix, not the log.
    assert len(cu["ops"]) < len(records) / 2


@pytest.mark.parametrize("seed", [3, 11])
def test_mergetree_differential_seeded(tmp_path, seed):
    records, _ = farm_records(seed=seed, rounds=8)
    drive_direct(str(tmp_path), records, summary_ops=24)
    assert_all_boots_equal(str(tmp_path), "doc0", records)


def test_ops_form_generic_docs(tmp_path):
    records = generic_records("gdoc", n_ops=70)
    drive_direct(str(tmp_path), records, summary_ops=20)
    mans, cold = assert_all_boots_equal(str(tmp_path), "gdoc", records)
    assert all(m["form"] == "ops" for m in mans)
    # Expected deterministic cadence count.
    assert len(mans) == len(records) // 20


def test_synthetic_stream_differential(tmp_path):
    """The bench generator's stream shape (trailing msn window,
    bounded doc) through the same gate."""
    records = build_mergetree_stream(600, n_clients=3, seed=4)
    drive_direct(str(tmp_path), records, summary_ops=128)
    assert_all_boots_equal(str(tmp_path), "doc0", records)


def test_stacked_multi_doc_fold(tmp_path):
    """Several docs triggering in one pump fold through ONE vmapped
    kernel dispatch (`apply_op_batch_docs_jit`) — and stay correct."""
    per_doc = {}
    interleaved = []
    streams = {}
    for d, seed in enumerate([5, 6, 7]):
        recs, _ = farm_records(doc=f"d{d}", seed=seed, rounds=6)
        streams[f"d{d}"] = recs
        per_doc[f"d{d}"] = recs
    # Round-robin interleave so all docs trigger inside one big pump.
    iters = [list(v) for v in per_doc.values()]
    while any(iters):
        for it in iters:
            if it:
                interleaved.append(it.pop(0))
    role = drive_direct(str(tmp_path), interleaved, summary_ops=24,
                        batch=100_000)
    assert role._m_stacked.value > 0, "stacked fold path never ran"
    for doc, recs in streams.items():
        assert_all_boots_equal(str(tmp_path), doc, recs)


# ---------------------------------------------------------------------------
# restarts: exactly-once, no fork, torn manifests
# ---------------------------------------------------------------------------


def test_restart_mid_stream_reemits_identical_summaries(tmp_path):
    """A summarizer killed mid-stream and restarted (fresh owner,
    fenced checkpoint + inOff recovery) must produce the EXACT manifest
    sequence of an uninterrupted run — same seqs, same byte-identical
    content-addressed handles, no duplicates."""
    records, _ = farm_records(seed=9, rounds=8)
    # Uninterrupted reference run.
    ref_dir = str(tmp_path / "ref")
    os.makedirs(ref_dir)
    drive_direct(ref_dir, records, summary_ops=16)
    ref = [(m["doc"], m["seq"], m["handle"])
           for m in manifests_of(ref_dir)]
    assert ref

    # Interrupted run: first life consumes ~half through step(), dies
    # (abandoned, lease expires), successor finishes.
    cut_dir = str(tmp_path / "cut")
    os.makedirs(os.path.join(cut_dir, "topics"))
    make_topic(os.path.join(cut_dir, "topics", "deltas.jsonl"),
               "json").append_many(records)
    half = len(records) // 2
    run_stepped(cut_dir, summary_ops=16, owner="g1",
                until_offset=half)
    time.sleep(2.2)  # the dead owner's lease must expire
    run_stepped(cut_dir, summary_ops=16, owner="g2",
                until_offset=len(records))
    got = [(m["doc"], m["seq"], m["handle"])
           for m in manifests_of(cut_dir)]
    assert got == ref, "restart forked or duplicated summaries"
    assert_all_boots_equal(cut_dir, "doc0", records)


def test_torn_manifest_append_reemitted(tmp_path):
    """A crash that clips the manifest append (torn tail) leaves the
    torn summary invisible; recovery re-emits exactly the missing
    manifest — no duplicate, byte-identical."""
    records, _ = farm_records(seed=13, rounds=8)
    shared = str(tmp_path)
    os.makedirs(os.path.join(shared, "topics"))
    make_topic(os.path.join(shared, "topics", "deltas.jsonl"),
               "json").append_many(records)
    run_stepped(shared, summary_ops=16, owner="g1",
                until_offset=len(records))
    full = manifests_of(shared)
    assert len(full) >= 2
    # Clip the LAST manifest line off the summaries topic (a writer
    # that died mid-append; the torn-tail rules make it invisible).
    path = os.path.join(shared, "topics", "summaries.jsonl")
    with open(path, "rb") as f:
        data = f.read()
    cut = data[:-1].rfind(b"\n") + 1
    with open(path, "wb") as f:
        f.write(data[:cut + 3])  # leave a torn, newline-less remnant
    assert len(manifests_of(shared)) == len(full) - 1
    # ALSO roll the checkpoint back before the clipped manifest's
    # trigger, so recovery actually re-processes it (a checkpoint at
    # the head would just resume past the gap).
    from fluidframework_tpu.server.queue import FencedCheckpointStore

    ck = FencedCheckpointStore(os.path.join(shared, "checkpoints"))
    env = ck.load("summarizer")
    prev_off = full[-2]["off"] + 1  # state as of the second-last one
    # Rebuild the state deterministically: a fresh role replays from
    # scratch up to prev_off (cheaper: just drop the checkpoint — the
    # successor replays the whole topic silently).
    assert env is not None
    os.remove(os.path.join(shared, "checkpoints",
                           "summarizer.ckpt.json"))
    del prev_off
    time.sleep(2.2)  # lease expiry
    run_stepped(shared, summary_ops=16, owner="g2",
                until_offset=len(records))
    after = manifests_of(shared)
    assert [(m["doc"], m["seq"], m["handle"]) for m in after] == \
        [(m["doc"], m["seq"], m["handle"]) for m in full]
    assert_all_boots_equal(shared, "doc0", records)


def test_freeze_on_undecodable_op(tmp_path):
    """A merge-tree doc hitting an undecodable op FREEZES its
    summaries (no new manifests, loud metric) instead of emitting a
    wrong one; earlier summaries still boot."""
    records, _ = farm_records(seed=21, rounds=8)
    bad_at = 40
    poisoned = list(records[:bad_at])
    last = records[bad_at - 1]
    poisoned.append({**last, "seq": last["seq"] + 1,
                     "contents": {"type": 42, "weird": True}})
    for r in records[bad_at:]:
        poisoned.append({**r, "seq": r["seq"] + 1})
    role = drive_direct(str(tmp_path), poisoned, summary_ops=16)
    mans = manifests_of(str(tmp_path))
    assert mans and all(m["seq"] <= bad_at for m in mans)
    assert role._m_frozen.value == 1
    # The pre-freeze summary still boots against its own-era tail.
    store = open_summary_store(str(tmp_path))
    blob = json.loads(store.get(mans[-1]["handle"]).decode())
    rep = SummaryReplica(blob)
    ok_tail = [r for r in records
               if mans[-1]["seq"] < r["seq"] <= bad_at]
    rep.apply_records(ok_tail)
    cold = SummaryReplica(None)
    cold.apply_records(records[:bad_at])
    assert rep.state_digest() == cold.state_digest()


# ---------------------------------------------------------------------------
# index / reader semantics
# ---------------------------------------------------------------------------


def test_summary_index_nearest(tmp_path):
    topic = make_topic(
        os.path.join(str(tmp_path), "topics", "summaries.jsonl"), "json"
    )
    topic.append_many([
        {"kind": "summary", "doc": "a", "seq": s, "msn": 0, "count": s,
         "form": "ops", "handle": f"h{s}", "bytes": 1, "off": s,
         "inOff": s}
        for s in (10, 20, 30)
    ])
    idx = SummaryIndex(str(tmp_path))
    idx.poll()
    assert idx.nearest("a")["seq"] == 30
    assert idx.nearest("a", 25)["seq"] == 20
    assert idx.nearest("a", 10)["seq"] == 10
    assert idx.nearest("a", 9) is None
    assert idx.nearest("b") is None
    # Incremental: a later manifest appears on the next poll.
    topic.append({"kind": "summary", "doc": "a", "seq": 40, "msn": 0,
                  "count": 40, "form": "ops", "handle": "h40",
                  "bytes": 1, "off": 40, "inOff": 40})
    idx.poll()
    assert idx.nearest("a")["seq"] == 40


# ---------------------------------------------------------------------------
# kernel-deli wire tracing (PR 9 follow-up b)
# ---------------------------------------------------------------------------


def test_kernel_deli_trace_parity(tmp_path, monkeypatch):
    """With FLUID_TRACE_WIRE on, the kernel deli's records carry the
    same span structure as the scalar role's — tr.stamp on every op,
    tr.sub threaded from the ingress record — with identical canonical
    streams and identical submit_to_stamp observation counts."""
    from fluidframework_tpu.server.deli_kernel import KernelDeliRole
    from fluidframework_tpu.server.queue import SharedFileTopic
    from fluidframework_tpu.server.supervisor import (
        DeliRole,
        canonical_record,
    )
    from fluidframework_tpu.utils import metrics as M

    monkeypatch.setenv("FLUID_TRACE_WIRE", "1")
    now = time.time()
    raws = []
    for c in (1, 2):
        raws.append({"kind": "join", "doc": "d", "client": c})
    for i in range(1, 6):
        for c in (1, 2):
            raws.append({"kind": "op", "doc": "d", "client": c,
                         "clientSeq": i, "refSeq": 0,
                         "contents": {"i": i}, "tr_sub": now})
    raws.append({"kind": "boxcar", "doc": "d", "client": 1,
                 "ops": [{"clientSeq": 6, "refSeq": 0, "contents": 1},
                         {"clientSeq": 7, "refSeq": 0, "contents": 2}],
                 "tr_sub": now})

    outs = {}
    counts = {}
    for impl, cls in (("scalar", DeliRole), ("kernel", KernelDeliRole)):
        d = str(tmp_path / impl)
        os.makedirs(os.path.join(d, "topics"), exist_ok=True)
        SharedFileTopic(
            os.path.join(d, "topics", "rawdeltas.jsonl")
        ).append_many(raws)
        reg = M.MetricsRegistry()
        prev = M.set_registry(reg)
        try:
            role = cls(d, owner=impl, ttl_s=3600.0)
        finally:
            M.set_registry(prev)
        assert role.trace_wire
        role.fence = 1
        out = []
        for li, rec in enumerate(raws):
            role.process(li, rec, out)
        role.flush_batch(out)
        outs[impl] = out
        counts[impl] = reg.histogram(
            "op_stage_ms", stage="submit_to_stamp"
        ).count

    canon = [canonical_record(r) for r in outs["scalar"]]
    assert canon == [canonical_record(r) for r in outs["kernel"]]
    assert counts["scalar"] == counts["kernel"] > 0
    for rec in outs["kernel"]:
        if rec.get("kind") != "op":
            continue
        tr = rec.get("tr")
        assert isinstance(tr, dict) and "stamp" in tr
        if rec["type"] == "op":
            assert tr["sub"] == now and tr["sub"] <= tr["stamp"]


# ---------------------------------------------------------------------------
# farm + fabric integration
# ---------------------------------------------------------------------------


def test_supervised_farm_emits_summaries(tmp_path):
    """The five-role supervised farm end to end: raw records in,
    summary manifests out (the summarizer as a ROLES member)."""
    from fluidframework_tpu.server.queue import SharedFileTopic
    from fluidframework_tpu.server.supervisor import (
        ROLES,
        ServiceSupervisor,
    )

    assert "summarizer" in ROLES
    shared = str(tmp_path)
    sup = ServiceSupervisor(shared, ttl_s=0.75, summary_ops=8).start()
    try:
        raw = SharedFileTopic(
            os.path.join(shared, "topics", "rawdeltas.jsonl")
        )
        recs = generic_records("fdoc", n_ops=30, n_clients=2)
        # Re-shape into raw ingress records (strip seq stamps).
        ingress = []
        for r in recs:
            if r["type"] == "join":
                ingress.append({"kind": "join", "doc": "fdoc",
                                "client": r["client"]})
            elif r["type"] == "op":
                ingress.append({"kind": "op", "doc": "fdoc",
                                "client": r["client"],
                                "clientSeq": r["clientSeq"],
                                "refSeq": 0,
                                "contents": r["contents"]})
        raw.append_many(ingress)
        deadline = time.time() + 90
        mans = []
        while time.time() < deadline:
            sup.poll_once()
            mans = manifests_of(shared)
            if len(mans) >= len(ingress) // 8:
                break
            time.sleep(0.05)
        assert len(mans) >= len(ingress) // 8
    finally:
        sup.stop()
    # Boot-equivalence against the farm's own deltas stream.
    deltas = make_topic(os.path.join(shared, "topics", "deltas.jsonl"),
                        "json")
    ops = [r for r in deltas.read_from(0)
           if isinstance(r, dict) and r.get("kind") == "op"]
    cu = read_catchup(shared, "fdoc", "json",
                      store=open_summary_store(shared))
    boot = SummaryReplica(cu["blob"])
    boot.apply_records(cu["ops"])
    cold = SummaryReplica(None)
    cold.apply_records(ops)
    assert boot.state_digest() == cold.state_digest()


def test_shard_worker_per_partition_summarizer(tmp_path):
    """The static fabric seam: ShardWorker(summarize=True) runs one
    summarizer per owned partition (deltas-p{k} → summaries-p{k});
    SummaryIndex(partitions=N) merges the manifest topics."""
    from fluidframework_tpu.server.queue import record_partition
    from fluidframework_tpu.server.shard_fabric import (
        ShardRouter,
        ShardWorker,
        spread_doc_names,
    )

    shared = str(tmp_path)
    n_p = 2
    docs = spread_doc_names(2, n_p)
    router = ShardRouter(shared, n_p, "json")
    worker = ShardWorker(shared, "w0", n_partitions=n_p, ttl_s=5.0,
                         summarize=True, summary_ops=8,
                         ckpt_interval_s=0.0)
    workload = []
    for doc in docs:
        for c in (1, 2):
            workload.append({"kind": "join", "doc": doc, "client": c})
        for i in range(1, 16):
            for c in (1, 2):
                workload.append({
                    "kind": "op", "doc": doc, "client": c,
                    "clientSeq": i, "refSeq": 0, "contents": {"i": i},
                })
    router.append(workload)
    per_doc = 2 + 2 * 15
    expected = 2 * (per_doc // 8)
    deadline = time.time() + 60
    while time.time() < deadline:
        worker.step()
        total = sum(
            len(manifests_of(shared, name=f"summaries-p{k}"))
            for k in range(n_p)
        )
        if total >= expected:
            break
        time.sleep(0.01)
    worker.stop()
    assert total >= expected
    idx = SummaryIndex(shared, partitions=n_p)
    idx.poll()
    store = open_summary_store(shared)
    for doc in docs:
        k = record_partition({"doc": doc}, n_p)
        cu = read_catchup(shared, doc, "json", index=idx, store=store,
                          deltas_topic=f"deltas-p{k}")
        assert cu["manifest"] is not None
        boot = SummaryReplica(cu["blob"])
        boot.apply_records(cu["ops"])
        deltas = make_topic(
            os.path.join(shared, "topics", f"deltas-p{k}.jsonl"), "json"
        )
        cold = SummaryReplica(None)
        cold.apply_records([
            r for r in deltas.read_from(0)
            if isinstance(r, dict) and r.get("kind") == "op"
            and r.get("doc") == doc
        ])
        assert boot.state_digest() == cold.state_digest()


def test_elastic_summarize_accepted(tmp_path):
    """REGRESSION for the retained absorb path: `summarize=True` on
    the ELASTIC fabric used to be a loud ValueError ("static-partition
    only") — the elastic summarizer now absorbs predecessor ranges'
    fold state, so the old rejection can no longer be raised and a
    ranged summarizer role is actually constructed per owned range."""
    from fluidframework_tpu.server.shard_fabric import ShardWorker
    from fluidframework_tpu.server.summarizer import SummarizerRole

    w = ShardWorker(str(tmp_path), "w0", n_partitions=2, elastic=True,
                    summarize=True, ttl_s=3600.0)
    try:
        w.sweep()
        assert w.summ_roles, "elastic worker built no summarizer roles"
        for rid, role in w.summ_roles.items():
            assert isinstance(role, SummarizerRole)
            assert role.rid == rid  # ranged identity, not partitioned
            assert role.in_topic_name == f"deltas-{rid}"
            assert role.out_topic_name == f"summaries-{rid}"
    finally:
        w.stop()


def test_elastic_summarizer_absorbs_across_live_split(tmp_path):
    """The absorb path itself: a live split mid-stream hands each
    range's summarizer state to the successors (seed from the parent's
    final fold checkpoint sliced by hash range, fence-bound pred
    manifest topics, exactly-once manifest re-emission) — and every
    doc's newest summary + tail boots bit-identical to a cold replay
    of the merged stream."""
    import time as _time

    from fluidframework_tpu.server.shard_fabric import (
        ShardRouter,
        ShardWorker,
        control_result,
        request_topology_change,
    )
    from fluidframework_tpu.server.summarizer import SummaryIndex

    d = str(tmp_path)
    w = ShardWorker(d, "w0", n_partitions=1, elastic=True,
                    summarize=True, ttl_s=5.0, summary_ops=8)
    w.sweep()
    router = ShardRouter(d, 1, elastic=True)
    docs = [f"doc{i}" for i in range(4)]
    recs = [{"kind": "join", "doc": doc, "client": 1} for doc in docs]
    for i in range(40):
        for doc in docs:
            recs.append({"kind": "op", "doc": doc, "client": 1,
                         "clientSeq": i + 1, "refSeq": 0,
                         "contents": {"i": i}})
    half = len(recs) // 2
    try:
        router.append(recs[:half])
        for _ in range(8):
            w.step()
        rid = list(w.roles)[0]
        cid = request_topology_change(d, {"op": "split", "rid": rid})
        deadline = _time.time() + 30
        while control_result(d, cid) is None and _time.time() < deadline:
            w.step()
            _time.sleep(0.02)
        assert control_result(d, cid), "split never committed"
        router.append(recs[half:])
        for _ in range(40):
            w.step()
        idx = SummaryIndex(
            d, topics=router.stage_topic_names("summaries")
        )
        idx.poll()
        store = open_summary_store(d)
        all_ops = [r for r in router.merged_reader("deltas").poll()
                   if isinstance(r, dict) and r.get("kind") == "op"]
        for doc in docs:
            man = idx.nearest(doc)
            assert man is not None, f"no manifest for {doc}"
            blob = json.loads(store.get(man["handle"]).decode())
            boot = SummaryReplica(blob)
            boot.apply_records(sorted(
                (r for r in all_ops
                 if r["doc"] == doc and r["seq"] > man["seq"]),
                key=lambda r: r["seq"],
            ))
            cold = SummaryReplica(None)
            cold.apply_records(sorted(
                (r for r in all_ops if r["doc"] == doc),
                key=lambda r: r["seq"],
            ))
            assert boot.state_digest() == cold.state_digest(), (
                f"elastic summary boot diverged for {doc}"
            )
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# chaos: summarizer kill never forks a summary
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_summarizer_kill_never_forks(tmp_path):
    """The acceptance gate: SIGKILL the whole farm (summarizer
    included) mid-stream; the run must converge bit-identical with
    zero dup/skip AND summary integrity — deterministic manifest
    count, one handle per (doc, seq), summary + tail == cold replay."""
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    res = run_chaos(ChaosConfig(
        seed=5, faults=("kill",), n_docs=2, n_clients=2,
        ops_per_client=23, timeout_s=240.0,
        summarizer=True, summary_ops=12,
        shared_dir=str(tmp_path),
    ))
    assert res.converged, res.detail
    assert res.summaries_ok
    assert res.summary_manifests > 0
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0
    assert res.restarts.get("summarizer", 0) >= 1


def test_chaos_summarizer_sharded_rejected():
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    with pytest.raises(ValueError, match="single-partition"):
        run_chaos(ChaosConfig(summarizer=True, n_partitions=2,
                              faults=("kill",)))


# ---------------------------------------------------------------------------
# cross-impl: identical summaries whatever deli produced the stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("log_format", ["json", "columnar"])
def test_summaries_identical_across_deli_impls(tmp_path, log_format):
    """Raw merge-tree submissions through the SCALAR and the KERNEL
    deli, each feeding its own summarizer: the manifest sequences —
    content-addressed handles included — must be identical (the deltas
    streams are bit-identical by the deli gates, and the summarizer is
    deterministic over them), for both log formats."""
    import random
    import string

    from fluidframework_tpu.server.deli_kernel import KernelDeliRole
    from fluidframework_tpu.server.supervisor import DeliRole

    rng = random.Random(31)
    raws = [{"kind": "join", "doc": "x", "client": 1}]
    length = 0
    for i in range(60):
        if length == 0 or rng.random() < 0.6:
            pos = rng.randint(0, length)
            text = "".join(rng.choices(string.ascii_lowercase,
                                       k=rng.randint(1, 5)))
            contents = {"type": 0, "pos1": pos, "seg": text}
            length += len(text)
        else:
            a = rng.randint(0, length - 1)
            b = min(length, a + rng.randint(1, 4))
            contents = {"type": 1, "pos1": a, "pos2": b}
            length -= b - a
        raws.append({"kind": "op", "doc": "x", "client": 1,
                     "clientSeq": i + 1, "refSeq": i,
                     "contents": contents})

    handles = {}
    for impl, cls in (("scalar", DeliRole), ("kernel", KernelDeliRole)):
        d = str(tmp_path / f"{impl}")
        os.makedirs(os.path.join(d, "topics"), exist_ok=True)
        raw_topic = make_topic(
            os.path.join(d, "topics", "rawdeltas.jsonl"), log_format
        )
        raw_topic.append_many(raws)
        deli = cls(d, owner=impl, ttl_s=3600.0, log_format=log_format)
        deli.fence = 1
        reader = make_tail_reader(raw_topic)
        out = []
        if deli.ingest_batches and hasattr(reader, "poll_batches"):
            for unit in reader.poll_batches(10_000):
                if unit[0] == "batch":
                    deli.process_batch(unit[1], unit[2], out)
                else:
                    deli.process(unit[1], unit[2], out)
        else:
            for li, rec in reader.poll(10_000):
                deli.process(li, rec, out)
        deli.flush_batch(out)
        deli.out_topic.append_many(out, fence=1, owner=impl)
        drive_direct(d, [], summary_ops=16, log_format=log_format,
                     append_first=False)
        mans = manifests_of(d, log_format)
        assert mans and all(m["form"] == "mergetree" for m in mans)
        handles[impl] = [(m["doc"], m["seq"], m["handle"])
                        for m in mans]
        deltas = make_topic(
            os.path.join(d, "topics", "deltas.jsonl"), log_format
        )
        recs = [r for r in deltas.read_from(0)
                if isinstance(r, dict) and r.get("kind") == "op"]
        assert_all_boots_equal(d, "x", recs, log_format)
    assert handles["scalar"] == handles["kernel"]


def test_undecided_cadence_point_skipped_not_forked(tmp_path):
    """>= summary_ops join records before a doc's first op: the
    all-join cadence points are deterministically SKIPPED (no empty
    blob, no dangling trigger), whether the first op lands in the
    same pump or a later one, and summary + tail still equals cold
    replay (the review-found empty-'ops'-blob bug)."""
    n_joins, n = 6, 4  # joins alone cross the cadence at count 4
    base = []
    seq = 0
    for c in range(1, n_joins + 1):
        seq += 1
        base.append({"kind": "op", "doc": "j", "seq": seq, "msn": 0,
                     "client": c, "clientSeq": 0, "refSeq": seq - 1,
                     "type": "join", "contents": c})
    ops = []
    for i in range(1, 11):
        seq += 1
        ops.append({"kind": "op", "doc": "j", "seq": seq,
                    "msn": max(0, seq - 4), "client": 1,
                    "clientSeq": i, "refSeq": seq - 1, "type": "op",
                    "contents": {"i": i}})
    records = base + ops
    for variant, batches in (("one_pump", [records]),
                             ("split_pump", [base, ops])):
        d = str(tmp_path / variant)
        os.makedirs(os.path.join(d, "topics"))
        make_topic(os.path.join(d, "topics", "deltas.jsonl"),
                   "json").append_many(records)
        role = SummarizerRole(d, owner="t", ttl_s=3600.0,
                              summary_ops=n)
        role.fence = 1
        li = 0
        for chunk in batches:
            out = []
            for rec in chunk:
                role.process(li, rec, out)
                li += 1
            role.flush_batch(out)
            if out:
                role.out_topic.append_many(out, fence=1, owner="t")
        mans = manifests_of(d)
        # Multiples 4 (all joins) and 8 skipped/emitted rule: count 4
        # is pre-decision -> skipped; 8, 12, 16 emitted.
        assert [m["count"] for m in mans] == [8, 12, 16], (variant, mans)
        store = open_summary_store(d)
        for m in mans:
            blob = json.loads(store.get(m["handle"]).decode())
            assert blob["form"] == "ops"
            assert len(blob["records"]) == m["count"]  # never empty
        assert_all_boots_equal(d, "j", records)
