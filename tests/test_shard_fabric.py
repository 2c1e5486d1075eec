"""Sharded ordering fabric: lease-balanced multi-partition deli farm
with fenced partition handoff (`server.shard_fabric`).

The reference splits the document space across Kafka partitions with
ZooKeeper arbitrating ownership (SURVEY.md §2.5); these tests prove
the reproduction's form of that topology: consistent-hash ingress
routing (boxcar-aware), emergent lease balance across workers
(membership change IS the rebalance trigger), fenced handoff with
exactly-once resumption, per-partition metric labels, and the
`LocalServer(n_partitions=)` in-proc face. The multi-process
supervised form under faults lives in tests/test_chaos_recovery.py.
"""

from __future__ import annotations

import os
import time

import pytest

from fluidframework_tpu.server.columnar_log import make_topic
from fluidframework_tpu.server.queue import (
    FencedError,
    LeaseManager,
    lease_table,
    partition_of,
    record_partition,
)
from fluidframework_tpu.server.shard_fabric import (
    ShardFabricSupervisor,
    ShardRouter,
    ShardWorker,
    partition_lease_name,
    spread_doc_names,
)
from fluidframework_tpu.server.supervisor import (
    DeliRole,
    _topic_path,
    partitioned_role_class,
)


def _fabric_workload(docs, n_clients=1, ops=8):
    recs = []
    for doc in docs:
        for c in range(1, n_clients + 1):
            recs.append({"kind": "join", "doc": doc, "client": c})
        for i in range(ops):
            for c in range(1, n_clients + 1):
                recs.append({"kind": "op", "doc": doc, "client": c,
                             "clientSeq": i + 1, "refSeq": 0,
                             "contents": {"i": i}})
    return recs


def _merged_ops(router):
    out = []
    for t in router.deltas_topics():
        out.extend(r for r in t.read_from(0)
                   if isinstance(r, dict) and r.get("kind") == "op")
    return out


def _drain(workers, router, expected, deadline_s=30):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        moved = sum(w.step() for w in workers)
        ops = _merged_ops(router)
        if len(ops) >= expected and moved == 0:
            return ops
    raise AssertionError(
        f"drain timed out: {len(_merged_ops(router))}/{expected}"
    )


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def test_record_partition_and_router_split():
    recs = [
        {"kind": "op", "doc": "a", "client": 1, "clientSeq": 1,
         "refSeq": 0, "contents": None},
        {"kind": "boxcar", "doc": "a", "client": 1, "ops": []},
        {"kind": "join", "doc": "b", "client": 2},
        {"weird": True},          # doc-less junk pins to partition 0
        "not even a dict",
    ]
    n = 4
    pa, pb = partition_of("a", n), partition_of("b", n)
    assert record_partition(recs[0], n) == pa
    assert record_partition(recs[1], n) == pa  # boxcar rides its doc
    assert record_partition(recs[3], n) == 0
    assert record_partition(recs[4], n) == 0
    assert record_partition(recs[0], 1) == 0  # single-partition: all p0


def test_router_appends_per_partition_in_order(tmp_path):
    shared = str(tmp_path)
    docs = spread_doc_names(4, 2)
    router = ShardRouter(shared, 2)
    recs = _fabric_workload(docs, ops=3)
    counts = router.append(recs)
    assert sum(counts.values()) == len(recs)
    assert len(counts) == 2  # both partitions got traffic
    for p in range(2):
        got = router.topics[p].read_from(0)
        want = [r for r in recs if record_partition(r, 2) == p]
        assert got == want  # arrival order preserved within partition


def test_spread_doc_names_covers_partitions():
    for n in (2, 4, 8):
        docs = spread_doc_names(2 * n, n)
        assert len(docs) == 2 * n
        per = {}
        for d in docs:
            per[partition_of(d, n)] = per.get(partition_of(d, n), 0) + 1
        assert set(per) == set(range(n))
        assert all(v == 2 for v in per.values())


# ---------------------------------------------------------------------------
# partitioned role identity
# ---------------------------------------------------------------------------


def test_partitioned_role_class_identity(tmp_path):
    cls = partitioned_role_class(DeliRole, 3)
    assert cls.name == "deli-p3"
    assert cls.in_topic_name == "rawdeltas-p3"
    assert cls.out_topic_name == "deltas-p3"
    assert cls.partition == 3 and cls.role_base == "deli"
    role = cls(str(tmp_path), owner="w", ttl_s=3600.0)
    assert role.in_topic.path.endswith("rawdeltas-p3.jsonl")
    assert role._metric_labels() == {"role": "deli", "partition": "3"}
    # Unpartitioned roles keep the historic label shape.
    plain = DeliRole(str(tmp_path / "plain"), owner="w", ttl_s=3600.0)
    assert plain._metric_labels() == {"role": "deli"}


def test_serve_role_partition_flag_runs_one_pinned_shard(tmp_path):
    """`serve_role --partition` (the supervisor CLI surface) serves
    exactly one partition's topic pair under its own lease."""
    import subprocess
    import sys

    shared = str(tmp_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    raw = make_topic(_topic_path(shared, "rawdeltas-p1"))
    raw.append_many(_fabric_workload(["solo"], ops=5))
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from fluidframework_tpu.server.supervisor import main; main()",
         "--role", "deli", "--dir", shared, "--owner", "W",
         "--partition", "1", "--ttl", "2.0"],
        stdout=subprocess.PIPE, text=True, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        line = proc.stdout.readline().strip()
        assert line == "READY deli-p1 W", line
        deltas = make_topic(_topic_path(shared, "deltas-p1"))
        deadline = time.time() + 20
        ops = []
        while time.time() < deadline:
            ops = [r for r in deltas.read_from(0)
                   if isinstance(r, dict) and r.get("kind") == "op"]
            if len(ops) >= 6:
                break
            time.sleep(0.05)
        assert [r["seq"] for r in ops] == list(range(1, 7))
        # Poll: an instantaneous read can catch the lease mid-expiry
        # when the child is scheduler-starved past the TTL on a loaded
        # box — it renews on its next step, so ownership converges.
        owner = None
        deadline = time.time() + 20
        while time.time() < deadline:
            owner = (lease_table(
                os.path.join(shared, "leases")
            ).get("deli-p1") or {}).get("owner")
            if owner == "W":
                break
            time.sleep(0.05)
        assert owner == "W"
    finally:
        proc.kill()
        proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# lease balance + handoff (in-proc workers: fast, deterministic-ish)
# ---------------------------------------------------------------------------


def test_workers_balance_on_membership_change(tmp_path):
    """A lone worker grabs every partition; a joining peer makes it
    shed down to its fair share (graceful fenced release → immediate
    takeover, no TTL wait)."""
    shared = str(tmp_path)
    wa = ShardWorker(shared, "wA", n_partitions=4, ttl_s=1.0)
    wa.heartbeat()
    wa.sweep()
    for _ in range(8):
        wa.step()
    assert sorted(wa.roles) == [0, 1, 2, 3]
    wb = ShardWorker(shared, "wB", n_partitions=4, ttl_s=1.0)
    wb.heartbeat()

    def settled():
        return (len(wa.roles) == 2 and len(wb.roles) == 2
                and all(r.fence is not None
                        for w in (wa, wb) for r in w.roles.values()))

    deadline = time.time() + 15
    while time.time() < deadline and not settled():
        wa.step()
        wb.step()
    assert settled(), (sorted(wa.roles), sorted(wb.roles))
    assert set(wa.roles) | set(wb.roles) == {0, 1, 2, 3}
    owners = lease_table(os.path.join(shared, "leases"))
    assert {v["owner"] for v in owners.values()} == {"wA", "wB"}
    # The fence field distinguishes every ownership generation.
    assert all(v["fence"] >= 1 for v in owners.values())
    wa.stop()
    wb.stop()


def test_dead_worker_partitions_resume_exactly_once(tmp_path):
    """Kill a worker (stop stepping + stale heartbeat): the survivor's
    target rises, it sweeps the expired leases, restores the fenced
    checkpoints and resumes with contiguous per-doc seqs — no dup, no
    skip, across the handoff."""
    shared = str(tmp_path)
    docs = spread_doc_names(4, 2)
    router = ShardRouter(shared, 2)
    router.append(_fabric_workload(docs, ops=6))
    wa = ShardWorker(shared, "wA", n_partitions=2, ttl_s=0.5,
                     max_partitions=1)
    wb = ShardWorker(shared, "wB", n_partitions=2, ttl_s=0.5,
                     max_partitions=1)
    for w in (wa, wb):
        w.heartbeat()
        w.sweep()
    _drain((wa, wb), router, 4 + 4 * 6, deadline_s=20)
    assert len(wa.roles) == 1 and len(wb.roles) == 1
    dead_parts = set(wa.roles)

    # "Kill" A: it stops stepping and its heartbeat goes stale; B's cap
    # rises so it may take both partitions.
    os.remove(wa._hb_path())
    wb.max_partitions = 2
    second = []
    for doc in docs:
        for i in range(6, 12):
            second.append({"kind": "op", "doc": doc, "client": 1,
                           "clientSeq": i + 1, "refSeq": 0,
                           "contents": {"i": i}})
    router.append(second)
    # Deflake: poll the LEASE TABLE for A's leases to expire instead
    # of a sleep-bounded guess — the fence/expiry fields make the
    # condition exact (a loaded box can stretch "1 second" well past
    # the TTL or not far enough).
    dead_leases = {f"deli-p{p}" for p in dead_parts}
    deadline = time.time() + 15
    while time.time() < deadline:
        live = lease_table(os.path.join(shared, "leases"))
        if not dead_leases & set(live):
            break
        time.sleep(0.05)
    ops = _drain((wb,), router, 4 + 4 * 12, deadline_s=25)
    per = {}
    for r in ops:
        per.setdefault(r["doc"], []).append(r["seq"])
    for doc, seqs in per.items():
        assert sorted(seqs) == list(range(1, len(seqs) + 1)), doc
        assert len(seqs) == 13  # 1 join + 12 ops, exactly once
    assert dead_parts <= set(wb.roles)
    wb.stop()


def test_deposed_partition_owner_write_rejected(tmp_path):
    """The write-path half of fenced handoff: after a takeover, the
    old owner's append to the partition's deltas topic (with its old
    fence) raises FencedError — exactly-once does not rest on the
    loser politely standing down."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 2)
    docs = spread_doc_names(2, 2)
    router.append(_fabric_workload(docs, ops=2))
    wa = ShardWorker(shared, "wA", n_partitions=2, ttl_s=0.4)
    wa.heartbeat()
    wa.sweep()
    _drain((wa,), router, 2 + 2 * 2, deadline_s=15)
    p = sorted(wa.roles)[0]
    old_fence = wa.roles[p].fence
    deltas = wa.roles[p].out_topic
    assert old_fence is not None

    # A stops renewing; its lease expires — polled off the lease
    # table (exact: the entry vanishes at expiry) instead of a
    # sleep-bounded guess. A successor then takes over and its FENCE
    # must strictly advance past the deposed owner's.
    os.remove(wa._hb_path())
    deadline = time.time() + 10
    while time.time() < deadline:
        if partition_lease_name(p) not in lease_table(
                os.path.join(shared, "leases")):
            break
        time.sleep(0.05)
    wb = ShardWorker(shared, "wB", n_partitions=2, ttl_s=5.0)
    wb.heartbeat()
    deadline = time.time() + 10
    while time.time() < deadline:
        wb.step()
        if p in wb.roles and wb.roles[p].fence is not None:
            break
    assert wb.roles[p].fence is not None
    assert wb.roles[p].fence > old_fence
    # And the observer view carries the successor's fence.
    info = lease_table(os.path.join(shared, "leases"))[
        partition_lease_name(p)]
    assert info["fence"] == wb.roles[p].fence > old_fence
    with pytest.raises(FencedError):
        deltas.append_many(
            [{"kind": "op", "doc": "zombie", "seq": -1}],
            fence=old_fence, owner=wa.owner,
        )
    wb.stop()


def test_graceful_release_skips_ttl_wait(tmp_path):
    """ShardWorker.stop() hands partitions off with expires=0: a
    successor acquires IMMEDIATELY instead of waiting out the TTL."""
    shared = str(tmp_path)
    wa = ShardWorker(shared, "wA", n_partitions=1, ttl_s=30.0)
    wa.heartbeat()
    wa.sweep()
    for _ in range(4):
        wa.step()
    assert 0 in wa.roles and wa.roles[0].fence is not None
    wa.stop()
    lm = LeaseManager(os.path.join(shared, "leases"), "wB", ttl_s=30.0)
    fence = lm.try_acquire(partition_lease_name(0))
    assert fence is not None  # no 30s wait: released, not expired


def test_worker_metrics_carry_partition_labels(tmp_path):
    """Per-partition metric labels (role="deli", partition="k") ride
    the worker heartbeat so the supervisor scrape can merge workers
    without collapsing partitions."""
    import json

    from fluidframework_tpu.utils import metrics as M

    shared = str(tmp_path)
    router = ShardRouter(shared, 2)
    router.append(_fabric_workload(spread_doc_names(2, 2), ops=2))
    reg = M.MetricsRegistry()
    prev = M.set_registry(reg)
    try:
        w = ShardWorker(shared, "wA", n_partitions=2, ttl_s=2.0)
        w.heartbeat()
        w.sweep()
        _drain((w,), router, 2 + 2 * 2, deadline_s=15)
        w.heartbeat()
    finally:
        M.set_registry(prev)
    hb = json.load(open(w._hb_path()))
    assert hb["partitions"] == [0, 1]
    labels = {
        (m.get("labels") or {}).get("partition")
        for m in hb["metrics"].get("counters", [])
        if m.get("name") == "role_records_total"
    }
    assert labels == {"0", "1"}
    w.stop()


# ---------------------------------------------------------------------------
# supervised fabric (multi-process, no faults — chaos runs the faults)
# ---------------------------------------------------------------------------


def test_supervised_fabric_drains_and_reports(tmp_path):
    shared = str(tmp_path)
    docs = spread_doc_names(4, 4)
    router = ShardRouter(shared, 4)
    sup = ShardFabricSupervisor(
        shared, n_workers=2, n_partitions=4, ttl_s=0.6,
        heartbeat_timeout_s=3.0,
    ).start()
    try:
        recs = _fabric_workload(docs, ops=4)
        router.append(recs)
        deadline = time.time() + 40
        ops = []
        while time.time() < deadline:
            sup.poll_once()
            ops = _merged_ops(router)
            if len(ops) >= len(recs):
                break
            time.sleep(0.05)
        assert len(ops) == len(recs)
        # The drain can finish before the second worker's rebalance
        # lands; give ownership a moment to settle across BOTH workers.
        deadline = time.time() + 20
        owners = {}
        while time.time() < deadline:
            sup.poll_once()
            owners = sup.partition_owners()
            if (set(owners) == {f"deli-p{k}" for k in range(4)}
                    and len({o.split("-g")[0]
                             for o in owners.values()}) == 2):
                break
            time.sleep(0.1)
        assert set(owners) == {f"deli-p{k}" for k in range(4)}
        assert len({o.split("-g")[0] for o in owners.values()}) == 2
        h = sup.health()
        assert h["status"] == "ok" and h["n_partitions"] == 4
        reg = sup.collect_metrics()
        assert reg.gauge("shard_partitions_total").value == 4
        assert reg.gauge("shard_partitions_owned_live").value == 4
    finally:
        sup.stop()


def test_chatty_child_stdout_drained_no_wedge(tmp_path):
    """A long-lived worker prints one line per deposed/fenced partition;
    the supervisor must drain its stdout pipe or the child's print()
    blocks once 64KB accumulate and the whole worker stalls with no
    real fault. Drive a child that outprints the pipe capacity many
    times over and prove it neither blocks nor gets restarted."""
    import sys

    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    shared = str(tmp_path)
    progress = str(tmp_path / "progress")
    child_src = (
        "import json, os, sys, time\n"
        "hb, prog = sys.argv[1], sys.argv[2]\n"
        "print('READY chatty', flush=True)\n"
        "n, t0 = 0, time.time()\n"
        "while time.time() - t0 < 8:\n"
        "    print('DEPOSED ' + 'x' * 1000, flush=True)\n"
        "    n += 1\n"
        "    if n % 100 == 0:\n"
        "        with open(hb + '.tmp', 'w') as f:\n"
        "            json.dump({'t': time.time()}, f)\n"
        "        os.replace(hb + '.tmp', hb)\n"
        "        with open(prog + '.tmp', 'w') as f:\n"
        "            f.write(str(n))\n"
        "        os.replace(prog + '.tmp', prog)\n"
    )

    class ChattySup(ServiceSupervisor):
        def _child_cmd(self, role, owner):
            return [sys.executable, "-c", child_src,
                    self._hb_file(role), progress]

    sup = ChattySup(shared, roles=("chatty",), heartbeat_timeout_s=6.0)
    sup.start()
    try:
        deadline = time.time() + 4
        while time.time() < deadline:
            sup.poll_once()
            time.sleep(0.05)
        lines = int(open(progress).read())
        # 64KB of 1KB lines is ~65 — well past that means the pipe is
        # being drained, not filled.
        assert lines * 1009 > 4 * 65536, f"child stalled at {lines} lines"
        assert sup.procs["chatty"].poll() is None
        assert sup.restarts["chatty"] == 0
        # The bounded tail survives for restart diagnostics.
        assert 0 < len(sup._stdout_tails["chatty"]) <= 2048
    finally:
        sup.stop()


# ---------------------------------------------------------------------------
# LocalServer(n_partitions=)
# ---------------------------------------------------------------------------


def test_localserver_sharded_ingress_and_restart(tmp_path):
    from fluidframework_tpu.protocol.messages import DocumentMessage
    from fluidframework_tpu.server import LocalServer

    persist = str(tmp_path / "srv")
    srv = LocalServer(persist_dir=persist, n_partitions=2)
    docs = spread_doc_names(4, 2)
    for doc in docs:
        sock = srv.connect(doc)
        sock.submit(DocumentMessage(client_seq=1, ref_seq=0,
                                    contents={"d": doc}))
        sock.submit_batch([
            DocumentMessage(client_seq=2, ref_seq=0, contents=1),
            DocumentMessage(client_seq=3, ref_seq=0, contents=2),
        ])
    for doc in docs:
        seqs = [m.sequence_number for m in srv.ops_from(doc, 0)]
        assert seqs == list(range(1, len(seqs) + 1))
    # Both partitions actually carried traffic and checkpoint per-k.
    cps = srv.checkpoints()
    assert "deli-p0" in cps and "deli-p1" in cps and "deli" not in cps
    assert all(
        srv.log.topic(f"rawdeltas-p{k}").head > 0 for k in range(2)
    )
    # Restart: per-partition journals + checkpoints resume the docs.
    srv2 = LocalServer(persist_dir=persist, n_partitions=2)
    for doc in docs:
        seqs = [m.sequence_number for m in srv2.ops_from(doc, 0)]
        assert seqs == list(range(1, len(seqs) + 1))
    sock = srv2.connect(docs[0])
    assert sock.client_id == 2  # join replay covered partition topics


def test_localserver_sharded_summary_controls_route(tmp_path):
    """Scribe's summary ack controls route back through the doc's
    partition (the raw_router seam): the summarize round-trip — client
    summary → scribe validate → SUMMARY_ACK via deli — works
    sharded."""
    from fluidframework_tpu.dds import StringFactory
    from fluidframework_tpu.runtime import ChannelRegistry, ContainerRuntime
    from fluidframework_tpu.runtime.summary_manager import SummaryManager
    from fluidframework_tpu.server import LocalServer

    registry = ChannelRegistry([StringFactory()])
    srv = LocalServer(n_partitions=4)
    rt = ContainerRuntime(registry)
    rt.create_datastore("default").create_channel(
        "s", StringFactory.type_name
    )
    rt.connect(srv.connect("doc0"))
    mgr = SummaryManager(rt, srv, max_ops=1)
    s = rt.get_datastore("default").get_channel("s")
    for i in range(3):
        s.insert_text(0, f"{i}")
        rt.flush()
    acks = []
    mgr.collection.on("ack", acks.append)
    assert mgr.maybe_summarize()
    assert len(acks) == 1  # ack came back through the partition topic
    assert srv.storage.get_ref("doc0") == acks[0]["handle"]


def test_localserver_rejects_bad_n_partitions():
    from fluidframework_tpu.server import LocalServer

    with pytest.raises(ValueError):
        LocalServer(n_partitions=0)


# ---------------------------------------------------------------------------
# partition count never changes a document's order
# ---------------------------------------------------------------------------


def _drain_partitions(work, workload, n_partitions):
    """Split `workload` by `record_partition` into one columnar raw
    topic per partition, drain each through the scalar deli, and
    return the outputs merged per document."""
    from fluidframework_tpu.testing.pipeline import (
        _canonical_by_doc,
        run_pipeline,
    )

    shards = [[] for _ in range(n_partitions)]
    for rec in workload:
        shards[record_partition(rec, n_partitions)].append(rec)
    assert all(shards), "a partition got no records"
    out_paths = []
    for p, recs in enumerate(shards):
        pdir = os.path.join(work, f"P{n_partitions}", f"p{p}")
        os.makedirs(pdir)
        raw_path = os.path.join(pdir, "rawdeltas.jsonl")
        make_topic(raw_path, "columnar").append_many(recs)
        out_paths.append(run_pipeline("scalar", raw_path, pdir,
                                      log_format="columnar")["out_path"])
    return _canonical_by_doc(out_paths)


@pytest.mark.parametrize("n_partitions", [2, 4])
def test_partition_count_keeps_each_documents_order(n_partitions,
                                                    tmp_path):
    """The same workload split over P partitions sequences every
    document exactly as one partition does: same seqs, MSNs, nacks
    and contents, record for record."""
    from fluidframework_tpu.testing.pipeline import build_pipeline_workload

    workload = build_pipeline_workload(
        24, 2, 2, doc_names=spread_doc_names(24, 4)
    )
    single = _drain_partitions(str(tmp_path), workload, 1)
    assert len(single) == 24
    assert sum(map(len, single.values())) == len(workload)
    assert _drain_partitions(str(tmp_path), workload,
                             n_partitions) == single


# ---------------------------------------------------------------------------
# per-partition downstream stages (static fabric, front-door PR)
# ---------------------------------------------------------------------------


def test_worker_runs_fused_downstream_per_partition(tmp_path):
    """ShardWorker(downstream="fused"): every owned partition gets its
    own fused durable+broadcast consumer (deltas-p{k} -> durable-p{k}
    + broadcast-p{k}) and scribe, riding deli ownership under their
    own fenced leases."""
    import json as _json

    from fluidframework_tpu.server.supervisor import canonical_record

    shared = str(tmp_path)
    n_p = 2
    router = ShardRouter(shared, n_p)
    w = ShardWorker(shared, "wA", n_partitions=n_p, ttl_s=5.0,
                    downstream="fused")
    w.heartbeat()
    w.sweep()
    assert set(w.down_roles) == set(w.roles)
    fused = w.down_roles[0][0]
    assert fused.bc_topic_name == "broadcast-p0"
    assert fused.name == "scriptorium_broadcaster-p0"
    docs = spread_doc_names(6, n_p)
    workload = _fabric_workload(docs, ops=4)
    router.append(workload)
    deadline = time.time() + 30
    while time.time() < deadline:
        moved = w.step()
        durable = []
        for p in range(n_p):
            t = make_topic(_topic_path(shared, f"durable-p{p}"))
            durable.extend(r for r in t.read_from(0)
                           if isinstance(r, dict)
                           and r.get("kind") == "op")
        if len(durable) >= len(workload) and moved == 0:
            break
    deltas_ops = _merged_ops(router)
    assert len(deltas_ops) == len(workload)
    want = sorted(_json.dumps(canonical_record(r), sort_keys=True)
                  for r in deltas_ops)
    for base in ("durable", "broadcast"):
        got = []
        for p in range(n_p):
            t = make_topic(_topic_path(shared, f"{base}-p{p}"))
            got.extend(r for r in t.read_from(0)
                       if isinstance(r, dict) and r.get("kind") == "op")
        assert sorted(
            _json.dumps(canonical_record(r), sort_keys=True)
            for r in got
        ) == want, f"{base} legs diverged"
    # Scribe folded every partition's stream under its own lease.
    total = 0
    for roles in w.down_roles.values():
        scribe = next(r for r in roles if r.role_base == "scribe")
        total += sum(int(st["count"]) for st in scribe.docs.values())
    assert total == len(deltas_ops)
    # Downstream leases are real: per-partition names, fenced.
    owners = lease_table(os.path.join(shared, "leases"))
    assert "scriptorium_broadcaster-p0" in owners
    assert "scribe-p1" in owners
    w.stop()


def test_downstream_validation():
    with pytest.raises(ValueError):
        ShardWorker("/tmp/x-nonexistent-vald", "w", downstream="bogus")
    with pytest.raises(ValueError):
        ShardWorker("/tmp/x-nonexistent-vald", "w", elastic=True,
                    downstream="fused")
    from fluidframework_tpu.server.shard_fabric import ranged_role_class
    from fluidframework_tpu.server.supervisor import (
        ScriptoriumBroadcasterRole,
    )

    with pytest.raises(ValueError):
        ranged_role_class(
            ScriptoriumBroadcasterRole,
            {"rid": "r0", "lo": 0, "hi": 10, "preds": []}, 1,
        )


def test_merged_reader_reads_downstream_stage(tmp_path):
    """MergedDeltasReader(base=...) is the elastic read surface for
    ANY stage's legs, not just deltas."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 2)
    for p in range(2):
        t = make_topic(_topic_path(shared, f"durable-p{p}"))
        t.append_many([{"kind": "op", "doc": f"d{p}", "seq": 1,
                        "inOff": 0}])
    reader = router.merged_reader("durable")
    recs = reader.poll()
    assert {r["doc"] for r in recs} == {"d0", "d1"}
    assert reader.poll() == []  # incremental: nothing new
