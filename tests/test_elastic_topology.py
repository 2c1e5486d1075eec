"""Elastic partition topology: hash-range leases with live split and
merge (`server.queue.RangeLeaseStore` + `server.shard_fabric` elastic
mode), and the storage fault matrix (ENOSPC / stalled fsync) with
graceful degradation.

The paper's routerlicious layer map is a farm of independent lambda
consumers behind a partitioned ordering log where capacity follows
load without a restart; these tests prove the reproduction's form of
that elasticity: a topology change is just another fault the
fenced-handoff machinery survives — the parent's final fenced
checkpoint seeds the children, the children's (fabric-scoped, strictly
higher) fences reject the pre-split owner, the exactly-once ``inOff``
scan closes the durable gap, and the merged per-doc stream never
duplicates or skips a sequence number while N changes mid-run. The
multi-process supervised form under seeded faults lives in
tests/test_chaos_recovery.py.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from fluidframework_tpu.server.queue import (
    HASH_SPACE,
    FencedError,
    RangeLeaseStore,
    doc_hash,
    initial_topology,
    lease_table,
    merge_ranges,
    range_containing,
    range_for_doc,
    split_ranges,
)
from fluidframework_tpu.server.shard_fabric import (
    ShardRouter,
    ShardWorker,
    control_result,
    range_lease_name,
    ranged_role_class,
    request_topology_change,
)
from fluidframework_tpu.server.supervisor import (
    DeliRole,
    _topic_path,
    unwrap_ranged_state,
)


def _workload(docs, n_clients=1, ops=6, base=0):
    recs = []
    for doc in docs:
        if base == 0:
            for c in range(1, n_clients + 1):
                recs.append({"kind": "join", "doc": doc, "client": c})
        for i in range(base, base + ops):
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


def _drain(workers, router, expected, deadline_s=45):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        moved = sum(w.step() for w in workers)
        if len(_merged_ops(router)) >= expected and moved == 0:
            return _merged_ops(router)
    raise AssertionError(
        f"drain timed out: {len(_merged_ops(router))}/{expected}"
    )


def _assert_exactly_once(ops, per_doc_expected=None):
    per = {}
    for r in ops:
        per.setdefault(r["doc"], []).append(r["seq"])
    for doc, seqs in per.items():
        assert sorted(seqs) == list(range(1, len(seqs) + 1)), (
            doc, sorted(seqs)
        )
        if per_doc_expected is not None:
            assert len(seqs) == per_doc_expected, (doc, len(seqs))
    return per


# ---------------------------------------------------------------------------
# topology record + math
# ---------------------------------------------------------------------------


def test_initial_topology_covers_ring_contiguously():
    for n in (1, 3, 4, 7):
        t = initial_topology(n)
        assert t["epoch"] == 1 and len(t["ranges"]) == n
        assert t["ranges"][0]["lo"] == 0
        assert t["ranges"][-1]["hi"] == HASH_SPACE
        for a, b in zip(t["ranges"], t["ranges"][1:]):
            assert a["hi"] == b["lo"]
        assert t["history"] == [e["rid"] for e in t["ranges"]]
    with pytest.raises(ValueError):
        initial_topology(0)


def test_split_and_merge_math_round_trip():
    t = initial_topology(4)
    rid = t["ranges"][1]["rid"]
    t2 = split_ranges(t, rid)
    assert len(t2["ranges"]) == 5
    kids = [e for e in t2["ranges"] if e["preds"] == [rid]]
    assert len(kids) == 2
    assert kids[0]["hi"] == kids[1]["lo"]  # adjacent halves
    # Children are epoch-tagged: a merge recreating the parent's exact
    # bounds must NOT inherit its topics/checkpoint key.
    t2["epoch"] += 1  # as commit_topology would
    t3 = merge_ranges(t2, kids[0]["rid"], kids[1]["rid"])
    merged = next(e for e in t3["ranges"] if len(e["preds"]) == 2)
    assert (merged["lo"], merged["hi"]) == (
        t["ranges"][1]["lo"], t["ranges"][1]["hi"]
    )
    assert merged["rid"] != rid
    # History only grows: every rid ever live stays readable.
    assert set(t["history"]) < set(t3["history"])
    with pytest.raises(ValueError):
        merge_ranges(t3, t3["ranges"][0]["rid"], t3["ranges"][-1]["rid"])
    with pytest.raises(ValueError):
        split_ranges(t, "no-such-range")


def test_range_containing_matches_doc_hash():
    t = split_ranges(initial_topology(3), initial_topology(3)[
        "ranges"][0]["rid"])
    for d in ("a", "b", "doc7", "… unicode ✓", ""):
        h = doc_hash(d)
        e = range_containing(t, h)
        assert e["lo"] <= h < e["hi"]
        assert range_for_doc(t, d) == e


def test_store_bootstrap_commit_cas(tmp_path):
    shared = str(tmp_path)
    s = RangeLeaseStore(shared, "w0")
    topo = s.ensure_topology(4)
    # Idempotent: the first bootstrap wins, later arguments ignored.
    assert RangeLeaseStore(shared, "w1").ensure_topology(8) == topo
    t2 = split_ranges(topo, topo["ranges"][0]["rid"])
    assert s.commit_topology(t2, expect_epoch=1)
    assert s.read_topology()["epoch"] == 2
    # Stale CAS: a concurrent committer must lose, not interleave.
    assert not s.commit_topology(t2, expect_epoch=1)
    assert s.read_topology()["epoch"] == 2


def test_fabric_fences_comparable_across_keys(tmp_path):
    """Range leases draw from ONE fabric-wide monotonic counter: a
    successor's fence is strictly greater than every fence any other
    range ever held — the property its bind on a predecessor's topics
    rests on."""
    s = RangeLeaseStore(str(tmp_path), "w0")
    fences = [s.leases.try_acquire(f"deli-r{i}") for i in range(5)]
    assert fences == [1, 2, 3, 4, 5]


# ---------------------------------------------------------------------------
# elastic routing
# ---------------------------------------------------------------------------


def test_router_routes_by_epoch_and_keeps_history_readable(tmp_path):
    shared = str(tmp_path)
    router = ShardRouter(shared, 2, elastic=True)
    recs = _workload(["a", "b", "doc7", "x1"], ops=2)
    counts = router.append(recs)
    assert sum(counts.values()) == len(recs)
    store = RangeLeaseStore(shared, "admin")
    topo = store.read_topology()
    # Commit a split of the first range behind the router's back: the
    # next append must adopt the new epoch and route to the children.
    t2 = split_ranges(topo, topo["ranges"][0]["rid"])
    assert store.commit_topology(t2, topo["epoch"])
    more = [{"kind": "op", "doc": d, "client": 1, "clientSeq": 3,
             "refSeq": 0, "contents": None}
            for d in ("a", "b", "doc7", "x1")]
    counts2 = router.append(more)
    live = {e["rid"] for e in router.topology["ranges"]}
    assert router.topology["epoch"] == topo["epoch"] + 1
    assert set(counts2) <= live
    # The retired parent's topic stays on the merged read surface.
    names = router.deltas_topic_names()
    assert len(names) == len(router.topology["history"])
    retired = topo["ranges"][0]["rid"]
    assert f"deltas-{retired}" in names


def test_merged_reader_per_range_cursors(tmp_path):
    """Records written under epoch E stay readable after E+1, and the
    reader never re-delivers across a topology change (per-range
    cursors, not re-reads from zero)."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 2, elastic=True)
    reader = router.merged_reader()
    # Sequenced records appear on deltas topics; write some directly.
    topo = router.topology
    t0 = router._topic(topo["ranges"][0]["deltas"])
    t0.append_many([{"kind": "op", "doc": "a", "seq": 1}])
    got = reader.poll()
    assert [r["seq"] for r in got] == [1]
    assert reader.poll() == []  # cursor held: no re-delivery
    # Split; the old topic gains a late record AND a child topic opens.
    store = RangeLeaseStore(shared, "admin")
    t2 = split_ranges(topo, topo["ranges"][0]["rid"])
    assert store.commit_topology(t2, topo["epoch"])
    t0.append_many([{"kind": "op", "doc": "a", "seq": 2}])
    child = next(e for e in router.store.read_topology()["ranges"]
                 if e["preds"])
    router._topic(child["deltas"]).append_many(
        [{"kind": "op", "doc": "a", "seq": 3}]
    )
    got = reader.poll()
    assert sorted(r["seq"] for r in got) == [2, 3]


# ---------------------------------------------------------------------------
# live split / merge, in-proc workers (fast)
# ---------------------------------------------------------------------------


def test_live_split_exactly_once_and_pre_split_owner_rejected(tmp_path):
    shared = str(tmp_path)
    router = ShardRouter(shared, 2, elastic=True)
    w = ShardWorker(shared, "wA", n_partitions=2, ttl_s=5.0,
                    elastic=True)
    w.heartbeat()
    w.sweep()
    docs = [f"doc{i}" for i in range(6)]
    first = _workload(docs, ops=4)
    router.append(first)
    _drain((w,), router, len(first))

    victim = sorted(w.roles)[0]
    deltas = w.roles[victim].out_topic
    old_fence, old_owner = w.roles[victim].fence, w.roles[victim].owner
    cid = request_topology_change(shared, {"op": "split",
                                           "rid": victim})
    deadline = time.time() + 20
    while time.time() < deadline and control_result(shared, cid) is None:
        w.step()
    res = control_result(shared, cid)
    assert res and res.get("op") == "split", res
    assert w.topology["epoch"] == 2

    second = _workload(docs, ops=4, base=4)
    router.append(second)
    ops = _drain((w,), router, len(first) + len(second))
    _assert_exactly_once(ops, per_doc_expected=9)

    # The demonstrable half of the handoff: the pre-split owner's
    # append with its old fence is REJECTED (the children bound
    # strictly higher fabric-scoped fences on the parent's topic).
    with pytest.raises(FencedError):
        deltas.append_many(
            [{"kind": "op", "doc": "zombie", "seq": -1}],
            fence=old_fence, owner=old_owner,
        )


def test_live_merge_exactly_once(tmp_path):
    shared = str(tmp_path)
    router = ShardRouter(shared, 4, elastic=True)
    w = ShardWorker(shared, "wA", n_partitions=4, ttl_s=5.0,
                    elastic=True)
    w.heartbeat()
    w.sweep()
    docs = [f"doc{i}" for i in range(8)]
    first = _workload(docs, ops=3)
    router.append(first)
    _drain((w,), router, len(first))

    ranges = sorted(w.topology["ranges"], key=lambda e: e["lo"])
    cid = request_topology_change(shared, {
        "op": "merge", "rids": [ranges[0]["rid"], ranges[1]["rid"]],
    })
    deadline = time.time() + 20
    while time.time() < deadline and control_result(shared, cid) is None:
        w.step()
    res = control_result(shared, cid)
    assert res and res.get("op") == "merge", res
    assert w.topology["epoch"] == 2
    assert len(w.topology["ranges"]) == 3
    merged = next(e for e in w.topology["ranges"] if e["preds"])
    assert sorted(merged["preds"]) == sorted(
        [ranges[0]["rid"], ranges[1]["rid"]]
    )

    second = _workload(docs, ops=3, base=3)
    router.append(second)
    ops = _drain((w,), router, len(first) + len(second))
    _assert_exactly_once(ops, per_doc_expected=7)


def test_split_two_workers_balance_over_ranges(tmp_path):
    """After a split the range count rises and a peer picks up the new
    capacity: target_partitions follows the LIVE range set."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 2, elastic=True)
    wa = ShardWorker(shared, "wA", n_partitions=2, ttl_s=1.0,
                     elastic=True)
    wb = ShardWorker(shared, "wB", n_partitions=2, ttl_s=1.0,
                     elastic=True)
    for w in (wa, wb):
        w.heartbeat()
        w.sweep()
    recs = _workload([f"doc{i}" for i in range(6)], ops=2)
    router.append(recs)
    _drain((wa, wb), router, len(recs))
    owner_map = {k: w.slot for w in (wa, wb) for k in w.roles}
    assert len(owner_map) == 2  # both ranges owned
    victim = sorted(owner_map)[0]
    cid = request_topology_change(shared, {"op": "split",
                                           "rid": victim})
    deadline = time.time() + 25
    while time.time() < deadline:
        wa.step()
        wb.step()
        wa.heartbeat()
        wb.heartbeat()
        done = control_result(shared, cid)
        total = len(wa.roles) + len(wb.roles)
        bound = all(r.fence is not None
                    for w in (wa, wb) for r in w.roles.values())
        if done and total == 3 and bound:
            break
    assert control_result(shared, cid)
    assert len(wa.roles) + len(wb.roles) == 3
    assert wa.topology["epoch"] == wb.topology["epoch"] == 2
    wa.stop()
    wb.stop()


def test_split_survivor_closes_uncheckpointed_gap(tmp_path):
    """A parent that CRASHED before its final checkpoint (durable
    outputs beyond — or entirely without — a checkpoint) still splits
    exactly-once: the children's fence bind + durable-prefix scan
    silently replays what already landed and emits only the rest."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 1, elastic=True)
    store = RangeLeaseStore(shared, "admin")
    topo = store.read_topology()
    parent = topo["ranges"][0]
    docs = [f"doc{i}" for i in range(4)]
    recs = _workload(docs, ops=5)
    router.append(recs)

    # The parent sequences everything but NEVER checkpoints (huge
    # cadence, no graceful release): its deltas are durable, its
    # checkpoint is absent — the worst crash window.
    cls = ranged_role_class(DeliRole, parent, topo["epoch"])
    role = cls(shared, owner="doomed", ttl_s=3600.0,
               ckpt_interval_s=3600.0)
    for _ in range(50):
        role.step(idle_sleep=0)
    durable = [r for r in role.out_topic.read_from(0)
               if isinstance(r, dict) and r.get("kind") == "op"]
    assert len(durable) == len(recs)
    assert role.ckpt.load(role.name) is None  # truly uncheckpointed
    # "Crash": drop the role, commit the split as an operator would
    # (the owner is dead, so no final checkpoint lands).
    t2 = split_ranges(topo, parent["rid"])
    assert store.commit_topology(t2, topo["epoch"])
    del role

    w = ShardWorker(shared, "wB", n_partitions=1, ttl_s=5.0,
                    elastic=True)
    w.heartbeat()
    w.sweep()
    second = _workload(docs, ops=5, base=5)
    router.append(second)
    ops = _drain((w,), router, len(recs) + len(second))
    _assert_exactly_once(ops, per_doc_expected=11)
    w.stop()


def test_ranged_checkpoint_restorable_by_classic_frontends(tmp_path):
    """The ranged checkpoint envelope (docs + predecessor cursors)
    unwraps for every deli restore path — a fabric checkpoint is not a
    dead end for the classic roles."""
    env = {"__ranged__": 1,
           "docs": {"d": {"doc_id": "d", "seq": 3, "min_seq": 1,
                          "clients": {"1": {"ref_seq": 1,
                                            "client_seq": 2,
                                            "last_update": 0.0}}}},
           "preds": {"r-old": 17}}
    assert unwrap_ranged_state(env) == env["docs"]
    assert unwrap_ranged_state(env["docs"]) == env["docs"]
    assert unwrap_ranged_state(None) is None
    role = DeliRole(str(tmp_path), owner="w", ttl_s=3600.0)
    role.restore_state(env)
    assert role.sequencers["d"].seq == 3


# ---------------------------------------------------------------------------
# disk fault matrix (graceful degradation)
# ---------------------------------------------------------------------------


def test_enospc_backoff_degraded_then_recovers(tmp_path, monkeypatch):
    shared = str(tmp_path / "shared")
    spec = str(tmp_path / "fault.json")
    monkeypatch.setenv("FLUID_DISK_FAULT", spec)
    router = ShardRouter(shared, 1)
    w = ShardWorker(shared, "wA", n_partitions=1, ttl_s=5.0)
    w.heartbeat()
    w.sweep()
    recs = _workload(["solo"], ops=4)
    router.append(recs)
    _drain((w,), router, len(recs))
    role = w.roles[0]
    assert role.degraded is False

    # ENOSPC on: the next durable write enters bounded-retry backoff;
    # the degraded flag must surface in the role heartbeat while it
    # waits. Clear the fault from WITHIN the backoff (on_retry writes
    # the heartbeat before sleeping) by racing a short fault window.
    # (Feed BEFORE arming the fault — the in-proc router shares the
    # env, and ingress is not the surface under test.)
    router.append(_workload(["solo"], ops=2, base=4))
    with open(spec, "w") as f:
        json.dump({"mode": "enospc", "kinds": ["topic"]}, f)

    cleared = {"done": False}
    real_sleep = time.sleep

    def clearing_sleep(s):
        # First backoff sleep observed -> assert visibility, then lift
        # the fault so the SAME write retries through.
        if not cleared["done"] and os.path.exists(spec):
            hb = json.load(open(role._hb_path))
            assert hb["degraded"] is True
            assert role.degraded is True
            os.remove(spec)
            cleared["done"] = True
        real_sleep(min(s, 0.01))

    monkeypatch.setattr(time, "sleep", clearing_sleep)
    try:
        _drain((w,), router, len(recs) + 2)
    finally:
        monkeypatch.setattr(time, "sleep", real_sleep)
    assert cleared["done"], "backoff never engaged"
    assert role.degraded is False  # recovery clears the flag
    ops = _merged_ops(router)
    _assert_exactly_once(ops, per_doc_expected=7)


def test_enospc_hard_fail_after_budget(tmp_path, monkeypatch):
    """A storage fault outlasting the retry budget HARD-FAILS (the
    record was never acknowledged; the supervisor restart is the next
    line of defense) — degradation must not become silent masking."""
    import errno

    shared = str(tmp_path / "shared")
    spec = str(tmp_path / "fault.json")
    monkeypatch.setenv("FLUID_DISK_FAULT", spec)
    router = ShardRouter(shared, 1)
    w = ShardWorker(shared, "wA", n_partitions=1, ttl_s=5.0)
    w.heartbeat()
    w.sweep()
    router.append(_workload(["solo"], ops=2))
    with open(spec, "w") as f:
        json.dump({"mode": "enospc", "kinds": ["topic"]}, f)
    monkeypatch.setattr(time, "sleep", lambda s: None)  # fast budget
    with pytest.raises(OSError) as exc_info:
        deadline = time.time() + 30
        while time.time() < deadline:
            w.step()
    assert exc_info.value.errno == errno.ENOSPC


def test_stalled_fsync_slows_but_never_reorders(tmp_path, monkeypatch):
    shared = str(tmp_path / "shared")
    spec = str(tmp_path / "fault.json")
    monkeypatch.setenv("FLUID_DISK_FAULT", spec)
    with open(spec, "w") as f:
        json.dump({"mode": "stall", "stall_s": 0.05,
                   "kinds": ["topic", "checkpoint"]}, f)
    router = ShardRouter(shared, 1)
    w = ShardWorker(shared, "wA", n_partitions=1, ttl_s=5.0)
    w.heartbeat()
    w.sweep()
    recs = _workload(["solo"], ops=6)
    router.append(recs)
    ops = _drain((w,), router, len(recs))
    _assert_exactly_once(ops, per_doc_expected=7)


def test_supervisor_health_surfaces_degraded_role(tmp_path):
    """The degraded flag rides the role heartbeat into
    `ShardFabricSupervisor.health()` — a fresh degraded role flips the
    fabric to degraded; a stale file does not pin it there."""
    from fluidframework_tpu.server.shard_fabric import (
        ShardFabricSupervisor,
    )

    shared = str(tmp_path)
    sup = ShardFabricSupervisor(shared, n_workers=1, n_partitions=2)
    hb_dir = os.path.join(shared, "hb")
    os.makedirs(hb_dir, exist_ok=True)
    with open(os.path.join(hb_dir, "deli-p1.json"), "w") as f:
        json.dump({"t": time.time(), "degraded": True}, f)
    assert sup.degraded_partitions() == ["deli-p1"]
    assert sup.health()["status"] == "degraded"
    # Stale (older than the heartbeat timeout): ignored.
    with open(os.path.join(hb_dir, "deli-p1.json"), "w") as f:
        json.dump({"t": time.time() - 10 * sup.heartbeat_timeout_s,
                   "degraded": True}, f)
    assert sup.degraded_partitions() == []


def test_lease_table_reports_fence_and_expiry(tmp_path):
    """Satellite: readers can tell a stale pre-split owner from the
    live one by the FENCE, not just the owner string."""
    store = RangeLeaseStore(str(tmp_path), "wA")
    rid = store.ensure_topology(1)["ranges"][0]["rid"]
    f1 = store.leases.try_acquire(range_lease_name(rid))
    tab = lease_table(os.path.join(str(tmp_path), "leases"))
    info = tab[range_lease_name(rid)]
    assert info["owner"] == "wA" and info["fence"] == f1
    assert info["expires"] > time.time()


def test_absorbed_pred_cursors_retired_and_restart_exactly_once(tmp_path):
    """ROADMAP item-2 follow-up: once a split child has drained its
    parent to quiescence (and the parent is dead in the topology by
    construction), the parent's `inSrc` cursor drops out of NEW
    checkpoints — replaced by a `done_preds` tombstone — and a
    restarted successor skips re-absorption entirely while
    exactly-once still holds across the restart."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 1, elastic=True)
    w = ShardWorker(shared, "wA", n_partitions=1, ttl_s=5.0,
                    elastic=True)
    w.heartbeat()
    w.sweep()
    docs = [f"doc{i}" for i in range(6)]
    first = _workload(docs, ops=4)
    router.append(first)
    _drain((w,), router, len(first))
    parent_rid = sorted(w.roles)[0]
    cid = request_topology_change(shared, {"op": "split",
                                           "rid": parent_rid})
    deadline = time.time() + 20
    while time.time() < deadline and control_result(shared, cid) is None:
        w.step()
    assert control_result(shared, cid)
    second = _workload(docs, ops=4, base=4)
    router.append(second)
    _drain((w,), router, len(first) + len(second))

    # Children hold the parent's cursor until the retirement grace
    # passes; shrink it and pump the (quiescent) preds.
    children = dict(w.roles)
    assert len(children) == 2
    for role in children.values():
        assert parent_rid in role._preds
        role.pred_retire_s = 0.05
    deadline = time.time() + 20
    while time.time() < deadline and not all(
        r._preds[parent_rid]["done"] for r in children.values()
    ):
        w.step()
        time.sleep(0.01)
    for role in children.values():
        assert role._preds[parent_rid]["done"]
        role.checkpoint()
        st = role.ckpt.load(role.name)["state"]["state"]
        assert st.get("preds") in ({}, None), st  # cursor DROPPED
        assert st["done_preds"] == [parent_rid]  # tombstone instead
        assert role.metrics.counter(
            "shard_pred_cursors_retired_total",
            **role._metric_labels()).value >= 1

    # Graceful handoff, then a fresh worker restores the tombstoned
    # checkpoints: no re-absorption, and the stream stays exactly-once
    # across the restart.
    w.stop()
    w2 = ShardWorker(shared, "wB", n_partitions=1, ttl_s=5.0,
                     elastic=True)
    w2.heartbeat()
    w2.sweep()
    third = _workload(docs, ops=4, base=8)
    router.append(third)
    ops = _drain((w2,), router,
                 len(first) + len(second) + len(third))
    _assert_exactly_once(ops, per_doc_expected=13)
    for role in w2.roles.values():
        p = role._preds.get(parent_rid)
        assert p is not None and p["done"], (
            "restart lost the retirement tombstone"
        )
    w2.stop()


# ---------------------------------------------------------------------------
# per-partition downstream stages on the elastic fabric (front-door PR)
# ---------------------------------------------------------------------------


def _merged_stage_ops(router, base):
    from fluidframework_tpu.server.columnar_log import make_topic

    out = []
    for name in router.stage_topic_names(base):
        t = make_topic(_topic_path(router.shared_dir, name),
                       router.log_format)
        out.extend(r for r in t.read_from(0)
                   if isinstance(r, dict) and r.get("kind") == "op")
    return out


def _drain_downstream(workers, router, expected, deadline_s=45):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        moved = sum(w.step() for w in workers)
        if (len(_merged_ops(router)) >= expected
                and len(_merged_stage_ops(router, "durable")) >= expected
                and len(_merged_stage_ops(router, "broadcast"))
                >= expected and moved == 0):
            return
    raise AssertionError(
        f"downstream drain timed out: deltas="
        f"{len(_merged_ops(router))} durable="
        f"{len(_merged_stage_ops(router, 'durable'))} of {expected}"
    )


def test_ranged_downstream_split_hands_legs_exactly_once(tmp_path):
    """The routerlicious shape: EVERY stage partitioned. A live split
    mid-stream must hand each range's durable/broadcast legs (and the
    scribe fold) to the successors exactly-once — the per-range
    predecessor absorption generalized beyond the deli."""
    shared = str(tmp_path)
    router = ShardRouter(shared, 2, elastic=True)
    w = ShardWorker(shared, "wA", n_partitions=2, ttl_s=5.0,
                    elastic=True, downstream="split")
    w.heartbeat()
    w.sweep()
    assert all(len(v) == 3 for v in w.down_roles.values())
    docs = [f"doc{i}" for i in range(6)]
    first = _workload(docs, ops=4)
    router.append(first)
    _drain_downstream((w,), router, len(first))

    victim = sorted(w.roles)[0]
    cid = request_topology_change(shared, {"op": "split",
                                           "rid": victim})
    deadline = time.time() + 20
    while time.time() < deadline and control_result(shared, cid) is None:
        w.step()
    assert control_result(shared, cid), "split never committed"
    second = _workload(docs, ops=4, base=4)
    router.append(second)
    expected = len(first) + len(second)
    _drain_downstream((w,), router, expected)

    deltas_ops = _merged_ops(router)
    _assert_exactly_once(deltas_ops, per_doc_expected=9)
    # Both downstream legs carry exactly the sequenced stream —
    # across the split, via their own pred absorption.
    from fluidframework_tpu.server.supervisor import canonical_record

    want = sorted(
        (json.dumps(canonical_record(r), sort_keys=True)
         for r in deltas_ops)
    )
    for base in ("durable", "broadcast"):
        got_ops = _merged_stage_ops(router, base)
        _assert_exactly_once(got_ops, per_doc_expected=9)
        got = sorted(
            (json.dumps(canonical_record(r), sort_keys=True)
             for r in got_ops)
        )
        assert got == want, f"{base} leg diverged from deltas"
    # The out-topic-less ranged stage: scribe folds survived the
    # split too (absorbed silently from the pred deltas tail).
    total = 0
    for roles in w.down_roles.values():
        scribe = next(r for r in roles if r.role_base == "scribe")
        total += sum(int(st["count"]) for st in scribe.docs.values())
    assert total == len(deltas_ops)
    w.stop()


def test_columnar_pred_drain_keeps_encode_columns_fast_path(tmp_path):
    """ROADMAP item-1 follow-up b: a RANGED kernel deli's steady-state
    pred drain tags inSrc via the frame-level src column instead of
    falling back to dict emission — the encode_columns fast path stays
    engaged through an elastic split, differentially checked against
    the dict-path (json log) oracle."""
    from fluidframework_tpu.server.supervisor import canonical_record
    from fluidframework_tpu.utils.metrics import get_registry

    def run(log_format, impl, root):
        shared = os.path.join(str(tmp_path), root)
        router = ShardRouter(shared, 1, log_format, elastic=True)
        w = ShardWorker(shared, "wA", n_partitions=1, ttl_s=5.0,
                        elastic=True, deli_impl=impl,
                        log_format=log_format)
        w.heartbeat()
        w.sweep()
        docs = [f"doc{i}" for i in range(4)]
        first = _workload(docs, ops=3)
        router.append(first)
        _drain((w,), router, len(first))
        parent_rid = sorted(w.roles)[0]
        parent_raw = w.roles[parent_rid].in_topic
        cid = request_topology_change(shared, {"op": "split",
                                               "rid": parent_rid})
        deadline = time.time() + 20
        while time.time() < deadline \
                and control_result(shared, cid) is None:
            w.step()
        assert control_result(shared, cid)
        # Recovery-time absorption settles first, so the NEXT batch
        # exercises the STEADY-STATE pred drain (the src fast path).
        for _ in range(5):
            w.step()
        before = get_registry().counter(
            "codec_encode_columns_total", codec="columnar"
        ).value
        # A stale router lands records on the RETIRED parent topic:
        # the children's pred drains must absorb them.
        stale = _workload(docs, ops=3, base=3)
        parent_raw.append_many(stale)
        expected = len(first) + len(stale)
        ops = _drain((w,), router, expected)
        _assert_exactly_once(ops, per_doc_expected=7)
        after = get_registry().counter(
            "codec_encode_columns_total", codec="columnar"
        ).value
        # Pred-drained records must carry the inSrc tag either way.
        drained = [r for r in _merged_ops(router)
                   if r.get("inSrc") == parent_rid]
        assert drained, "no pred-drained records tagged inSrc"
        w.stop()
        return (sorted(json.dumps(canonical_record(r), sort_keys=True)
                       for r in ops), after - before, len(drained))

    cols, cols_delta, n_src = run("columnar", "kernel", "cols")
    oracle, _j, n_dict = run("json", "scalar", "oracle")
    # Differential: the src-tagged columnar drain reproduces the
    # dict-path oracle bit-identically (canonical form), tags the
    # same record set, and actually ran through encode_columns.
    assert cols == oracle
    assert n_src == n_dict
    assert cols_delta > 0, (
        "pred drain fell back to dict emission (encode_columns "
        "never engaged)"
    )


def test_merge_then_split_live_pred_consumer_deposed_no_dup(tmp_path):
    """The merge→split double-emission hole (caught by the front-door
    storm gate under full-suite contention): after A+B merge into M
    and M splits into C+D, the still-LIVE M may be mid-drain of A's
    tail when C recovers. C must depose M on EVERY pred topic —
    including M's own output — BEFORE scanning any of them; otherwise
    M lands more A-records after C's scan and the same record exists
    in durable-M and durable-C (a downstream-leg duplicate)."""
    from fluidframework_tpu.server.columnar_log import make_topic
    from fluidframework_tpu.server.supervisor import ScriptoriumRole

    shared = str(tmp_path)
    store = RangeLeaseStore(shared, "test")
    topo1 = store.ensure_topology(2)
    r1 = sorted(topo1["ranges"], key=lambda e: e["lo"])
    a, b = r1[0], r1[1]
    # Commit the merge (A+B -> M), then the split (M -> C, D).
    topo2 = merge_ranges(topo1, a["rid"], b["rid"])
    assert store.commit_topology(topo2, topo1["epoch"])
    topo2 = store.read_topology()
    m = topo2["ranges"][0]
    topo3 = split_ranges(topo2, m["rid"])
    # M's downstream consumer, built against epoch 2, still live.
    role_m = ranged_role_class(ScriptoriumRole, m, 2)(
        shared, "owner-m", ttl_s=30.0
    )
    # A's sequenced stream: ops for a doc in C's (lower) half.
    lo_doc = next(f"doc{i}" for i in range(64)
                  if doc_hash(f"doc{i}") < split_ranges(
                      topo2, m["rid"])["ranges"][0]["hi"])
    deltas_a = make_topic(_topic_path(shared, f"deltas-{a['rid']}"))
    mk = lambda s: {"kind": "op", "doc": lo_doc, "seq": s, "msn": s,
                    "client": 1, "clientSeq": s, "refSeq": 0,
                    "type": "op", "contents": {"s": s}, "inOff": s - 1}
    deltas_a.append_many([mk(1), mk(2)])
    role_m.step()           # M drains A's first two records
    role_m.checkpoint()     # cursors land; C will seed from this
    # More A-tail arrives (a stale writer); M has NOT drained it yet.
    deltas_a.append_many([mk(3), mk(4)])
    assert store.commit_topology(topo3, topo2["epoch"])
    c_entry = sorted(store.read_topology()["ranges"],
                     key=lambda e: e["lo"])[0]
    assert m["rid"] in c_entry["preds"]
    role_c = ranged_role_class(ScriptoriumRole, c_entry, 3)(
        shared, "owner-c", ttl_s=30.0
    )
    # Interleave the race at its exact window: the still-live M tries
    # to drain the same A-tail into ITS topic right after C absorbed
    # pred A but BEFORE C's absorb pass reaches pred M. Without the
    # up-front all-preds fence bind, M's append lands (C already
    # re-emitted those records) — the duplicate; with it, M is
    # deposed before C's first scan.
    raced = []
    orig_absorb = role_c._absorb_pred

    def hooked(prid):
        orig_absorb(prid)
        if prid == a["rid"]:
            try:
                role_m.step()
            except (FencedError, SystemExit) as exc:
                raced.append(type(exc).__name__)

    role_c._absorb_pred = hooked
    role_c.step()
    assert raced, "the live pred consumer was never deposed"
    ops = []
    for rid in store.read_topology()["history"]:
        t = make_topic(_topic_path(shared, f"durable-{rid}"))
        ops.extend(r for r in t.read_from(0)
                   if isinstance(r, dict) and r.get("kind") == "op")
    keys = [(r["doc"], r["seq"]) for r in ops]
    assert sorted(keys) == [(lo_doc, s) for s in (1, 2, 3, 4)], keys
