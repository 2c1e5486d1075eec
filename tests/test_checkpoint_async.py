"""Cadence checkpoints flush off the pump thread.

`_Role.maybe_checkpoint` takes the snapshot and encodes the envelope on
the pump (`FencedCheckpointStore.encode`); one writer thread a role
flushes it (`FencedCheckpointStore.flush`: lock, fence check, write,
fsync, replace). The file holds the bytes `save` writes; one flush is
in flight at a time and a checkpoint due meanwhile is deferred, never
queued; an explicit `checkpoint()` waits for the flush and writes
inline; a fence rejection or a storage fault in the flush surfaces on
the pump; a successor takes over a role abandoned mid-flush
exactly-once.
"""

from __future__ import annotations

import fcntl
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark import traffic as gen
from benchmark.reference import deli_ref
from fluidframework_tpu.server.columnar_log import make_topic
from fluidframework_tpu.server.queue import DISK_FAULT_ENV, \
    FencedCheckpointStore
from fluidframework_tpu.server.supervisor import EXIT_FENCED, DeliRole
from fluidframework_tpu.utils import metrics as M

WAIT_S = 20.0


@pytest.fixture
def registry():
    old_reg = M.set_registry(M.MetricsRegistry())
    old_log = M.set_span_log(M.SpanLog())
    yield M.get_registry()
    M.set_span_log(old_log)
    M.set_registry(old_reg)


def role_class(impl):
    if impl == "kernel":
        from fluidframework_tpu.server.deli_kernel import KernelDeliRole

        return KernelDeliRole
    return DeliRole


def farm(tmp_path):
    shared = str(tmp_path / "farm")
    os.makedirs(os.path.join(shared, "topics"))
    raw = make_topic(os.path.join(shared, "topics", "rawdeltas.jsonl"),
                     "columnar")
    return shared, raw


def new_role(impl, shared, owner, **kw):
    """A deli whose cadence is due after every step that appends (the
    byte bound at one byte, the storm guard off)."""
    kw = {"ttl_s": 3600.0, "batch": 4096, "log_format": "columnar",
          "ckpt_interval_s": 3600.0, "ckpt_bytes": 1, "ckpt_duty": 0.0,
          **kw}
    return role_class(impl)(shared, owner=owner, **kw)


def started(impl, shared, owner="a"):
    """A role past its first step, with no input yet: lease taken and
    recovery done (an empty output topic leaves it nothing to
    checkpoint)."""
    role = new_role(impl, shared, owner)
    assert role.step(idle_sleep=0) == 0 and role.fence is not None
    return role


def feed(role, raw, recs, wait=True):
    """Append `recs` and step them through in one pump (a cadence
    checkpoint, or a deferral while a flush is in flight)."""
    raw.append_many(recs)
    assert role.step(idle_sleep=0) == len(recs)
    if wait:
        role.wait_checkpoint()


def schedule(seed=7, docs=12, clients=4, rate=400.0, seconds=2.0):
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/docs-10k-64c.json")
    cfg.update(docs=docs, clients=clients)
    trf = harness.load_json(f"{harness.BENCH_DIR}/traffic/open-zipf.json")
    trf.update(rate_per_s=rate)
    return gen.open_loop(cfg, trf, seed, seconds)


def raw_records(s):
    """Every client's join, document-major, then the schedule's ops."""
    recs = [{"kind": "join", "doc": f"doc{d}", "client": c}
            for d in range(s.n_docs) for c in range(1, s.n_clients + 1)]
    recs += [{"kind": "op", "doc": f"doc{int(d)}", "client": int(c),
              "clientSeq": int(q), "refSeq": int(r), "contents": {"i": i}}
             for i, (d, c, q, r) in enumerate(zip(s.doc, s.client, s.cseq,
                                                  s.ref))]
    return recs


def on_disk(role):
    with open(role.ckpt._path(role.name), "rb") as f:
        return f.read()


def envelope(role):
    return json.loads(on_disk(role))


def value(registry, name):
    return registry.counter(name, role="deli").value


def flush_threads():
    return [t for t in threading.enumerate()
            if t.name == "deli.checkpoint.flush" and t.is_alive()]


class Gate:
    """Holds a role's checkpoint flushes at the store until opened, and
    records the offset of each envelope that lands."""

    def __init__(self, role):
        self.opened = threading.Event()
        self.landed = []
        flush = role.ckpt.flush

        def gated(key, payload, fence, owner=None, lock_timeout_s=None):
            if not self.opened.wait(WAIT_S):
                raise TimeoutError("gate never opened")
            n = flush(key, payload, fence, owner, lock_timeout_s)
            self.landed.append(
                json.loads(b"".join(payload))["state"]["offset"])
            return n

        role.ckpt.flush = gated


def wait_until(cond, what):
    deadline = time.time() + WAIT_S
    while not cond():
        assert time.time() < deadline, what
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# the bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["scalar", "kernel"])
def test_async_flush_writes_the_bytes_save_writes(tmp_path, registry, impl):
    """The envelope a cadence checkpoint flushes is byte for byte what
    the synchronous `save` writes for the same state: the kernel deli's
    pre-encoded `EncodedJson` map and the scalar deli's plain dicts."""
    shared, raw = farm(tmp_path)
    role = started(impl, shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:300])
    feed(role, raw, recs[300:700])
    assert value(registry, "checkpoint_flushes_async_total") == 2
    assert value(registry, "checkpoint_writes_total") == 2
    ref = FencedCheckpointStore(str(tmp_path / "ref"))
    state = {"offset": role.offset,
             "state": (role.checkpoint_state or role.snapshot_state)()}
    n = ref.save(role.name, state, fence=role.fence, owner=role.owner)
    with open(ref._path(role.name), "rb") as f:
        want = f.read()
    assert on_disk(role) == want and n == len(want)
    if impl == "kernel":
        assert want == json.dumps({
            "fence": role.fence, "owner": role.owner,
            "state": {"offset": role.offset,
                      "state": role.core.pool.checkpoint_docs()}}).encode()
    assert value(registry, "checkpoint_bytes_total") > len(want)


def test_state_changed_after_the_snapshot_never_reaches_the_file(
        tmp_path, registry):
    """The scalar deli's snapshot is live dicts of its sequencers; they
    are encoded on the pump, so records sequenced while the flush is in
    flight leave the envelope as it was at the snapshot."""
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:200])
    gate = Gate(role)
    feed(role, raw, recs[200:500], wait=False)
    want = json.dumps({"fence": role.fence, "owner": role.owner,
                       "state": {"offset": role.offset,
                                 "state": role.snapshot_state()}}).encode()
    # What the writer holds is the envelope's bytes, not the state.
    assert b"".join(role._ckpt_job.payload) == want
    # Mutates the sequencers it touches; its checkpoint is deferred.
    feed(role, raw, recs[500:900], wait=False)
    gate.opened.set()
    role.wait_checkpoint()
    assert gate.landed == [500]
    assert on_disk(role) == want


def test_async_checkpoint_span_trees(tmp_path, registry):
    """The pump's `deli.checkpoint` holds only what stalls it; the
    writer's `deli.checkpoint.flush` is a root with the store's fence
    and write phases under it."""
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:100])
    M.get_span_log().clear()
    feed(role, raw, recs[100:200])
    spans = M.get_span_log().snapshot()
    kids = {}
    for r in spans:
        kids.setdefault(r[3], []).append(r)

    def children(root):
        return [r[0] for r in sorted(kids.get(root[4], []),
                                     key=lambda r: r[1])]

    (ckpt,) = [r for r in spans if r[0] == "deli.checkpoint"]
    assert children(ckpt) == ["deli.checkpoint.snapshot",
                              "checkpoint.encode",
                              "deli.checkpoint.snapshot"]
    (flush,) = [r for r in spans if r[0] == "deli.checkpoint.flush"]
    assert flush[3] is None and flush[1] >= ckpt[1]
    assert children(flush) == ["checkpoint.fence", "checkpoint.write"]
    # The stall is the pump's time; the flush has its own histogram.
    stall = registry.histogram("checkpoint_ms", role="deli")
    flushed = registry.histogram("checkpoint_flush_ms", role="deli")
    assert stall.count == flushed.count == 2
    assert flushed.sum >= (flush[2] - flush[1]) * 1000.0


# ---------------------------------------------------------------------------
# one flush at a time
# ---------------------------------------------------------------------------


def test_one_flush_in_flight_and_due_checkpoints_deferred(tmp_path,
                                                          registry):
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:100])
    gate = Gate(role)
    lo = 100
    for k in range(5):
        feed(role, raw, recs[lo:lo + 50], wait=False)
        lo += 50
        assert len(flush_threads()) == 1
        assert value(registry, "checkpoint_flush_deferred_total") == k
    assert role._ckpt_dirty  # the deferred progress is still owed
    gate.opened.set()
    role.wait_checkpoint()
    assert not flush_threads()
    assert gate.landed == [150]
    assert value(registry, "checkpoint_flushes_async_total") == 2
    # The next due checkpoint takes the newest state.
    feed(role, raw, recs[lo:lo + 50])
    assert gate.landed == [150, lo + 50] and role.offset == lo + 50
    assert envelope(role)["state"]["offset"] == role.offset
    assert value(registry, "checkpoint_flushes_async_total") == 3
    assert value(registry, "checkpoint_flush_deferred_total") == 4


def test_explicit_checkpoint_waits_and_leaves_the_newer_envelope(
        tmp_path, registry):
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:100])
    gate = Gate(role)
    feed(role, raw, recs[100:300], wait=False)  # its flush, held
    feed(role, raw, recs[300:600], wait=False)  # deferred
    writes = value(registry, "checkpoint_writes_total")
    opener = threading.Timer(0.1, gate.opened.set)
    opener.start()
    try:
        role.checkpoint()
    finally:
        opener.join(WAIT_S)
    assert not opener.is_alive()
    # `save` flushes through the store too: the held flush first, then
    # the inline write.
    assert gate.landed == [300, 600]
    assert envelope(role)["state"]["offset"] == role.offset == 600
    assert value(registry, "checkpoint_writes_total") == writes + 2
    assert not role._ckpt_dirty and role._ckpt_job is None


def test_interleaved_flushes_never_land_out_of_order(tmp_path, registry):
    """Steps, cadence checkpoints and explicit ones racing the writer
    under a short switch interval: no envelope lands at an offset
    below the one before, and the last holds the role's offset."""
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    gate = Gate(role)
    gate.opened.set()
    writes = value(registry, "checkpoint_writes_total")
    recs = raw_records(schedule(rate=1500.0))
    lo = 0
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        deadline = time.time() + WAIT_S
        while lo < len(recs) and time.time() < deadline:
            raw.append_many(recs[lo:lo + 37])
            lo += 37
            role.step(idle_sleep=0)
            if lo % 10 == 0:
                role.checkpoint()
        role.wait_checkpoint()
    finally:
        sys.setswitchinterval(interval)
    assert lo >= len(recs)
    assert value(registry, "checkpoint_flushes_async_total") >= 5
    # Inline writes land here too, and may repeat the offset before.
    assert all(a <= b for a, b in zip(gate.landed, gate.landed[1:]))
    assert value(registry, "checkpoint_writes_total") - writes == \
        len(gate.landed)
    role.checkpoint()
    assert envelope(role)["state"]["offset"] == role.offset == len(recs)


# ---------------------------------------------------------------------------
# fencing and storage faults surface on the pump
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("next_step", ["idle", "busy"])
def test_fenced_flush_exits_the_old_owner_at_its_next_step(
        tmp_path, registry, next_step):
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:100])
    gate = Gate(role)
    feed(role, raw, recs[100:200], wait=False)
    job = role._ckpt_job
    # A successor binds a higher fence on the checkpoint while the old
    # owner's flush is in flight.
    succ = FencedCheckpointStore(os.path.join(shared, "checkpoints"))
    succ.save(role.name, {"offset": 0, "state": None},
              fence=role.fence + 1, owner="b")
    gate.opened.set()
    job.thread.join(WAIT_S)
    assert not job.thread.is_alive() and gate.landed == []
    if next_step == "busy":
        raw.append_many(recs[200:250])
    with pytest.raises(SystemExit) as exc:
        role.step(idle_sleep=0)
    assert exc.value.code == EXIT_FENCED
    assert value(registry, "fence_rejections_total") == 1
    assert envelope(role) == {"fence": role.fence + 1, "owner": "b",
                              "state": {"offset": 0, "state": None}}
    with open(role._hb_path) as f:
        hb = json.load(f)
    assert any(c["name"] == "fence_rejections_total" and c["value"] == 1
               for c in hb["metrics"]["counters"])


def test_disk_fault_in_the_flush_degrades_through_the_pump(
        tmp_path, registry, monkeypatch):
    """ENOSPC on checkpoint writes: the writer retries under the
    storage budget, the pump keeps stepping and exports `degraded` in
    its heartbeat; once the fault clears the flush lands and the flag
    clears the same way."""
    spec = str(tmp_path / "fault.json")
    monkeypatch.setenv(DISK_FAULT_ENV, spec)
    shared, raw = farm(tmp_path)
    role = started("scalar", shared)
    recs = raw_records(schedule())
    feed(role, raw, recs[:100])

    def hb_degraded():
        with open(role._hb_path) as f:
            return json.load(f)["degraded"]

    with open(spec, "w") as f:
        json.dump({"mode": "enospc", "kinds": ["checkpoint"]}, f)
    feed(role, raw, recs[100:200], wait=False)
    job = role._ckpt_job
    assert job is not None

    def step_until(cond, what):
        deadline = time.time() + WAIT_S
        while not cond():
            assert time.time() < deadline, what
            role.step(idle_sleep=0.002)

    step_until(hb_degraded, "degraded never reached the heartbeat")
    assert job.thread.is_alive()  # still inside its retry budget
    assert registry.gauge("role_degraded", role="deli").value == 1.0
    os.remove(spec)
    step_until(lambda: not hb_degraded() and role._ckpt_job is None,
               "the flush never recovered")
    assert job.error is None
    assert value(registry, "disk_retries_total") >= 1
    assert registry.gauge("role_degraded", role="deli").value == 0.0
    assert value(registry, "checkpoint_flushes_async_total") == 2
    assert envelope(role)["state"]["offset"] == 200


# ---------------------------------------------------------------------------
# takeover with a flush in flight
# ---------------------------------------------------------------------------


def deltas_by_op(shared, s):
    """Each op's and join's stamps off the deltas topic, keyed as the
    schedule keys them; a key seen twice is a duplicate."""
    topic = make_topic(os.path.join(shared, "topics", "deltas.jsonl"),
                       "columnar")
    ops, joins, dups = {}, {}, 0
    for r in topic.read_from(0):
        if r.get("type") == "join":
            key, into = (r["doc"], r["client"]), joins
        else:
            key, into = (r["doc"], r["client"], r["clientSeq"]), ops
        dups += key in into
        into[key] = (r["seq"], r["msn"])
    return ops, joins, dups


@pytest.mark.parametrize("impl", ["scalar", "kernel"])
def test_successor_recovers_exactly_once_from_an_abandoned_flush(
        tmp_path, registry, impl):
    """The old owner stops with its flush blocked on the store's lock;
    the successor recovers while it is still in flight. Whichever write
    takes the lock first, the stream is the reference's: every op and
    join stamped once, with the reference's seq and MSN."""
    s = schedule(seed=13, rate=1000.0)
    recs = raw_records(s)
    assert len(recs) > 1500
    shared, raw = farm(tmp_path)
    old = started(impl, shared)
    feed(old, raw, recs[:400])
    feed(old, raw, recs[400:900])
    lock = open(old.ckpt._path(old.name) + ".lock", "a+")
    fcntl.flock(lock.fileno(), fcntl.LOCK_EX)
    try:
        feed(old, raw, recs[900:1300], wait=False)
        job = old._ckpt_job
        time.sleep(0.05)
        assert job.thread.is_alive()  # blocked on the lock
        old.leases.release(old.name)  # abandoned: it steps no more
        new = new_role(impl, shared, "b")
        took = threading.Thread(target=new.step, kwargs={"idle_sleep": 0})
        took.start()
        wait_until(lambda: new.fence is not None, "no takeover")
        time.sleep(0.05)
    finally:
        fcntl.flock(lock.fileno(), fcntl.LOCK_UN)
        lock.close()
    took.join(WAIT_S)
    job.thread.join(WAIT_S)
    assert not took.is_alive() and not job.thread.is_alive()
    assert new.fence > old.fence
    raw.append_many(recs[1300:])
    deadline = time.time() + WAIT_S
    while new.offset < len(recs) and time.time() < deadline:
        new.step(idle_sleep=0)
    new.wait_checkpoint()
    assert new.offset == len(recs)
    env = envelope(new)
    assert (env["fence"], env["owner"]) == (new.fence, "b")
    assert env["state"]["offset"] == len(recs)

    ops, joins, dups = deltas_by_op(shared, s)
    assert dups == 0
    seq_e, msn_e = deli_ref.expected_ops(s)
    got = np.array([ops.get((f"doc{int(d)}", int(c), int(q)), (-1, -1))
                    for d, c, q in zip(s.doc, s.client, s.cseq)])
    assert len(ops) == len(s.doc)
    assert np.array_equal(got[:, 0], seq_e)
    assert np.array_equal(got[:, 1], msn_e)
    jseq, jmsn = deli_ref.expected_joins(s.n_clients)
    for d in range(s.n_docs):
        for c in range(1, s.n_clients + 1):
            assert joins[(f"doc{d}", c)] == (jseq[c - 1], jmsn[c - 1])
    assert len(joins) == s.n_docs * s.n_clients
