"""Chaos harness + self-healing supervisor: fenced, exactly-once
lambda recovery.

The convergence claim (identical deterministic replay of one totally
ordered stream) exercised OFF the happy path: the lambda pipeline runs
as supervised child processes (`server.supervisor`), faults are
injected at seeded points (`testing.chaos`), and the farm must
converge bit-identical to the no-fault GOLDEN digest with zero
duplicate and zero skipped sequence numbers — while a deposed lease
holder's writes are demonstrably REJECTED by the fence.

Quick single-fault runs stay in tier-1; the full five-fault suite is
`slow` + `chaos` (tools/chaos_run.py is its CLI twin).
"""

from __future__ import annotations

import json
import os
import time

import pytest

from fluidframework_tpu.server.queue import SharedFileTopic
from fluidframework_tpu.server.supervisor import DeliRole, \
    ServiceSupervisor
from fluidframework_tpu.testing.chaos import (
    ChaosConfig,
    build_workload,
    golden_stream,
    run_chaos,
    sequence_integrity,
    stream_digest,
)


def _assert_converged(res):
    assert res.duplicate_seqs == 0, res.detail
    assert res.skipped_seqs == 0, res.detail
    assert res.digest == res.golden_digest, res.detail
    assert res.scribe_ok, res.detail
    assert res.converged, res.detail


def _assert_no_crash_restarts(res):
    """A NO-FAULT run must never see a child CRASH (`exit=` restart).
    A stale-heartbeat restart, by contrast, is the supervisor
    recovering a scheduler-STARVED child — on a loaded 2-core suite
    run a healthy role can miss its heartbeat window — and the run
    still converges bit-identically (asserted separately), so tolerate
    a bounded number of those rather than flake."""
    crashes = [e for e in res.events
               if e.startswith("restart") and "exit=" in e]
    assert not crashes, crashes
    assert sum(res.restarts.values()) <= 2, (res.restarts, res.events)


def test_supervised_farm_no_fault_matches_golden(tmp_path):
    """The multi-process farm with NO faults reproduces the in-proc
    golden stream bit-identically — the baseline every fault class is
    measured against."""
    # timeout is a deadline for a CONDITION poll inside run_chaos, not
    # a sleep: generous bounds deflake slow boxes without slowing the
    # happy path (240s: the old 120s still tripped on a contended
    # 2-core box when child spawns landed behind a bench run).
    res = run_chaos(ChaosConfig(
        seed=11, faults=(), n_docs=1, n_clients=2, ops_per_client=15,
        timeout_s=240, shared_dir=str(tmp_path),
    ))
    _assert_converged(res)
    _assert_no_crash_restarts(res)


def test_supervised_farm_no_fault_columnar_matches_golden(tmp_path):
    """The farm over the COLUMNAR binary op-log (every topic a
    record-batch log, ingress riding wire boxcars — the ROADMAP
    (a)/(d) storage path) reproduces the in-proc golden stream
    bit-identically: the wire form must never change the order."""
    res = run_chaos(ChaosConfig(
        seed=11, faults=(), n_docs=1, n_clients=2, ops_per_client=15,
        timeout_s=240, shared_dir=str(tmp_path),
        log_format="columnar", boxcar_rate=0.3,
    ))
    _assert_converged(res)
    _assert_no_crash_restarts(res)


@pytest.mark.chaos
def test_sharded_fabric_kill_lease_mid_boxcar_converges(tmp_path):
    """THE sharded-fabric acceptance gate (server.shard_fabric): kill
    a shard worker mid-stream (boxcars in flight) AND depose a
    partition owner via expired-lease takeover, on the KERNEL deli
    over COLUMNAR partition topics — the merged sequenced stream
    across all four deltas-p{k} must converge bit-identical to the
    single-partition in-proc golden with zero duplicated or skipped
    per-document sequence numbers, and the deposed owner's writes must
    be demonstrably fence-rejected."""
    res = run_chaos(ChaosConfig(
        seed=7, faults=("kill", "lease"), n_docs=4, n_clients=2,
        ops_per_client=12, timeout_s=240, shared_dir=str(tmp_path),
        deli_impl="kernel", log_format="columnar", boxcar_rate=0.25,
        n_partitions=4, n_workers=2,
    ))
    assert res.duplicate_seqs == 0, res.detail
    assert res.skipped_seqs == 0, res.detail
    assert res.digest == res.golden_digest, res.detail
    assert res.converged, res.detail
    assert res.fence_rejections >= 1  # deposed partition owner rejected
    # Both workers draw a seeded kill; a kill landing on an
    # already-dead slot is skipped, so >=1 restart is the hard floor.
    assert sum(res.restarts.values()) >= 1


@pytest.mark.chaos
def test_elastic_fabric_kill_split_merge_converges(tmp_path):
    """THE elastic-topology acceptance gate (ISSUE 8): a worker
    SIGKILLed mid-stream AND a live range split AND a live merge —
    kernel deli over columnar topics, 4 initial hash ranges, boxcars
    in flight, N changing mid-run twice — must converge bit-identical
    to the single-partition in-proc golden with zero duplicated or
    skipped sequence numbers, while the PRE-SPLIT owner's stale-fence
    write is demonstrably rejected. Capacity following load without a
    restart is exactly this: a topology change is just another fault
    the fenced-handoff machinery survives."""
    res = run_chaos(ChaosConfig(
        seed=7, faults=("kill", "split", "merge"), n_docs=4,
        n_clients=2, ops_per_client=12, timeout_s=300,
        shared_dir=str(tmp_path), deli_impl="kernel",
        log_format="columnar", boxcar_rate=0.25,
        n_partitions=4, n_workers=2,
    ))
    assert res.duplicate_seqs == 0, res.detail
    assert res.skipped_seqs == 0, res.detail
    assert res.digest == res.golden_digest, res.detail
    assert res.converged, res.detail
    assert res.fence_rejections >= 1  # pre-split owner rejected
    assert len(res.epochs) >= 3, res.epochs  # split AND merge committed
    assert sum(res.restarts.values()) >= 1  # the kill actually landed


@pytest.mark.chaos
def test_elastic_fabric_disk_faults_degrade_and_recover(tmp_path):
    """The storage fault classes (ISSUE 8): ENOSPC on the workers'
    topic/checkpoint writes plus a stalled-fsync episode. The fabric
    must degrade gracefully — bounded-retry backoff with `degraded`
    visible in health() while the fault holds — and converge with no
    lost acknowledged record once it clears."""
    res = run_chaos(ChaosConfig(
        seed=3, faults=("disk",), n_docs=2, n_clients=2,
        ops_per_client=10, timeout_s=240, shared_dir=str(tmp_path),
        n_partitions=2, n_workers=2,
    ))
    assert res.duplicate_seqs == 0, res.detail
    assert res.skipped_seqs == 0, res.detail
    assert res.digest == res.golden_digest, res.detail
    assert res.degraded_seen, res.detail
    assert res.converged, res.detail


@pytest.mark.chaos
def test_chaos_kill_torn_columnar_kernel_converges(tmp_path):
    """Kill + torn faults against the KERNEL deli over COLUMNAR topics
    (boxcarred ingress): exactly-once recovery, torn-tail sealing, and
    CRC-guarded framing must keep the binary log bit-identical to the
    scalar JSON golden."""
    res = run_chaos(ChaosConfig(
        seed=3, faults=("kill", "torn"), n_docs=2, n_clients=2,
        ops_per_client=12, timeout_s=150, shared_dir=str(tmp_path),
        deli_impl="kernel", log_format="columnar", boxcar_rate=0.25,
    ))
    _assert_converged(res)
    assert sum(res.restarts.values()) >= 4
    # Recovery restored from the kernel deli's incremental checkpoints:
    # the last one is the full encoding of its own content, and both
    # deli implementations restore it to the same heads.
    from fluidframework_tpu.server.deli_kernel import KernelDeliRole

    with open(os.path.join(str(tmp_path), "checkpoints",
                           "deli.ckpt.json"), "rb") as f:
        raw = f.read()
    env = json.loads(raw)
    assert raw == json.dumps(env).encode()
    docs = env["state"]["state"]
    assert docs and all(st["seq"] > 0 for st in docs.values())
    kernel = KernelDeliRole(str(tmp_path / "k"), owner="k", ttl_s=3600.0)
    kernel.restore_state(docs)
    assert kernel.snapshot_state() == docs
    scalar = DeliRole(str(tmp_path / "s"), owner="s", ttl_s=3600.0)
    scalar.restore_state(docs)
    assert {d: st["seq"] for d, st in scalar.snapshot_state().items()} \
        == {d: st["seq"] for d, st in docs.items()}


def test_chaos_kill_every_role_exactly_once(tmp_path):
    """SIGKILL of each lambda role at seeded points: the supervisor
    restarts it, recovery replays deterministically from the fenced
    checkpoint, and the stream carries no duplicate or skipped seq."""
    res = run_chaos(ChaosConfig(
        seed=1, faults=("kill",), n_docs=1, n_clients=2,
        ops_per_client=25, timeout_s=90, shared_dir=str(tmp_path),
    ))
    _assert_converged(res)
    assert sum(res.restarts.values()) >= 4  # every role died once


def test_chaos_lease_takeover_rejects_deposed_writer(tmp_path):
    """Expired-lease takeover: the sequencer is stalled past its TTL,
    a usurper binds the next fence, and the deposed owner's topic AND
    checkpoint writes are rejected — convergence must still hold."""
    res = run_chaos(ChaosConfig(
        seed=2, faults=("lease",), n_docs=1, n_clients=2,
        ops_per_client=20, timeout_s=90, shared_dir=str(tmp_path),
    ))
    _assert_converged(res)
    assert res.fence_rejections >= 2  # topic + checkpoint both rejected


def test_chaos_torn_appends_and_resubmit_dedup(tmp_path):
    """Torn topic appends plus client mid-batch resubmissions: readers
    skip sealed junk without crashing and deli dedups duplicates, so
    the total order is byte-for-byte the no-fault one."""
    res = run_chaos(ChaosConfig(
        seed=4, faults=("torn", "client"), n_docs=1, n_clients=2,
        ops_per_client=20, timeout_s=90, shared_dir=str(tmp_path),
    ))
    _assert_converged(res)


def test_chaos_net_duplicated_delayed_delivery(tmp_path):
    """Duplicated/delayed delivery on the broadcast edge: the client
    gap/dedup guard reconstructs the exact stream."""
    res = run_chaos(ChaosConfig(
        seed=6, faults=("net",), n_docs=1, n_clients=2,
        ops_per_client=20, timeout_s=120, shared_dir=str(tmp_path),
    ))
    _assert_converged(res)
    assert res.client_digest == res.golden_digest


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_full_chaos_suite_converges(seed, tmp_path):
    """The acceptance gate: all five fault classes composed (SIGKILL of
    every role, torn appends, expired-lease takeover with fence
    rejection, duplicated/delayed delivery, client disconnect
    mid-batch) at full workload size, per seed."""
    res = run_chaos(ChaosConfig(
        seed=seed, timeout_s=180, shared_dir=str(tmp_path),
    ))
    _assert_converged(res)
    assert res.fence_rejections > 0
    assert res.client_digest == res.golden_digest
    assert sum(res.restarts.values()) >= 4


def test_workload_and_golden_deterministic(tmp_path):
    """Same seed → byte-identical workload and golden digest; a
    different seed diverges (the suite is genuinely seeded)."""
    cfg = ChaosConfig(seed=9, n_docs=2, n_clients=2, ops_per_client=10)
    w1 = build_workload(cfg)
    w2 = build_workload(ChaosConfig(
        seed=9, n_docs=2, n_clients=2, ops_per_client=10
    ))
    assert w1 == w2
    g1 = golden_stream(w1, str(tmp_path / "a"))
    g2 = golden_stream(w2, str(tmp_path / "b"))
    assert stream_digest(g1) == stream_digest(g2)
    w3 = build_workload(ChaosConfig(
        seed=10, n_docs=2, n_clients=2, ops_per_client=10
    ))
    assert w3 != w1
    assert sequence_integrity(g1) == (0, 0)


def test_client_farm_survives_server_sigkill_live_reconnect(tmp_path):
    """Client-side chaos composed with a REAL process kill: containers
    stay live through `kill -9` of the ordering service. The
    FaultInjectionDriver wraps the socket driver (the test-service-load
    composition), the jittered ConnectionManager rides the restart on
    the same port, pending ops made while the service was DOWN
    resubmit exactly once, and the replicas converge."""
    import signal
    import subprocess
    import sys

    from fluidframework_tpu.dds import MapFactory, StringFactory
    from fluidframework_tpu.drivers import FaultInjectionDriver
    from fluidframework_tpu.drivers.socket_driver import SocketDriver
    from fluidframework_tpu.loader import ConnectionManager, Loader
    from fluidframework_tpu.runtime import ChannelRegistry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    storage = str(tmp_path / "srv")

    def spawn(port=0):
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(repo, "tools", "socket_server_main.py"),
             str(port), "--storage-dir", storage, "--allow-anonymous"],
            stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=repo,
        )
        line = proc.stdout.readline().strip()
        assert line.startswith("LISTENING"), line
        _, host, p = line.split()
        return proc, host, int(p)

    proc, host, port = spawn()
    proc2 = None
    registry = ChannelRegistry([MapFactory(), StringFactory()])
    try:
        driver = FaultInjectionDriver(SocketDriver(host, port))
        loader = Loader(driver, registry)
        c1 = loader.create_detached()
        c1.runtime.create_datastore("default").create_channel(
            "s", StringFactory.type_name
        )
        doc = c1.attach()
        cm = ConnectionManager(
            c1, max_attempts=12, base_delay=0.05, max_delay=0.5,
            jitter=0.2, seed=13,
        )
        s1 = c1.runtime.get_datastore("default").get_channel("s")
        s1.insert_text(0, "before")
        c1.flush()

        def wait_clean(deadline_s=10.0):
            # Bounded condition poll (not a wall-clock sleep): the op
            # is ack'd round-trip once the runtime is no longer dirty,
            # which is exactly when the durable journal has it.
            deadline = time.time() + deadline_s
            while c1.runtime.is_dirty and time.time() < deadline:
                time.sleep(0.02)
            assert not c1.runtime.is_dirty, "op never became durable"

        wait_clean()

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
        s1.insert_text(0, "down:")  # pending while the service is dead
        proc2, _, _ = spawn(port)  # same port: clients reconnect blind

        deadline = time.time() + 20
        while not c1.connected and time.time() < deadline:
            time.sleep(0.05)
        assert c1.connected, f"reconnect failed (delays={cm.delays})"
        assert cm.delays, "the ladder must actually have backed off"
        c1.flush()
        wait_clean(20.0)

        c2 = Loader(SocketDriver(host, port), registry).resolve(doc)
        s2 = c2.runtime.get_datastore("default").get_channel("s")
        assert s2.get_text() == "down:before"
        assert s1.get_text() == "down:before"
        assert not c1.runtime.is_dirty
    finally:
        for p in (proc, proc2):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)


def test_supervisor_restarts_stalled_child(tmp_path):
    """A live-but-wedged child (stale heartbeat) is killed and
    restarted — the second failure-detection signal next to process
    exit."""
    import signal

    sup = ServiceSupervisor(
        str(tmp_path), roles=("scribe",), ttl_s=0.4,
        heartbeat_timeout_s=1.0,
    ).start()
    try:
        proc = sup.procs["scribe"]
        deadline = time.time() + 5
        while sup._heartbeat_age("scribe") > 0.5 and time.time() < deadline:
            time.sleep(0.05)
        os.kill(proc.pid, signal.SIGSTOP)
        deadline = time.time() + 15
        while not sup.poll_once() and time.time() < deadline:
            time.sleep(0.1)
        assert sup.restarts["scribe"] == 1
        assert any("stale-heartbeat" in e for e in sup.events)
        assert sup.procs["scribe"].pid != proc.pid
    finally:
        sup.stop()


def test_supervised_farm_processes_after_restart(tmp_path):
    """End-to-end continuity: kill the sequencer AFTER it has
    checkpointed some work, feed more, and the restarted child resumes
    from the checkpoint (no reset, no gap, no dup)."""
    shared = str(tmp_path)
    sup = ServiceSupervisor(shared, ttl_s=0.4, batch=8).start()
    raw = SharedFileTopic(os.path.join(shared, "topics", "rawdeltas.jsonl"))
    durable = SharedFileTopic(os.path.join(shared, "topics", "durable.jsonl"))

    def wait_ops(n, timeout=30):
        deadline = time.time() + timeout
        while time.time() < deadline:
            sup.poll_once()
            ops = [r for r in durable.read_from(0)
                   if isinstance(r, dict) and r.get("kind") == "op"]
            if len(ops) >= n:
                return ops
            time.sleep(0.05)
        raise AssertionError(
            f"timed out waiting for {n} durable ops: {sup.events}"
        )

    try:
        raw.append_many(
            [{"kind": "join", "doc": "d", "client": 1}]
            + [{"kind": "op", "doc": "d", "client": 1,
                "clientSeq": i + 1, "refSeq": 0, "contents": i}
               for i in range(10)]
        )
        wait_ops(11)
        sup.procs["deli"].kill()
        raw.append_many(
            [{"kind": "op", "doc": "d", "client": 1,
              "clientSeq": i + 1, "refSeq": 0, "contents": i}
             for i in range(10, 20)]
        )
        ops = wait_ops(21)
        seqs = sorted(r["seq"] for r in ops)
        assert seqs == list(range(1, 22)), seqs
        assert sup.restarts["deli"] >= 1
    finally:
        sup.stop()


# ---------------------------------------------------------------------------
# front door + autoscale (ISSUE 12 acceptance gates)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_front_door_autoscale_storm_split_converges(tmp_path):
    """THE front-door acceptance gate: kernel x columnar ELASTIC
    fabric with the supervised admission ingress, per-partition
    downstream stages and the load-driven autoscale policy all on,
    kill faults landing on workers AND the front door, boxcars in
    flight — a POLICY-driven split must fire mid-stream, every
    unauthorized/oversized submit must be nacked-never-sequenced
    (exactly once, across the ingress kill), and the merged stream
    plus both downstream legs must converge bit-identical with zero
    dup/skip."""
    res = run_chaos(ChaosConfig(
        seed=12, faults=("kill",), n_docs=2, n_clients=3,
        ops_per_client=24, boxcar_rate=0.35, timeout_s=300.0,
        deli_impl="kernel", log_format="columnar",
        n_partitions=2, n_workers=2, elastic=True,
        ingress=True, autoscale=True, downstream="split",
        shared_dir=str(tmp_path),
    ))
    assert res.converged, res.detail
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0
    # A LOAD-driven topology change actually fired mid-stream.
    assert res.autoscale_actions > 0 and len(res.epochs) > 1, res.detail
    # The nack taxonomy on the wire: tampered/oversized/unknown-tenant
    # submits all rejected, never sequenced, exactly once each.
    assert res.never_sequenced_ok
    assert res.ingress_nacks.get("auth", 0) >= 2
    assert res.ingress_nacks.get("size", 0) >= 1
    # Downstream legs bit-identical through the policy split + kills.
    assert res.downstream_ok


@pytest.mark.chaos
def test_front_door_overload_throttle_retry_converges(tmp_path):
    """The overload episode: a small per-partition backlog budget
    forces throttle nacks mid-storm; the feeder retries per the
    client contract and the stream still converges bit-identical —
    overload degrades visibly, never unboundedly and never lossily."""
    res = run_chaos(ChaosConfig(
        seed=5, faults=(), n_docs=2, n_clients=3, ops_per_client=20,
        n_partitions=2, n_workers=2, timeout_s=240.0,
        ingress=True, ingress_backlog=6,
        shared_dir=str(tmp_path),
    ))
    assert res.converged, res.detail
    assert res.ingress_nacks.get("backpressure", 0) > 0, res.detail
    assert res.throttle_retries > 0
    assert res.never_sequenced_ok
