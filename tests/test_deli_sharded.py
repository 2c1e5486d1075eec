"""Multi-device deli: the [D, C] sequencer pool sharded across a
device mesh (`shard_map` over `PartitionSpec('docs')`).

Differential gates against the single-device kernel (itself gated
against the scalar oracle): identical verdicts — stamps, nacks, MSNs,
boxcar aborts, resubmission dedup — whatever the device count, plus
cross-topology checkpoint interop (4-device ⇄ 1-device ⇄ scalar
`DocumentSequencer`, bit-identical replay) and a chaos kill+lease run
whose sharded-kernel farm converges bit-identical to the scalar
golden. Runs on the conftest-forced 8 virtual host CPU devices — the
code is identical on a real multi-chip slice.
"""

from __future__ import annotations

import random

import jax
import pytest

from fluidframework_tpu.ops.sequencer_kernel import (
    NO_GROUP,
    SUB_JOIN,
    SUB_LEAVE,
    SUB_OP,
    SUB_SYSTEM,
)
from fluidframework_tpu.server.deli_kernel import (
    KernelDeliLambda,
    PackedDeliCore,
    mesh_for_devices,
)
from fluidframework_tpu.server.lambdas import DeliLambda, LocalServer
from fluidframework_tpu.server.log import MessageLog
from fluidframework_tpu.protocol.messages import (
    DocumentMessage,
    SequencedMessage,
)


def _need_devices(n: int) -> None:
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} (virtual) devices")


# ---------------------------------------------------------------------------
# core-level differential
# ---------------------------------------------------------------------------


def drive_core(core: PackedDeliCore, seed: int, pumps: int = 4,
               per_pump: int = 80, docs: int = 6, clients: int = 5):
    """Seeded mixed traffic straight into a PackedDeliCore: joins,
    leaves, system stamps, standalone ops (some invalid), atomic
    boxcars, and verbatim RESUBMISSIONS (the dedup path). Returns the
    flat verdict tuples per pump."""
    rng = random.Random(seed)
    results = []
    recent: list = []
    for _ in range(pumps):
        core.begin()
        for _ in range(per_pump):
            doc = f"doc{rng.randrange(docs)}"
            h = core.touch(doc)
            slot = h["slot"]
            r = rng.random()
            if r < 0.15:
                cid = rng.randrange(1, clients + 1)
                core.add(slot, SUB_JOIN, core.pool.col_of_join(h, cid))
            elif r < 0.22:
                cid = rng.randrange(1, clients + 1)
                core.add(slot, SUB_LEAVE, h["cmap"].get(cid, 0))
            elif r < 0.27:
                core.add(slot, SUB_SYSTEM)
            elif r < 0.4:
                g = core.new_group(slot)
                col = rng.randrange(0, clients + 1)
                for k in range(rng.randrange(2, 5)):
                    core.add(slot, SUB_OP, col, rng.randrange(1, 9),
                             rng.randrange(0, 5), g)
            elif r < 0.5 and recent:
                core.add(*rng.choice(recent))  # resubmission -> dedup
            else:
                sub = (slot, SUB_OP, rng.randrange(0, clients + 1),
                       rng.randrange(1, 9), rng.randrange(0, 5),
                       NO_GROUP)
                recent.append(sub)
                if len(recent) > 32:
                    recent.pop(0)
                core.add(*sub)
        res = core.run()
        results.append((res.seq, res.msn, res.nack, res.skipped))
    return results


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_sharded_core_matches_single_device(n_dev):
    _need_devices(n_dev)
    single = drive_core(PackedDeliCore(dedup=True), seed=11)
    sharded = drive_core(
        PackedDeliCore(dedup=True, mesh=mesh_for_devices(n_dev)),
        seed=11,
    )
    assert sharded == single


def test_sharded_pool_growth_keeps_device_multiple():
    _need_devices(4)
    core = PackedDeliCore(n_docs=2, dedup=True, mesh=mesh_for_devices(4))
    single = drive_core(PackedDeliCore(n_docs=2, dedup=True), seed=3,
                        docs=24)
    sharded = drive_core(core, seed=3, docs=24)
    assert sharded == single
    assert core.pool.n_docs % 4 == 0
    assert core.pool.n_docs >= 24  # grew past the initial 4-multiple


def test_sharded_pool_evict_park_matches():
    """max_resident forces park/reload churn: the sharded pool's host
    mirror and row scatter must behave exactly like the single-device
    pool's (verdicts identical through evictions)."""
    _need_devices(2)
    single = drive_core(
        PackedDeliCore(dedup=True, max_resident=3), seed=5, docs=10
    )
    sharded = drive_core(
        PackedDeliCore(dedup=True, max_resident=3,
                       mesh=mesh_for_devices(2)),
        seed=5, docs=10,
    )
    assert sharded == single


def test_sharded_checkpoint_format_is_topology_free():
    _need_devices(4)
    a = PackedDeliCore(dedup=True)
    b = PackedDeliCore(dedup=True, mesh=mesh_for_devices(4))
    drive_core(a, seed=9)
    drive_core(b, seed=9)
    assert a.pool.checkpoint_docs() == b.pool.checkpoint_docs()


# ---------------------------------------------------------------------------
# lambda-level differential + checkpoint interop
# ---------------------------------------------------------------------------


def gen_raw(seed: int, n: int = 240, docs: int = 4, clients: int = 4):
    """Raw in-proc ingress records (the KernelDeliLambda wire): joins,
    leaves, ops with seeded invalid submissions, boxcars."""
    rng = random.Random(seed)
    recs = []
    conn = {d: set() for d in range(docs)}
    cseq: dict = {}
    for _ in range(n):
        d = rng.randrange(docs)
        doc = f"doc{d}"
        r = rng.random()
        if r < 0.12 or not conn[d]:
            c = rng.randrange(1, clients + 1)
            recs.append({"doc": doc, "kind": "join", "client": c})
            conn[d].add(c)
            cseq[(d, c)] = cseq.get((d, c), 0)
        elif r < 0.17:
            c = rng.randrange(1, clients + 1)
            recs.append({"doc": doc, "kind": "leave", "client": c})
            conn[d].discard(c)
        elif r < 0.3:
            c = rng.choice(sorted(conn[d]))
            msgs = []
            for _ in range(rng.randrange(2, 5)):
                cs = cseq[(d, c)] + 1
                cseq[(d, c)] = cs
                msgs.append(DocumentMessage(
                    client_seq=cs, ref_seq=0, contents={"b": 1}
                ))
            recs.append({"doc": doc, "kind": "boxcar", "client": c,
                         "msgs": msgs})
        else:
            c = rng.choice(sorted(conn[d]))
            cs = cseq[(d, c)] + 1
            if rng.random() < 0.08:
                cs += 1  # clientSeq gap -> nack
            else:
                cseq[(d, c)] = cs
            recs.append({"doc": doc, "kind": "op", "client": c,
                         "msg": DocumentMessage(
                             client_seq=cs, ref_seq=0,
                             contents={"v": rng.randrange(99)})})
    return recs


def norm(entries):
    out = []
    for e in entries:
        m = e["msg"]
        if isinstance(m, SequencedMessage):
            out.append((e["doc"], e["kind"], m.sequence_number,
                        m.minimum_sequence_number, m.client_id,
                        m.client_seq, m.ref_seq, str(m.type), m.contents))
        else:
            out.append((e["doc"], e["kind"], m.client_id, m.client_seq,
                        m.code))
    return out


def _run_lambda(recs, deli_devices=None, checkpoint=None, log=None,
                scalar=False):
    log = log or MessageLog()
    log.topic("rawdeltas").append_many(recs)
    if scalar:
        lam = DeliLambda(log, checkpoint)
    else:
        lam = KernelDeliLambda(log, checkpoint,
                               deli_devices=deli_devices)
    while lam.pump():
        pass
    return lam, log


@pytest.mark.parametrize("n_dev", [2, 4])
def test_kernel_lambda_sharded_matches_scalar(n_dev, seed=21):
    _need_devices(n_dev)
    recs = gen_raw(seed)
    _, slog = _run_lambda(recs, scalar=True)
    _, klog = _run_lambda(recs, deli_devices=n_dev)
    assert norm(klog.topic("deltas").read(0)) == \
        norm(slog.topic("deltas").read(0))


def _interop(prefix, suffix, first, second):
    """Run `prefix` under topology `first`, checkpoint, restore under
    `second`, run `suffix`; return the normalized full deltas.
    Topology: int device count for the kernel lambda, "scalar" for
    the scalar DeliLambda."""
    def build(log, ckpt, topo):
        if topo == "scalar":
            return DeliLambda(log, ckpt)
        return KernelDeliLambda(log, ckpt, deli_devices=topo)

    log = MessageLog()
    log.topic("rawdeltas").append_many(prefix)
    a = build(log, None, first)
    while a.pump():
        pass
    ckpt = a.checkpoint()
    log.topic("rawdeltas").append_many(suffix)
    b = build(log, ckpt, second)
    while b.pump():
        pass
    return norm(log.topic("deltas").read(0))


def test_cross_topology_checkpoint_interop():
    """The satellite contract: a checkpoint written by the 4-device
    sharded kernel restores into the single-device kernel and the
    scalar `DocumentSequencer` path (and back, and sharded→sharded
    with a different N), with bit-identical replay of the suffix."""
    _need_devices(4)
    recs = gen_raw(33, n=300)
    prefix, suffix = recs[:150], recs[150:]
    want = _interop(prefix, suffix, "scalar", "scalar")
    assert _interop(prefix, suffix, 4, 1) == want
    assert _interop(prefix, suffix, 4, "scalar") == want
    assert _interop(prefix, suffix, "scalar", 4) == want
    assert _interop(prefix, suffix, 1, 4) == want
    assert _interop(prefix, suffix, 4, 2) == want


def test_local_server_deli_devices_validation():
    with pytest.raises(ValueError, match="deli_devices"):
        LocalServer(deli_devices=4)  # scalar impl has no device axis


def test_local_server_sharded_end_to_end():
    _need_devices(2)
    ref = LocalServer(deli_impl="kernel")
    srv = LocalServer(deli_impl="kernel", deli_devices=2)
    for s in (ref, srv):
        conns = [s.connect("docA"), s.connect("docA")]
        for i in range(30):
            conns[i % 2].submit(DocumentMessage(
                client_seq=i // 2 + 1, ref_seq=0, contents={"i": i}
            ))
        s.process_all()
    want = [m.sequence_number for m in ref.scriptorium.ops_from("docA", 0)]
    got = [m.sequence_number for m in srv.scriptorium.ops_from("docA", 0)]
    assert got == want
    assert srv.deli.core.pool._n_shards == 2


# ---------------------------------------------------------------------------
# role-level differential (the supervised-farm datapath)
# ---------------------------------------------------------------------------


def test_kernel_role_sharded_pipeline_matches_scalar(tmp_path):
    _need_devices(2)
    from fluidframework_tpu.testing.pipeline import (
        build_pipeline_workload,
        run_pipeline,
        read_canonical,
    )
    from fluidframework_tpu.server.queue import SharedFileTopic

    workload = build_pipeline_workload(16, 4, 2)
    raw = str(tmp_path / "rawdeltas.jsonl")
    SharedFileTopic(raw).append_many(workload)
    scal = run_pipeline("scalar", raw, str(tmp_path), batch=64)
    shard = run_pipeline("kernel", raw, str(tmp_path), batch=64,
                         deli_devices=2)
    assert read_canonical(shard["out_path"]) == \
        read_canonical(scal["out_path"])


# ---------------------------------------------------------------------------
# device-emulation helper + config validation
# ---------------------------------------------------------------------------


def test_forced_host_device_env_and_subprocess():
    from fluidframework_tpu.utils.devices import (
        forced_host_device_env,
        run_forced_host_subprocess,
    )

    env = forced_host_device_env(3, base={"XLA_FLAGS":
                                          "--xla_force_host_platform_device_count=9 --foo"})
    assert "--xla_force_host_platform_device_count=3" in env["XLA_FLAGS"]
    assert "=9" not in env["XLA_FLAGS"]
    assert "--foo" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"
    res = run_forced_host_subprocess(
        "import jax; print(len(jax.devices()))", 3, timeout_s=300,
    )
    assert res.stdout.strip().splitlines()[-1] == "3"


@pytest.mark.parametrize("platforms,pinned", [
    ("cpu", True), ("cpu,cpu", True), ("", False), ("tpu", False),
    ("tpu,cpu", False),
])
def test_cpu_pinned_reads_the_environment(platforms, pinned):
    from fluidframework_tpu.utils.devices import cpu_pinned

    assert cpu_pinned({"JAX_PLATFORMS": platforms}) is pinned


def test_compile_cache_set_by_env_is_left_alone(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, the helper uses it and
    sets nothing in code."""
    import jax

    from fluidframework_tpu.utils.devices import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_holds_accelerator_pins_jax_backend_state():
    """`holds_accelerator` reads jax's private backends-initialized
    flag; pin what it relies on in a fresh process: False before any
    backend starts, True after, and a CPU backend never holds a chip."""
    import subprocess
    import sys

    code = (
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from fluidframework_tpu.utils.devices import holds_accelerator\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "assert not holds_accelerator()\n"
        "jax.devices()\n"
        "assert xla_bridge.backends_are_initialized()\n"
        "assert not holds_accelerator()\n"
    )
    from fluidframework_tpu.utils.devices import cpu_child_env

    res = subprocess.run([sys.executable, "-c", code],
                         env=cpu_child_env(), capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr


def test_compile_seconds_counts_backend_compiles():
    """`track_compiles` counts what a process spends compiling: the
    number a chip child's heartbeat reports (`compile_s`)."""
    import jax.numpy as jnp

    from fluidframework_tpu.utils.devices import (
        compile_seconds,
        track_compiles,
    )

    track_compiles()
    c0 = compile_seconds()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert compile_seconds() > c0


def test_forced_host_subprocess_failure_is_loud():
    from fluidframework_tpu.utils.devices import run_forced_host_subprocess

    with pytest.raises(RuntimeError, match="rc=7"):
        run_forced_host_subprocess("raise SystemExit(7)", 2)


def test_devices_require_kernel_impl_everywhere(tmp_path):
    from fluidframework_tpu.server.supervisor import (
        ServiceSupervisor,
        serve_role,
    )
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    with pytest.raises(ValueError, match="kernel"):
        ServiceSupervisor(str(tmp_path), deli_impl="scalar",
                          deli_devices=4)
    with pytest.raises(ValueError, match="kernel"):
        serve_role(str(tmp_path), "deli", "o", deli_impl="scalar",
                   deli_devices=4)
    with pytest.raises(ValueError, match="kernel"):
        serve_role(str(tmp_path), "scriptorium", "o",
                   deli_impl="kernel", deli_devices=4)
    with pytest.raises(ValueError, match="kernel"):
        run_chaos(ChaosConfig(deli_impl="scalar", deli_devices=2))


def test_supervisor_child_cmd_carries_devices(tmp_path):
    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    sup = ServiceSupervisor(str(tmp_path), deli_impl="kernel",
                            deli_devices=2)
    cmd = sup._child_cmd("deli", "deli-g1")
    assert "--deli-devices" in cmd
    assert cmd[cmd.index("--deli-devices") + 1] == "2"
    # Non-deli roles never get the flag (they'd refuse it).
    assert "--deli-devices" not in sup._child_cmd("scribe", "scribe-g1")
    env = sup._child_env("deli")
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]


def test_supervisor_gives_the_chip_to_one_child(tmp_path, monkeypatch):
    """On an accelerator host (JAX unpinned) only the kernel deli
    inherits the accelerator; every other child is pinned to the CPU.
    The rule reads the environment alone — no backend starts."""
    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    sup = ServiceSupervisor(str(tmp_path), deli_impl="kernel")
    assert sup.chip_role == "deli"
    assert "JAX_PLATFORMS" not in sup._child_env("deli")
    for role in sup.roles:
        if role != "deli":
            assert sup._child_env(role)["JAX_PLATFORMS"] == "cpu"


def test_supervisor_refuses_two_chip_children(tmp_path, monkeypatch):
    """A layout that needs the chip in two children fails at start,
    naming both; an interpreted overlay fold runs on the CPU and so
    leaves the chip to the deli."""
    from fluidframework_tpu.server.summarizer import FOLD_INTERPRET_ENV
    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv(FOLD_INTERPRET_ENV, raising=False)
    with pytest.raises(ValueError, match="'deli' and 'summarizer'"):
        ServiceSupervisor(str(tmp_path), deli_impl="kernel",
                          fold_backend="overlay")
    sup = ServiceSupervisor(str(tmp_path), deli_impl="kernel",
                            fold_backend="overlay",
                            child_env={FOLD_INTERPRET_ENV: "1"})
    assert sup.chip_role == "deli"


def test_chip_child_outlives_the_cpu_heartbeat_bar(tmp_path,
                                                  monkeypatch):
    """The chip child is silent while it compiles: past the CPU roles'
    heartbeat bar, inside its own, only the CPU roles are restarted."""
    import time

    from fluidframework_tpu.server.supervisor import (
        CHIP_HEARTBEAT_TIMEOUT_S,
        ServiceSupervisor,
    )

    class Silent:
        stdout = None
        returncode = None

        def poll(self):
            return None

        def kill(self):
            pass

        def wait(self, timeout=None):
            return 0

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    sup = ServiceSupervisor(str(tmp_path), deli_impl="kernel",
                            heartbeat_timeout_s=2.0)
    assert CHIP_HEARTBEAT_TIMEOUT_S > 10
    respawned = []
    monkeypatch.setattr(sup, "_spawn", respawned.append)
    for role in sup.roles:
        sup.procs[role] = Silent()
        sup.spawned_at[role] = time.time() - 10.0
    sup.poll_once()
    assert sorted(respawned) == sorted(r for r in sup.roles
                                       if r != "deli")


def test_supervisor_emulation_pins_every_child(tmp_path):
    """Under the CPU pin (conftest) no child owns a chip."""
    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    sup = ServiceSupervisor(str(tmp_path), deli_impl="kernel")
    assert sup.chip_role is None
    assert all(sup._child_env(r)["JAX_PLATFORMS"] == "cpu"
               for r in sup.roles)


# ---------------------------------------------------------------------------
# scoped re-place (PR-6 follow-up (b))
# ---------------------------------------------------------------------------


def test_scoped_scatter_skips_untouched_shards():
    """Restoring a parked doc re-places ONLY the device slab that owns
    its slot row: every untouched shard keeps its buffer by IDENTITY
    (same unsafe_buffer_pointer), so a grow/park cycle at large D no
    longer re-transfers the whole pool across the mesh."""
    import numpy as np

    from fluidframework_tpu.server.deli_kernel import SeqPool

    _need_devices(4)
    pool = SeqPool(n_docs=8, n_clients=4, mesh=mesh_for_devices(4))
    for i in range(8):
        pool.touch(f"d{i}")
    pool.prepare()
    assert pool._placed
    fields = ("seq", "min_seq", "connected", "ref_seq", "client_seq")
    ptrs0 = {
        name: [s.data.unsafe_buffer_pointer()
               for s in getattr(pool.state, name).addressable_shards]
        for name in fields
    }
    # Park + touch a doc whose slot lives in shard 0 — the only slab
    # whose buffers may change.
    victim = pool.slot_owner[0]
    pool.docs[victim]["clients"] = {1: [0, 3]}
    pool.docs[victim]["cmap"] = {1: 1}
    pool.park(victim)
    pool.begin()
    h = pool.touch(victim)
    rows = pool.n_docs // 4
    assert h["slot"] // rows == 0
    pool.prepare()
    for name in fields:
        cur = [s.data.unsafe_buffer_pointer()
               for s in getattr(pool.state, name).addressable_shards]
        assert cur[1:] == ptrs0[name][1:], (
            name, "untouched shards were re-transferred"
        )
        assert cur[0] != ptrs0[name][0], (name, "row never scattered")
    # The scattered values actually landed where the kernel reads.
    row = np.asarray(
        pool.state.client_seq.addressable_shards[0].data
    )[h["slot"]]
    assert row[1] == 3
    # And growth still re-places everything (new shape, new buffers) —
    # the scoped path must not break the grow invariant.
    pool._need_clients = 16
    pool.prepare()
    assert pool.state.connected.shape[1] >= 16
    assert pool._placed


def test_scoped_scatter_differential_verdicts_unchanged():
    """The scoped scatter is a pure placement optimization: a sharded
    lambda that churns docs through park/restore still produces
    bit-identical verdicts to the scalar oracle."""
    _need_devices(2)
    log_a, log_b = MessageLog(), MessageLog()
    kern = KernelDeliLambda(log_a, deli_devices=2, n_docs=2,
                            max_resident=2)
    oracle = DeliLambda(log_b)
    rng = random.Random(11)
    docs = [f"doc{i}" for i in range(6)]  # > max_resident: churn
    seqs = {d: 0 for d in docs}
    for d in docs:
        for log in (log_a, log_b):
            log.topic("rawdeltas").append(
                {"kind": "join", "doc": d, "client": 1}
            )
    for i in range(40):
        d = rng.choice(docs)
        seqs[d] += 1
        for log in (log_a, log_b):
            log.topic("rawdeltas").append({
                "kind": "op", "doc": d, "client": 1,
                "msg": DocumentMessage(client_seq=seqs[d], ref_seq=0,
                                       contents={"i": i}),
            })
        kern.pump()
        oracle.pump()
    a = [(e["doc"], e["msg"].sequence_number,
          e["msg"].minimum_sequence_number)
         for e in log_a.topic("deltas").read(0) if e["kind"] == "op"]
    b = [(e["doc"], e["msg"].sequence_number,
          e["msg"].minimum_sequence_number)
         for e in log_b.topic("deltas").read(0) if e["kind"] == "op"]
    assert a == b


# ---------------------------------------------------------------------------
# the chaos acceptance gate
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_kill_lease_sharded_kernel_converges():
    """Acceptance: the sharded-kernel farm's output is bit-identical
    to the (single-device, scalar-path) golden across a chaos
    kill+lease run — zero duplicated/skipped sequence numbers, with
    the deli child running the pool over a 2-device mesh."""
    _need_devices(2)
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    res = run_chaos(ChaosConfig(
        seed=6, faults=("kill", "lease"), n_docs=2, n_clients=3,
        ops_per_client=18, deli_impl="kernel", deli_devices=2,
        timeout_s=240.0,
    ))
    assert res.converged, (res.detail, res.events)
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0
    assert res.digest == res.golden_digest
    assert res.fence_rejections > 0  # the lease fault demonstrably bit
