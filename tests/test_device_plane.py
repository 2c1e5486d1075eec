"""2-D device plane (ISSUE 15): ONE ``docs x model`` mesh serving the
sequencer AND the summarizer folds.

Gates: typed slices (disjoint model columns, stable worker mapping),
the sequencer bit-identical on a plane slice vs single-device
(including the deferred per-shard GROW scatter and its logical→
physical slot map), cross-topology checkpoint interop extended to the
2-D layout (scalar ⇄ 1-dev ⇄ 1-D ⇄ plane slice), and the overlay-
pallas fold backend (`core.overlay_fold`) byte-identical to the
vmapped kernel fold at every emission — the content-addressed
no-fork contract is backend-invariant. Runs on the conftest-forced 8
virtual host CPU devices (overlay through the pallas interpreter);
the code is identical on a real slice.
"""

from __future__ import annotations

import json
import os
import random

import jax
import numpy as np
import pytest

from fluidframework_tpu.parallel.device_plane import (
    DevicePlane,
    PLANE_ENV,
    parse_plane_spec,
    plane_column_of,
    resolve_plane,
    shared_plane,
)
from fluidframework_tpu.server.deli_kernel import (
    KernelDeliLambda,
    PackedDeliCore,
    mesh_for_devices,
    mesh_for_plane,
)
from fluidframework_tpu.ops.sequencer_kernel import (
    NO_GROUP,
    SUB_JOIN,
    SUB_LEAVE,
    SUB_OP,
)


def _need_devices(n: int) -> None:
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} (virtual) devices")


# ---------------------------------------------------------------------------
# the plane itself
# ---------------------------------------------------------------------------


def test_plane_spec_parse_and_validation():
    assert parse_plane_spec("2x2") == (2, 2)
    assert parse_plane_spec("4X2") == (4, 2)
    assert parse_plane_spec("2*3") == (2, 3)
    assert parse_plane_spec((3, 1)) == (3, 1)
    with pytest.raises(ValueError, match="DOCSxMODEL"):
        parse_plane_spec("4")
    with pytest.raises(ValueError, match=">= 1"):
        parse_plane_spec("0x2")


def test_shared_plane_cache_and_slices():
    _need_devices(4)
    plane = shared_plane(2, 2)
    assert resolve_plane("2x2") is plane
    assert resolve_plane(plane) is plane
    assert resolve_plane(None) is None
    assert plane.size == 4
    assert dict(plane.mesh.shape) == {"docs": 2, "model": 2}
    m0, m1 = plane.seq_mesh(0), plane.seq_mesh(1)
    assert plane.seq_mesh(2) is m0  # columns wrap mod model
    assert tuple(m0.axis_names) == ("docs",)
    # Typed slices: the two ordering columns are DISJOINT device sets
    # tiling the pool — tenants don't contend for the same chips.
    assert not (set(m0.devices.flat) & set(m1.devices.flat))
    assert (set(m0.devices.flat) | set(m1.devices.flat)
            == set(plane.mesh.devices.flat))
    d = plane.describe()
    assert d["docs"] == 2 and d["model"] == 2 and d["devices"] == 4


def test_plane_env_resolution(monkeypatch):
    _need_devices(4)
    monkeypatch.setenv(PLANE_ENV, "2x2")
    assert resolve_plane(None, env=True) is shared_plane(2, 2)
    assert resolve_plane(None, env=False) is None
    monkeypatch.delenv(PLANE_ENV)
    assert resolve_plane(None, env=True) is None


def test_plane_column_mapping_stable():
    assert plane_column_of(0, 2) == 0
    assert plane_column_of(3, 2) == 1
    assert plane_column_of("w1", 2) == plane_column_of("w1", 2)
    assert plane_column_of("deli-r0-7fffffff", 4) in range(4)


# ---------------------------------------------------------------------------
# sequencer on a plane slice (+ the deferred GROW scatter)
# ---------------------------------------------------------------------------


def drive_core(core: PackedDeliCore, seed: int, pumps: int = 4,
               per_pump: int = 80, docs: int = 6, clients: int = 5):
    """Seeded mixed traffic (the test_deli_sharded driver shape):
    joins/leaves, boxcars, invalid ops, resubmissions."""
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
            elif r < 0.35:
                g = core.new_group(slot)
                col = rng.randrange(0, clients + 1)
                for _k in range(rng.randrange(2, 5)):
                    core.add(slot, SUB_OP, col, rng.randrange(1, 9),
                             rng.randrange(0, 5), g)
            elif r < 0.45 and recent:
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


def test_plane_slice_core_matches_single_device():
    _need_devices(4)
    single = drive_core(PackedDeliCore(dedup=True), seed=51)
    for col in (0, 1):
        sliced = drive_core(
            PackedDeliCore(dedup=True,
                           mesh=shared_plane(2, 2).seq_mesh(col)),
            seed=51,
        )
        assert sliced == single


def test_placed_grow_stays_on_device_and_matches():
    """The deferred GROW scatter: doubling an already-placed pool pads
    each shard's slab device-locally (no full re-place), remaps the
    logical→physical slot map per shard, and the verdict stream stays
    bit-identical to the scalar pool's through repeated growth."""
    _need_devices(4)
    mesh = mesh_for_devices(4)
    core = PackedDeliCore(n_docs=4, dedup=True, mesh=mesh)
    single = PackedDeliCore(n_docs=4, dedup=True)
    a = drive_core(core, seed=52, docs=5)
    b = drive_core(single, seed=52, docs=5)
    assert a == b
    pool = core.pool
    assert pool._placed
    d0 = pool.n_docs
    # Growth traffic: many more docs force repeated doubling.
    a = drive_core(core, seed=53, docs=40)
    b = drive_core(single, seed=53, docs=40)
    assert a == b
    assert pool.n_docs > d0
    assert pool._placed, "grow fell back to a full re-place"
    assert pool.n_docs % pool._n_shards == 0
    # The slot map is a bijection and shard-preserving: every logical
    # slot's physical row stayed on the shard it lived on pre-grow.
    assert sorted(pool._phys.tolist()) == list(range(pool.n_docs))
    # And the checkpoint is still topology-free.
    assert pool.checkpoint_docs() == single.pool.checkpoint_docs()


def test_placed_grow_reuses_untouched_shard_buffers():
    """After a grow, the next queued-row scatter still takes the
    scoped path: shards owning no touched row keep their (padded)
    buffers by identity — nothing re-transfers."""
    _need_devices(4)
    mesh = mesh_for_devices(4)
    core = PackedDeliCore(n_docs=8, dedup=True, mesh=mesh)
    drive_core(core, seed=54, docs=24, pumps=3)  # grows while placed
    pool = core.pool
    assert pool._placed and pool.n_docs >= 16
    # Park + touch ONE doc: exactly one shard's slab is rebuilt.
    doc = next(iter(pool.slot_owner.values()))
    pool.park(doc)
    h = pool.touch(doc)
    assert pool._loads
    def ptrs(name):
        return [s.data.unsafe_buffer_pointer() for s in sorted(
            getattr(pool.state, name).addressable_shards,
            key=lambda s: (s.index[0].start or 0) if s.index else 0,
        )]

    before = {name: ptrs(name) for name in pool.state._fields}
    rows = pool.n_docs // pool._n_shards
    touched_shard = int(pool._phys[h["slot"]]) // rows
    pool.prepare()
    for name, olds in before.items():
        cur = ptrs(name)
        for si, (old, now) in enumerate(zip(olds, cur)):
            if si != touched_shard:
                assert now == old, (
                    f"{name} shard {si} was rebuilt though untouched"
                )


def test_plane_conflicts_are_loud():
    _need_devices(4)
    from fluidframework_tpu.server.log import MessageLog

    with pytest.raises(ValueError, match="exclusive"):
        KernelDeliLambda(MessageLog(), deli_devices=4,
                         device_plane="2x2")
    from fluidframework_tpu.server.shard_fabric import ShardWorker

    with pytest.raises(ValueError, match="deli_impl='kernel'"):
        ShardWorker("/tmp/nowhere-plane", "w0", device_plane="2x2")
    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    with pytest.raises(ValueError, match="deli_impl='kernel'"):
        ServiceSupervisor("/tmp/nowhere-plane", device_plane="2x2")
    with pytest.raises(ValueError, match="exclusive"):
        ServiceSupervisor("/tmp/nowhere-plane", deli_impl="kernel",
                          deli_devices=4, device_plane="2x2")


def test_serve_role_plane_validation():
    from fluidframework_tpu.server.supervisor import serve_role

    with pytest.raises(ValueError, match="device_plane"):
        serve_role("/tmp/nowhere", "scriptorium", "o",
                   device_plane="2x2")
    with pytest.raises(ValueError, match="device_plane"):
        serve_role("/tmp/nowhere", "deli", "o", deli_impl="scalar",
                   device_plane="2x2")
    with pytest.raises(ValueError, match="fold_backend"):
        serve_role("/tmp/nowhere", "deli", "o", deli_impl="kernel",
                   fold_backend="overlay")


# ---------------------------------------------------------------------------
# cross-topology checkpoint interop at 2-D
# ---------------------------------------------------------------------------


def _interop(prefix, suffix, first, second):
    from fluidframework_tpu.server.lambdas import DeliLambda
    from fluidframework_tpu.server.log import MessageLog
    from test_deli_sharded import norm

    def build(log, ckpt, topo):
        if topo == "scalar":
            return DeliLambda(log, ckpt)
        if isinstance(topo, str) and "x" in topo:
            # 2-D: the plane's docs-axis slice (column 0).
            return KernelDeliLambda(log, ckpt, device_plane=topo)
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


def test_cross_topology_interop_includes_plane():
    """Satellite contract at 2-D: scalar ⇄ 1-dev ⇄ 1-D (4 devices) ⇄
    plane slice (2x2) checkpoints restore bit-identical — the
    checkpoint format stays topology-free under the plane too."""
    _need_devices(4)
    from test_deli_sharded import gen_raw

    recs = gen_raw(44, n=260)
    prefix, suffix = recs[:130], recs[130:]
    want = _interop(prefix, suffix, "scalar", "scalar")
    assert _interop(prefix, suffix, "2x2", "scalar") == want
    assert _interop(prefix, suffix, "scalar", "2x2") == want
    assert _interop(prefix, suffix, "2x2", 1) == want
    assert _interop(prefix, suffix, 4, "2x2") == want
    assert _interop(prefix, suffix, "2x2", 4) == want


# ---------------------------------------------------------------------------
# the overlay fold backend (canonical rows backend-invariant)
# ---------------------------------------------------------------------------


def _emission_sweep(backend: str, recs, summary_ops: int,
                    plane=None):
    """The summarizer's exact emission loop (boot-from-rows, encode,
    fold, canonical serialization, rebuild) for one doc's stream;
    returns every emission's canonical rows."""
    from fluidframework_tpu.core.overlay_fold import (
        boot_overlay,
        fold_jobs_overlay,
    )
    from fluidframework_tpu.server.summarizer import (
        _boot_mergetree,
        _canonical_rows,
        _encode_fold,
        _fold_jobs,
    )

    def boot(rows, msn):
        if backend == "overlay":
            return boot_overlay(rows, msn, interpret=True)
        return _boot_mergetree(rows, msn)

    rows, base_msn = [], 0
    out = []
    window = []
    count = msn = 0
    rep = None
    for rec in recs:
        window.append(rec)
        count += 1
        msn = max(msn, rec["msn"])
        if count % summary_ops == 0:
            if rep is None:
                rep = boot(rows, base_msn)
            _encode_fold(rep, window)
            window = []
            if backend == "overlay":
                fold_jobs_overlay([(rep, None)], plane=plane,
                                  interpret=True)
                rows = rep.canonical_rows(msn)
            else:
                _fold_jobs([(rep, None)], plane=plane)
                rows = _canonical_rows(rep, msn)
            base_msn = msn
            out.append(rows)
            rep = boot(rows, base_msn)
    return out


@pytest.mark.parametrize("seed,cadence", [(10, 60), (11, 25)])
def test_overlay_fold_canonical_rows_bit_identical(seed, cadence):
    """THE backend-invariance gate: the overlay-pallas fold's
    canonical rows equal the vmapped kernel fold's byte-for-byte at
    EVERY emission point — same blob bytes, same content-addressed
    handles, restart-stable across either engine."""
    from fluidframework_tpu.testing.pipeline import (
        build_mergetree_stream,
    )

    recs = build_mergetree_stream(300, n_clients=4, seed=seed)
    k = _emission_sweep("kernel", recs, cadence)
    o = _emission_sweep("overlay", recs, cadence)
    assert len(k) == len(o) > 0
    assert json.dumps(k, sort_keys=True) == json.dumps(o,
                                                       sort_keys=True)


def test_boot_overlay_roundtrip_idempotent():
    """boot-from-rows then serialize-with-no-new-ops returns the SAME
    rows (the restart path's fixed point) for both backends."""
    from fluidframework_tpu.core.overlay_fold import boot_overlay
    from fluidframework_tpu.server.summarizer import (
        _boot_mergetree,
        _canonical_rows,
    )
    from fluidframework_tpu.testing.pipeline import (
        build_mergetree_stream,
    )

    recs = build_mergetree_stream(200, n_clients=3, seed=12)
    rows = _emission_sweep("kernel", recs, 100)[-1]
    msn = max(r["msn"] for r in recs[:200])
    k = _canonical_rows(_boot_mergetree(rows, msn), msn)
    o = boot_overlay(rows, msn, interpret=True).canonical_rows(msn)
    assert k == rows and o == rows


def test_stacked_fold_group_over_plane_bit_identical():
    """Several docs folding in one round stack over the 2-D plane —
    kernel (rows sharded on 'model') and overlay (doc stack tiling
    the pool, dummy-padded to the mesh size) both byte-identical to
    the unplaced single-doc folds."""
    _need_devices(4)
    from fluidframework_tpu.testing.pipeline import (
        build_mergetree_stream,
    )

    plane = shared_plane(2, 2)
    streams = {
        f"doc{i}": build_mergetree_stream(120, n_clients=3,
                                          seed=30 + i, doc=f"doc{i}")
        for i in range(3)
    }
    want = {d: _emission_sweep("kernel", r, 60)
            for d, r in streams.items()}
    for backend in ("kernel", "overlay"):
        got = {d: _emission_sweep(backend, r, 60, plane=plane)
               for d, r in streams.items()}
        assert got == want, f"{backend} diverged under the plane"


def test_mesh_for_plane_partition_key_routing():
    _need_devices(4)
    m_a = mesh_for_plane("2x2", partition_key=0)
    m_b = mesh_for_plane("2x2", partition_key=1)
    assert m_a is shared_plane(2, 2).seq_mesh(0)
    assert m_b is shared_plane(2, 2).seq_mesh(1)
    assert mesh_for_plane(None) is None


# ---------------------------------------------------------------------------
# the summarizer role on the overlay backend
# ---------------------------------------------------------------------------


def _drive_summ_role(shared, recs, log_format="json", **role_kw):
    from fluidframework_tpu.server.columnar_log import (
        make_tail_reader,
        make_topic,
    )
    from fluidframework_tpu.server.summarizer import SummarizerRole

    os.makedirs(os.path.join(shared, "topics"), exist_ok=True)
    deltas = make_topic(
        os.path.join(shared, "topics", "deltas.jsonl"), log_format
    )
    deltas.append_many(recs)
    role = SummarizerRole(shared, owner="t-summ", ttl_s=3600.0,
                          log_format=log_format, **role_kw)
    role.fence = 1
    reader = make_tail_reader(deltas)
    manifests = []
    while True:
        entries = reader.poll(4096)
        if not entries:
            break
        out = []
        for line_idx, rec in entries:
            role.process(line_idx, rec, out)
        role.flush_batch(out)
        if out:
            role.out_topic.append_many(out, fence=1, owner="t-summ")
            manifests.extend(out)
        role.offset = reader.next_line
    return role, manifests


def test_summarizer_role_overlay_backend_identical_handles(tmp_path):
    """The role-level gate: a summarizer folding through the OVERLAY
    backend emits the identical manifest sequence — same seqs, same
    content-addressed handles — as the kernel-backend role over the
    same stream (and the resolved-backend gauge says which engine
    actually ran)."""
    from fluidframework_tpu.testing.pipeline import (
        build_mergetree_stream,
    )

    recs = build_mergetree_stream(260, n_clients=4, seed=60)
    _, mk = _drive_summ_role(str(tmp_path / "k"), recs,
                             summary_ops=64, fold_backend="kernel")
    role_o, mo = _drive_summ_role(str(tmp_path / "o"), recs,
                                  summary_ops=64,
                                  fold_backend="overlay",
                                  fold_interpret=True)
    assert role_o.fold_backend() == "overlay"
    key = lambda ms: [(m["doc"], m["seq"], m["handle"], m["count"])
                      for m in ms]  # noqa: E731
    assert len(mk) > 0 and key(mk) == key(mo)


def test_fold_backend_overlay_refused_off_tpu(tmp_path):
    """fold_backend=overlay WITHOUT the interpreter on a host where
    pallas cannot lower raises — the summarizer never switches to the
    other backend behind its caller's back."""
    from fluidframework_tpu.core.overlay_fold import overlay_available
    from fluidframework_tpu.server.summarizer import SummarizerRole

    assert not overlay_available(False)  # conftest pins the CPU
    role = SummarizerRole(str(tmp_path), owner="t", ttl_s=3600.0,
                          fold_backend="overlay",
                          fold_interpret=False)
    with pytest.raises(RuntimeError, match="fold_backend=overlay"):
        role.fold_backend()


def test_fold_backend_env_default(tmp_path, monkeypatch):
    from fluidframework_tpu.server.summarizer import SummarizerRole

    monkeypatch.setenv("FLUID_FOLD_BACKEND", "overlay")
    monkeypatch.setenv("FLUID_FOLD_INTERPRET", "1")
    role = SummarizerRole(str(tmp_path), owner="t", ttl_s=3600.0)
    assert role._fold_backend_requested == "overlay"
    assert role.fold_interpret
    monkeypatch.setenv("FLUID_FOLD_BACKEND", "bogus")
    with pytest.raises(ValueError, match="FLUID_FOLD_BACKEND"):
        SummarizerRole(str(tmp_path), owner="t2", ttl_s=3600.0)


# ---------------------------------------------------------------------------
# the chaos acceptance gate (2-D farm vs scalar golden)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_device_plane_chaos_kill_converges(tmp_path):
    """ISSUE 15 acceptance: a supervised kernel+columnar farm on a
    2x2 plane — deli children sharding on the plane's docs slice, the
    summarizer folding through the OVERLAY backend (interpreter) —
    survives kill faults bit-identical to the scalar golden with
    summary integrity intact (blobs == cold scalar replay, no
    fork/dup). The workload's contents are merge-tree wire ops, so
    the overlay engine demonstrably RAN (mergetree-form blobs), not
    just resolved."""
    from fluidframework_tpu.server.columnar_log import make_topic
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    d = str(tmp_path / "plane-chaos")
    res = run_chaos(ChaosConfig(
        seed=151, faults=("kill",), n_docs=2, n_clients=3,
        ops_per_client=12, timeout_s=420.0, deli_impl="kernel",
        log_format="columnar", summarizer=True, summary_ops=8,
        device_plane="2x2", fold_backend="overlay", shared_dir=d,
    ))
    assert res.converged, res.detail
    assert res.summaries_ok and res.summary_manifests > 0
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0
    mans = [r for r in make_topic(
        os.path.join(d, "topics", "summaries.jsonl"), "columnar"
    ).read_from(0) if isinstance(r, dict)
        and r.get("kind") == "summary"]
    assert mans and all(m["form"] == "mergetree" for m in mans), (
        "fold backend never engaged: no mergetree-form blobs"
    )


def test_chaos_plane_validation():
    from fluidframework_tpu.testing.chaos import ChaosConfig, run_chaos

    with pytest.raises(ValueError, match="deli_impl='kernel'"):
        run_chaos(ChaosConfig(device_plane="2x2"))
    with pytest.raises(ValueError, match="exclusive"):
        run_chaos(ChaosConfig(deli_impl="kernel", device_plane="2x2",
                              deli_devices=4))
    with pytest.raises(ValueError, match="summarizer"):
        run_chaos(ChaosConfig(fold_backend="overlay"))
    with pytest.raises(ValueError, match="DOCSxMODEL"):
        run_chaos(ChaosConfig(deli_impl="kernel", device_plane="4"))
