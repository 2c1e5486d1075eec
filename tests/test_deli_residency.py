"""The kernel deli's bounded resident pool: the role's residency rule,
the ordered eviction pick, the reload program, and a churning partition
(many small documents, a moving hot set, joins and leaves) sequenced
through a bounded pool record for record like the benchmark's plain
reference."""

from __future__ import annotations

import os
import random
import time
from collections import OrderedDict
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from fluidframework_tpu.server import deli_kernel as dk
from fluidframework_tpu.server.deli_kernel import KernelDeliRole, SeqPool
from fluidframework_tpu.utils import metrics as M

CHURN_TRAFFIC = {"rate_per_s": 1200, "frame_ms": 5, "insert_share": 0.05,
                 "leave_share": 0.01, "ref_lag_max": 32,
                 "contents_bytes": 32,
                 "doc_popularity": {"dist": "latest", "theta": 0.99}}


@pytest.fixture
def registry():
    old = M.set_registry(M.MetricsRegistry())
    yield M.get_registry()
    M.set_registry(old)


class _Compiles:
    """Backend compiles (or persistent-cache loads) while counting, as
    the benchmark harness counts them."""

    def __init__(self):
        self.n = 0
        self.on = False

        def listen(event, duration, **_kw):
            if self.on and event == \
                    "/jax/core/compile/backend_compile_duration":
                self.n += 1

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._listen)


@pytest.fixture
def compiles():
    c = _Compiles()
    yield c
    c.close()


# ---------------------------------------------------------------------------
# the ordered victim pick
# ---------------------------------------------------------------------------


def _scan_pick(pool):
    """The eviction pick as a scan over every document: the least
    recently touched MSN-cold resident doc not active this pump, else
    the least recently touched such doc."""
    victim = key = None
    for doc_id, h in pool.docs.items():
        if h["slot"] is None or doc_id in pool._active:
            continue
        k = (h["min_seq"] < h["seq"], h["t"])
        if key is None or k < key:
            victim, key = doc_id, k
    if victim is None:
        return None
    return victim, "lru" if key[0] else "msn_cold"


@pytest.mark.parametrize("seed", [3, 17])
def test_victim_pick_equals_the_scan(seed, registry):
    rng = random.Random(seed)
    pool = SeqPool(n_docs=8, n_clients=4, max_resident=12)
    picks = []
    ordered = pool._victim

    def checked():
        got = ordered()
        assert got == _scan_pick(pool)
        picks.append(got)
        return got

    pool._victim = checked
    n_docs = 60
    for _ in range(120):
        pool.begin()
        docs = rng.sample(range(n_docs), rng.randint(1, 9))
        for d in docs:
            h = pool.touch(f"d{d}")
            if rng.random() < 0.3:
                pool.touch(f"d{d}")  # touched again in the same pump
            # The pump's verdicts move head and MSN: cold or lagging.
            h["seq"] += rng.randint(1, 3)
            h["min_seq"] = h["seq"] if rng.random() < 0.4 else \
                rng.randint(0, h["seq"] - 1)
        if rng.random() < 0.1:
            victim = pool._victim()
            if victim is not None:
                pool.park(victim[0])  # an eviction from outside
    policies = {p for v in picks if v for p in [v[1]]}
    assert len(picks) > 100 and policies == {"lru", "msn_cold"}
    assert pool.resident_docs() <= 12


# ---------------------------------------------------------------------------
# the reload program
# ---------------------------------------------------------------------------


def _reload_cycles(pool, rng, cycles=12):
    """Pumps that park random resident docs and touch them again, each
    flushed through `prepare`; returns the rows loaded."""
    loaded = 0
    for _ in range(cycles):
        pool.begin()
        docs = list(pool.slot_owner.values())
        for d in rng.sample(docs, rng.randint(1, len(docs))):
            h = pool.docs[d]
            h["clients"] = {1: [h["seq"], 1]}
            h["cmap"] = {1: 1}
            h["seq"] += 1
            pool.park(d)
            pool.touch(d)
        loaded += pool.prepare()
    return loaded


def test_reload_program_compiles_once_per_bucket(registry, compiles):
    dk._load_rows.clear_cache()
    pool = SeqPool(n_docs=48, n_clients=4)
    for i in range(48):
        pool.touch(f"d{i}")
    assert pool.prepare() == 0  # new documents on never-used rows
    compiles.on = True
    pool.warm_loads()
    assert compiles.n == 4  # buckets 8, 16, 32, 64 over 48 rows
    compiles.n = 0
    loaded = _reload_cycles(pool, random.Random(5))
    assert loaded > 0 and compiles.n == 0
    assert registry.counter("deli_pool_loads_total").value == loaded
    # The rows landed where the kernel reads them.
    h = pool.docs["d7"]
    assert int(np.asarray(pool.state.seq)[h["slot"]]) == h["seq"]


def test_sharded_pool_reloads_compile_nothing(registry, compiles):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    pool = SeqPool(n_docs=48, n_clients=4, mesh=dk.mesh_for_devices(2))
    for i in range(48):
        pool.touch(f"d{i}")
    pool.prepare()
    assert pool._placed
    compiles.on = True
    pool.warm_loads()
    loaded = _reload_cycles(pool, random.Random(6))
    # Placed: the scoped per-shard scatter, on the host.
    assert loaded > 0 and compiles.n == 0
    h = pool.docs["d9"]
    row = int(pool._phys[h["slot"]])
    assert int(np.asarray(pool.state.seq)[row]) == h["seq"]


# ---------------------------------------------------------------------------
# the role's residency rule
# ---------------------------------------------------------------------------


def _joins(role, docs, t, monkeypatch, client=1):
    """One pump of joins of `client` to `docs`, at `time.monotonic()`
    reading `t` in the role."""
    monkeypatch.setattr(dk, "time", SimpleNamespace(
        monotonic=lambda: t, time=time.time, perf_counter=time.perf_counter))
    out = []
    for i, d in enumerate(docs):
        role.process(i, {"kind": "join", "doc": f"d{d}", "client": client},
                     out)
    role.flush_batch(out)
    return out


def test_residency_budget_follows_the_working_set(tmp_path, registry,
                                                  monkeypatch):
    monkeypatch.setattr(dk, "RESIDENT_FLOOR", 16)
    role = KernelDeliRole(str(tmp_path), owner="k", ttl_s=3600.0)
    pool = role.core.pool
    # One pump of 100 new documents: more than the budget, so the pool
    # grows to cover the pump (actives never park).
    _joins(role, range(100), 100.0, monkeypatch)
    assert pool.max_resident == 16 and pool.n_docs >= 100
    assert role.residency_budget(100.5) == 256  # 2 x 100 touched
    _joins(role, range(100, 110), 100.5, monkeypatch)
    assert pool.max_resident == 256 and pool.resident_docs() == 110
    # Two seconds on, the working set is the next pump alone: the pool
    # parks down to the floor and shrinks to it.
    out = _joins(role, [3, 200], 102.5, monkeypatch, client=2)
    assert pool.max_resident == 16 and pool.n_docs == 16
    assert pool.resident_docs() <= 16
    assert registry.counter("deli_pool_shrinks_total", role="deli").value \
        == 1
    # Doc 3 came back from the mirror: its second client joins at seq 2.
    (rec,) = [r for r in out if r["doc"] == "d3"]
    assert (rec["seq"], rec["msn"]) == (2, 0)
    assert role.residency_budget(103.6) == 16


def test_residency_floor_keeps_a_small_partition_resident(tmp_path,
                                                          registry,
                                                          monkeypatch):
    role = KernelDeliRole(str(tmp_path), owner="k", ttl_s=3600.0)
    for k in range(5):
        _joins(role, range(k * 200, (k + 1) * 200), 10.0 * k, monkeypatch)
    pool = role.core.pool
    assert pool.max_resident == dk.RESIDENT_FLOOR
    assert pool.resident_docs() == 1000
    assert registry.counter("deli_pool_evictions_total",
                            role="deli").value == 0


# ---------------------------------------------------------------------------
# a churning partition through a bounded pool, against the reference
# ---------------------------------------------------------------------------


def _read_rows(path):
    from fluidframework_tpu.server.columnar_log import (
        make_tail_reader,
        make_topic,
    )

    reader = make_tail_reader(make_topic(path, "columnar"), 0)
    cols = {k: [] for k in ("kind", "type", "doc", "client", "cseq", "seq",
                            "msn")}
    for unit in reader.poll_batches(None):
        assert unit[0] == "batch"
        b = unit[2]
        ids = np.array([int(d[3:]) for d in b.docs], np.int64)
        cols["kind"].append(np.array(b.kind))
        cols["type"].append(np.array(b.type_code))
        cols["doc"].append(ids[b.doc_idx])
        cols["client"].append(np.array(b.client))
        cols["cseq"].append(np.array(b.client_seq))
        cols["seq"].append(np.array(b.seq))
        cols["msn"].append(np.array(b.msn))
    return {k: np.concatenate(v) for k, v in cols.items()}


@pytest.mark.parametrize("restart", [False, True],
                         ids=["one-role", "restored-while-parked"])
def test_churning_partition_matches_reference(tmp_path, registry,
                                              monkeypatch, restart):
    from benchmark.drivers import deli_churn
    from benchmark.traffic import contents_heap
    from fluidframework_tpu.server.columnar_log import make_topic

    monkeypatch.setattr(dk, "RESIDENT_FLOOR", 64)
    monkeypatch.setattr(dk, "RESIDENT_HORIZON_S", 0.0)
    cfg = {"docs": 400, "clients_min": 2, "clients_max": 8}
    sched = deli_churn.schedule(cfg, CHURN_TRAFFIC, 2**31 + 4, 1.0)
    assert np.count_nonzero(sched.kind == deli_churn.LEAVE) > 0
    shared = str(tmp_path)
    os.makedirs(os.path.join(shared, "topics"))
    raw = make_topic(os.path.join(shared, "topics", "rawdeltas.jsonl"),
                     "columnar")

    def new_role(owner):
        return KernelDeliRole(shared, owner=owner, ttl_s=3600.0, batch=256,
                              log_format="columnar")

    role = new_role("k1")
    for fr in deli_churn.setup_frames(sched, 256):
        raw.append_many([fr])
        role.step()
    n = len(sched.doc)
    for lo in range(0, n, 40):
        hi = min(n, lo + 40)
        heap, off = contents_heap(sched, lo, hi)
        raw.append_many([deli_churn.frame(
            sched.kind[lo:hi], sched.doc[lo:hi], sched.client[lo:hi],
            sched.cseq[lo:hi], sched.ref[lo:hi], heap, off)])
        role.step()
        if restart and lo == 40 * (n // 80):
            pool = role.core.pool
            assert pool.resident_docs() < len(pool.docs)
            role.checkpoint()  # most documents parked
            role.leases.release("deli")
            role = new_role("k2")
    while role.step():
        pass
    rows = _read_rows(os.path.join(shared, "topics", "deltas.jsonl"))
    counts = deli_churn.compare(sched, rows)[0]
    assert all(v == 0 for v in counts.values()), counts
    assert len(rows["seq"]) == len(sched.setup_doc) + n
    evicts = registry.counter("deli_pool_evictions_total", role="deli")
    loads = registry.counter("deli_pool_loads_total", role="deli")
    assert evicts.value > 0 and loads.value > 0
    assert role.core.pool.n_docs <= 128


def test_recent_docs_age_out_in_touch_order(tmp_path, registry):
    role = KernelDeliRole(str(tmp_path), owner="k", ttl_s=3600.0)
    role._recent = OrderedDict((f"d{i}", 10.0 + i) for i in range(5))
    assert role.residency_budget(12.5) == dk.RESIDENT_FLOOR
    assert list(role._recent) == ["d2", "d3", "d4"]
