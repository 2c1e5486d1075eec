"""The kernel deli's checkpoint encodes incrementally and writes the
bytes of a full encoding.

`SeqPool` keeps each document's encoded checkpoint item and re-encodes
only the documents whose mirror entry changed since the last
checkpoint; `FencedCheckpointStore.save` splices the items into the
envelope (`queue.EncodedJson`). At every checkpoint of seeded
histories, the file must be byte for byte
``json.dumps({"fence", "owner", "state": {"offset", "state":
checkpoint_docs()}})``, as the full encoding wrote it, for the plain
role and for the elastic fabric's ranged wrapper.
"""

from __future__ import annotations

import json
import os
import random

import pytest

from fluidframework_tpu.server import deli_kernel as dk
from fluidframework_tpu.server.columnar_log import make_topic
from fluidframework_tpu.server.deli_kernel import KernelDeliRole, SeqPool
from fluidframework_tpu.server.queue import EncodedJson, \
    FencedCheckpointStore
from fluidframework_tpu.server.shard_fabric import ranged_role_class
from fluidframework_tpu.server.supervisor import DeliRole
from fluidframework_tpu.utils import metrics as M

ODD_DOCS = ['q"uote', "back\\slash", "é中\U0001f600", "ctl\n\t",
            ""]
ODD_CLIENTS = [-7, 3, 2 ** 40, 2 ** 62]
NO_CLIENTS = "no-clients"


@pytest.fixture
def registry():
    old = M.set_registry(M.MetricsRegistry())
    yield M.get_registry()
    M.set_registry(old)


def counter(registry, kind, role="deli"):
    return registry.counter(f"deli_checkpoint_docs_{kind}_total",
                            role=role).value


def full_loads(registry, key="deli"):
    return registry.counter("checkpoint_fence_full_loads_total",
                            role=key).value


# ---------------------------------------------------------------------------
# seeded histories
# ---------------------------------------------------------------------------


def history(seed: int, n_docs: int = 40, n_recs: int = 1500):
    """Raw records over `n_docs` documents (the odd ids among them):
    joins, single ops, bursts of ops long enough for the columnar span
    path, leaves, ops from unknown clients (nacks, and documents that
    never hold a client)."""
    rng = random.Random(seed)
    docs = ODD_DOCS + [f"d{i}" for i in range(n_docs - len(ODD_DOCS) - 1)]
    conn = {d: {} for d in docs}  # doc -> {client: clientSeq}
    head = {d: 0 for d in docs}
    recs = []

    def op(d, c):
        conn[d][c] += 1
        recs.append({"kind": "op", "doc": d, "client": c,
                     "clientSeq": conn[d][c], "refSeq": head[d],
                     "contents": {"v": rng.randrange(99)}})
        head[d] += 1

    while len(recs) < n_recs:
        d = rng.choice(docs)
        r = rng.random()
        free = [c for c in ODD_CLIENTS + list(range(4, 9))
                if c not in conn[d]]
        if r < 0.02:
            # A document that never holds a client: unknown-client ops
            # and leaves only.
            d = NO_CLIENTS
            recs.append({"kind": "leave", "doc": d, "client": 5})
            recs.append({"kind": "op", "doc": d, "client": 6,
                         "clientSeq": 1, "refSeq": 0, "contents": None})
        elif (r < 0.12 or not conn[d]) and free:
            c = rng.choice(free)
            recs.append({"kind": "join", "doc": d, "client": c})
            conn[d][c] = 0
            head[d] += 1
        elif r < 0.17 and conn[d]:
            c = rng.choice(sorted(conn[d]))
            recs.append({"kind": "leave", "doc": d, "client": c})
            del conn[d][c]
            head[d] += 1
        elif r < 0.20:
            recs.append({"kind": "op", "doc": d, "client": 99,
                         "clientSeq": 1, "refSeq": head[d],
                         "contents": None})  # unknown client: nack
        elif r < 0.45 and conn[d]:
            # A burst over a few documents: a long K_RAW_OP run.
            burst = [x for x in rng.sample(docs, 4) if conn[x]]
            for _ in range(rng.randint(16, 40)):
                if burst:
                    x = rng.choice(burst)
                    op(x, rng.choice(sorted(conn[x])))
        elif conn[d]:
            op(d, rng.choice(sorted(conn[d])))
    return recs


def plain(state, docs):
    """`state` with each `EncodedJson` replaced by the per-doc map it
    stands for, and how many it held."""
    if isinstance(state, EncodedJson):
        return docs, 1
    if isinstance(state, dict):
        out, n = {}, 0
        for k, v in state.items():
            out[k], m = plain(v, docs)
            n += m
        return out, n
    return state, 0


def verify_saves(role, seen):
    """Wrap the role's store: after each save, the file must hold
    exactly the full encoding of the envelope, `checkpoint_docs()` in
    place of the pre-encoded map, and `save` returns its size."""
    save = role.ckpt.save

    def checked(key, state, fence, owner=None, **kw):
        docs = role.core.pool.checkpoint_docs()
        n = save(key, state, fence, owner, **kw)
        with open(role.ckpt._path(key), "rb") as f:
            got = f.read()
        env, spliced = plain({"fence": fence, "owner": owner,
                              "state": state}, docs)
        assert got == json.dumps(env).encode()
        assert n == len(got) == os.path.getsize(role.ckpt._path(key))
        seen.append(spliced)
        return n

    role.ckpt.save = checked
    return role


def drive(tmp_path, monkeypatch, seed, log_format, role_cls=KernelDeliRole,
          restart_at=None, pool_floor=8):
    """Feed a seeded history through `role_cls` in frames, one step a
    frame, a checkpoint every step (a pool of `pool_floor` rows parks
    and reloads documents throughout); at `restart_at` a new role
    restores from the last checkpoint. Returns (role, saves seen)."""
    monkeypatch.setattr(dk, "RESIDENT_FLOOR", pool_floor)
    monkeypatch.setattr(dk, "RESIDENT_HORIZON_S", 0.0)
    shared = str(tmp_path)
    os.makedirs(os.path.join(shared, "topics"), exist_ok=True)
    seen = []

    def new_role(owner):
        return verify_saves(role_cls(
            shared, owner=owner, ttl_s=3600.0, batch=96,
            ckpt_interval_s=0.0, log_format=log_format), seen)

    role = new_role("k1")
    raw = make_topic(os.path.join(
        shared, "topics", f"{role.in_topic_name}.jsonl"), log_format)
    recs = history(seed)
    rng = random.Random(seed + 1)
    lo = 0
    while lo < len(recs):
        hi = min(len(recs), lo + rng.randint(30, 90))
        raw.append_many(recs[lo:hi])
        lo = hi
        role.step(idle_sleep=0)
        if restart_at is not None and lo >= restart_at:
            restart_at = None
            role.checkpoint()
            role.leases.release(role.name)
            role = new_role("k2")
    while role.step(idle_sleep=0):
        pass
    role.checkpoint()
    return role, seen


# ---------------------------------------------------------------------------
# byte identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("log_format", ["columnar", "json"])
@pytest.mark.parametrize("seed", [1, 29])
def test_every_checkpoint_is_the_full_encoding(tmp_path, registry,
                                               monkeypatch, seed,
                                               log_format):
    role, seen = drive(tmp_path, monkeypatch, seed, log_format)
    pool = role.core.pool
    assert len(seen) > 10 and all(n == 1 for n in seen)
    assert len(pool.docs) == 40 and pool.docs[NO_CLIENTS]["clients"] == {}
    assert registry.counter("deli_pool_evictions_total",
                            role="deli").value > 0
    assert registry.counter("deli_pool_loads_total", role="deli").value > 0
    assert registry.counter("deli_pool_compactions_total",
                            role="deli").value > 0
    assert full_loads(registry) == 0
    assert counter(registry, "reused") > 0


@pytest.mark.parametrize("log_format", ["columnar", "json"])
def test_restored_role_checkpoints_the_full_encoding(tmp_path, registry,
                                                     monkeypatch,
                                                     log_format):
    role, seen = drive(tmp_path, monkeypatch, 7, log_format,
                       restart_at=700)
    assert role.owner == "k2" and len(seen) > 10
    assert all(n == 1 for n in seen)
    assert full_loads(registry) == 0


@pytest.mark.parametrize("preds", [(), ("r-old",)], ids=["root", "succ"])
def test_ranged_wrapper_checkpoints_the_full_encoding(tmp_path, registry,
                                                      monkeypatch, preds):
    entry = {"rid": "r-all", "lo": 0, "hi": 1 << 32, "preds": list(preds)}
    cls = ranged_role_class(KernelDeliRole, entry, 3)
    role, seen = drive(tmp_path, monkeypatch, 11, "columnar",
                       role_cls=cls, restart_at=800)
    assert sum(seen) > 10
    env = role.ckpt.load(role.name)
    state = env["state"]["state"]
    assert state["__ranged__"] == 1 and state["epoch"] == 3
    assert state["docs"] == role.core.pool.checkpoint_docs()
    assert full_loads(registry, role.name) == 0


def test_pool_encoding_covers_stamps_and_odd_values():
    """The per-record mirror writes of the in-proc frontend (stamps
    included), and values outside the integer fast path, encode as
    `json.dumps` writes them."""
    pool = SeqPool()

    def check():
        enc, _ = pool.encode_docs()
        assert b"".join(enc.parts) == \
            json.dumps(pool.checkpoint_docs()).encode()

    check()
    assert pool.encode_docs()[0].parts == [b"{", b"}"]
    for i, d in enumerate(ODD_DOCS):
        h = pool.touch(d)
        for c in ODD_CLIENTS:
            pool.col_of_join(h, c)
            pool.apply_join(d, c, h["seq"] + 1, 0)
        check()
        pool.apply_op(d, 3, h["seq"] + 1, 1, 1, h["seq"])
        pool.apply_stamp(d, h["seq"] + 1, 2)
        pool.apply_leave(d, 2 ** 40, h["seq"] + 1, 2)
        check()
    h = pool.touch("odd")
    h["clients"].update({'x"y': [True, 2.5], 5: [None, float("nan")]})
    pool.mark_dirty(["odd"])
    check()
    h["clients"].clear()
    h["seq"] = 2.0
    pool.mark_dirty(["odd"])
    check()
    pool.touch(7)  # a non-string document id
    check()


def test_inproc_frontend_pool_encodes_what_it_checkpoints():
    """`KernelDeliLambda` (joins, leaves, control stamps, boxcars) keeps
    the same marks: its pool's encoding after every pump is its
    checkpoint's per-doc map."""
    from fluidframework_tpu.protocol.messages import DocumentMessage, \
        MessageType
    from fluidframework_tpu.server.log import MessageLog

    rng = random.Random(3)
    log = MessageLog()
    deli = dk.KernelDeliLambda(log)
    seqs = {}
    for step in range(30):
        for _ in range(rng.randint(1, 12)):
            d = f"doc{rng.randrange(4)}"
            c = rng.randrange(1, 4)
            r = rng.random()
            if r < 0.25:
                log.topic("rawdeltas").append(
                    {"doc": d, "kind": "join", "client": c})
                seqs[(d, c)] = 0
            elif r < 0.35:
                log.topic("rawdeltas").append(
                    {"doc": d, "kind": "leave", "client": c})
            elif r < 0.5:
                log.topic("rawdeltas").append(
                    {"doc": d, "kind": "control",
                     "type": MessageType.SUMMARY_ACK, "contents": {}})
            else:
                seqs[(d, c)] = seqs.get((d, c), 0) + 1
                log.topic("rawdeltas").append(
                    {"doc": d, "kind": "op", "client": c,
                     "msg": DocumentMessage(client_seq=seqs[(d, c)],
                                            ref_seq=0, contents=None)})
        deli.pump()
        docs = deli.checkpoint()["docs"]
        enc, _ = deli.core.pool.encode_docs()
        assert b"".join(enc.parts) == json.dumps(docs).encode()


# ---------------------------------------------------------------------------
# engagement: the counters, the compaction sweep
# ---------------------------------------------------------------------------


def _joined_role(tmp_path, n_docs=12, clients=3):
    role = KernelDeliRole(str(tmp_path), owner="k", ttl_s=3600.0)
    out = []
    i = 0
    for d in range(n_docs):
        for c in range(1, clients + 1):
            role.process(i, {"kind": "join", "doc": f"d{d}", "client": c},
                         out)
            i += 1
    role.flush_batch(out)
    return role, i


def test_checkpoint_without_traffic_reuses_every_doc(tmp_path, registry):
    role, _ = _joined_role(tmp_path)
    role.fence = 1
    role.checkpoint()
    assert (counter(registry, "encoded"), counter(registry, "reused")) \
        == (12, 0)
    with open(role.ckpt._path("deli"), "rb") as f:
        first = f.read()
    role.checkpoint()
    assert (counter(registry, "encoded"), counter(registry, "reused")) \
        == (12, 12)
    assert role.ckpt_docs_encoded == 0
    with open(role.ckpt._path("deli"), "rb") as f:
        assert f.read() == first


@pytest.mark.parametrize("k", [1, 5])
def test_ops_on_k_docs_encode_k(tmp_path, registry, k):
    role, i = _joined_role(tmp_path)
    role.fence = 1
    role.checkpoint()
    out = []
    for n in range(30):
        d = n % k
        role.process(i + n, {"kind": "op", "doc": f"d{d}", "client": 1,
                             "clientSeq": n // k + 1, "refSeq": 0,
                             "contents": None}, out)
    role.flush_batch(out)
    assert sum(r["kind"] == "op" for r in out) == 30
    before = counter(registry, "encoded")
    role.checkpoint()
    assert counter(registry, "encoded") - before == k
    assert role.ckpt_docs_encoded == k
    assert counter(registry, "reused") == 12 - k
    env = role.ckpt.load("deli")
    assert env["state"]["state"] == role.core.pool.checkpoint_docs()
    assert full_loads(registry) == 0


def test_counters_on_metrics_from_the_first_checkpoint(tmp_path,
                                                       registry):
    role, _ = _joined_role(tmp_path, n_docs=0)
    role.fence = 1
    assert "deli_checkpoint_docs_encoded_total" not in \
        registry.to_prometheus()
    role.checkpoint()
    text = registry.to_prometheus()
    for kind in ("encoded", "reused"):
        (line,) = [ln for ln in text.splitlines()
                   if f"deli_checkpoint_docs_{kind}_total{{" in ln]
        assert 'role="deli"' in line
        assert float(line.rsplit(" ", 1)[1]) == 0


def test_snapshot_span_counts_docs_encoded(tmp_path, registry):
    log = M.SpanLog()
    old = M.set_span_log(log)
    try:
        role, _ = _joined_role(tmp_path, n_docs=7)
        role.fence = 1
        role.checkpoint()
        role.checkpoint()
    finally:
        M.set_span_log(old)
    snaps = [s for s in log.snapshot() if s[0] == "deli.checkpoint.snapshot"]
    # Two spans a checkpoint: building the state (n = docs encoded)
    # and freeing it after the write.
    assert [s[5] for s in snaps] == [7, None, 0, None]


@pytest.mark.parametrize("seed", [2, 9])
def test_sweep_visits_only_candidates_and_reclaims_the_same(seed):
    """The compaction sweep visits only documents touched since the
    last one, and reclaims exactly what a sweep over every document
    would, queueing the same reloads."""
    rng = random.Random(seed)
    pool = SeqPool(n_docs=8, n_clients=4)
    for _ in range(25):
        pool.begin()
        touched = set()
        for d in rng.sample(range(30), 8):
            doc = f"d{d}"
            h = pool.touch(doc)
            touched.add(id(h))
            for _ in range(rng.randint(1, 4)):
                c = rng.randrange(1, 7)
                col = pool.col_of_join(h, c)
                pool.note_client(col)
                if rng.random() < 0.7:  # some joins do not take
                    pool.apply_join(doc, c, h["seq"] + 1, h["min_seq"])
                    assert col == h["cmap"][c]
                if h["clients"] and rng.random() < 0.3:
                    pool.apply_leave(doc, rng.choice(sorted(h["clients"])),
                                     h["seq"] + 1, h["min_seq"])
        pool.prepare()
        want = {d: len(h["cmap"]) - len(h["clients"])
                for d, h in pool.docs.items()
                if len(h["cmap"]) > len(h["clients"])}
        loads = {pool.docs[d]["slot"] for d in want
                 if pool.docs[d]["slot"] is not None}
        visited = []
        entry = pool._compact_entry
        pool._compact_entry = lambda h: visited.append(h) or entry(h)
        assert pool.compact_all() == sum(want.values())
        del pool._compact_entry
        assert {id(h) for h in visited} <= touched
        assert {s for s, _ in pool._loads} == loads
        assert all(len(h["cmap"]) == len(h["clients"])
                   for h in pool.docs.values())


# ---------------------------------------------------------------------------
# round trip
# ---------------------------------------------------------------------------


def test_incremental_checkpoint_restores_in_both_roles(tmp_path, registry,
                                                       monkeypatch):
    role, _ = drive(tmp_path / "k", monkeypatch, 5, "columnar")
    want = role.core.pool.checkpoint_docs()
    env = FencedCheckpointStore(str(tmp_path / "k" / "checkpoints")).load(
        "deli")
    state = env["state"]["state"]
    assert state == want
    kernel = KernelDeliRole(str(tmp_path / "k2"), owner="a", ttl_s=3600.0)
    kernel.restore_state(state)
    assert kernel.snapshot_state() == want
    scalar = DeliRole(str(tmp_path / "s"), owner="b", ttl_s=3600.0)
    scalar.restore_state(state)
    got = scalar.snapshot_state()
    assert set(got) == set(want)
    for doc, st in want.items():
        assert (got[doc]["seq"], got[doc]["min_seq"]) == \
            (st["seq"], st["min_seq"])
        assert {c: (v["ref_seq"], v["client_seq"])
                for c, v in got[doc]["clients"].items()} == \
            {c: (v["ref_seq"], v["client_seq"])
             for c, v in st["clients"].items()}
