"""The churning-partition cell (`sequence-churn`): its traffic, its plain
reference, a run at a small size on the CPU past the harness's look for
a chip (sound, it comes out correct; with a fault planted under the
timed path, not), and its per-layer readers."""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import deli_churn
from benchmark.reference import deli_churn_ref
from fluidframework_tpu.utils import metrics as M

CELL = "sequence-churn"
SMALL = {"docs": 2000, "batch": 1024}
# Under the residency floor a small partition parks nothing; scaled
# down with it, the rule parks and reloads as it does at full size.
SMALL_FLOOR = 1024


def spec():
    return harness.find_cell(harness.bench_spec(), CELL)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


def test_latest_churn_schedule():
    _, cfg, trf = spec()
    sched = deli_churn.schedule(cfg, trf, 2**31 + 3, 10.0, rate=4000)
    n_docs = int(cfg["docs"])
    # Set-up: every document in the partition, 2-8 clients each.
    k = np.bincount(sched.setup_doc, minlength=n_docs)
    assert k.min() == 2 and k.max() == 8
    # Inserts: 5% of events, each a new document joined by 2-8 clients
    # in one event, numbered after the partition's documents.
    joins = sched.kind == deli_churn.JOIN
    new_docs, per_doc = np.unique(sched.doc[joins], return_counts=True)
    assert new_docs.min() >= n_docs and per_doc.min() >= 2 \
        and per_doc.max() <= 8
    assert len(new_docs) == sched.n_docs_created
    assert abs(sched.n_docs_created / sched.n_events - 0.05) < 0.005
    ev_of_join = sched.event[joins]
    assert all(len(np.unique(sched.doc[joins][ev_of_join == e])) == 1
               for e in np.unique(ev_of_join)[:50])
    # Latest: the busiest document is among the newest.
    ops = sched.kind == deli_churn.OP
    busiest = np.bincount(sched.doc[ops]).argmax()
    created = sched.setup_doc[np.r_[True, np.diff(sched.setup_doc) != 0]]
    newest = set(created[-5:].tolist()) | set(new_docs.tolist())
    assert busiest in newest
    # Leaves about 1% of events, and no document drops below 2.
    leaves = sched.kind == deli_churn.LEAVE
    assert abs(np.count_nonzero(leaves) / sched.n_events - 0.01) < 0.003
    conn = np.bincount(sched.setup_doc, minlength=n_docs
                       + sched.n_docs_created)
    conn += np.bincount(sched.doc[joins], minlength=len(conn))
    for d in sched.doc[leaves]:
        conn[d] -= 1
        assert conn[d] >= 2
    # The same seed gives the same schedule; arrivals are the seed's own
    # only in which event each one is.
    again = deli_churn.schedule(cfg, trf, 2**31 + 3, 10.0, rate=4000)
    assert np.array_equal(again.doc, sched.doc)
    other = deli_churn.schedule(cfg, trf, 2**31 + 4, 10.0, rate=4000)
    assert np.array_equal(other.due_event, sched.due_event)
    assert not np.array_equal(other.doc[:100], sched.doc[:100])


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------


def _brute_force(kind, doc, client, cseq, ref):
    """One pass over the log in order, every document's state in one
    table, the MSN a full minimum after each stamp."""
    state = {}
    out = []
    for k, d, c, cs, r in zip(kind, doc, client, cseq, ref):
        st = state.setdefault(d, {"head": 0, "msn": 0, "refs": {},
                                  "cseqs": {}})
        if k == deli_churn.JOIN:
            st["refs"][c] = st["head"]
            st["cseqs"][c] = 0
        elif k == deli_churn.LEAVE:
            if c not in st["refs"]:
                out.append((0, st["msn"], False))
                continue
            del st["refs"][c]
        else:
            if c not in st["refs"] or r < st["msn"] or r > st["head"] \
                    or cs != st["cseqs"][c] + 1:
                out.append((0, st["msn"], True))
                continue
            st["refs"][c] = r
            st["cseqs"][c] = cs
        st["head"] += 1
        low = min(st["refs"].values()) if st["refs"] else st["head"]
        st["msn"] = max(st["msn"], low)
        out.append((st["head"], st["msn"], False))
    return out


def test_reference_equals_brute_force():
    _, cfg, trf = spec()
    cfg = dict(cfg, docs=30)
    sched = deli_churn.schedule(cfg, trf, 7, 1.0, rate=600)
    kind, doc, client, cseq, ref = (np.array(a) for a in
                                    deli_churn.full_log(sched))
    # Invalid records too: stale and future refSeqs, a clientSeq gap,
    # an unknown client's op and leave.
    rng = np.random.default_rng(1)
    w = np.flatnonzero(kind == deli_churn.OP)
    bad = rng.choice(w, 40, replace=False)
    ref[bad[:10]] = -1
    ref[bad[10:20]] += 1000
    cseq[bad[20:30]] += 3
    client[bad[30:35]] = 15
    kind[bad[35:]] = deli_churn.LEAVE
    client[bad[35:]] = 14
    seq, msn, nack = deli_churn_ref.stamps(kind, doc, client, cseq, ref)
    want = _brute_force(kind.tolist(), doc.tolist(), client.tolist(),
                        cseq.tolist(), ref.tolist())
    assert [(int(a), int(b), bool(c)) for a, b, c in zip(seq, msn, nack)] \
        == want
    assert nack.sum() > 0 and (seq == 0).sum() > nack.sum()


# ---------------------------------------------------------------------------
# runs at a small size
# ---------------------------------------------------------------------------


@pytest.fixture
def small_pool(monkeypatch):
    from fluidframework_tpu.server import deli_kernel

    monkeypatch.setattr(deli_kernel, "RESIDENT_FLOOR", SMALL_FLOOR)


def run_cell(seconds=1.0, seed=2**31 + 41, **trf_up):
    cell, cfg, trf = spec()
    cfg.update(SMALL)
    trf.update({"rate_per_s": 600.0, **trf_up})
    old = M.set_registry(M.MetricsRegistry())
    run = harness.Run(cell, cfg, trf, seed, seconds, False,
                      require_chip=False)
    try:
        harness.driver_for(cfg).run(run)
        return run.result(), run.facts
    finally:
        run.cleanup()
        M.set_registry(old)


def checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_sound_run_is_correct(small_pool):
    res, facts = run_cell()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(checks(res)) == set(deli_churn.CHECKS)
    d = facts["diag"]
    assert d["pool_evictions_window"] > 0 and d["pool_loads_window"] > 0
    assert d["pool_rows"] <= d["pool_budget"] == SMALL_FLOOR
    assert {"seq_latency_p95_ms", "sequenced_records_per_s",
            "setup_s"} <= set(res["metrics"])


@pytest.fixture
def no_caches():
    """A fault planted in a traced function must reach the programs the
    run compiles, and must not outlive the test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_stale_reload_is_caught(small_pool, monkeypatch):
    from fluidframework_tpu.server.deli_kernel import SeqPool

    def stale(self, idx, updates):
        return self.state  # the reloaded rows keep what was there

    monkeypatch.setattr(SeqPool, "_load", stale)
    res, _ = run_cell()
    assert not res["correct"]
    c = checks(res)
    assert c["stamps_mismatched"] + c["nacks"] + c["joins_mismatched"] > 0


def test_leave_without_msn_recompute_is_caught(small_pool, monkeypatch,
                                               no_caches):
    import jax.numpy as jnp

    from fluidframework_tpu.ops import sequencer_kernel as sk

    step = sk._step_one_doc

    def keep_msn_on_leave(state, aborted, kind, client, client_seq,
                          ref_seq, group, *, dedup=False):
        st, ab, out = step(state, aborted, kind, client, client_seq,
                           ref_seq, group, dedup=dedup)
        leave = kind == sk.SUB_LEAVE
        msn = jnp.where(leave, state.min_seq, st.min_seq)
        return st._replace(min_seq=msn), ab, out._replace(
            min_seq=jnp.where(leave, state.min_seq, out.min_seq))

    monkeypatch.setattr(sk, "_step_one_doc", keep_msn_on_leave)
    res, _ = run_cell(leave_share=0.05)
    assert not res["correct"]
    assert checks(res)["leaves_mismatched"] > 0


def test_dropped_join_is_caught(small_pool, monkeypatch):
    from fluidframework_tpu.ops.sequencer_kernel import SUB_JOIN, SUB_PAD
    from fluidframework_tpu.server.deli_kernel import PackedDeliCore

    orig = PackedDeliCore.add
    seen = [0]

    def add(self, slot, kind, *a, **kw):
        if kind == SUB_JOIN:
            seen[0] += 1
            if seen[0] == 500:
                kind = SUB_PAD  # the join never reaches the sequencer
        return orig(self, slot, kind, *a, **kw)

    monkeypatch.setattr(PackedDeliCore, "add", add)
    res, _ = run_cell()
    assert not res["correct"]
    c = checks(res)
    assert c["joins_mismatched"] + c["records_missing"] > 0


# ---------------------------------------------------------------------------
# per-layer readers
# ---------------------------------------------------------------------------

INTERVAL = (10.0, 20.0)
SPANS = [
    ("deli.pool.prepare", 5.0, 5.5, 3, 2, 900),  # before the interval
    ("deli.pool.prepare", 11.0, 11.002, 3, 4, 40),
    ("deli.pump", 11.0, 11.05, None, 1, 100),
    ("deli.pool.prepare", 12.0, 12.006, 13, 14, 0),
    ("deli.pump", 12.0, 12.1, None, 10, 300),
]


@pytest.fixture
def span_log():
    log = M.SpanLog(capacity=64)
    for rec in SPANS:
        log.append(rec)
    old = M.set_span_log(log)
    yield log
    M.set_span_log(old)


def _run(**facts):
    ts = SimpleNamespace(programs={"jit_seq_pool_load": 2e-6})
    ts.program_seconds = lambda p: sum(
        s for n, s in ts.programs.items() if p in n)
    return SimpleNamespace(
        trace_interval=INTERVAL, trace_summary=ts,
        device={"kind": "TPU v5 lite"},
        facts={"window": INTERVAL, "pool_clients": 16,
               "pool_loads_window": 120.0, "records_window": 600, **facts})


def test_pool_readers(span_log):
    from benchmark import roofline, roofline_pool

    read = harness.metric_reader
    assert read("pool_prepare_us_per_rec.churn")(_run()) == pytest.approx(
        1e6 * 0.008 / 400)
    assert read("pool_reload_rows_per_rec.churn")(_run()) == 0.2
    pct = read("pool_reload_roofline.churn")(_run())
    assert pct == pytest.approx(roofline.roofline_pct(
        roofline_pool.pool_load_bytes(40, 16), 2e-6, "TPU v5 lite"))
    assert 0 < pct < 100
    assert roofline_pool.pool_load_bytes(1, 16) == 4 + 8 + 16 * 9


def test_pool_readers_read_nothing_where_the_program_has_no_pool(span_log):
    """A program without the residency rule (no pool span, counter or
    reload program): every reader returns None, and none raises."""
    span_log.clear()
    run = _run(pool_loads_window=None)
    run.trace_summary.programs = {}
    for name in ("pool_prepare_us_per_rec.churn",
                 "pool_reload_rows_per_rec.churn",
                 "pool_reload_roofline.churn"):
        assert harness.metric_reader(name)(run) is None
