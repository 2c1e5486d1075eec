"""The program's own spans (`utils.metrics.span`): nesting and parent
links, the bounded span log, the `span_ms` histogram, the off switch,
the deli step's and checkpoint's span trees, the replay feed's spans,
and the pinned XLA program names that profiler readers match."""

from __future__ import annotations

import os
import re
import subprocess
import sys

import numpy as np
import pytest

from fluidframework_tpu.utils import metrics as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh():
    """A registry and span log of the test's own."""
    reg = M.MetricsRegistry()
    old_reg = M.set_registry(reg)
    log = M.SpanLog(capacity=4096)
    old_log = M.set_span_log(log)
    yield reg, log
    M.set_registry(old_reg)
    M.set_span_log(old_log)


def _span_hist(reg, name):
    return [h for h in reg.snapshot()["histograms"]
            if h["name"] == "span_ms" and h["labels"]["span"] == name]


def test_span_nesting_parents_and_histogram(fresh):
    reg, log = fresh
    outer, inner = M.span("a.outer", role="r"), M.span("a.inner", role="r")
    with outer as o:
        with inner as i:
            i.n = 7
        with inner:
            pass
        o.n = 3
    recs = log.snapshot()
    assert [r[0] for r in recs] == ["a.inner", "a.inner", "a.outer"]
    (n1, t0a, t1a, pa, sa, ca), (_, t0b, t1b, pb, sb, cb), \
        (_, t0o, t1o, po, so, co) = recs
    assert po is None and pa == so and pb == so
    assert len({sa, sb, so}) == 3
    assert (ca, cb, co) == (7, None, 3)
    assert t0o <= t0a <= t1a <= t0b <= t1b <= t1o
    h = _span_hist(reg, "a.inner")
    assert len(h) == 1 and h[0]["count"] == 2
    assert h[0]["labels"] == {"span": "a.inner", "role": "r"}
    assert _span_hist(reg, "a.outer")[0]["sum"] == pytest.approx(
        (t1o - t0o) * 1000.0)


def test_span_records_and_pops_on_exception(fresh):
    _, log = fresh
    sp = M.span("a.fails")
    with pytest.raises(ValueError):
        with sp:
            raise ValueError("boom")
    with M.span("a.after"):
        pass
    recs = log.snapshot()
    assert [r[0] for r in recs] == ["a.fails", "a.after"]
    assert recs[1][3] is None  # the failed span left the stack


def test_span_log_bound_and_overwritten(fresh):
    _, _ = fresh
    log = M.SpanLog(capacity=3)
    M.set_span_log(log)
    sp = M.span("a.x")
    ends = []
    for _ in range(5):
        with sp:
            pass
        ends.append(log.snapshot()[-1][2])
    assert len(log.snapshot()) == 3
    assert log.overwritten == 2
    assert log.overwritten_until == ends[1]
    assert log.lost_since(ends[1]) and not log.lost_since(ends[2])
    log.clear()
    assert log.snapshot() == [] and log.overwritten == 0
    assert not log.lost_since(0.0)


def test_set_enabled_false_records_nothing(fresh):
    reg, log = fresh
    sp = M.span("a.off")
    old = M.set_enabled(False)
    try:
        with sp as f:
            f.n = 5  # a no-op frame takes the count silently
            with M.span("a.inner"):
                pass
    finally:
        M.set_enabled(old)
    assert log.snapshot() == []
    assert all(h["count"] == 0 for h in _span_hist(reg, "a.off"))
    # A span entered while off and left while on records nothing and
    # leaves the stack alone.
    M.set_enabled(False)
    try:
        sp.__enter__()
    finally:
        M.set_enabled(True)
    sp.__exit__(None, None, None)
    with M.span("a.on"):
        pass
    assert [(r[0], r[3]) for r in log.snapshot()] == [("a.on", None)]


def test_span_does_not_import_jax():
    code = (
        "import sys\n"
        "from fluidframework_tpu.utils import metrics as M\n"
        "with M.span('a.x'):\n"
        "    pass\n"
        "assert M.get_span_log().snapshot()[0][0] == 'a.x'\n"
        "print('jax' in sys.modules)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# ---------------------------------------------------------------------------
# the deli step and its checkpoint
# ---------------------------------------------------------------------------


def _tree(recs, root_sid):
    """(depth, name) of every span under `root_sid`, depth first, each
    span's children in start order."""
    kids = {}
    for r in recs:
        kids.setdefault(r[3], []).append(r)
    out = []

    def walk(sid, depth):
        for r in sorted(kids.get(sid, []), key=lambda r: r[1]):
            out.append((depth, r[0]))
            walk(r[4], depth + 1)
    walk(root_sid, 1)
    return out


def _raw_records(n_docs=3, n_clients=2, ops=6):
    recs = [{"kind": "join", "doc": f"doc{d}", "client": c}
            for d in range(n_docs) for c in range(1, n_clients + 1)]
    for k in range(ops):
        for d in range(n_docs):
            for c in range(1, n_clients + 1):
                recs.append({"kind": "op", "doc": f"doc{d}", "client": c,
                             "clientSeq": k + 1, "refSeq": 0,
                             "contents": {"k": k}})
    return recs


def test_kernel_deli_step_and_checkpoint_span_trees(tmp_path, fresh):
    from fluidframework_tpu.server.columnar_log import make_topic
    from fluidframework_tpu.server.deli_kernel import KernelDeliRole

    _, log = fresh
    shared = str(tmp_path)
    os.makedirs(os.path.join(shared, "topics"))
    raw = make_topic(os.path.join(shared, "topics", "rawdeltas.jsonl"),
                     "columnar")
    recs = _raw_records()
    raw.append_many(recs)
    role = KernelDeliRole(shared, owner="k", ttl_s=3600.0, batch=4096,
                          log_format="columnar", ckpt_interval_s=3600.0,
                          ckpt_bytes=1 << 40)
    assert role.step() == len(recs)
    spans = log.snapshot()
    roots = [r for r in spans if r[0] == "deli.pump"]
    assert len(roots) == 1 and roots[0][3] is None
    assert roots[0][5] == len(recs)
    assert _tree(spans, roots[0][4]) == [
        (1, "deli.poll"), (1, "deli.flush"), (2, "deli.pool.prepare"),
        (2, "deli.kernel"), (2, "deli.emit"), (1, "deli.append"),
        (1, "deli.heartbeat"),
    ]
    by_name = {r[0]: r for r in spans}
    assert by_name["deli.poll"][5] == len(recs)
    assert by_name["deli.kernel"][5] == len(recs)
    # New documents on never-used rows: nothing to reload.
    assert by_name["deli.pool.prepare"][5] == 0

    log.clear()
    role.checkpoint()
    spans = log.snapshot()
    (root,) = [r for r in spans if r[0] == "deli.checkpoint"]
    assert root[3] is None
    # The snapshot's second span frees it, after the write.
    assert _tree(spans, root[4]) == [
        (1, "deli.checkpoint.snapshot"), (1, "checkpoint.encode"),
        (1, "checkpoint.fence"), (1, "checkpoint.write"),
        (1, "deli.checkpoint.snapshot"),
    ]
    # The phases cover the checkpoint, and its histogram times it.
    phases = sum(r[2] - r[1] for r in spans if r[3] == root[4])
    assert phases <= root[2] - root[1]
    ckpt = role.metrics.histogram("checkpoint_ms", role="deli")
    assert ckpt.count == 1 and ckpt.sum <= (root[2] - root[1]) * 1000.0

    # An idle step: a pump with nothing polled, then the idle wait.
    log.clear()
    assert role.step(idle_sleep=0.001) == 0
    names = [(r[0], r[5]) for r in log.snapshot()]
    assert ("deli.pump", 0) in names and ("deli.poll", 0) in names
    assert names[-1][0] == "deli.idle"


# ---------------------------------------------------------------------------
# the replay feed
# ---------------------------------------------------------------------------


def _small_replica(**kw):
    from fluidframework_tpu.core.overlay_replay import OverlayDeviceReplica
    from fluidframework_tpu.testing.synthetic import generate_stream

    stream = generate_stream(600, n_clients=16, seed=5, initial_len=64,
                             window=128)
    return OverlayDeviceReplica(stream, initial_len=64, chunk_size=128,
                                window=1024, interpret=True, **kw)


def test_overlay_feed_spans(fresh):
    _, log = fresh
    rep = _small_replica()
    rep.prepare()
    assert [r[0] for r in log.snapshot()] == [
        "overlay.alloc", "overlay.pad", "overlay.upload"]
    log.clear()
    rep2 = _small_replica()
    log.clear()
    rep2.replay_streaming(n_segments=2)
    names = [r[0] for r in log.snapshot()]
    assert names == ["overlay.pad", "overlay.segment_put",
                     "overlay.segment_put"]


# ---------------------------------------------------------------------------
# pinned program names
# ---------------------------------------------------------------------------


def _module_name(lowered) -> str:
    m = re.search(r"module @(\S+)", lowered.as_text())
    assert m, "no module in the lowered text"
    return m.group(1)


def _seq_args(n_docs=8, n_clients=4, b=8):
    import jax.numpy as jnp

    from fluidframework_tpu.ops import sequencer_kernel as sk

    state = sk.make_state(n_docs, n_clients)
    z = jnp.zeros((n_docs, b), jnp.int32)
    batch = sk.SeqBatch(kind=z + sk.SUB_PAD, client=z, client_seq=z,
                        ref_seq=z)
    return state, sk.no_aborts(n_docs), batch, z + sk.NO_GROUP


def test_sequencer_program_names_are_pinned():
    import jax

    from fluidframework_tpu.ops import sequencer_kernel as sk

    state, aborted, batch, groups = _seq_args()
    one = sk._sequence_batch_grouped_jit.lower(state, aborted, batch,
                                               groups, True)
    assert sk.SEQUENCER_PROGRAM in _module_name(one)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("docs",))
    sharded = sk.sharded_sequence_fn(mesh, dedup=True).lower(
        state, aborted, batch, groups)
    assert sk.SEQUENCER_PROGRAM in _module_name(sharded)


def test_pool_load_program_name_is_pinned():
    import jax.numpy as jnp

    from fluidframework_tpu.ops import sequencer_kernel as sk
    from fluidframework_tpu.server import deli_kernel as dk

    state = sk.make_state(8, 4)
    idx = jnp.zeros(8, jnp.int32)
    rows = [jnp.zeros((8,) + a.shape[1:], a.dtype) for a in state]
    lowered = dk._load_rows.lower(state, idx, *rows)
    assert dk.SEQ_POOL_LOAD_PROGRAM in _module_name(lowered)


def test_overlay_program_names_are_pinned():
    import jax.numpy as jnp

    from fluidframework_tpu.core import overlay_replay as orp
    from fluidframework_tpu.ops import overlay_pallas as op

    rep = _small_replica()
    rep.prepare()
    fused = op.replay_fused.lower(
        rep.table, rep._dev, rep.log, rep.counts, rep._msn_by_chunk,
        rep.chunk_size, True)
    assert op.OVERLAY_PROGRAM in _module_name(fused)

    host = rep._host
    shapes = tuple((a.shape[1] if a.ndim > 1 else 0) for a in host)
    packed = jnp.asarray(np.concatenate(
        [np.ascontiguousarray(a).reshape(-1) for a in host]
        + [rep._host_msn]).astype(np.int32))
    step = orp._stream_step_fn(rep.chunk_size, True, len(host.seq),
                               rep.n_chunks, shapes)
    seg = step.lower(rep.table, rep.log, rep.counts, packed, jnp.int32(0))
    assert op.OVERLAY_PROGRAM in _module_name(seg)
