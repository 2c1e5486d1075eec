"""The benchmark's traffic generator: determinism by seed, agreement
with the program's own generator, and the open-loop schedule's
no-nack construction through the scalar deli."""

import numpy as np
import pytest

from benchmark import harness
from benchmark import traffic as gen
from benchmark.reference import deli_ref

LAGGED_FIELDS = ("op_type", "pos1", "pos2", "seq", "ref_seq", "client",
                 "buf_start", "ins_len", "prop_key", "prop_val", "min_seq",
                 "text")


def small_string_config(n_ops=1500):
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/string-1024c.json")
    cfg.update(ops_per_log=n_ops, clients=32, collab_window=128)
    return cfg, harness.load_json(f"{harness.BENCH_DIR}/traffic/"
                                  "lagged-mix.json")


def small_deli_config(docs=40, clients=6, rate=800.0):
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/docs-10k-64c.json")
    cfg.update(docs=docs, clients=clients)
    trf = harness.load_json(f"{harness.BENCH_DIR}/traffic/open-zipf.json")
    trf.update(rate_per_s=rate)
    return cfg, trf


@pytest.mark.parametrize("seed", [3, 2**31 + 77])
def test_lagged_log_is_deterministic_by_seed(seed):
    cfg, trf = small_string_config()
    a, b = gen.lagged_log(cfg, trf, seed), gen.lagged_log(cfg, trf, seed)
    for f in LAGGED_FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    c = gen.lagged_log(cfg, trf, seed + 1)
    assert not np.array_equal(a.pos1, c.pos1)


def test_lagged_log_matches_the_program_generator():
    from fluidframework_tpu.testing.synthetic import generate_lagged_stream

    cfg, trf = small_string_config(2000)
    ours = gen.lagged_log(cfg, trf, 11)
    theirs = generate_lagged_stream(2000, n_clients=32, seed=11, window=128)
    for f in LAGGED_FIELDS:
        assert np.array_equal(getattr(ours, f), getattr(theirs, f)), f


@pytest.mark.parametrize("seed", [5, 2**31 + 3])
def test_open_loop_is_deterministic_by_seed(seed):
    cfg, trf = small_deli_config()
    a = gen.open_loop(cfg, trf, seed, 2.0)
    b = gen.open_loop(cfg, trf, seed, 2.0)
    for f in ("due", "doc", "client", "cseq", "ref", "pad"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f
    (ha, oa), (hb, ob) = gen.contents_heap(a), gen.contents_heap(b)
    assert ha == hb and np.array_equal(oa, ob)
    c = gen.open_loop(cfg, trf, seed + 1, 2.0)
    # Same arrivals for every seed; the seed draws who sends what.
    assert np.array_equal(a.due, c.due)
    assert not np.array_equal(a.doc, c.doc)


def test_open_loop_schedule_is_well_formed():
    cfg, trf = small_deli_config(docs=30, clients=5, rate=2000.0)
    s = gen.open_loop(cfg, trf, 9, 2.0)
    assert len(s.due) == 4000 and np.all(np.diff(s.due) >= 0)
    heap, off = gen.contents_heap(s)
    assert len(off) == len(s.due) + 1
    assert 24 <= np.median(np.diff(off)) <= 48
    for d in range(30):
        for c in range(1, 6):
            m = (s.doc == d) & (s.client == c)
            assert np.array_equal(s.cseq[m], np.arange(1, m.sum() + 1))
            assert np.all(np.diff(s.ref[m]) >= 0)
            assert np.all(s.ref[m] >= c - 1)


def test_open_loop_gets_no_nack_through_the_scalar_deli():
    """The schedule through the program's scalar sequencer: no nack,
    and the benchmark's reference stamps equal the scalar deli's."""
    from fluidframework_tpu.protocol.messages import (
        DocumentMessage,
        NackMessage,
    )
    from fluidframework_tpu.server.sequencer import DocumentSequencer

    cfg, trf = small_deli_config(docs=25, clients=6, rate=1500.0)
    s = gen.open_loop(cfg, trf, 21, 2.0)
    docs = [DocumentSequencer(f"doc{d}") for d in range(25)]
    for seqr in docs:
        for c in range(1, 7):
            seqr.join(c)
    seq_e, msn_e = deli_ref.expected_ops(s)
    for i in range(len(s.due)):
        res = docs[s.doc[i]].sequence(int(s.client[i]), DocumentMessage(
            client_seq=int(s.cseq[i]), ref_seq=int(s.ref[i]), contents=None))
        assert not isinstance(res, NackMessage), (i, res)
        assert res.sequence_number == seq_e[i]
        assert res.minimum_sequence_number == msn_e[i]


def test_deli_control_differs_from_the_reference():
    cfg, trf = small_deli_config(rate=2000.0)
    for seed in (1, 2, 3):
        s = gen.open_loop(cfg, trf, seed, 2.0)
        seq_e, msn_e = deli_ref.expected_ops(s)
        seq_c, msn_c = deli_ref.expected_ops(s, control=True)
        assert np.array_equal(seq_c, seq_e)
        assert np.count_nonzero(msn_c != msn_e) > 0


def test_deli_control_fails_the_run_comparison():
    """The control's stamps, put through the run's own comparison in
    the program's place, come out not correct; the reference's pass."""
    from benchmark import controls

    cfg, trf = small_deli_config(docs=30, clients=6, rate=3000.0)
    for seed in (1, 2, 3):
        got = controls.deli_control(cfg, trf, seed, 2.0)
        assert got["stamps_mismatched"] > 0
        assert got["records_missing"] == 0
