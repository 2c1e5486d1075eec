"""The benchmark's one traffic generator. A traffic mix is a JSON file
under ``traffic/``; its ``kind`` picks the shape of what is generated
and every other key is a parameter. Nothing here imports the program.

- ``lagged_log``: a whole sequenced SharedString op log, as columns. A
  share of the ops are caught up (``refSeq = seq - 1``); the rest lag
  the head uniformly inside the collaboration window, kept at or above
  the MSN and non-decreasing per client. Positions are drawn inside
  the visible length at each op's own (refSeq, client) perspective,
  queried from the benchmark's reference replica as the log grows
  (the loop runs in `reference/mergetree_ref.cpp`). Random draws are
  made in the order of the program's original generator
  (`testing.synthetic.generate_lagged_stream`), so one seed gives the
  same log in both.
- ``open_loop``: a deli partition's op schedule for an open-loop
  window: due times, documents, clients, clientSeqs, refSeqs and
  opaque contents. Arrival times come from a fixed stream, the same for
  every seed; the seed draws which document and client each arrival
  is, its refSeq lag and its contents.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Optional

import numpy as np

OP_INSERT, OP_REMOVE, OP_ANNOTATE = 0, 1, 2
NO_KEY = -1
ARRIVAL_SEED = 0x5EED


def lagged_log(config: dict, traffic: dict, seed: int) -> SimpleNamespace:
    """The op log of a `string-*` configuration under a `lagged_log`
    mix: columns op_type/pos1/pos2/seq/ref_seq/client/buf_start/ins_len/
    prop_key/prop_val/min_seq (int32[N]) and the text arena (int32[S])."""
    from benchmark.reference import string_ref

    n = int(config["ops_per_log"])
    n_clients = int(config["clients"])
    window = int(config["collab_window"])
    initial_len = int(config["initial_chars"])
    n_keys = int(config["prop_keys"])
    n_vals = int(config["prop_values"])
    w = traffic["op_weights"]
    w_total = w["insert"] + w["remove"] + w["annotate"]
    t_ins = w["insert"] / w_total
    t_rem = t_ins + w["remove"] / w_total

    rng = np.random.default_rng(seed)
    type_u = rng.random(n)
    pos_u = rng.random(n)
    lag_u = rng.random(n)
    lag_draw = rng.integers(1, max(window - 1, 1) + 1, n)
    len_draw = rng.integers(1, int(traffic["max_insert_len"]) + 1,
                            n).astype(np.int64)
    range_draw = rng.integers(1, int(traffic["max_range_len"]) + 1,
                              n).astype(np.int64)
    keys = rng.integers(0, n_keys, n).astype(np.int32)
    vals = rng.integers(0, n_vals, n).astype(np.int32)
    arena = rng.integers(ord("a"), ord("z") + 1,
                         initial_len + int(np.sum(len_draw))).astype(np.int32)

    cols, used = string_ref.gen_lagged(
        n, n_clients, window, float(traffic["caught_up_share"]), t_ins,
        t_rem, type_u, pos_u, lag_u, lag_draw, len_draw, range_draw, arena,
        initial_len,
    )
    ann = cols["op_type"] == OP_ANNOTATE
    prop_key = np.full(n, NO_KEY, np.int32)
    prop_val = np.zeros(n, np.int32)
    prop_key[ann] = keys[ann]
    prop_val[ann] = vals[ann]
    seq = np.arange(1, n + 1, dtype=np.int32)
    return SimpleNamespace(
        op_type=cols["op_type"], pos1=cols["pos1"], pos2=cols["pos2"],
        seq=seq, ref_seq=cols["ref_seq"],
        client=(np.arange(n, dtype=np.int32) % n_clients) + 1,
        buf_start=cols["buf_start"], ins_len=cols["ins_len"],
        prop_key=prop_key, prop_val=prop_val,
        min_seq=np.maximum(0, seq - window).astype(np.int32),
        text=arena[:used],
    )


def _group_rank(keys: np.ndarray) -> np.ndarray:
    """0-based rank of each element among earlier equal keys."""
    n = keys.shape[0]
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    first = np.ones(n, bool)
    first[1:] = ks[1:] != ks[:-1]
    ar = np.arange(n)
    rank_sorted = ar - np.maximum.accumulate(np.where(first, ar, 0))
    rank = np.empty(n, np.int64)
    rank[order] = rank_sorted
    return rank


def _group_cummax(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Running maximum of `values` within each key, in input order."""
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    v = values[order].astype(np.int64)
    gid = np.concatenate([[0], np.cumsum(ks[1:] != ks[:-1])])
    big = int(v.max() - v.min() + 1) if v.size else 1
    shifted = v - v.min() + gid * big
    run = np.maximum.accumulate(shifted) - gid * big + v.min()
    out = np.empty_like(run)
    out[order] = run
    return out


def doc_popularity(n_docs: int, spec: dict, rng) -> np.ndarray:
    """Per-document probabilities. ``scrambled_zipf`` is YCSB's
    scrambled zipfian: rank r has weight 1/(r+1)^theta and the ranks
    are spread over the documents by a permutation drawn from the
    seed; ``uniform`` gives every document the same weight."""
    dist = spec["dist"]
    if dist == "uniform":
        return np.full(n_docs, 1.0 / n_docs)
    if dist != "scrambled_zipf":
        raise ValueError(f"unknown document popularity {dist!r}")
    w = 1.0 / np.power(np.arange(1, n_docs + 1, dtype=np.float64),
                       float(spec["theta"]))
    p = np.empty(n_docs)
    p[rng.permutation(n_docs)] = w / w.sum()
    return p


def open_loop(config: dict, traffic: dict, seed: int,
              seconds: float, rate: Optional[float] = None
              ) -> SimpleNamespace:
    """The op schedule of one open-loop window of `seconds`: arrays in
    arrival order — due (s after the window opens), doc (0..D-1),
    client (1..C), cseq, ref, and contents (heap bytes + offsets).
    Every client of every document has joined before the window, in
    document-major order, so client c joined doc d at head c-1."""
    n_docs = int(config["docs"])
    n_clients = int(config["clients"])
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    n = int(round(rate * seconds))
    arrivals = np.random.default_rng(ARRIVAL_SEED)
    due = np.sort(arrivals.random(n)) * seconds

    rng = np.random.default_rng(seed)
    p = doc_popularity(n_docs, traffic["doc_popularity"], rng)
    doc = rng.choice(n_docs, size=n, p=p).astype(np.int64)
    client = rng.integers(1, n_clients + 1, n).astype(np.int64)
    lag = rng.integers(0, int(traffic["ref_lag_max"]) + 1, n)
    pad = rng.integers(0, 1 << 62, n, dtype=np.int64)

    pair = doc * n_clients + (client - 1)
    cseq = _group_rank(pair) + 1
    head = n_clients + _group_rank(doc)  # the doc's seq before this op
    ref = np.maximum(head - lag, client - 1)  # never below the join
    ref = _group_cummax(pair, ref)
    width = int(traffic["contents_bytes"])
    return SimpleNamespace(due=due, doc=doc, client=client, cseq=cseq,
                           ref=ref, pad=pad, contents_bytes=width,
                           n_docs=n_docs, n_clients=n_clients, rate=rate)


def contents_heap(sched: SimpleNamespace, lo: int = 0,
                  hi: Optional[int] = None):
    """Opaque JSON contents of ops lo..hi-1: each carries its due time
    in microseconds and a seed-drawn pad to `contents_bytes`. Returns
    (heap bytes, uint32 offsets[n+1])."""
    hi = len(sched.due) if hi is None else hi
    width = sched.contents_bytes
    parts = []
    for i in range(lo, hi):
        head = '{"t":%d,"p":"' % int(sched.due[i] * 1e6)
        fill = max(1, width - len(head) - 2)
        parts.append((head + ("%016x" % sched.pad[i]) * 2)[:len(head) + fill]
                     + '"}')
    blobs = [s.encode() for s in parts]
    off = np.zeros(len(blobs) + 1, np.uint32)
    off[1:] = np.cumsum([len(b) for b in blobs])
    return b"".join(blobs), off
