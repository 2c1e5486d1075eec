"""Driver of the churning-partition cells (configuration "system":
"deli_churn").

The system under test is the served ordering loop, as in
`drivers/deli.py`: the program's `server.deli_kernel.KernelDeliRole`
driven through `_Role.step` in this process, which holds the chip,
between a load generator (`procs/churn_gen_proc.py`) and the deltas
reader (`procs/reader_proc.py`), neither of which imports JAX.

The partition holds many small documents, created in an order drawn
from the seed. Set-up joins each document's clients through the deli,
oldest document first. In the window, events arrive open loop: an op on
a document drawn by YCSB's `latest` distribution (a zipfian over how
recently the document was created), a new document whose clients all
join it in one frame, or a client leaving a document that keeps at
least two. The newest documents are the busiest and older ones go
quiet, so a role that bounds its resident pool parks and reloads
documents all through the window.

Correctness, after the window: every record in the deltas topic is
compared with the benchmark's reference (`reference/deli_churn_ref.py`)
over the whole raw log, set-up joins included: seq and MSN of every
join, leave and op, each exactly once, and no nack.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np

DRAIN_SECONDS = 60.0
PROCS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "procs")
OP, JOIN, LEAVE = 0, 1, 2  # record kinds (as the reference numbers them)
ARRIVAL_SEED = 0x5EED  # the arrival stream, the same for every seed
LEAVE_TRIES = 64  # draws for a document that can lose a client


def latest_ranks(u, counts, theta: float, n_max: int) -> np.ndarray:
    """YCSB `latest`: for each uniform draw u[i], a recency rank in
    [0, counts[i]) (0 = the newest document) from the zipfian with
    constant `theta` over counts[i] items, by its exact inverse CDF."""
    w = np.cumsum(1.0 / np.power(np.arange(1, n_max + 1, dtype=np.float64),
                                 theta))
    r = np.searchsorted(w, u * w[counts - 1], side="right")
    return np.minimum(r, counts - 1)


def schedule(config: dict, traffic: dict, seed: int, seconds: float,
             rate: float = None) -> SimpleNamespace:
    """The raw log of one run: the set-up joins (`setup_doc`,
    `setup_client`: every client of every document in the partition,
    oldest document first) and the window's records in order (`due`
    seconds after the window opens, `kind`, `doc`, `client`, `cseq`,
    `ref`, `pad` for the contents, and `event`, the arrival each record
    belongs to). Arrival times come from a fixed stream; the seed draws
    the creation order, the client counts and every event."""
    n_docs = int(config["docs"])
    kmin, kmax = int(config["clients_min"]), int(config["clients_max"])
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    n = int(round(rate * seconds))
    due_ev = np.sort(np.random.default_rng(ARRIVAL_SEED).random(n)) \
        * seconds

    rng = np.random.default_rng(seed)
    order = rng.permutation(n_docs)
    k_pre = rng.integers(kmin, kmax + 1, n_docs)
    u = rng.random(n)
    rank_u = rng.random(n)
    client_u = rng.random(n)
    k_new = rng.integers(kmin, kmax + 1, n)
    lag = rng.integers(0, int(traffic["ref_lag_max"]) + 1, n)
    pad_ev = rng.integers(0, 1 << 62, n, dtype=np.int64)
    retry = np.random.default_rng([seed, 1])

    ins = u < float(traffic["insert_share"])
    leave = ~ins & (u < float(traffic["insert_share"])
                    + float(traffic["leave_share"]))
    n_ins = int(np.count_nonzero(ins))
    counts = n_docs + np.cumsum(ins) - ins  # documents before each event
    theta = float(traffic["doc_popularity"]["theta"])
    ranks = latest_ranks(rank_u, counts, theta, n_docs + n_ins)
    created = np.concatenate([order, n_docs + np.arange(n_ins)])
    target = created[counts - 1 - ranks]

    k_setup = k_pre[order]
    setup_doc = np.repeat(order, k_setup)
    first = np.concatenate([[0], np.cumsum(k_setup)[:-1]])
    setup_client = np.arange(len(setup_doc)) - np.repeat(first, k_setup) + 1

    head = np.zeros(n_docs + n_ins, np.int64)
    head[:n_docs] = k_pre
    conn = {}  # doc -> connected clients (made on first use)
    last = {}  # doc * 16 + client -> [refSeq, clientSeq]
    out = {k: [] for k in ("event", "kind", "doc", "client", "cseq", "ref")}

    def put(ev, kind, d, c, cs, rf):
        out["event"].append(ev)
        out["kind"].append(kind)
        out["doc"].append(d)
        out["client"].append(c)
        out["cseq"].append(cs)
        out["ref"].append(rf)

    def clients_of(d):
        cl = conn.get(d)
        if cl is None:
            cl = conn[d] = list(range(1, int(head[d]) + 1))
        return cl

    next_new = n_docs
    for i in range(n):
        if ins[i]:
            d = next_new
            next_new += 1
            conn[d] = list(range(1, int(k_new[i]) + 1))
            for c in conn[d]:
                put(i, JOIN, d, c, 0, c - 1)
            head[d] = k_new[i]
            continue
        d = int(target[i])
        if leave[i]:
            tries = 0
            while len(clients_of(d)) <= 2 and tries < LEAVE_TRIES:
                r = latest_ranks(retry.random(1), counts[i:i + 1], theta,
                                 n_docs + n_ins)[0]
                d = int(created[counts[i] - 1 - r])
                tries += 1
            cl = clients_of(d)
            if len(cl) > 2:
                c = cl.pop(int(client_u[i] * len(cl)))
                put(i, LEAVE, d, c, 0, 0)
                head[d] += 1
                continue
            # No document within reach can lose a client: an op instead.
        cl = clients_of(d)
        c = cl[int(client_u[i] * len(cl))]
        st = last.get(d * 16 + c)
        if st is None:
            st = last[d * 16 + c] = [c - 1, 0]  # joined at head c - 1
        st[0] = max(int(head[d]) - int(lag[i]), st[0])
        st[1] += 1
        put(i, OP, d, c, st[1], st[0])
        head[d] += 1
    cols = {k: np.asarray(v, np.int64) for k, v in out.items()}
    ev = cols["event"]
    return SimpleNamespace(
        setup_doc=setup_doc.astype(np.int64),
        setup_client=setup_client.astype(np.int64),
        due=due_ev[ev], kind=cols["kind"].astype(np.uint8), doc=cols["doc"],
        client=cols["client"], cseq=cols["cseq"], ref=cols["ref"],
        pad=pad_ev[ev], event=ev, due_event=due_ev, n_events=n,
        contents_bytes=int(traffic["contents_bytes"]), rate=rate,
        n_docs=n_docs, n_docs_created=n_ins)


def full_log(sched):
    """(kind, doc, client, cseq, ref) of the whole raw log: set-up joins,
    then the window's records."""
    nj = len(sched.setup_doc)
    cat = np.concatenate
    return (cat([np.full(nj, JOIN, np.uint8), sched.kind]),
            cat([sched.setup_doc, sched.doc]),
            cat([sched.setup_client, sched.client]),
            cat([np.zeros(nj, np.int64), sched.cseq]),
            cat([sched.setup_client - 1, sched.ref]))


def frame(kind, doc, client, cseq, ref, heap: bytes = b"", off=None):
    """One columnar raw frame of the given records."""
    from fluidframework_tpu.protocol import record_batch as rb

    codes = np.array([rb.K_RAW_OP, rb.K_RAW_JOIN, rb.K_RAW_LEAVE],
                     np.uint8)
    uniq, inv = np.unique(doc, return_inverse=True)
    n = len(doc)
    zeros = np.zeros(n, np.int64)
    if off is None:
        off = np.zeros(n + 1, np.uint32)
    return rb.ColumnarRecords(
        [f"doc{d}" for d in uniq.tolist()], codes[kind],
        np.full(n, 255, np.uint8), inv.astype(np.int32),
        np.asarray(client, np.int64), np.asarray(cseq, np.int64),
        np.asarray(ref, np.int64), zeros, zeros, np.full(n, -1, np.int64),
        off, heap)


def setup_frames(sched, per_frame: int):
    """The set-up joins as columnar frames of `per_frame` records."""
    total = len(sched.setup_doc)
    for lo in range(0, total, per_frame):
        hi = min(total, lo + per_frame)
        yield frame(np.full(hi - lo, JOIN, np.uint8), sched.setup_doc[lo:hi],
                    sched.setup_client[lo:hi], np.zeros(hi - lo, np.int64),
                    np.zeros(hi - lo, np.int64))


def compare(sched, rows):
    """Counts of every way the deltas topic departs from the reference
    over the whole raw log: records missing, duplicated or foreign,
    nacks, and ops, joins and leaves mis-stamped. Also returns, per
    read row that matched, the raw-log index it matched."""
    from benchmark.reference import deli_churn_ref
    from fluidframework_tpu.protocol import record_batch as rb

    kind, doc, client, cseq, ref = full_log(sched)
    seq_e, msn_e, nack_e = deli_churn_ref.stamps(kind, doc, client, cseq,
                                                 ref)
    n = len(doc)
    width = int(cseq.max()) + 1 if n else 1
    type_of = np.full(256, -1, np.int64)
    for k, name in ((OP, "op"), (JOIN, "join"), (LEAVE, "leave")):
        type_of[rb._TYPE_CODE[name]] = k

    def keys(k, d, c, cs):
        return ((d * 16 + c) * 4 + k) * width + cs

    key_s = keys(kind.astype(np.int64), doc, client, cseq)
    order = np.argsort(key_s)
    keys_sorted = key_s[order]
    rk = rows["kind"].astype(np.int64)
    rtyp = type_of[rows["type"].astype(np.int64)]
    seq_rows = (rk == rb.K_SEQ_OP) & (rtyp >= 0)
    out = {"nacks": int(np.count_nonzero(rk == rb.K_NACK))}
    rd, rc, rcs = (rows[k][seq_rows].astype(np.int64)
                   for k in ("doc", "client", "cseq"))
    ok = (rc >= 0) & (rc < 16) & (rd >= 0) & (rcs >= 0) & (rcs < width)
    key_r = np.where(ok, keys(rtyp[seq_rows], rd, rc, rcs), -1)
    pos = np.minimum(np.searchsorted(keys_sorted, key_r), max(n - 1, 0))
    found = ok & (keys_sorted[pos] == key_r) if n else ok & False
    idx = order[pos[found]]
    hits = np.bincount(idx, minlength=n)
    out["records_foreign"] = int(np.count_nonzero(
        ~seq_rows & (rk != rb.K_NACK))) + int(np.count_nonzero(~found))
    out["records_missing"] = int(np.count_nonzero((hits == 0) & ~nack_e))
    out["records_duplicated"] = int(np.count_nonzero(hits > 1))
    bad = (rows["seq"][seq_rows][found] != seq_e[idx]) \
        | (rows["msn"][seq_rows][found] != msn_e[idx])
    kinds = kind[idx]
    for k, name in ((OP, "stamps_mismatched"), (JOIN, "joins_mismatched"),
                    (LEAVE, "leaves_mismatched")):
        out[name] = int(np.count_nonzero(bad & (kinds == k)))
    out["reference_nacks"] = int(np.count_nonzero(nack_e))
    return out, seq_rows, found, idx


CHECKS = ("records_missing", "records_duplicated", "records_foreign",
          "nacks", "stamps_mismatched", "joins_mismatched",
          "leaves_mismatched", "reference_nacks")


def _stop(proc, timeout: float = 10.0) -> None:
    if proc is None or proc.poll() is not None:
        return
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def _counter(registry, name: str, **labels):
    """A counter's value, or None where the program never made it."""
    want = {k: str(v) for k, v in labels.items()}
    for c in registry.snapshot()["counters"]:
        if c["name"] == name and c["labels"] == want:
            return float(c["value"])
    return None


def _warm_sequencer(jax, role) -> None:
    """The sequencer program at every batch width, for a program whose
    role has no warm-up of its own (as `drivers/deli.py` warms it)."""
    from fluidframework_tpu.ops import sequencer_kernel as sk

    pool = role.core.pool
    pool.prepare()
    b = 8
    while b <= role.core.max_cols:
        state = sk.make_state(pool.n_docs, pool.n_clients)
        z = np.zeros((pool.n_docs, b), np.int32)
        batch = sk.SeqBatch(kind=z + sk.SUB_PAD, client=z, client_seq=z,
                            ref_seq=z)
        jax.block_until_ready(sk.sequence_batch_grouped(
            state, jax.tree_util.tree_map(jax.numpy.asarray, batch),
            jax.numpy.asarray(z + sk.NO_GROUP), True))
        b *= 2


def run(run) -> None:
    from benchmark.harness import log, quantile

    cfg, trf = run.config, run.traffic
    jax = run.start_jax()
    with run.part("schedule"):
        sched = schedule(cfg, trf, run.seed, run.seconds)
    n_setup, n_win = len(sched.setup_doc), len(sched.doc)
    shared = os.path.join(run.work, "farm")
    topics = os.path.join(shared, "topics")
    os.makedirs(topics)
    raw_path = os.path.join(topics, "rawdeltas.jsonl")
    deltas_path = os.path.join(topics, "deltas.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    spec_path = os.path.join(run.work, "gen.json")
    late_path = os.path.join(run.work, "late.npy")
    rows_path = os.path.join(run.work, "rows.npz")
    stop_path = os.path.join(run.work, "stop")
    with open(spec_path, "w") as f:
        json.dump({"raw": raw_path, "config": cfg, "traffic": trf,
                   "seed": run.seed, "seconds": run.seconds,
                   "out": late_path}, f)
    gen_p = reader_p = None
    try:
        gen_p = subprocess.Popen(
            [sys.executable, os.path.join(PROCS, "churn_gen_proc.py"),
             spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)
        with run.part("import"):
            from fluidframework_tpu.server import deli_kernel
            from fluidframework_tpu.server.columnar_log import make_topic
        role = deli_kernel.KernelDeliRole(
            shared, owner="bench-deli", ttl_s=3600.0,
            batch=int(cfg["batch"]), log_format="columnar")
        reader_p = subprocess.Popen(
            [sys.executable, os.path.join(PROCS, "reader_proc.py"),
             deltas_path, rows_path, str(n_setup + n_win), stop_path],
            env=env)
        with run.part("joins"):
            raw = make_topic(raw_path, "columnar")
            for fr in setup_frames(sched, int(cfg["batch"])):
                raw.append_many([fr])
            while role.offset < n_setup:
                role.step()
        with run.part("warmup"):
            if hasattr(role, "warm_up"):
                # Let the joins' working set age out of the residency
                # horizon, so the pool fits the budget the window holds.
                time.sleep(deli_kernel.RESIDENT_HORIZON_S + 0.05)
                role.warm_up()
            else:
                _warm_sequencer(jax, role)
        with run.part("generator"):
            if gen_p.stdout.readline().strip() != "READY":
                raise RuntimeError("the load generator did not start")
        with run.part("checkpoint"):
            # Phase the checkpoint cadence to the window, as
            # drivers/deli.py does: the window's first pump writes the
            # next checkpoint.
            t = time.perf_counter()
            role.checkpoint()
            time.sleep((time.perf_counter() - t) / role.ckpt_duty + 0.1)

        ckpt_hist = role.metrics.histogram("checkpoint_ms", role=role.name)
        labels = {"role": role.name}
        t0_mono = time.monotonic() + 0.05
        gen_p.stdin.write(f"{t0_mono!r}\n")
        gen_p.stdin.flush()
        time.sleep(max(0.0, t0_mono - time.monotonic()))
        t0 = run.open_window()
        mono_off = t0_mono - t0  # perf_counter -> monotonic
        ckpt0, n_ckpt0 = ckpt_hist.sum, ckpt_hist.count
        loads0 = _counter(role.metrics, "deli_pool_loads_total", **labels)
        evicts0 = _counter(role.metrics, "deli_pool_evictions_total",
                           **labels)
        off0_win = role.offset
        run.trace_start()
        pumps = []
        end = t0 + run.seconds
        while True:
            off0 = role.offset
            with run.span("deli.step"):
                ts = time.perf_counter()
                moved = role.step()
            te = time.perf_counter()
            pumps.append((ts, te - ts, moved, off0, role.offset))
            if te >= end:
                break
        run.close_window(end)
        run.trace_stop()  # the whole window
        pool = role.core.pool
        run.facts.update(
            checkpoint_ms=ckpt_hist.sum - ckpt0,
            checkpoints=ckpt_hist.count - n_ckpt0,
            records_window=role.offset - off0_win,
            pool_rows=int(pool.n_docs), pool_clients=int(pool.n_clients),
            pool_resident=int(pool.resident_docs()))
        loads1 = _counter(role.metrics, "deli_pool_loads_total", **labels)
        evicts1 = _counter(role.metrics, "deli_pool_evictions_total",
                           **labels)
        if loads0 is not None and loads1 is not None:
            run.facts["pool_loads_window"] = loads1 - loads0
        if evicts0 is not None and evicts1 is not None:
            run.facts["pool_evictions_window"] = evicts1 - evicts0
        if hasattr(role, "residency_budget"):
            run.facts["pool_budget"] = role.residency_budget()
        deadline = time.perf_counter() + DRAIN_SECONDS
        while role.offset < n_setup + n_win and \
                time.perf_counter() < deadline:
            role.step()
        given_up = time.monotonic()
        run.memory_peak()
        _stop(gen_p, timeout=DRAIN_SECONDS)
        open(stop_path, "w").close()
        _stop(reader_p, timeout=DRAIN_SECONDS)
    finally:
        for p in (gen_p, reader_p):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
    del role

    rows = dict(np.load(rows_path))
    counts, seq_rows, found, idx = compare(sched, rows)
    for name in CHECKS:
        run.check(name, counts[name], 0)

    # Latency of every window record: due -> read. A record never read
    # counts as read when the drain gave up, later than any that was.
    t_all = np.full(n_setup + n_win, np.inf)
    np.minimum.at(t_all, idx, rows["t"][seq_rows][found])
    t_read = t_all[n_setup:]
    unread = ~np.isfinite(t_read)
    t_read[unread] = max(given_up, float(np.max(rows["t"], initial=0.0)))
    lat_ms = (t_read - (t0_mono + sched.due)) * 1000.0

    run.attempted = n_win
    run.failed = int(np.count_nonzero(unread)) + counts["nacks"]
    run.e2e["seq_latency_p95_ms"] = quantile(lat_ms, 0.95)
    close_mono = end + mono_off
    run.e2e["sequenced_records_per_s"] = float(
        np.count_nonzero(t_read <= close_mono)) / run.seconds
    # Whether the rate is sustained (benchmark/sweep.py): the events
    # due by the close with a record still unread there, and the p95 by
    # thirds of the window (by due time).
    ev_read = np.full(sched.n_events, -np.inf)
    np.maximum.at(ev_read, sched.event, t_read)
    due_by_close = sched.due_event <= run.seconds
    thirds = [quantile(lat_ms[(sched.due >= run.seconds * i / 3)
                              & (sched.due < run.seconds * (i + 1) / 3)],
                       0.95) for i in range(3)]
    run.facts["diag"] = {
        "rate": sched.rate, "records": n_win,
        "p50_ms": quantile(lat_ms, 0.5),
        "p95_ms": run.e2e["seq_latency_p95_ms"],
        "p99_ms": quantile(lat_ms, 0.99), "p95_thirds_ms": thirds,
        "backlog_at_close": int(np.count_nonzero(
            due_by_close & (ev_read > close_mono))),
        "steps": len(pumps), "checkpoints": run.facts.get("checkpoints"),
        "pool_rows": run.facts.get("pool_rows"),
        "pool_resident": run.facts.get("pool_resident"),
        "pool_budget": run.facts.get("pool_budget"),
        "pool_loads_window": run.facts.get("pool_loads_window"),
        "pool_evictions_window": run.facts.get("pool_evictions_window"),
        "docs_created": sched.n_docs_created}
    log(f"diag {json.dumps(run.facts['diag'])}")

    run.facts.update(pumps=pumps, sched=sched, n_joins=n_setup,
                     window=(t0, end))
    if os.path.exists(late_path):
        run.facts["late_s"] = np.load(late_path)
    run.reduce_trace()
