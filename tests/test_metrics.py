"""The observability layer (ISSUE 3): metrics registry semantics and
concurrency, op-lifecycle tracing across a LocalServer round-trip, the
live /metrics + /healthz endpoint, checkpoint cadence, and the
supervisor-side heartbeat-snapshot merge.

Determinism contract checked elsewhere but relied on here: traces and
metrics are observational only — chaos suites (tests/
test_chaos_recovery.py) and the deli differential suites (tests/
test_deli_kernel.py) run with tracing enabled (it is always on) and
still converge bit-identical to their goldens.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.request

import pytest

from fluidframework_tpu.dds import StringFactory
from fluidframework_tpu.runtime import ChannelRegistry, ContainerRuntime
from fluidframework_tpu.server import LocalServer
from fluidframework_tpu.server.monitor import MetricsServer
from fluidframework_tpu.utils import metrics as M

REGISTRY = ChannelRegistry([StringFactory()])


@pytest.fixture
def fresh_registry():
    """Isolate each test's instruments from the process default (the
    default registry is process-global by design)."""
    reg = M.MetricsRegistry()
    old = M.set_registry(reg)
    yield reg
    M.set_registry(old)


def connect_runtime(server, doc="doc", client_id=None):
    rt = ContainerRuntime(REGISTRY)
    ds = rt.create_datastore("default")
    ds.create_channel("s", StringFactory.type_name)
    rt.connect(server.connect(doc, client_id))
    return rt


def scrape(url: str):
    return urllib.request.urlopen(url, timeout=10).read().decode()


def parse_prometheus(text: str):
    """Line form -> {metric{labels}: float} (scrape-parses cleanly)."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.fullmatch(r"([a-zA-Z_:][\w:]*(?:\{[^}]*\})?) (\S+)", line)
        assert m, f"unparseable exposition line: {line!r}"
        out[m.group(1)] = float(m.group(2))
    return out


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = M.MetricsRegistry()
    c = reg.counter("ops_total", role="deli")
    c.inc()
    c.inc(2.5)
    assert reg.counter("ops_total", role="deli") is c  # create-or-return
    assert c.value == 3.5
    g = reg.gauge("fill", role="deli")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0
    # Same name different labels = distinct instrument.
    assert reg.counter("ops_total", role="scribe").value == 0
    # Same name different KIND is a registration error.
    with pytest.raises(ValueError):
        reg.gauge("ops_total", role="deli")


def test_histogram_bucket_edges():
    """Prometheus `le` semantics: an observation exactly on a bound
    lands IN that bucket; just above goes to the next; beyond the last
    bound goes to +Inf."""
    reg = M.MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1.0, 5.0, 10.0))
    for v in (0.0, 1.0, 1.0000001, 5.0, 10.0, 10.1):
        h.observe(v)
    assert h.counts == [2, 2, 1, 1]  # [<=1, <=5, <=10, +Inf]
    assert h.count == 6
    assert h.sum == pytest.approx(27.1000001)
    # Re-registering with different buckets is an error; same is fine.
    assert reg.histogram("lat_ms", buckets=(1.0, 5.0, 10.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("lat_ms", buckets=(1.0, 2.0))
    # Quantile interpolation stays inside the right bucket.
    snap = reg.snapshot()["histograms"][0]
    assert 0 <= M.histogram_quantile(snap, 0.25) <= 1.0
    assert M.histogram_quantile(snap, 1.0) == float("inf")


def test_registry_concurrency_exact_totals():
    """The lock-safety contract: concurrent increments/observations
    lose nothing."""
    reg = M.MetricsRegistry()
    n_threads, n_iter = 8, 5000
    c = reg.counter("hits")
    h = reg.histogram("obs_ms", buckets=(1.0, 10.0))

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe(i % 20)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    assert sum(h.counts) == h.count


def test_merge_and_report():
    a = M.MetricsRegistry()
    a.counter("x_total", role="deli").inc(3)
    a.histogram("lat_ms", buckets=(1.0, 2.0)).observe(1.5)
    a.gauge("fill").set(0.25)
    b = M.MetricsRegistry()
    b.merge(a.snapshot())
    b.merge(a.snapshot())  # counters/histograms ADD, gauges last-write
    assert b.counter("x_total", role="deli").value == 6
    h = b.histogram("lat_ms", buckets=(1.0, 2.0))
    assert h.count == 2 and h.counts == [0, 2, 0]
    assert b.gauge("fill").value == 0.25
    report = M.format_report([a.snapshot(), a.snapshot()])
    assert "lat_ms" in report and "x_total" in report
    assert "role=deli" in report


def test_histogram_snapshot_consistent_under_concurrent_observe():
    """The ISSUE-9 satellite fix: a snapshot's explicit sum/count must
    agree with its buckets even while observers race — the fields are
    copied under the instruments' lock, so no torn (sum != counts)
    snapshot can reach merge()/quantile estimation."""
    reg = M.MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(10.0,))
    stop = threading.Event()

    def work():
        while not stop.is_set():
            h.observe(5.0)  # every observation adds exactly 5 to sum

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = reg.snapshot()["histograms"][0]
            assert snap["sum"] == pytest.approx(5.0 * snap["count"])
            assert sum(snap["counts"]) == snap["count"]
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_merge_preserves_sum_count_quantiles_across_processes():
    """Two 'process' snapshots (JSON round-tripped, as the heartbeat
    channel carries them) merged into one registry must reproduce the
    exact sum/count and the same quantile estimates as a registry that
    observed every value directly."""
    values_a = [0.3, 1.5, 4.0, 9.0, 60.0]
    values_b = [0.7, 2.0, 30.0, 400.0]
    a, b, direct = (M.MetricsRegistry() for _ in range(3))
    for reg, vals in ((a, values_a), (b, values_b),
                      (direct, values_a + values_b)):
        h = reg.histogram("lat_ms")
        for v in vals:
            h.observe(v)
    merged = M.MetricsRegistry()
    for reg in (a, b):
        merged.merge(json.loads(json.dumps(reg.snapshot())))
    got = merged.snapshot()["histograms"][0]
    want = direct.snapshot()["histograms"][0]
    assert got["counts"] == want["counts"]
    assert got["count"] == want["count"] == 9
    assert got["sum"] == pytest.approx(want["sum"])
    for q in (0.5, 0.95, 0.99):
        assert M.histogram_quantile(got, q) == pytest.approx(
            M.histogram_quantile(want, q)
        )
    assert got["quantiles"] == want["quantiles"]


def test_histogram_stats_and_slo_summary():
    reg = M.MetricsRegistry()
    h = reg.histogram("op_stage_ms", buckets=(1.0, 10.0, 100.0),
                      stage="submit_to_broadcast")
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    reg.histogram("empty_ms", buckets=(1.0,))  # no observations
    snap = reg.snapshot()
    stats = M.histogram_stats(
        next(x for x in snap["histograms"] if x["name"] == "op_stage_ms")
    )
    assert stats["count"] == 4
    assert stats["mean"] == pytest.approx(555.5 / 4)
    assert 0 < stats["p50"] <= 10.0
    assert stats["p99"] == float("inf")  # beyond the last bucket
    slo = M.slo_summary(snap)
    [entry] = slo["histograms"]  # empty histograms are omitted
    assert entry["name"] == "op_stage_ms"
    assert entry["labels"] == {"stage": "submit_to_broadcast"}
    assert entry["p99"] is None  # JSON-safe overflow marker
    json.dumps(slo)  # the /slo body must be strict-JSON-able


def test_prometheus_quantile_series():
    reg = M.MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0))
    for v in (0.5, 0.6, 0.8, 5.0):
        h.observe(v)
    vals = parse_prometheus(reg.to_prometheus())
    assert 0 < vals['fluid_lat_ms_q{quantile="0.5"}'] <= 1.0
    assert 1.0 < vals['fluid_lat_ms_q{quantile="0.99"}'] <= 10.0
    # An estimate beyond the last finite bucket is omitted, not faked.
    h.observe(100.0)
    h.observe(100.0)
    vals = parse_prometheus(reg.to_prometheus())
    assert 'fluid_lat_ms_q{quantile="0.99"}' not in vals


def test_prometheus_exposition_parses():
    reg = M.MetricsRegistry()
    reg.counter("ops_total", role="deli").inc(7)
    h = reg.histogram("lat_ms", buckets=(1.0, 5.0))
    for v in (0.5, 3.0, 9.0):
        h.observe(v)
    vals = parse_prometheus(reg.to_prometheus())
    assert vals['fluid_ops_total{role="deli"}'] == 7
    # Cumulative buckets, +Inf == _count.
    assert vals['fluid_lat_ms_bucket{le="1"}'] == 1
    assert vals['fluid_lat_ms_bucket{le="5"}'] == 2
    assert vals['fluid_lat_ms_bucket{le="+Inf"}'] == 3
    assert vals["fluid_lat_ms_count"] == 3
    assert vals["fluid_lat_ms_sum"] == pytest.approx(12.5)


def test_set_enabled_swaps_null_registry():
    old = M.set_enabled(False)
    try:
        reg = M.get_registry()
        assert isinstance(reg, M.NullRegistry)
        reg.counter("whatever", role="x").inc()  # no-op, no error
        assert reg.to_prometheus() == ""
    finally:
        M.set_enabled(old)
    assert not isinstance(M.get_registry(), M.NullRegistry)


# ---------------------------------------------------------------------------
# op-lifecycle tracing across the live pipeline
# ---------------------------------------------------------------------------


def test_trace_monotone_across_localserver_roundtrip(fresh_registry):
    """Every sequenced op carries monotone per-stage timestamps
    (submit ≤ stamp ≤ durable ≤ broadcast) and the apply side folds
    them into nonzero stage histograms."""
    server = LocalServer()
    a = connect_runtime(server, client_id=1)
    b = connect_runtime(server, client_id=2)
    a.get_datastore("default").get_channel("s").insert_text(0, "hello")
    a.flush()
    b.get_datastore("default").get_channel("s").insert_text(0, ">> ")
    b.flush()
    order = {"submit": 0, "stamp": 1, "durable": 2, "broadcast": 3}
    data_ops = 0
    for msg in server.ops_from("doc", 0):
        assert msg.traces, f"untraced sequenced message seq={msg.sequence_number}"
        stages = [s for s, _ in msg.traces]
        assert stages == sorted(stages, key=order.__getitem__)
        ts = [t for _, t in msg.traces]
        assert ts == sorted(ts), f"non-monotone trace {msg.traces}"
        if "submit" in stages:
            data_ops += 1
            assert stages[0] == "submit"  # client-driver origin stamp
    assert data_ops == 2
    # All four stage histograms observed something.
    for stage in ("submit_to_stamp", "stamp_to_durable",
                  "stamp_to_broadcast", "broadcast_to_apply",
                  "submit_to_apply"):
        h = fresh_registry.histogram("op_stage_ms", stage=stage)
        assert h.count > 0, f"stage {stage} never observed"
    # Wire-format semantics for batch markers are unchanged by the
    # trace stamp: the trace rides metadata under its own key.
    raws = server.log.topic("rawdeltas").read(0)
    op_raws = [r for r in raws if r.get("kind") == "op"]
    assert all("tr_sub" in r["msg"].metadata for r in op_raws)


def test_metrics_endpoint_scrape_localserver(fresh_registry):
    server = LocalServer()
    rt = connect_runtime(server, client_id=1)
    rt.get_datastore("default").get_channel("s").insert_text(0, "x")
    rt.flush()
    mon = server.serve_metrics()
    try:
        assert server.serve_metrics() is mon  # idempotent
        vals = parse_prometheus(scrape(mon.url + "/metrics"))
        assert vals['fluid_op_stage_ms_count{stage="submit_to_stamp"}'] >= 1
        assert vals['fluid_deli_pump_records_count{impl="scalar"}'] >= 1
        hz = json.loads(scrape(mon.url + "/healthz"))
        assert hz["status"] == "ok" and hz["docs"] == 1
        snap = json.loads(scrape(mon.url + "/metrics.json"))
        assert any(
            h["name"] == "op_stage_ms" and h["count"] > 0
            for h in snap["histograms"]
        )
        with pytest.raises(urllib.error.HTTPError):
            scrape(mon.url + "/nope")
    finally:
        server.stop_metrics()


def test_kernel_deli_occupancy_gauges(fresh_registry):
    """The acceptance-criteria shape: a kernel-deli LocalServer run
    serves /metrics with nonzero op-latency histograms AND kernel
    occupancy gauges."""
    server = LocalServer(deli_impl="kernel")
    for d in range(3):
        rt = connect_runtime(server, doc=f"doc{d}", client_id=1)
        rt.get_datastore("default").get_channel("s").insert_text(0, "k")
        rt.flush()
    mon = server.serve_metrics()
    try:
        vals = parse_prometheus(scrape(mon.url + "/metrics"))
        assert vals["fluid_deli_pool_resident_docs"] == 3
        assert vals["fluid_deli_pool_doc_slots"] >= 3
        assert 0 < vals["fluid_deli_pool_fill_ratio"] <= 1
        assert vals['fluid_deli_pump_records_count{impl="kernel"}'] >= 3
        assert vals['fluid_op_stage_ms_count{stage="submit_to_stamp"}'] >= 3
        assert vals['fluid_op_stage_ms_count{stage="submit_to_apply"}'] >= 3
    finally:
        server.stop_metrics()


def test_kernel_pool_grow_evict_counters(fresh_registry):
    """Doc-slot pool growth and eviction are visible as counters."""
    from fluidframework_tpu.server.deli_kernel import SeqPool

    pool = SeqPool(n_docs=2, n_clients=2, max_resident=2)
    for i in range(5):
        pool.begin()
        pool.touch(f"doc{i}")
    grows = fresh_registry.counter("deli_pool_grows_total").value
    evicts = fresh_registry.counter("deli_pool_evictions_total").value
    assert evicts >= 3  # max_resident=2 parked the cold docs
    assert grows == 0  # eviction kept the pool at its cap
    # Touching everything in ONE pump forces growth (actives can't park).
    pool.begin()
    for i in range(5):
        pool.touch(f"doc{i}")
    assert fresh_registry.counter("deli_pool_grows_total").value >= 1


# ---------------------------------------------------------------------------
# checkpoint cadence (ROADMAP item (b))
# ---------------------------------------------------------------------------


def _mk_deli_role(tmp_path, fresh_registry, **kw):
    from fluidframework_tpu.server.queue import SharedFileTopic
    from fluidframework_tpu.server.supervisor import DeliRole

    role = DeliRole(str(tmp_path), owner="cadence-test", ttl_s=3600.0,
                    batch=8, **kw)
    raw = SharedFileTopic(str(tmp_path / "topics" / "rawdeltas.jsonl"))
    return role, raw


def test_checkpoint_cadence_time_byte_bounds(tmp_path, fresh_registry):
    """With both bounds huge, steps stop writing per-step checkpoints
    (the seed behavior); dropping either bound to zero resumes them.
    Durability is unaffected: recovery replays the checkpoint→durable
    gap (chaos suites prove that under kills)."""
    role, raw = _mk_deli_role(
        tmp_path, fresh_registry,
        ckpt_interval_s=3600.0, ckpt_bytes=1 << 40,
    )
    writes = fresh_registry.counter("checkpoint_writes_total", role="deli")
    raw.append_many([
        {"kind": "join", "doc": "d", "client": 1},
        {"kind": "op", "doc": "d", "client": 1, "clientSeq": 1,
         "refSeq": 0, "contents": {"i": 0}},
    ])
    assert role.step() == 2
    baseline = writes.value  # _recover()'s forced anchor checkpoint
    for i in range(2, 6):
        raw.append({"kind": "op", "doc": "d", "client": 1,
                    "clientSeq": i, "refSeq": 0, "contents": {"i": i}})
        assert role.step() == 1
    assert writes.value == baseline  # cadence held: no per-step writes
    assert role._ckpt_dirty
    # Byte bound: one more appended byte crosses it -> checkpoint.
    role.ckpt_bytes = 1
    raw.append({"kind": "op", "doc": "d", "client": 1, "clientSeq": 6,
                "refSeq": 0, "contents": {"i": 6}})
    role.step()
    role.wait_checkpoint()  # the cadence flushes on the writer thread
    assert writes.value == baseline + 1
    assert not role._ckpt_dirty
    # Time bound: interval 0 == the seed's every-step policy.
    role.ckpt_bytes = 1 << 40
    role.ckpt_interval_s = 0.0
    raw.append({"kind": "op", "doc": "d", "client": 1, "clientSeq": 7,
                "refSeq": 0, "contents": {"i": 7}})
    role.step()
    assert writes.value == baseline + 2
    # The durable checkpoint offset matches everything consumed, and
    # bytes/duration metrics recorded every write.
    env = role.ckpt.load("deli")
    assert env["state"]["offset"] == role.offset
    assert fresh_registry.counter(
        "checkpoint_bytes_total", role="deli").value > 0
    assert fresh_registry.histogram(
        "checkpoint_ms", role="deli").count == writes.value


def test_checkpoint_cadence_idle_flush(tmp_path, fresh_registry):
    """Progress folded before quiescence goes durable from the IDLE
    step once the interval elapses — a quiet stream cannot pin dirty
    state in memory forever."""
    # ckpt_duty=0 disables the storm guard: this test is about the
    # idle-flush contract alone, and an fsync stall on a loaded box
    # (last write cost S -> next gated for 5*S with the default duty)
    # would otherwise outlast the 60ms sleep below and flake.
    role, raw = _mk_deli_role(
        tmp_path, fresh_registry,
        ckpt_interval_s=0.05, ckpt_bytes=1 << 40, ckpt_duty=0.0,
    )
    writes = fresh_registry.counter("checkpoint_writes_total", role="deli")
    raw.append({"kind": "join", "doc": "d", "client": 1})
    role.step()
    role.wait_checkpoint()  # the cadence flushes on the writer thread
    before = writes.value
    if not role._ckpt_dirty:
        # The batch step itself crossed the 50ms interval and flushed;
        # make new dirty progress to exercise the idle path.
        raw.append({"kind": "op", "doc": "d", "client": 1,
                    "clientSeq": 1, "refSeq": 0, "contents": {}})
        role.step()
        role.wait_checkpoint()
        before = writes.value
    if role._ckpt_dirty:
        time.sleep(0.06)
        role.step(idle_sleep=0.0)  # no new input: the idle branch
        role.wait_checkpoint()
        assert writes.value >= before + 1
    assert not role._ckpt_dirty


# ---------------------------------------------------------------------------
# supervisor-side merge + endpoint
# ---------------------------------------------------------------------------


def test_supervisor_merges_heartbeat_metrics(tmp_path, fresh_registry):
    """Children report metrics up through the heartbeat channel; the
    supervisor's registry (and /metrics endpoint) merges the
    snapshots per scrape, plus its own liveness gauges."""
    from fluidframework_tpu.server.supervisor import ServiceSupervisor

    child = M.MetricsRegistry()
    child.counter("role_records_total", role="deli").inc(42)
    child.histogram("checkpoint_ms", role="deli").observe(3.0)
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir(exist_ok=True)
    (hb_dir / "deli.json").write_text(json.dumps({
        "pid": 1, "owner": "deli-g1", "t": time.time(),
        "metrics": child.snapshot(),
    }))
    sup = ServiceSupervisor(str(tmp_path), roles=("deli", "scribe"))
    reg = sup.collect_metrics()
    assert reg.counter("role_records_total", role="deli").value == 42
    assert reg.gauge("supervisor_child_alive", role="deli").value == 0
    assert reg.gauge("supervisor_restarts", role="scribe").value == 0
    health = sup.health()
    assert health["status"] == "degraded"  # nothing actually running
    assert health["roles"]["deli"]["alive"] is False
    mon = sup.serve_metrics()
    try:
        vals = parse_prometheus(scrape(mon.url + "/metrics"))
        assert vals['fluid_role_records_total{role="deli"}'] == 42
        assert vals['fluid_checkpoint_ms_count{role="deli"}'] == 1
        assert 'fluid_supervisor_restarts{role="deli"}' in vals
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            scrape(mon.url + "/healthz")
        assert exc_info.value.code == 503  # degraded farm -> 503
        assert json.loads(exc_info.value.read())["status"] == "degraded"
    finally:
        sup.stop()
    assert sup._monitor is None
