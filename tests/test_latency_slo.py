"""Tail-latency SLO layer (ISSUE 9): topic doorbells (event-driven
consumer wakeups with poll fallback), the /slo + /traces endpoints,
the slow-op flight recorder, and the open-loop latency driver's
trace/quantile correctness contract.

Determinism is the standing constraint: doorbells are advisory-only
(every consumer keeps its bounded-timeout poll loop, so fencing and
torn-read semantics never depend on a FIFO), and wire traces ride a
side "tr" key that `canonical_record`/digests never see — the chaos
suites (tests/test_chaos_recovery.py) run with doorbells on and still
converge bit-identical.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request

import pytest

from fluidframework_tpu.server.monitor import MetricsServer
from fluidframework_tpu.server.queue import (
    SharedFileTopic,
    TopicDoorbell,
    doorbells_enabled,
    wait_doorbells,
)
from fluidframework_tpu.utils import metrics as M


def scrape(url: str):
    return urllib.request.urlopen(url, timeout=10).read().decode()


# ---------------------------------------------------------------------------
# doorbells
# ---------------------------------------------------------------------------


def test_doorbell_rings_on_append_and_times_out_idle(tmp_path):
    assert doorbells_enabled()
    t = SharedFileTopic(str(tmp_path / "t.jsonl"))
    bell = TopicDoorbell(t.path)
    try:
        t0 = time.perf_counter()
        assert bell.wait(0.05) is False  # nothing appended: timeout
        assert time.perf_counter() - t0 >= 0.04
        threading.Timer(
            0.02, lambda: t.append_many([{"x": 1}])
        ).start()
        t0 = time.perf_counter()
        assert bell.wait(2.0) is True
        assert time.perf_counter() - t0 < 0.5  # woke on the ring
    finally:
        bell.close()


def test_doorbell_pending_ring_wakes_next_wait(tmp_path):
    """A ring that lands while the consumer is mid-step is retained in
    the FIFO: the next wait returns immediately — wakeups are never
    lost, only (harmlessly) early."""
    t = SharedFileTopic(str(tmp_path / "t.jsonl"))
    bell = TopicDoorbell(t.path)
    try:
        t.append_many([{"x": 1}])  # consumer is "busy", not waiting
        t0 = time.perf_counter()
        assert bell.wait(1.0) is True
        assert time.perf_counter() - t0 < 0.05
        assert bell.wait(0.02) is False  # drained: back to timeout
    finally:
        bell.close()


def test_doorbell_multiple_consumers_all_ring(tmp_path):
    t = SharedFileTopic(str(tmp_path / "t.jsonl"))
    a, b = TopicDoorbell(t.path), TopicDoorbell(t.path)
    try:
        t.append_many([{"x": 1}])
        assert a.wait(1.0) and b.wait(1.0)
        # wait_doorbells: ANY of several bells wakes the caller.
        t.append_many([{"x": 2}])
        assert wait_doorbells([a, b], 1.0) is True
    finally:
        a.close()
        b.close()


def test_doorbell_dead_consumer_reaped_and_empty_append_no_ring(tmp_path):
    t = SharedFileTopic(str(tmp_path / "t.jsonl"))
    bell = TopicDoorbell(t.path)
    live = TopicDoorbell(t.path)
    try:
        bell.close()  # "crashed" consumer: FIFO file left behind
        t.append_many([{"x": 1}])  # ring reaps the dead bell
        assert live.wait(1.0) is True
        names = os.listdir(t.path + ".bells")
        assert len(names) == 1  # only the live bell remains
        # An empty append (the fence-bind probe) must not ring.
        t.append_many([])
        assert live.wait(0.03) is False
    finally:
        live.close()


def test_doorbell_env_kill_switch(tmp_path, monkeypatch):
    monkeypatch.setenv("FLUID_DOORBELL", "0")
    assert not doorbells_enabled()
    from fluidframework_tpu.server.supervisor import DeliRole

    role = DeliRole(str(tmp_path), owner="w", ttl_s=3600.0)
    assert role.doorbell() is None  # poll fallback
    role.close_doorbell()


def test_role_idle_wait_uses_doorbell_and_cleanup(tmp_path):
    from fluidframework_tpu.server.supervisor import DeliRole

    role = DeliRole(str(tmp_path), owner="w", ttl_s=3600.0)
    raw = SharedFileTopic(str(tmp_path / "topics" / "rawdeltas.jsonl"))
    raw.append_many([{"kind": "join", "doc": "d", "client": 1}])
    while role.step() == 0:
        pass
    # Idle step creates the bell lazily; an append wakes the next one.
    role.step(idle_sleep=0.01)
    assert role._bell is not None
    threading.Timer(0.02, lambda: raw.append_many([
        {"kind": "op", "doc": "d", "client": 1, "clientSeq": 1,
         "refSeq": 0, "contents": {}},
    ])).start()
    t0 = time.perf_counter()
    moved = 0
    while moved == 0 and time.perf_counter() - t0 < 2.0:
        moved = role.step(idle_sleep=0.2)
    assert moved == 1
    assert time.perf_counter() - t0 < 0.6  # woke well inside a tick
    bell_path = role._bell.path
    role.close_doorbell()
    assert not os.path.exists(bell_path)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_threshold_and_ring_bound():
    fr = M.FlightRecorder(capacity=3, threshold_ms=10.0)
    for i, v in enumerate((1.0, 12.0, 3.0, 15.0, 11.0, 20.0)):
        if fr.note(v):
            fr.add(v, {"i": i})
    spans = fr.snapshot()
    assert [s["e2e_ms"] for s in spans] == [15.0, 11.0, 20.0]  # ring
    assert fr.seen == 6 and fr.recorded == 4
    fr.clear()
    assert fr.snapshot() == [] and fr.seen == 0


def test_flight_recorder_rolling_p99_mode():
    fr = M.FlightRecorder(capacity=8, threshold_ms=None,
                          window=128, min_samples=32)
    # Below min_samples nothing qualifies (no p99 to speak of).
    assert not any(fr.note(float(v)) for v in range(1, 32))
    # A spread distribution + one spike: only the tail qualifies.
    for v in range(1, 97):
        fr.note(float(v % 96 + 1))
    assert fr.note(500.0) is True
    fr.add(500.0, {"slow": 1})
    assert fr.snapshot()[-1]["e2e_ms"] == 500.0
    # The spike fed the window, but a median op still doesn't qualify.
    assert fr.note(40.0) is False


def test_default_flight_recorder_swap():
    old = M.get_flight_recorder()
    mine = M.FlightRecorder(capacity=2, threshold_ms=0.0)
    prev = M.set_flight_recorder(mine)
    try:
        assert prev is old
        assert M.get_flight_recorder() is mine
        M.get_flight_recorder().observe(1.0, {"x": 1})
        assert mine.snapshot() == [{"e2e_ms": 1.0, "x": 1}]
    finally:
        M.set_flight_recorder(prev)


def test_runtime_apply_feeds_flight_recorder():
    """The in-proc pipeline's apply side records slow ops: with a zero
    threshold every traced op qualifies, and the span carries the
    stage timestamps plus seq/client identity."""
    from fluidframework_tpu.dds import StringFactory
    from fluidframework_tpu.runtime import ChannelRegistry, ContainerRuntime
    from fluidframework_tpu.server import LocalServer

    prev = M.set_flight_recorder(
        M.FlightRecorder(capacity=16, threshold_ms=0.0)
    )
    try:
        server = LocalServer()
        rt = ContainerRuntime(ChannelRegistry([StringFactory()]))
        ds = rt.create_datastore("default")
        ds.create_channel("s", StringFactory.type_name)
        rt.connect(server.connect("doc", 1))
        ds.get_channel("s").insert_text(0, "hello")
        rt.flush()
        spans = M.get_flight_recorder().snapshot()
        assert spans, "no slow-op span recorded"
        s = spans[-1]
        assert s["seq"] > 0 and s["client"] == 1
        st = s["stages"]
        assert st["submit"] <= st["stamp"] <= st["apply"]
    finally:
        M.set_flight_recorder(prev)


# ---------------------------------------------------------------------------
# /slo + /traces endpoints
# ---------------------------------------------------------------------------


def test_slo_and_traces_endpoints():
    reg = M.MetricsRegistry()
    h = reg.histogram("op_stage_ms", stage="submit_to_broadcast")
    for v in (1.0, 2.0, 3.0, 40.0):
        h.observe(v)
    fr = M.FlightRecorder(capacity=4, threshold_ms=0.0)
    fr.observe(40.0, {"doc": "d", "seq": 4})
    mon = MetricsServer(registry=reg, traces=fr.snapshot).start()
    try:
        slo = json.loads(scrape(mon.url + "/slo"))
        [entry] = slo["histograms"]
        assert entry["name"] == "op_stage_ms"
        assert entry["count"] == 4
        assert entry["p50"] is not None and entry["p99"] is not None
        assert entry["p50"] <= entry["p95"] <= entry["p99"]
        traces = json.loads(scrape(mon.url + "/traces"))
        assert traces["slow_ops"] == [
            {"e2e_ms": 40.0, "doc": "d", "seq": 4}
        ]
    finally:
        mon.stop()


def test_traces_endpoint_defaults_to_process_recorder():
    prev = M.set_flight_recorder(
        M.FlightRecorder(capacity=2, threshold_ms=0.0)
    )
    mon = MetricsServer(registry=M.MetricsRegistry()).start()
    try:
        M.get_flight_recorder().observe(7.0, {"seq": 1})
        traces = json.loads(scrape(mon.url + "/traces"))
        assert traces["slow_ops"][0]["e2e_ms"] == 7.0
    finally:
        mon.stop()
        M.set_flight_recorder(prev)


# ---------------------------------------------------------------------------
# the open-loop latency driver's correctness contract
# ---------------------------------------------------------------------------


def test_latency_variant_traces_quantiles_and_slow_ops(tmp_path):
    """One open-loop run at low rate: every op exactly-once in
    broadcast, monotone spans, the child-reported histogram
    bucket-identical to the wire spans (asserted inside), and the
    slow-op spans naming real ops."""
    from fluidframework_tpu.testing.pipeline import _run_latency_variant

    res = _run_latency_variant(
        str(tmp_path), rate_hz=50.0, duration_s=1.2,
        n_docs=2, n_clients=2, ttl_s=0.75, timeout_s=60.0,
    )
    assert res["records"] == 60 + res["lead_in"]
    q = res["submit_to_broadcast_ms"]
    assert q["count"] == 60 and q["p50"] <= q["p95"] <= q["p99"]
    for s in res["slow_ops"]:
        st = s["stages"]
        assert st["sub"] <= st["stamp"] <= st["bc"]
        assert s["doc"] in ("doc0", "doc1")
