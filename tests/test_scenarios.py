"""Scenario observability plane (ISSUE 13): the traffic-profile
scenario layer (testing/scenarios.py — hot-doc storm, reconnect
stampede, read swarm, tenant-skewed mix), fabric-wide trace coverage
(partition-tagged slow-op spans + /traces under ShardWorker),
per-partition p99 quantiles and the autoscale trigger on them, the
`admit_to_stamp` ingress stage, and the storm-during-faults chaos
gate.

The standing constraints: every scenario is OPEN-LOOP (offered load
never waits on completion), every run ends in a convergence digest
(a scenario cannot pass by dropping work), and trace observation is
recovery-silent (the trace_stage_once contract — a restart's replay
must not double-observe a stage)."""

from __future__ import annotations

import json
import os
import time
import urllib.request

import pytest

from fluidframework_tpu.testing.chaos import (
    ChaosConfig,
    build_workload,
    run_chaos,
)
from fluidframework_tpu.testing.scenarios import (
    run_hotdoc_storm,
    run_read_swarm,
    run_reconnect_stampede,
    run_tenant_mix,
)
from fluidframework_tpu.utils import metrics as M


def scrape(url: str):
    return urllib.request.urlopen(url, timeout=10).read().decode()


# ---------------------------------------------------------------------------
# scenario primitives (scaled-down; gates are inside the primitives,
# these assert the CONTRACT surface they return)
# ---------------------------------------------------------------------------


def test_hotdoc_storm_open_loop_contract(tmp_path):
    """Scaled storm: the skew is real (hot doc dominates), the feed is
    open-loop (wall clock tracks the schedule, not the pipeline), and
    the run carries all three evidence artifacts — /slo quantiles,
    slow-op spans, and the convergence digest the internal gates
    already enforced (exactly-once + contiguous seqs)."""
    res = run_hotdoc_storm(
        n_writers=24, cold_docs=3, rate_hz=150.0, duration_s=1.2,
        hot_fraction=0.85, timeout_s=90.0,
        work_dir=str(tmp_path / "storm"),
    )
    assert res["open_loop"] is True
    assert res["records"] == res["hot_ops"] + res["cold_ops"]
    assert res["hot_ops"] > res["cold_ops"]  # the skew is the point
    # Open loop: the feed finished near its schedule (records/rate),
    # backlog or not. A completion-waiting feeder would stretch with
    # the pipeline instead.
    assert res["feed_wall_s"] < 3.0 * (res["records"] / res["rate_hz"])
    # Evidence artifacts.
    assert res["digest"]
    assert res["slow_ops"], "no flight-recorder spans"
    stages = {h["labels"].get("stage")
              for h in res["slo"]["histograms"]
              if h["name"] == "op_stage_ms"}
    assert "submit_to_broadcast" in stages
    q = res["submit_to_broadcast_ms"]
    assert q["count"] == res["records"] and q["p50"] <= q["p99"]
    assert res["scenario_p99_ms"] == q["p99"]
    # Hot and cold tails are reported separately.
    assert res["hot_submit_to_broadcast_ms"]["count"] == res["hot_ops"]


def test_reconnect_stampede_converges_and_measures(tmp_path):
    """Scaled stampede: concurrent catch-ups all land one signature,
    boots stay bit-identical to cold replay, and the latency evidence
    (quantiles + slow sessions) is attached."""
    res = run_reconnect_stampede(
        n_sessions=48, log_len=2048, summary_ops=256, threads=8,
        work_dir=str(tmp_path / "stampede"),
    )
    assert res["sessions"] == 48
    assert res["boots_bit_identical"] is True
    assert res["digest"]  # the single-valued catch-up signature
    assert res["catchup_ms"]["count"] == 48
    assert res["slow_ops"], "no slow-session spans"
    stages = {h["labels"].get("stage")
              for h in res["slo"]["histograms"]}
    assert "read_catchup" in stages
    assert res["tail_ops"] >= 0 and res["summary_seq"] > 0


def test_reconnect_stampede_elastic_ranges_single_signature(tmp_path):
    """ISSUE 15 satellite (PR 13 follow-up b): the stampede through
    PER-RANGE elastic summaries — the stream split into hash-range
    ``deltas-{rid}`` topics, a RANGED summarizer per range, and every
    session catching up through the MERGED `SummaryIndex` over the
    ``summaries-{rid}`` topics. One catch-up signature across the
    burst, hot-doc boots bit-identical to cold replay, and the merged
    surface resolves every background range's doc too (asserted
    inside the scenario)."""
    res = run_reconnect_stampede(
        n_sessions=32, log_len=1024, summary_ops=128, threads=8,
        elastic_ranges=3,
        work_dir=str(tmp_path / "stampede-elastic"),
    )
    assert res["elastic_ranges"] == 3
    assert res["boots_bit_identical"] is True
    assert res["digest"]  # one signature across the whole burst
    assert res["catchup_ms"]["count"] == 32
    assert res["summary_seq"] > 0


def test_read_swarm_scaled_loud_skip_and_convergence(tmp_path):
    """A scaled swarm must SAY it is scaled: below the 100k-session
    bar the throughput evidence carries an explicit skip reason (the
    host-capability rule every perf gate follows), while the fan-out
    convergence gate still ran over every session — in-proc and TCP."""
    res = run_read_swarm(
        n_sessions=250, n_docs=2, n_records=24, n_tcp=3,
        work_dir=str(tmp_path / "swarm"),
    )
    assert res["sessions"] == 250 and res["tcp_sessions"] == 3
    assert "skipped" in res and "100000-session bar" in res["skipped"]
    assert res["deliveries"] == 250 * 24
    assert res["deliveries_per_sec"] > 0
    assert res["digest"]
    # TCP sessions measured the push stage off the wire.
    stages = {h["labels"].get("stage")
              for h in res["slo"]["histograms"]}
    assert "broadcast_to_push" in stages


def test_tenant_mix_throttles_hot_tenant_only(tmp_path):
    """Scaled tenant mix through the real front door: the hot tenant
    is visibly throttled (and ONLY the hot tenant), the throttled tail
    retries to exactly-once, and the /slo body carries both the
    admit_to_stamp quantiles and the ingress refusal counters."""
    res = run_tenant_mix(
        n_tenants=5, records=240, rate_hz=240.0, rate_limit=60.0,
        n_partitions=2, timeout_s=90.0,
        work_dir=str(tmp_path / "mix"),
    )
    assert set(res["throttle_nacks"]) == {"t0"}
    assert res["throttle_nacks"]["t0"] > 0 and res["retries"] > 0
    assert res["admit_to_stamp_ms"]["count"] > 0
    assert res["scenario_p99_ms"] == res["admit_to_stamp_ms"]["p99"]
    names = {c["name"] for c in res["slo"].get("counters", ())}
    assert "ingress_nacks_total" in names
    assert "ingress_admitted_total" in names
    stages = {h["labels"].get("stage")
              for h in res["slo"]["histograms"]
              if h["name"] == "op_stage_ms"}
    assert "admit_to_stamp" in stages
    assert res["slow_ops"], "no slow-admission spans"


# ---------------------------------------------------------------------------
# admit_to_stamp: one clock read, recovery-silent (trace_stage_once)
# ---------------------------------------------------------------------------


def _mix_roles(shared, monkeypatch):
    from fluidframework_tpu.server.ingress import (
        IngressRole,
        write_tenants,
    )
    from fluidframework_tpu.server.riddler import sign_token
    from fluidframework_tpu.server.supervisor import DeliRole

    monkeypatch.setenv("FLUID_TRACE_WIRE", "1")
    write_tenants(shared, {"t0": "k0"})
    tok = sign_token("k0", "t0", "d0", ["doc:write"],
                     lifetime_s=3600.0)
    ing = IngressRole(shared, "ing", ttl_s=3600.0, batch=512)
    return ing, tok, DeliRole


def test_admit_to_stamp_monotone_and_observed(tmp_path, monkeypatch):
    """The front door stamps `tr_adm` on admitted records (one clock
    read); the deli folds it into the wire `tr` dict as `adm` and
    observes op_stage_ms{stage=admit_to_stamp} — adm <= stamp on every
    record, histogram count == sequenced ops."""
    from fluidframework_tpu.server.columnar_log import make_topic

    shared = str(tmp_path)
    reg = M.MetricsRegistry()
    prev = M.set_registry(reg)
    try:
        ing, tok, DeliRole = _mix_roles(shared, monkeypatch)
        deli = DeliRole(shared, "deli-1", ttl_s=3600.0, batch=512,
                        ckpt_interval_s=3600.0)
        ingt = make_topic(
            os.path.join(shared, "topics", "ingress.jsonl"), "json"
        )
        ingt.append_many(
            [{"kind": "auth", "doc": "d0", "client": 1, "tenant": "t0",
              "token": tok},
             {"kind": "join", "doc": "d0", "client": 1}]
            + [{"kind": "op", "doc": "d0", "client": 1,
                "clientSeq": i + 1, "refSeq": 0, "contents": {"i": i},
                "tr_sub": time.time()} for i in range(8)]
        )
        while ing.step() > 0:
            pass
        while deli.step() > 0:
            pass
        deltas = make_topic(
            os.path.join(shared, "topics", "deltas.jsonl"), "json"
        )
        ops = [r for r in deltas.read_from(0)
               if isinstance(r, dict) and r.get("kind") == "op"
               and r.get("type") == "op"]
        assert len(ops) == 8
        for r in ops:
            tr = r["tr"]
            assert tr["adm"] <= tr["stamp"], tr
            assert tr["sub"] <= tr["stamp"], tr  # sub rode through too
        h = reg.histogram("op_stage_ms", stage="admit_to_stamp")
        assert h.count == 8
    finally:
        M.set_registry(prev)


def test_admit_to_stamp_kernel_deli_parity(tmp_path, monkeypatch):
    """The KERNEL deli threads the admission stamp too (the plan
    tuple carries adm_ts next to sub_ts): same wire shape, same
    histogram, one clock read per flush — the kernel+ingress topology
    must not silently lose the stage the scalar role has."""
    from fluidframework_tpu.server.columnar_log import make_topic
    from fluidframework_tpu.server.supervisor import resolve_role_class

    shared = str(tmp_path)
    reg = M.MetricsRegistry()
    prev = M.set_registry(reg)
    try:
        ing, tok, _DeliRole = _mix_roles(shared, monkeypatch)
        deli = resolve_role_class("deli", "kernel")(
            shared, "kdeli", ttl_s=3600.0, batch=512,
            ckpt_interval_s=3600.0,
        )
        ingt = make_topic(
            os.path.join(shared, "topics", "ingress.jsonl"), "json"
        )
        ingt.append_many(
            [{"kind": "auth", "doc": "d0", "client": 1, "tenant": "t0",
              "token": tok},
             {"kind": "join", "doc": "d0", "client": 1}]
            + [{"kind": "op", "doc": "d0", "client": 1,
                "clientSeq": i + 1, "refSeq": 0, "contents": {"i": i},
                "tr_sub": time.time()} for i in range(8)]
            + [{"kind": "boxcar", "doc": "d0", "client": 1,
                "ops": [{"clientSeq": 9, "refSeq": 0,
                         "contents": {"b": 1}},
                        {"clientSeq": 10, "refSeq": 0,
                         "contents": {"b": 2}}],
                "tr_sub": time.time()}]
        )
        while ing.step() > 0:
            pass
        while deli.step() > 0:
            pass
        deltas = make_topic(
            os.path.join(shared, "topics", "deltas.jsonl"), "json"
        )
        ops = [r for r in deltas.read_from(0)
               if isinstance(r, dict) and r.get("kind") == "op"
               and r.get("type") == "op"]
        assert len(ops) == 10  # 8 singles + the 2-op boxcar
        for r in ops:
            tr = r["tr"]
            assert tr["adm"] <= tr["stamp"], tr
        h = reg.histogram("op_stage_ms", stage="admit_to_stamp")
        assert h.count == 10
    finally:
        M.set_registry(prev)


def test_admit_to_stamp_recovery_silent_across_restart(tmp_path,
                                                       monkeypatch):
    """trace_stage_once: a deli successor's recovery replays the
    checkpoint→durable gap SILENTLY — the admit_to_stamp histogram
    must not grow by a single observation, and the on-disk records'
    stamps stay monotone (no re-stamping of already-durable output)."""
    from fluidframework_tpu.server.columnar_log import make_topic

    shared = str(tmp_path)
    reg = M.MetricsRegistry()
    prev = M.set_registry(reg)
    try:
        ing, tok, DeliRole = _mix_roles(shared, monkeypatch)
        deli = DeliRole(shared, "deli-g1", ttl_s=0.4, batch=512,
                        ckpt_interval_s=3600.0, ckpt_bytes=1 << 30)
        ingt = make_topic(
            os.path.join(shared, "topics", "ingress.jsonl"), "json"
        )
        ingt.append_many(
            [{"kind": "auth", "doc": "d0", "client": 1, "tenant": "t0",
              "token": tok},
             {"kind": "join", "doc": "d0", "client": 1}]
            + [{"kind": "op", "doc": "d0", "client": 1,
                "clientSeq": i + 1, "refSeq": 0, "contents": {"i": i}}
               for i in range(6)]
        )
        while ing.step() > 0:
            pass
        while deli.step() > 0:
            pass
        h = reg.histogram("op_stage_ms", stage="admit_to_stamp")
        observed = h.count
        assert observed == 6
        before = make_topic(
            os.path.join(shared, "topics", "deltas.jsonl"), "json"
        ).read_from(0)
        # "Crash": the role never checkpointed (cadence pinned high),
        # so a successor recovers from offset 0 and must silently
        # replay the whole durable gap.
        time.sleep(0.5)  # the dead owner's lease expires
        deli2 = DeliRole(shared, "deli-g2", ttl_s=0.4, batch=512,
                         ckpt_interval_s=3600.0, ckpt_bytes=1 << 30)
        deli2.step()  # acquire + recover
        assert deli2.fence is not None
        assert h.count == observed, (
            "recovery replay re-observed admit_to_stamp "
            f"({h.count} vs {observed})"
        )
        after = make_topic(
            os.path.join(shared, "topics", "deltas.jsonl"), "json"
        ).read_from(0)
        assert after == before  # replay emitted nothing new
    finally:
        M.set_registry(prev)


# ---------------------------------------------------------------------------
# per-partition p99: labeled series, merged scrape, autoscale trigger
# ---------------------------------------------------------------------------


def test_partitioned_stage_histograms_carry_partition_label(tmp_path):
    from fluidframework_tpu.server.supervisor import (
        BroadcasterRole,
        partitioned_role_class,
    )

    reg = M.MetricsRegistry()
    prev = M.set_registry(reg)
    try:
        role = partitioned_role_class(BroadcasterRole, 3)(
            str(tmp_path), "w0", ttl_s=3600.0
        )
        role._observe_stage("submit_to_broadcast", 5.0)
        snap = reg.snapshot()
        h = next(x for x in snap["histograms"]
                 if x["name"] == "op_stage_ms")
        assert h["labels"] == {"partition": "3",
                               "stage": "submit_to_broadcast"}
    finally:
        M.set_registry(prev)


def test_per_partition_p99_merge_and_q_gauges():
    """Worker heartbeats carry op_stage_ms{stage=...,partition=k}
    histograms; the supervisor scrape merges them, `stage_p99s` reads
    a farm-wide quantile (bucket-sum, not quantile-of-quantiles) plus
    the per-partition ones, and the Prometheus exposition grows
    partition-labeled `_q` gauges."""
    from fluidframework_tpu.server.shard_fabric import stage_p99s

    workers = []
    for rid, lat in (("ra", 2.0), ("rb", 60.0)):
        w = M.MetricsRegistry()
        h = w.histogram("op_stage_ms", stage="submit_to_stamp",
                        partition=rid)
        for _ in range(100):
            h.observe(lat)
        workers.append(w)
    merged = M.MetricsRegistry()
    for w in workers:
        merged.merge(w.snapshot())
    farm, per = stage_p99s(merged.snapshot(), "submit_to_stamp")
    assert set(per) == {"ra", "rb"}
    assert per["ra"] < 5.0 < per["rb"]
    # Farm-wide sits inside rb's bucket (half the mass at 60ms puts
    # the 99th percentile there), not at an average of quantiles.
    assert farm is not None and farm > per["ra"]
    text = merged.to_prometheus()
    assert 'fluid_op_stage_ms_q{partition="rb"' in text
    assert 'quantile="0.99"' in text


def test_autoscale_p99_per_partition_triggers_hot_range():
    """A single hot range's OWN p99 (not the farm-wide quantile, not
    the busiest range) drives the split when p99_per_partition is on;
    with it off, the old farm-wide behavior is unchanged."""
    from fluidframework_tpu.server.shard_fabric import AutoscalePolicy

    topo = {"epoch": 1, "ranges": [
        {"rid": "ra", "lo": 0, "hi": 8, "preds": []},
        {"rid": "rb", "lo": 8, "hi": 16, "preds": []},
    ]}
    pol = AutoscalePolicy(split_rate=1e9, merge_rate=0.0,
                          sustain_s=0.0, min_interval_s=0.0,
                          p99_hot_ms=10.0, p99_per_partition=True)
    # rb is latency-hot on its own series while ra carries more rate.
    cmd = pol.observe(1.0, {"ra": 5.0, "rb": 1.0}, topo,
                      p99_ms=None,
                      p99_by_partition={"ra": 2.0, "rb": 50.0})
    assert cmd == {"op": "split", "rid": "rb", "why": "autoscale-hot"}
    # Old behavior: farm-wide p99 marks the HIGHEST-RATE range hot.
    pol2 = AutoscalePolicy(split_rate=1e9, merge_rate=0.0,
                           sustain_s=0.0, min_interval_s=0.0,
                           p99_hot_ms=10.0)
    cmd2 = pol2.observe(1.0, {"ra": 5.0, "rb": 1.0}, topo,
                        p99_ms=50.0,
                        p99_by_partition={"ra": 2.0, "rb": 50.0})
    assert cmd2 == {"op": "split", "rid": "ra", "why": "autoscale-hot"}


# ---------------------------------------------------------------------------
# /slo counters + fabric /traces over HTTP
# ---------------------------------------------------------------------------


def test_slo_summary_surfaces_ingress_counters_only():
    reg = M.MetricsRegistry()
    reg.counter("ingress_nacks_total", reason="rate",
                role="ingress").inc(3)
    reg.counter("ingress_admitted_total", role="ingress").inc(7)
    reg.counter("role_records_total", role="deli").inc(100)
    body = M.slo_summary(reg.snapshot())
    names = {c["name"] for c in body["counters"]}
    assert names == {"ingress_nacks_total", "ingress_admitted_total"}
    json.dumps(body)  # the /slo body must stay strict-JSON-able


def test_fabric_traces_and_partition_slo_over_http(tmp_path):
    """THE fabric trace-coverage gate (ISSUE 13 satellite a+b over the
    wire): an ELASTIC fabric run with per-partition downstream stages
    and wire traces must serve NON-EMPTY partition-tagged spans on
    `/traces` and partition-labeled stage quantiles on `/slo` from the
    supervisor's monitor — the blind spot PR 9 left (spans were
    classic-runner-only) is closed."""
    from fluidframework_tpu.server.shard_fabric import (
        ShardFabricSupervisor,
        ShardRouter,
        spread_doc_names,
    )
    from fluidframework_tpu.testing.pipeline import (
        build_pipeline_workload,
    )

    shared = str(tmp_path)
    env = {"FLUID_TRACE_WIRE": "1", "FLUID_TRACE_SLOW_MS": "0",
           "FLUID_DOORBELL": "1"}
    docs = spread_doc_names(4, 2)
    workload = build_pipeline_workload(4, 2, 4, doc_names=docs)
    sup = ShardFabricSupervisor(
        shared, n_workers=1, n_partitions=2, ttl_s=0.75,
        heartbeat_timeout_s=8.0, elastic=True, downstream="split",
        child_env=env,
    ).start()
    try:
        router = ShardRouter(shared, 2, elastic=True)
        now = time.time()
        router.append([{**r, "tr_sub": now} for r in workload])
        expected = len(workload)
        reader = router.merged_reader(base="broadcast")
        got = 0
        deadline = time.time() + 90.0
        while time.time() < deadline:
            sup.poll_once()
            got += sum(1 for r in reader.poll()
                       if isinstance(r, dict) and r.get("kind") == "op")
            if got >= expected:
                break
            time.sleep(0.02)
        assert got >= expected, f"fabric drained {got}/{expected}"
        time.sleep(0.6)  # one more worker heartbeat with the spans
        sup.poll_once()
        mon = sup.serve_metrics(port=0)
        traces = json.loads(scrape(mon.url + "/traces"))
        assert traces["slow_ops"], "/traces empty on the elastic fabric"
        assert any("partition" in s for s in traces["slow_ops"])
        slo = json.loads(scrape(mon.url + "/slo"))
        part_stages = [
            h for h in slo["histograms"]
            if h["name"] == "op_stage_ms" and "partition" in h["labels"]
        ]
        assert part_stages, "no partition-labeled stage quantiles"
        assert any(h["labels"]["stage"] == "submit_to_broadcast"
                   for h in part_stages)
    finally:
        sup.stop()


# ---------------------------------------------------------------------------
# chaos scenario: a storm DURING the faults
# ---------------------------------------------------------------------------


def test_scenario_workload_shape_and_validation():
    cfg = ChaosConfig(seed=3, n_docs=2, n_clients=3, ops_per_client=8,
                      scenario="hotdoc")
    base = ChaosConfig(seed=3, n_docs=2, n_clients=3, ops_per_client=8)
    w = build_workload(cfg)
    w0 = build_workload(base)
    assert len(w) > len(w0)
    storm = [i for i, r in enumerate(w)
             if isinstance(r.get("client"), int)
             and r["client"] > cfg.n_clients]
    assert storm, "no storm records"
    # Contiguous block in the middle (joins first, then the burst).
    assert storm == list(range(storm[0], storm[0] + len(storm)))
    assert 0 < storm[0] < len(w) - len(storm)
    # All storm records ride ONE viral doc.
    assert len({w[i]["doc"] for i in storm}) == 1
    with pytest.raises(ValueError, match="unknown scenario"):
        run_chaos(ChaosConfig(scenario="blizzard"))
    with pytest.raises(ValueError, match="summarizer"):
        run_chaos(ChaosConfig(scenario="hotdoc", summarizer=True))


@pytest.mark.chaos
def test_storm_during_split_and_kill_converges(tmp_path):
    """THE scenario-chaos acceptance gate: a hot-doc storm is IN
    FLIGHT while a kill and a live range split land (the seeded fault
    points are clamped into the storm window), kernel deli over
    columnar topics, per-partition downstream stages, wire traces on —
    the merged stream must converge bit-identical with zero dup/skip,
    the pre-split owner demonstrably fence-rejected, and the worker
    heartbeats must carry partition-tagged e2e spans."""
    res = run_chaos(ChaosConfig(
        seed=13, faults=("kill", "split"), n_docs=2, n_clients=3,
        ops_per_client=12, timeout_s=300.0, shared_dir=str(tmp_path),
        deli_impl="kernel", log_format="columnar",
        n_partitions=2, n_workers=2, elastic=True,
        trace_wire=True, downstream="split", scenario="hotdoc",
    ))
    assert res.converged, res.detail
    assert res.digest == res.golden_digest, res.detail
    assert res.duplicate_seqs == 0 and res.skipped_seqs == 0
    assert res.fence_rejections >= 1  # pre-split owner rejected
    assert len(res.epochs) > 1, res.epochs  # the split fired mid-storm
    assert res.downstream_ok
    assert any("storm spans chunks" in e for e in res.events)
    assert res.slow_ops, "elastic fabric produced no slow-op spans"
    assert any(s.get("partition") for s in res.slow_ops)
