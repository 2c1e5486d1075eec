"""Self-healing service supervisor: the lambda pipeline as SUPERVISED
child processes with fenced, exactly-once recovery.

The reference deploys the routerlicious lambdas as separate pods under
an orchestrator (SURVEY.md §2.5's deployment topology): each lambda is
its own process consuming a Kafka topic, checkpointing to Mongo, and a
crashed pod is restarted to resume from its checkpoint under a new
ZooKeeper epoch. Round 5 had the lambda CLASSES but no topology —
everything ran in one interpreter on the happy path. This module is
that topology over the cross-process primitives in `server.queue`:

    rawdeltas.jsonl → deli → deltas.jsonl → { scriptorium → durable.jsonl
                                            , broadcaster → broadcast.jsonl
                                            , scribe      → (fold ckpt) }

- Every role runs as a child process (`python -m
  fluidframework_tpu.server.supervisor --role <r> ...`) holding a
  FENCED lease on its role (`server.queue.LeaseManager`), renewing it
  while alive and writing a liveness heartbeat each step.
- `ServiceSupervisor` launches the four roles, monitors child liveness
  (process exit + heartbeat staleness), and restarts a dead/stalled
  child with a fresh owner identity; the restarted child re-acquires
  the lease (waiting out the TTL), loads the last durable checkpoint,
  and resumes.
- **Exactly-once recovery**: a role crashing BETWEEN its output append
  and its checkpoint would classically replay the batch (at-least-once)
  — the partition-worker round punted that to consumer-side dedup.
  Here every output record carries the input line offset it was
  produced from (`inOff`); on recovery the role scans its output topic
  for the largest `inOff` already durable, deterministically reprocesses
  the checkpoint→`inOff` input gap WITHOUT emitting (rebuilding
  sequencer state — the paper's determinism doing the work), and only
  then resumes emitting. Output appends and checkpoint writes are both
  fenced, so a deposed owner (expired lease, SIGSTOP zombie) is
  rejected at the write path with `FencedError`, not merely asked to
  stand down.

`testing/chaos.py` + `tools/chaos_run.py` drive this farm under
injected faults (SIGKILL, torn appends, lease takeover, duplicated /
delayed delivery) and assert bit-identical convergence with the
no-fault golden digest.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.devices import (
    cpu_child_env,
    cpu_pinned,
    device_report,
    forced_host_device_env,
    compile_seconds,
    holds_accelerator,
    track_compiles,
    use_compile_cache,
)
from .columnar_log import (
    LOG_FORMATS,
    default_log_format,
    make_tail_reader,
    make_topic,
)
from .queue import (
    FencedCheckpointStore,
    FencedError,
    LeaseManager,
    SharedFileTopic,
    TailReader,
    TopicDoorbell,
    doorbells_enabled,
    partition_suffix,
    retry_durable,
)
from .sequencer import DocumentSequencer

__all__ = [
    "BroadcasterRole",
    "CHIP_HEARTBEAT_TIMEOUT_S",
    "DELI_IMPLS",
    "DeliRole",
    "FUSED_PIPELINE_ROLES",
    "LOG_FORMATS",
    "PIPELINE_ROLES",
    "ROLES",
    "ScribeRole",
    "ScriptoriumBroadcasterRole",
    "ScriptoriumRole",
    "ServiceSupervisor",
    "canonical_record",
    "fused_roles",
    "partitioned_role_class",
    "resolve_role_class",
    "serve_role",
    "unwrap_ranged_state",
]

# The supervised farm: the classic four-lambda pipeline plus the
# summary service (`server.summarizer.SummarizerRole` — deltas →
# content-addressed summary blobs + a `summaries` manifest topic, the
# catch-up read side). PIPELINE_ROLES is the pre-summary four-stage
# core for callers that want the ordering path alone.
PIPELINE_ROLES = ("deli", "scriptorium", "scribe", "broadcaster")
ROLES = PIPELINE_ROLES + ("summarizer",)

EXIT_DEPOSED = 4  # lease renew failed: a successor owns the role
EXIT_FENCED = 3  # write-path fence rejection: we are a zombie

# Liveness bar of the one child that owns the chip. It starts the
# backend before READY and compiles inside its step, with no heartbeat
# while the compiler runs: one compile per new pool shape, seconds to
# tens of seconds on a v5e (PERF.md). The CPU roles keep the 2 s bar.
CHIP_HEARTBEAT_TIMEOUT_S = 120.0

# Opt-in WIRE tracing for the supervised farm: with FLUID_TRACE_WIRE
# set, the deli stamps per-stage wall-clock timestamps into a "tr" dict
# on its output records and scriptorium/broadcaster extend it — the
# farm twin of the in-proc `SequencedMessage.traces`. Off by default:
# timestamps differ run to run, so any bit-identity comparison that
# keeps all record keys must run untraced. Digest/convergence forms are
# safe either way (`canonical_record` keeps a fixed key set that
# excludes "tr").
TRACE_WIRE_ENV = "FLUID_TRACE_WIRE"


def trace_wire_enabled() -> bool:
    return os.environ.get(TRACE_WIRE_ENV, "").lower() not in (
        "", "0", "off", "no"
    )


def _topic_path(shared_dir: str, name: str) -> str:
    return os.path.join(shared_dir, "topics", f"{name}.jsonl")


def unwrap_ranged_state(state: Any) -> Any:
    """Deli checkpoint states come in two shapes: the classic per-doc
    `DocumentSequencer` map, and the elastic fabric's ranged envelope
    (``{"__ranged__": 1, "docs": {...}, "preds": {...}}`` — per-doc
    map plus predecessor catch-up cursors, `server.shard_fabric`).
    Every deli restore path unwraps through here, so a checkpoint
    written by a ranged role stays restorable by ANY frontend (scalar,
    kernel, in-proc) — the cursors only mean something to a ranged
    successor, the doc states mean the same thing everywhere."""
    if (isinstance(state, dict) and state.get("__ranged__")
            and "docs" in state):
        return state.get("docs") or {}
    return state


def canonical_record(rec: dict) -> dict:
    """A sequenced record minus transport bookkeeping (`inOff`, worker
    tags) — the form digests and convergence checks compare."""
    return {
        k: rec[k]
        for k in ("kind", "doc", "seq", "msn", "client", "clientSeq",
                  "refSeq", "type", "contents")
        if k in rec
    }


# ---------------------------------------------------------------------------
# Roles
# ---------------------------------------------------------------------------


class _CheckpointFlush:
    """One cadence checkpoint's flush on its own writer thread: the
    lock, fence check, write, fsync and replace of an envelope its role
    encoded on the pump (`FencedCheckpointStore.flush`), under the same
    storage-fault retry budget as an inline write. The writer records
    a `degraded` change and leaves its export to the pump; its result
    or error is read by the pump (`_Role._reap_checkpoint`)."""

    def __init__(self, role: "_Role", payload: List[bytes]):
        self.role = role
        self.payload = payload
        self.fence, self.owner = role.fence, role.owner
        self.pump_s = self.flush_s = self.t_done = 0.0
        self.n_bytes = 0
        self.marked_degraded = False
        self.error: Optional[Exception] = None
        self.thread = threading.Thread(
            target=self._run, name=f"{role.name}.checkpoint.flush",
            daemon=True)

    def start(self, pump_s: float) -> None:
        self.pump_s = pump_s
        self.thread.start()

    def _degraded(self, flag: bool) -> None:
        role = self.role
        role.degraded = self.marked_degraded = flag
        role._m_degraded.set(1.0 if flag else 0.0)
        role._ckpt_news = True  # after the flag: the pump exports both

    def _note_retry(self, attempt, exc, delay) -> None:
        self.role._m_disk_retries.inc()
        self._degraded(True)

    def _flush(self) -> int:
        role = self.role
        with role._sp_ckpt_flush:
            return role.ckpt.flush(role.name, self.payload,
                                   fence=self.fence, owner=self.owner)

    def _run(self) -> None:
        t0 = time.perf_counter()
        try:
            self.n_bytes = retry_durable(self._flush,
                                         on_retry=self._note_retry)
            if self.marked_degraded:
                self._degraded(False)  # recovery is news too
        except Exception as exc:  # re-raised on the pump
            self.error = exc
        self.payload = None
        self.flush_s = time.perf_counter() - t0
        self.t_done = time.time()


class _Role:
    """One supervised lambda: fenced lease + heartbeat + exactly-once
    consume/transform/append loop over shared file topics."""

    name: str = ""
    in_topic_name: str = ""
    out_topic_name: Optional[str] = None
    # Roles that ingest columnar `RecordBatch` frames whole (the deli
    # family) set this; everyone else reads decoded records.
    ingest_batches: bool = False
    # Sharded-fabric identity (`partitioned_role_class`): the partition
    # this role instance owns, and the base role name its metrics are
    # labeled with. None = the classic single-partition farm.
    partition: Optional[int] = None
    role_base: Optional[str] = None
    # Set True around a flush whose output records will be
    # POST-PROCESSED as wire dicts (the ranged fabric's predecessor
    # drains tag `inSrc` onto each record): columnar-emitting roles
    # (the kernel deli) then fall back to per-record dict emission for
    # that flush. Recovery and wire tracing force the dict path on
    # their own flags.
    _dict_emit: bool = False
    # LOGICAL input-topic byte position at the START of the batch being
    # processed (captured off the incremental reader before each poll;
    # None during recovery replay and predecessor drains, where no such
    # anchor exists). The summarizer stamps it into its manifests as
    # ``byteOff`` — a hard lower bound for the catch-up tail seek,
    # stable under op-log truncation.
    _in_pos: Optional[int] = None
    # A role that keeps its state pre-encoded provides
    # `checkpoint_state()`: `snapshot_state` with `queue.EncodedJson`
    # values in it, which `checkpoint` writes in its place (the same
    # bytes). `ckpt_docs_encoded` is the documents its last call
    # encoded anew (the `n` of the `<role>.checkpoint.snapshot` span).
    checkpoint_state: Optional[Callable[[], Any]] = None
    ckpt_docs_encoded: Optional[int] = None

    def _metric_labels(self) -> Dict[str, str]:
        """Metric label set: single-partition roles keep the historic
        {role: name}; partitioned roles label {role: base, partition: k}
        so the supervisor scrape can aggregate across the fabric while
        per-partition series stay distinguishable."""
        if self.partition is None:
            return {"role": self.name}
        return {"role": self.role_base or self.name,
                "partition": str(self.partition)}

    def __init__(self, shared_dir: str, owner: str, ttl_s: float = 1.0,
                 batch: int = 512, ckpt_interval_s: float = 0.25,
                 ckpt_bytes: int = 256 * 1024,
                 log_format: Optional[str] = None,
                 ckpt_duty: float = 0.2):
        """`ckpt_interval_s` / `ckpt_bytes`: checkpoint cadence —
        a checkpoint is written when EITHER bound is crossed since the
        last one (ROADMAP item (b): the seed checkpointed every step,
        and at 10k-doc scale the per-step JSON snapshot dwarfs the
        batch). Correctness is cadence-independent: exactly-once
        recovery scans the output topic for the durable `inOff` prefix
        and silently replays the checkpoint→prefix gap, however wide.
        `ckpt_interval_s=0` restores every-step checkpointing.

        `log_format` ("json" | "columnar", default env
        ``FLUID_LOG_FORMAT``) picks the topic wire form: JSONL lines or
        binary record batches (`server.columnar_log`). Columnar
        readers parse both, so a JSONL farm may UPGRADE to columnar
        across a restart and resume the same topics mid-stream (the
        reverse needs drained topics — JSON readers cannot parse
        frames).

        `ckpt_duty` is the checkpoint-STORM guard: once state grows to
        where one snapshot costs S seconds (a 10k-doc deli checkpoint
        runs to tens of MB), a cadence that fires every pump would
        spend most of the wall clock checkpointing — so a checkpoint
        whose snapshot and flush cost S is followed by the next at most
        every ``S / ckpt_duty`` seconds, bounding checkpoint work to
        that fraction of wall time however large the state gets.
        Recovery granularity widens with it; correctness does not (the
        inOff scan replays any gap).
        Explicit every-step mode (``ckpt_interval_s=0``) bypasses the
        guard."""
        self.shared_dir = shared_dir
        self.owner = owner
        self.batch = batch
        self.ckpt_interval_s = ckpt_interval_s
        self.ckpt_bytes = ckpt_bytes
        self.ckpt_duty = ckpt_duty
        self.log_format = default_log_format(log_format)
        self.leases = LeaseManager(
            os.path.join(shared_dir, "leases"), owner, ttl_s,
            claim_ttl_s=max(0.25, ttl_s / 2),
        )
        self.ckpt = FencedCheckpointStore(
            os.path.join(shared_dir, "checkpoints")
        )
        self.in_topic = make_topic(
            _topic_path(shared_dir, self.in_topic_name), self.log_format
        )
        self.out_topic = (
            make_topic(_topic_path(shared_dir, self.out_topic_name),
                       self.log_format)
            if self.out_topic_name else None
        )
        self.fence: Optional[int] = None
        self.offset = 0
        # Storage degradation flag: True while a durable write (topic
        # append, checkpoint) is inside its bounded-retry backoff
        # budget (ENOSPC, stalled volume). Rides the heartbeat so the
        # supervisor's health surface can show a limping-but-live
        # role; cleared by the next durable write that lands.
        self.degraded = False
        self._reader: Optional[TailReader] = None
        self._last_renew = 0.0
        # Event-driven idle: instead of sleeping the poll interval
        # blind, the idle branch waits on the input topic's doorbell
        # (queue.TopicDoorbell) with the SAME bounded timeout — an
        # append wakes the role immediately, and a missed ring only
        # costs the old poll latency. Created lazily on first idle so
        # roles driven in-process (which never idle) register no FIFO.
        self._bell: Optional[TopicDoorbell] = None
        self._doorbell_ok = doorbells_enabled()
        # Wire tracing (off by default — see TRACE_WIRE_ENV) and the
        # per-stage histogram cache it feeds. `_recovering` gates the
        # OBSERVATION side off during recovery's silent replay:
        # replayed records would otherwise be observed a second time,
        # with a "latency" that spans the crash — phantom multi-second
        # slow ops in the very evidence surface this exists for.
        self.trace_wire = trace_wire_enabled()
        self._recovering = False
        self._stage_hists: Dict[str, Any] = {}
        self._hb_path = os.path.join(shared_dir, "hb", f"{self.name}.json")
        os.makedirs(os.path.dirname(self._hb_path), exist_ok=True)
        # Checkpoint-cadence state + role metrics. The registry is
        # per-process; `heartbeat()` snapshots it into the hb file so
        # the supervisor can merge children's metrics for /metrics.
        self._ckpt_dirty = False
        self._ckpt_last_t = time.time()
        self._ckpt_last_s = 0.0
        self._ckpt_pending_bytes = 0
        # The cadence checkpoint's flush in flight on its writer thread,
        # and whether that writer changed `degraded` since the pump last
        # exported it (the heartbeat file has one writer: the pump).
        self._ckpt_job: Optional[_CheckpointFlush] = None
        self._ckpt_news = False
        self._hb_t = 0.0
        from ..utils.metrics import get_registry, span

        self.metrics = get_registry()
        m = self.metrics
        labels = self._metric_labels()
        self._m_records = m.counter("role_records_total", **labels)
        self._m_ckpt_writes = m.counter(
            "checkpoint_writes_total", **labels
        )
        self._m_ckpt_bytes = m.counter(
            "checkpoint_bytes_total", **labels
        )
        # The pump's time in a checkpoint (the stall), and the flush
        # each cadence checkpoint leaves to its writer thread.
        self._m_ckpt_ms = m.histogram("checkpoint_ms", **labels)
        self._m_ckpt_flush_ms = m.histogram("checkpoint_flush_ms", **labels)
        self._m_ckpt_async = m.counter(
            "checkpoint_flushes_async_total", **labels
        )
        self._m_ckpt_deferred = m.counter(
            "checkpoint_flush_deferred_total", **labels
        )
        self._m_fenced = m.counter("fence_rejections_total", **labels)
        self._m_disk_retries = m.counter("disk_retries_total", **labels)
        self._m_degraded = m.gauge("role_degraded", **labels)
        # The step's layer spans, `<role>.<phase>` (`utils.metrics.span`):
        # the pump is the root of every step that reaches the poll.
        self.span_prefix = self.role_base or self.name
        (self._sp_pump, self._sp_poll, self._sp_flush, self._sp_append,
         self._sp_ckpt, self._sp_snapshot, self._sp_ckpt_flush,
         self._sp_heartbeat, self._sp_idle) = (
            span(f"{self.span_prefix}.{phase}", **labels)
            for phase in ("pump", "poll", "flush", "append", "checkpoint",
                          "checkpoint.snapshot", "checkpoint.flush",
                          "heartbeat", "idle"))

    # ------------------------------------------------------------ state

    def snapshot_state(self) -> Any:
        return None

    def restore_state(self, state: Any) -> None:
        pass

    def process(self, line_idx: int, rec: Any,
                out: List[dict]) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def flush_batch(self, out: List[dict]) -> None:
        """End-of-batch hook: batching roles (the kernel deli) buffer
        in `process` and emit here; scalar roles emit per record."""

    def _append_outputs(self, out: List[dict]) -> int:
        """The fenced durable output append for one step's batch
        (fused roles extend it to several topics — each leg wraps its
        OWN retry budget, so a retried leg can never re-append a leg
        that already landed). Returns bytes written."""
        return self._durable(lambda: self.out_topic.append_many(
            out, fence=self.fence, owner=self.owner
        ))

    def _absorb_predecessors(self) -> None:
        """Recovery hook between the output fence bind and the
        own-topic durable scan: the elastic fabric's ranged roles
        (`shard_fabric._RangedMixin`) absorb their predecessor ranges'
        tails here. Classic roles have no predecessors."""

    # -------------------------------------------------------- doorbells

    def doorbell(self) -> Optional[TopicDoorbell]:
        """This role's input-topic doorbell (created lazily; None when
        doorbells are disabled or the FIFO cannot be made — the caller
        then falls back to the plain poll sleep)."""
        if not self._doorbell_ok:
            return None
        if self._bell is None:
            try:
                self._bell = TopicDoorbell(self.in_topic.path)
            except OSError:
                self._doorbell_ok = False
                return None
        return self._bell

    def close_doorbell(self) -> None:
        """Release the FIFO (a worker dropping a deposed partition
        role must not leave its bell absorbing rings forever)."""
        if self._bell is not None:
            self._bell.close()
            self._bell = None

    # With a live bell the idle timeout stretches to this (still
    # bounded — the poll fallback): rings are retained in the FIFO
    # even while the role is mid-step, so the only append a wait can
    # "miss" predates the bell's creation, and that one costs at most
    # this. Meanwhile idle churn (a heartbeat write per poll tick)
    # drops ~5x, which is itself tail latency on a contended host.
    bell_wait_s: float = 0.05

    def _idle_wait(self, timeout_s: float) -> None:
        """The idle quantum: event wake on new input, bounded by the
        poll fallback that keeps every correctness property
        doorbell-independent."""
        if timeout_s <= 0:
            return
        bell = self.doorbell()
        if bell is None:
            time.sleep(timeout_s)
        else:
            bell.wait(max(timeout_s, self.bell_wait_s))

    def _observe_stage(self, stage: str, ms: float) -> None:
        """Fold one wire-trace stage latency into `op_stage_ms` (the
        same histogram family the in-proc pipeline feeds; instruments
        cached per stage). Partitioned/ranged roles label the series
        with their partition too — the worker heartbeat then carries
        per-partition stage histograms, the supervisor scrape merges
        them, and the `_q` quantile gauges come out labeled
        ``{partition=k}`` (the per-range p99 the autoscale policy's
        `p99_per_partition` trigger reads). Classic single-partition
        roles keep the historic label set."""
        h = self._stage_hists.get(stage)
        if h is None:
            labels = {"stage": stage}
            if self.partition is not None:
                labels["partition"] = str(self.partition)
            h = self._stage_hists[stage] = self.metrics.histogram(
                "op_stage_ms", **labels
            )
        h.observe(ms)

    # -------------------------------------------------------- lifecycle

    # Minimum seconds between heartbeat file writes (0 = every call —
    # the classic farm's liveness contract, where THIS file is what the
    # supervisor watches). The shard fabric raises it on its embedded
    # roles: worker-level heartbeats are the fabric's liveness/metrics
    # channel, so per-partition role heartbeats would otherwise be
    # O(partitions) registry-snapshot writes per pump that nothing
    # reads.
    hb_interval_s: float = 0.0

    def heartbeat(self, force: bool = False) -> None:
        now = time.time()
        if (not force and self.hb_interval_s > 0
                and now - self._hb_t < self.hb_interval_s):
            return
        self._hb_t = now
        with self._sp_heartbeat:
            self._write_heartbeat()

    def _write_heartbeat(self) -> None:
        tmp = self._hb_path + f".tmp.{os.getpid()}"
        hb = {
            "pid": os.getpid(), "owner": self.owner, "t": time.time(),
            "fence": self.fence, "offset": self.offset,
            "degraded": self.degraded,
            # Which device this process computes on (the supervisor's
            # one-chip rule is checked against it: `child_devices`).
            "device": device_report(),
            # Seconds this process has spent in backend compiles: what
            # its heartbeat went silent for (`CHIP_HEARTBEAT_TIMEOUT_S`).
            "compile_s": compile_seconds(),
            # Metrics report UP through the existing heartbeat
            # channel: the supervisor merges these snapshots into
            # its /metrics registry (per-process registries, one
            # explicit merge point).
            "metrics": self.metrics.snapshot(),
        }
        if self.trace_wire:
            # Slow-op flight-recorder spans ride the same channel (the
            # supervisor's /traces merges them); only in wire-trace
            # mode — nothing feeds the recorder otherwise.
            from ..utils.metrics import get_flight_recorder

            spans = get_flight_recorder().snapshot()
            if spans:
                hb["slow_ops"] = spans
        with open(tmp, "w") as f:
            # One string from the C encoder: `json.dump` streams through
            # the pure-Python one, several times slower per instrument.
            f.write(json.dumps(hb))
        os.replace(tmp, self._hb_path)

    def _durable(self, fn):
        """Run one durable write under the storage-fault budget:
        bounded-retry jittered backoff on OSError (ENOSPC, EIO, a
        stalled volume), flagging the role `degraded` — and force-
        heartbeating, so liveness AND the flag stay visible while it
        waits — for as long as the retry budget lasts. A write that
        lands clears the flag; a spent budget re-raises (hard-fail:
        the record was never acknowledged, so the supervisor restart
        loses nothing). `FencedError` passes straight through — a
        deposed writer must die, not loop."""
        def note(attempt, exc, delay):
            self.degraded = True
            self._m_degraded.set(1.0)
            self._m_disk_retries.inc()
            self.heartbeat(force=True)  # export the flag while limping

        out = retry_durable(fn, on_retry=note)
        if self.degraded:
            self.degraded = False
            self._m_degraded.set(0.0)
            self.heartbeat(force=True)  # recovery is news too
        return out

    def _renew_or_die(self, now: Optional[float] = None) -> None:
        """Lease upkeep (every ttl/3): a failed renewal means a
        successor owns the role — stand down loudly. ONE helper for
        every pump path (base step, ranged step, predecessor drains)
        so deposed handling can never fork."""
        now = time.time() if now is None else now
        if now - self._last_renew <= self.leases.ttl_s / 3:
            return
        if not self.leases.renew(self.name):
            print(f"DEPOSED {self.name} {self.owner}", flush=True)
            raise SystemExit(EXIT_DEPOSED)
        self._last_renew = now

    def _recover(self) -> None:
        """Resume from the durable checkpoint, then close the
        append-vs-checkpoint crash window: deterministically reprocess
        (silently) every input whose output is already durable."""
        self._recovering = True
        try:
            self._recover_inner()
        finally:
            self._recovering = False

    def _recover_inner(self) -> None:
        env = self.ckpt.load(self.name)
        self.offset = 0
        if env is not None:
            st = env["state"]
            self.offset = int(st.get("offset", 0))
            self.restore_state(st.get("state"))
        else:
            self.restore_state(None)
        if self.out_topic is None:
            return
        # Bind our fence on the output topic BEFORE scanning it: from
        # this append on, a deposed predecessor's in-flight batch is
        # rejected (FencedError), so the scan below sees the final
        # durable prefix and no zombie write can land after it — the
        # write-path half of the takeover contract.
        self._durable(lambda: self.out_topic.append_many(
            [], fence=self.fence, owner=self.owner
        ))
        # Ranged successors absorb their predecessors' tails HERE —
        # after our fence is bound, before the own-topic scan: a doc's
        # own-topic records always postdate its predecessor records,
        # so this is the per-document input order (no-op otherwise).
        self._absorb_predecessors()
        done_counts = self._durable_done_counts(self.out_topic)
        if not done_counts:
            return
        max_done = max(done_counts)
        gap, next_off = self.in_topic.read_entries(self.offset)
        sink: List[dict] = []
        for line_idx, rec in gap:
            if line_idx > max_done:
                next_off = line_idx
                break
            self.process(line_idx, rec, sink)  # silent: already durable
        else:
            next_off = max(self.offset, max_done + 1, next_off)
        self.flush_batch(sink)  # batching roles rebuild state here
        # Re-emit the missing tail of max_done's outputs, if the crash
        # clipped them: deterministic replay regenerates the exact
        # records, so emitting from the durable count onward completes
        # the input without duplicating its prefix.
        tail = [r for r in sink if r.get("inOff") == max_done]
        tail = tail[done_counts[max_done]:]
        if tail:
            self._durable(lambda: self.out_topic.append_many(
                tail, fence=self.fence, owner=self.owner
            ))
        self.offset = next_off
        self._reader = None  # re-anchor the tail at the new offset
        # The replayed records MUST match what is already on disk —
        # that is the determinism claim this service rests on.
        # (Checked cheaply: counts; the chaos harness checks digests.)
        self.checkpoint()

    def _durable_done_counts(self, topic) -> Dict[int, int]:
        """Durable outputs per input offset on `topic`: one input may
        emit SEVERAL outputs (a wire boxcar), and a crash mid-append
        can leave a durable PREFIX of them — outputs land in input
        order, so only the LAST durable input (max over the keys) can
        be partial; everything below it is complete. Records tagged
        `inSrc` live in a PREDECESSOR's offset space (a ranged
        successor's absorbed catch-up, server.shard_fabric) — their
        inOff would collide with ours, so the predecessor scan owns
        them, not this one."""
        entries, _ = topic.read_entries(0)
        done: Dict[int, int] = {}
        for _, r in entries:
            if (isinstance(r, dict) and r.get("inSrc") is None
                    and r.get("inOff", -1) >= self.offset):
                off = r["inOff"]
                done[off] = done.get(off, 0) + 1
        return done

    def checkpoint(self) -> None:
        """Write a checkpoint now, on this thread: wait for the flush in
        flight (re-raising its error), then snapshot and save. Recovery,
        handoffs and every-step mode take this path."""
        self.wait_checkpoint()
        self._take_checkpoint(background=False)

    def _take_checkpoint(self, background: bool) -> None:
        """Snapshot the state at this offset and write it: inline, or
        with `background` encoded here and flushed by a writer thread
        (`_CheckpointFlush`), the pump stalling for the snapshot and
        its encoding alone."""
        with self._sp_ckpt:
            t0 = time.perf_counter()
            with self._sp_snapshot as snap:
                state = {"offset": self.offset, "state": (
                    self.checkpoint_state or self.snapshot_state)()}
                snap.n = self.ckpt_docs_encoded
            if background:
                payload = self.ckpt.encode(self.name, state,
                                           fence=self.fence, owner=self.owner)
                self._ckpt_job = _CheckpointFlush(self, payload)
            else:
                n_bytes = self._durable(lambda: self.ckpt.save(
                    self.name, state, fence=self.fence, owner=self.owner,
                ))
            with self._sp_snapshot:
                del state  # freeing the snapshot's dicts is its cost too
            self._ckpt_dirty = False
            self._ckpt_pending_bytes = 0
            pump_s = time.perf_counter() - t0
        self._m_ckpt_ms.observe(pump_s * 1000.0)
        if background:
            # Until the flush lands, the cadence counts from here.
            self._ckpt_last_t = time.time()
            self._ckpt_job.start(pump_s)
        else:
            self._checkpoint_landed(pump_s, n_bytes, time.time())

    def _checkpoint_landed(self, total_s: float, n_bytes: int,
                           t_done: float) -> None:
        self._ckpt_last_s = total_s
        self._ckpt_last_t = t_done
        self._m_ckpt_writes.inc()
        self._m_ckpt_bytes.inc(n_bytes)

    def _reap_checkpoint(self) -> None:
        """The pump's side of the writer: export a `degraded` change the
        writer made, and once its flush has ended, account for it or
        re-raise its error (a `FencedError` takes the pump's fenced
        exit; an `OSError` past the retry budget fails the role, as a
        failed inline write does)."""
        if self._ckpt_news:
            self._ckpt_news = False
            self.heartbeat(force=True)
        job = self._ckpt_job
        if job is None or job.thread.is_alive():
            return
        self._ckpt_job = None
        if job.error is not None:
            self._ckpt_dirty = True  # what it held is not durable
            raise job.error
        self._m_ckpt_async.inc()
        self._m_ckpt_flush_ms.observe(job.flush_s * 1000.0)
        self._checkpoint_landed(job.pump_s + job.flush_s, job.n_bytes,
                                job.t_done)

    def wait_checkpoint(self) -> None:
        """Block until the flush in flight, if any, has ended, and reap
        it here (its error re-raised). Paths that end a role cleanly,
        and readers of its checkpoint, call this first."""
        job = self._ckpt_job
        if job is not None:
            job.thread.join()
        self._reap_checkpoint()

    def maybe_checkpoint(self) -> bool:
        """Take a checkpoint iff the cadence says so (dirty AND the time
        or byte bound crossed), subject to the checkpoint-storm guard: a
        checkpoint whose snapshot and flush cost S seconds is followed
        by the next at most every ``S / ckpt_duty`` seconds, so huge
        states cannot turn the cadence into a wall-clock sink (a
        10k-doc deli's envelope is tens of MB). The pump takes the
        snapshot and encodes it; a writer thread flushes it (lock, fence
        check, write, fsync, replace). One flush at a time: a checkpoint
        due while one is in flight is deferred (counted), never queued,
        so an older snapshot cannot land after a newer one. Every-step
        mode (``ckpt_interval_s=0``) writes inline. Returns whether one
        was taken."""
        self._reap_checkpoint()
        if not self._ckpt_dirty:
            return False
        now = time.time()
        if (self._ckpt_pending_bytes < self.ckpt_bytes
                and now - self._ckpt_last_t < self.ckpt_interval_s):
            return False
        if (self.ckpt_interval_s > 0 and self.ckpt_duty > 0
                and self._ckpt_last_s > 0
                and now - self._ckpt_last_t
                < self._ckpt_last_s / self.ckpt_duty):
            # Storm guard (ckpt_interval_s=0 — every-step mode — and
            # ckpt_duty=0 — guard disabled — both bypass it).
            return False
        if self.ckpt_interval_s <= 0:
            self.checkpoint()
            return True
        if self._ckpt_job is not None:
            self._m_ckpt_deferred.inc()
            return False
        self._take_checkpoint(background=True)
        return True

    def step(self, idle_sleep: float = 0.01) -> int:
        """One supervision quantum: lease upkeep, one input batch,
        fenced append + checkpoint, heartbeat. Returns records moved."""
        now = time.time()
        if self.fence is None:
            fence = self.leases.try_acquire(self.name)
            self.heartbeat()
            if fence is None:
                time.sleep(idle_sleep)
                return 0
            self.fence = fence
            self._last_renew = now
            self._recover()
        else:
            self._renew_or_die(now)
        with self._sp_pump as pump:
            pump.n = moved = self._pump()
        if not moved:
            with self._sp_idle:
                self._idle_wait(idle_sleep)
        return moved

    def _pump(self) -> int:
        """One input batch: poll, process, flush, fenced append,
        checkpoint cadence, heartbeat. Returns records moved."""
        # Micro-batch cap (threaded into the read): a deep input
        # backlog yields between steps, so lease renewal + heartbeat
        # stay live no matter how far behind the role is. The tail is
        # read incrementally (TailReader) — re-reading the whole topic
        # per step is O(topic²) over a role's lifetime.
        if self._reader is None or self._reader.next_line != self.offset:
            self._reader = make_tail_reader(self.in_topic, self.offset)
        # Batch-start input byte anchor (see `_in_pos`): every record
        # of the coming poll sits at/after this logical position.
        self._in_pos = getattr(self._reader, "_pos", None)
        out: List[dict] = []
        moved = 0
        with self._sp_poll as poll:
            if self.ingest_batches and hasattr(self._reader,
                                               "poll_batches"):
                # Columnar zero-decode path: whole RecordBatch frames go
                # to process_batch; stray decoded records (a migrated
                # JSONL history) take the per-record path.
                for unit in self._reader.poll_batches(self.batch):
                    if unit[0] == "batch":
                        moved += unit[2].n
                        self.process_batch(unit[1], unit[2], out)
                    else:
                        moved += 1
                        self.process(unit[1], unit[2], out)
            else:
                entries = self._reader.poll(self.batch)
                moved = len(entries)
                for line_idx, rec in entries:
                    self.process(line_idx, rec, out)
            poll.n = moved
        next_off = self._reader.next_line
        if not moved:
            if next_off != self.offset:
                self.offset = next_off  # junk-only progress still counts
                self._ckpt_dirty = True
            try:
                # Idle flush: progress folded since the last
                # checkpoint goes durable once the interval elapses
                # (a quiescent stream must not pin state in memory).
                self.maybe_checkpoint()
            except FencedError as exc:
                self._m_fenced.inc()
                self.heartbeat(force=True)  # export the rejection before dying
                print(f"FENCED {self.name} {self.owner}: {exc}", flush=True)
                raise SystemExit(EXIT_FENCED)
            self.heartbeat()
            return 0
        with self._sp_flush:
            self.flush_batch(out)
        try:
            if self.out_topic is not None:
                # Append THEN checkpoint; the recovery scan makes the
                # crash window between them exactly-once, whatever the
                # checkpoint cadence. Durable = retried under the
                # storage-fault budget (degraded, not dead, through a
                # transient ENOSPC).
                with self._sp_append:
                    self._ckpt_pending_bytes += self._append_outputs(out)
            self.offset = next_off
            self._ckpt_dirty = True
            self.maybe_checkpoint()
        except FencedError as exc:
            self._m_fenced.inc()
            self.heartbeat(force=True)  # export the rejection before dying
            print(f"FENCED {self.name} {self.owner}: {exc}", flush=True)
            raise SystemExit(EXIT_FENCED)
        self._m_records.inc(moved)
        self.heartbeat()
        return moved


class DeliRole(_Role):
    """The sequencer lambda: rawdeltas → deltas, one DocumentSequencer
    per document, resubmission dedup by (client, clientSeq).

    Over a columnar op-log the role ingests whole `RecordBatch` frames
    (`process_batch`): int fields come straight off the codec columns,
    doc ids from the batch dictionary, and standalone ops' `contents`
    stay pre-encoded JSON blobs end to end when the out topic is
    columnar too — the scalar twin of `KernelDeliRole`'s zero-JSON
    ingest (ROADMAP PR-4 follow-up: the per-record lazy `record(i)`
    decode was the last JSON cost on the scalar-on-columnar path)."""

    name = "deli"
    in_topic_name = "rawdeltas"
    out_topic_name = "deltas"
    ingest_batches = True  # _Role.step feeds RecordBatch frames whole

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.sequencers: Dict[str, DocumentSequencer] = {}
        # Blob pass-through is only legal when the output topic can
        # carry raw JSON bytes (a columnar sibling); a JSON out topic
        # needs decoded values for its json.dumps.
        from .columnar_log import ColumnarFileTopic

        self.out_columnar = isinstance(self.out_topic, ColumnarFileTopic)

    def snapshot_state(self) -> Any:
        return {d: s.checkpoint() for d, s in self.sequencers.items()}

    def restore_state(self, state: Any) -> None:
        state = unwrap_ranged_state(state)
        self.sequencers = {
            d: DocumentSequencer.restore(s) for d, s in (state or {}).items()
        }

    def _doc(self, doc_id: str) -> DocumentSequencer:
        if doc_id not in self.sequencers:
            self.sequencers[doc_id] = DocumentSequencer(doc_id)
        return self.sequencers[doc_id]

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or "doc" not in rec:
            return  # foreign/junk record: consume and move on
        doc = self._doc(rec["doc"])
        kind = rec.get("kind")
        if kind == "join":
            if rec["client"] in doc.clients:
                return  # duplicate join (at-least-once ingress)
            msg = doc.join(rec["client"])
            out.append(self._wire(rec["doc"], msg, line_idx))
            return
        if kind == "leave":
            msg = doc.leave(rec["client"])
            if msg is not None:
                out.append(self._wire(rec["doc"], msg, line_idx))
            return
        if kind == "boxcar":
            # Wire schema rev (ROADMAP (d)): one ingress record carries
            # a whole client batch, ticketed back-to-back so it
            # sequences ATOMICALLY — a nack aborts the rest of the
            # boxcar (matching the in-proc `lambdas` semantics and the
            # kernel's group-abort machinery), while resubmission dedup
            # stays per-op and silent (a re-appended boxcar vanishes
            # without polluting the order).
            client = int(rec["client"])
            for op in rec.get("ops") or []:
                if not self._ticket_wire(
                    doc, rec["doc"], client, int(op["clientSeq"]),
                    int(op.get("refSeq", 0)), op.get("contents"),
                    line_idx, out, sub_ts=rec.get("tr_sub"),
                    adm_ts=rec.get("tr_adm"),
                ):
                    break
            return
        if kind != "op":
            return
        self._ticket_wire(
            doc, rec["doc"], int(rec["client"]), int(rec["clientSeq"]),
            int(rec.get("refSeq", 0)), rec.get("contents"), line_idx, out,
            sub_ts=rec.get("tr_sub"), adm_ts=rec.get("tr_adm"),
        )

    def process_batch(self, start_line: int, batch: Any,
                      out: List[dict]) -> None:
        """Columnar ingest: ticket one `RecordBatch` (records numbered
        start_line..start_line+n-1) reusing the already-decoded codec
        columns — no per-record dict build, no lazy full-record JSON
        decode; op contents ride as raw blobs when the out topic is
        columnar (the kernel role's pass-through rule)."""
        import json as _json

        from ..protocol import record_batch as _rb

        rb = batch
        kinds = rb.kind.tolist()
        doci = rb.doc_idx.tolist()
        clients = rb.client.tolist()
        cseqs = rb.client_seq.tolist()
        refs = rb.ref_seq.tolist()
        docs = rb.docs
        passthrough = self.out_columnar
        for i in range(rb.n):
            k = kinds[i]
            if k == _rb.K_RAW_OP:
                doc_id = docs[doci[i]]
                contents: Any = _rb.JsonBlob(rb.blob(i))
                if not passthrough:
                    contents = contents.value
                self._ticket_wire(
                    self._doc(doc_id), doc_id, clients[i], cseqs[i],
                    refs[i], contents, start_line + i, out,
                )
            elif k == _rb.K_RAW_JOIN:
                doc = self._doc(docs[doci[i]])
                if clients[i] in doc.clients:
                    continue  # duplicate join (at-least-once ingress)
                out.append(self._wire(
                    docs[doci[i]], doc.join(clients[i]), start_line + i
                ))
            elif k == _rb.K_RAW_LEAVE:
                msg = self._doc(docs[doci[i]]).leave(clients[i])
                if msg is not None:
                    out.append(self._wire(
                        docs[doci[i]], msg, start_line + i
                    ))
            elif k == _rb.K_RAW_BOXCAR:
                doc_id = docs[doci[i]]
                doc = self._doc(doc_id)
                # v2 frames hand per-op contents as raw-blob handles
                # (no once-per-boxcar JSON decode); v1 as plain values.
                for cseq, ref, contents in rb.boxcar(i):
                    if not passthrough and isinstance(
                            contents, _rb.JsonBlob):
                        contents = contents.value
                    if not self._ticket_wire(
                        doc, doc_id, clients[i], cseq, ref, contents,
                        start_line + i, out,
                    ):
                        break  # nack aborts the rest of the boxcar
            else:
                # Generic / foreign record inside the frame: decode
                # this one record and route it the legacy way.
                self.process(start_line + i, rb.record(i), out)

    def _ticket_wire(self, doc: DocumentSequencer, doc_id: str,
                     client: int, client_seq: int, ref_seq: int,
                     contents: Any, line_idx: int,
                     out: List[dict], sub_ts: Optional[float] = None,
                     adm_ts: Optional[float] = None) -> bool:
        """Ticket one wire op; returns False on a nack (the boxcar
        abort signal). Deduped resubmissions return True silently."""
        state = doc.clients.get(client)
        if state is not None and client_seq <= state.client_seq:
            # Resubmission dedup (the idempotent-producer role): a
            # client that lost its ack mid-batch re-appends the whole
            # batch; everything already sequenced is dropped HERE, so
            # the deltas stream carries each op exactly once and no
            # out-of-order nacks pollute the total order.
            return True
        from ..protocol.messages import DocumentMessage, NackMessage

        res = doc.sequence(client, DocumentMessage(
            client_seq=client_seq, ref_seq=ref_seq, contents=contents,
        ))
        if isinstance(res, NackMessage):
            out.append({
                "kind": "nack", "doc": doc_id, "client": client,
                "clientSeq": res.client_seq, "code": res.code,
                "reason": res.reason, "inOff": line_idx,
            })
            return False
        out.append(self._wire(doc_id, res, line_idx, sub_ts=sub_ts,
                              adm_ts=adm_ts))
        return True

    def _wire(self, doc_id: str, msg, line_idx: int,
              sub_ts: Optional[float] = None,
              adm_ts: Optional[float] = None) -> dict:
        # Timestamps deliberately excluded from the CANONICAL keys:
        # the stream must be a pure function of the input order (the
        # bit-identity contract). In wire-trace mode the stamp rides
        # the side "tr" dict, which canonical_record/digests never see
        # — one clock read serves both the record stamp and the
        # submit_to_stamp histogram so the two surfaces agree exactly.
        rec = {
            "kind": "op", "doc": doc_id, "seq": msg.sequence_number,
            "msn": msg.minimum_sequence_number, "client": msg.client_id,
            "clientSeq": msg.client_seq, "refSeq": msg.ref_seq,
            "type": msg.type.value, "contents": msg.contents,
            "inOff": line_idx,
        }
        if self.trace_wire:
            now = time.time()
            tr = {"stamp": now}
            if isinstance(sub_ts, (int, float)):
                tr["sub"] = sub_ts
                if not self._recovering:
                    # Recovery's silent replay regenerates records it
                    # never emits (plus the genuinely-missing tail,
                    # first stamped now) — observing those would
                    # double-count with crash-spanning durations.
                    self._observe_stage(
                        "submit_to_stamp", (now - sub_ts) * 1000.0
                    )
            if isinstance(adm_ts, (int, float)):
                # The front door's admission stamp (`tr_adm`, one
                # clock read inside `IngressRole.process`): the SAME
                # `now` that stamps this record measures
                # admit_to_stamp, and the same recovery gate keeps
                # replayed records from being observed twice (the
                # trace_stage_once contract every stage follows).
                tr["adm"] = adm_ts
                if not self._recovering:
                    self._observe_stage(
                        "admit_to_stamp", (now - adm_ts) * 1000.0
                    )
            rec["tr"] = tr
        return rec


class ScriptoriumRole(_Role):
    """Durable op log: deltas → durable.jsonl (the Mongo deltas
    collection role). Stateless 1:1 map; exactly-once comes entirely
    from the inOff fast-forward."""

    name = "scriptorium"
    in_topic_name = "deltas"
    out_topic_name = "durable"

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or rec.get("kind") != "op":
            return
        # `inOff`/`inSrc` are the UPSTREAM stage's transport
        # bookkeeping (the deli's input offsets, the elastic fabric's
        # pred-drain tags): stripped here and re-keyed to THIS stage's
        # input offset, so the downstream exactly-once scan reads its
        # own offset space.
        rec2 = {**{k: v for k, v in rec.items()
                   if k not in ("inOff", "inSrc")},
                "inOff": line_idx}
        tr = rec.get("tr")
        if self.trace_wire and isinstance(tr, dict):
            now = time.time()
            rec2["tr"] = {**tr, "dur": now}
            stamp = tr.get("stamp")
            if isinstance(stamp, (int, float)) and not self._recovering:
                # Silent replay re-processes already-durable records;
                # observing them again would skew /slo with
                # crash-spanning durations.
                self._observe_stage(
                    "stamp_to_durable", (now - stamp) * 1000.0
                )
        out.append(rec2)


class BroadcasterRole(_Role):
    """Fan-out feed: deltas → broadcast.jsonl, which connected clients
    tail (the socket push edge). Delivery to clients is at-least-once
    by nature — the chaos harness's delayed/duplicated delivery faults
    live on the consumer side of this topic."""

    name = "broadcaster"
    in_topic_name = "deltas"
    out_topic_name = "broadcast"

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or rec.get("kind") not in (
            "op", "nack"
        ):
            return
        rec2 = {**{k: v for k, v in rec.items()
                   if k not in ("inOff", "inSrc")},
                "inOff": line_idx}
        tr = rec.get("tr")
        if self.trace_wire and isinstance(tr, dict):
            now = time.time()
            rec2["tr"] = {**tr, "bc": now}
            if self._recovering:
                # Silent replay: already-observed records must not be
                # re-observed (crash-spanning durations) nor fed to
                # the flight recorder as phantom slow ops.
                out.append(rec2)
                return
            stamp = tr.get("stamp")
            if isinstance(stamp, (int, float)):
                self._observe_stage(
                    "stamp_to_broadcast", (now - stamp) * 1000.0
                )
            sub = tr.get("sub")
            if isinstance(sub, (int, float)):
                # The farm's end-to-end stage AND the slow-op flight
                # recorder: a tail observation beyond the rolling p99
                # (or fixed threshold) keeps its full span — the exact
                # slow op a regression report needs attached.
                e2e = (now - sub) * 1000.0
                self._observe_stage("submit_to_broadcast", e2e)
                from ..utils.metrics import get_flight_recorder

                fr = get_flight_recorder()
                if fr.note(e2e):
                    span = {
                        "doc": rec.get("doc"), "seq": rec.get("seq"),
                        "client": rec.get("client"),
                        "clientSeq": rec.get("clientSeq"),
                        "stages": rec2["tr"],
                    }
                    if self.partition is not None:
                        # Fabric runs: the span names its partition so
                        # the supervisor's merged /traces can pin a
                        # tail regression to the hot range.
                        span["partition"] = str(self.partition)
                    fr.add(e2e, span)
        out.append(rec2)


class ScriptoriumBroadcasterRole(_Role):
    """The FUSED durable+broadcast hop: ONE supervised consumer plays
    both `ScriptoriumRole` and `BroadcasterRole`, so a record crosses
    deltas → durable → broadcast for one topic read, one process wake
    and ~one fsync per batch instead of one of each PER STAGE. The
    wire contract
    is unchanged — `durable` and `broadcast` carry exactly the records
    the split roles wrote — only the consumer topology fuses.

    - The durable leg keeps its fsync; the broadcast leg appends
      UNFSYNCED (`append_many(fsync=False)`): broadcast is a DERIVED
      feed, deterministically regenerable from the durable deltas
      stream, and recovery's per-topic durable-prefix scan re-emits
      anything the page cache lost — exactly-once holds leg by leg.
    - On columnar topics the transform is a frame PASS-THROUGH:
      K_SEQ_OP / K_NACK rows re-emit as `ColumnarRecords` slices with
      only the inOff column rewritten — no decode, no re-encode, blob
      bytes ride untouched (`record_batch.ColumnarRecords.from_batch`).
    - Recovery generalizes the single-topic contract: the fence binds
      on BOTH topics, each topic's durable prefix scans independently,
      the gap replays silently once, and each topic gets exactly its
      missing suffix re-emitted (the durable leg appends first in
      steady state, so the broadcast leg is the one that usually
      trails a crash).

    In wire-trace mode one clock read stamps both `dur` and `bc` and
    feeds the same stage histograms + slow-op flight recorder the
    split roles fed."""

    name = "scriptorium_broadcaster"
    in_topic_name = "deltas"
    out_topic_name = "durable"
    # The second output leg (partitioned_role_class suffixes it along
    # with the in/out pair, so a per-partition fused consumer reads
    # deltas-p{k} and writes durable-p{k} + broadcast-p{k}).
    bc_topic_name = "broadcast"
    ingest_batches = True  # columnar pass-through wants whole frames

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.bc_topic = make_topic(
            _topic_path(self.shared_dir, self.bc_topic_name),
            self.log_format,
        )
        self._bc_out: List[Any] = []
        from .columnar_log import ColumnarFileTopic

        self.out_columnar = isinstance(self.out_topic, ColumnarFileTopic)

    # ------------------------------------------------------------- pump

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or rec.get("kind") not in (
            "op", "nack"
        ):
            return
        rec2 = {**{k: v for k, v in rec.items()
                   if k not in ("inOff", "inSrc")},
                "inOff": line_idx}
        tr = rec.get("tr")
        if self.trace_wire and isinstance(tr, dict):
            now = time.time()
            # One clock read serves both stage stamps (the fused hop
            # IS one instant) and every observation below.
            rec2["tr"] = {**tr, "dur": now, "bc": now}
            if not self._recovering:
                stamp = tr.get("stamp")
                if isinstance(stamp, (int, float)):
                    ms = (now - stamp) * 1000.0
                    self._observe_stage("stamp_to_durable", ms)
                    self._observe_stage("stamp_to_broadcast", ms)
                sub = tr.get("sub")
                if isinstance(sub, (int, float)):
                    e2e = (now - sub) * 1000.0
                    self._observe_stage("submit_to_broadcast", e2e)
                    from ..utils.metrics import get_flight_recorder

                    fr = get_flight_recorder()
                    if fr.note(e2e):
                        span = {
                            "doc": rec.get("doc"), "seq": rec.get("seq"),
                            "client": rec.get("client"),
                            "clientSeq": rec.get("clientSeq"),
                            "stages": rec2["tr"],
                        }
                        if self.partition is not None:
                            span["partition"] = str(self.partition)
                        fr.add(e2e, span)
        if rec.get("kind") == "op":
            out.append(rec2)
        # Broadcast carries ops AND nacks; the very same dict object
        # rides both legs (no per-leg rebuild).
        self._bc_out.append(rec2)

    def process_batch(self, start_line: int, batch: Any,
                      out: List[dict]) -> None:
        """Columnar ingest: pass K_SEQ_OP/K_NACK spans through as
        column slices (durable takes the seq-ops, broadcast takes
        both), decode only generic strays — in stream order, so the
        spliced output frames carry records exactly where the split
        roles would have."""
        if (not self.out_columnar or self.trace_wire
                or self._recovering or self._dict_emit):
            for i in range(batch.n):
                self.process(start_line + i, batch.record(i), out)
            return
        import numpy as np

        from ..protocol import record_batch as _rb

        n = batch.n
        if n == 0:
            return
        kind = batch.kind
        is_pass = (kind == _rb.K_SEQ_OP) | (kind == _rb.K_NACK)
        for run_pass, lo, hi in _rb.mask_runs(is_pass):
            if not run_pass:
                for i in range(lo, hi):
                    self.process(start_line + i, batch.record(i), out)
                continue
            rows = np.arange(lo, hi)
            offs = np.arange(start_line + lo, start_line + hi,
                             dtype=np.int64)
            self._bc_out.append(
                _rb.ColumnarRecords.from_batch(batch, rows, offs)
            )
            ops = kind[lo:hi] == _rb.K_SEQ_OP
            if ops.all():
                out.append(self._bc_out[-1])  # same object, both legs
            elif ops.any():
                out.append(_rb.ColumnarRecords.from_batch(
                    batch, rows[ops], offs[ops]
                ))

    def _append_outputs(self, out: List[Any]) -> int:
        # Durable first (fsync, the base append), broadcast second
        # (unfsynced): a crash between the legs leaves broadcast
        # trailing, which recovery's per-topic scan closes. Each leg
        # owns its retry budget — a retry must never re-append the leg
        # that already landed.
        n = super()._append_outputs(out)
        bc, self._bc_out = self._bc_out, []
        n += self._durable(lambda: self.bc_topic.append_many(
            bc, fence=self.fence, owner=self.owner, fsync=False
        ))
        return n

    # --------------------------------------------------------- recovery

    def _recover_inner(self) -> None:
        env = self.ckpt.load(self.name)
        self.offset = 0
        if env is not None:
            st = env["state"]
            self.offset = int(st.get("offset", 0))
            self.restore_state(st.get("state"))
        else:
            self.restore_state(None)
        self._bc_out = []
        # Bind our fence on BOTH output topics before scanning either:
        # a deposed predecessor's in-flight append to either leg is
        # rejected from here on.
        self._durable(lambda: self.out_topic.append_many(
            [], fence=self.fence, owner=self.owner
        ))
        self._durable(lambda: self.bc_topic.append_many(
            [], fence=self.fence, owner=self.owner
        ))
        done_d = self._durable_done_counts(self.out_topic)
        done_b = self._durable_done_counts(self.bc_topic)
        if not done_d and not done_b:
            return
        max_done = max(list(done_d) + list(done_b))
        gap, next_off = self.in_topic.read_entries(self.offset)
        sink: List[dict] = []
        for line_idx, rec in gap:
            if line_idx > max_done:
                next_off = line_idx
                break
            self.process(line_idx, rec, sink)  # silent: already durable
        else:
            next_off = max(self.offset, max_done + 1, next_off)
        self.flush_batch(sink)
        bc_sink, self._bc_out = self._bc_out, []
        # Per-leg tail: everything past that leg's own durable prefix
        # (its max_done's clipped suffix, plus whole inputs the other
        # leg reached first). Records sit in `snk` in input order, so
        # the concatenation preserves stream order.
        for topic, snk, done, fs in (
            (self.out_topic, sink, done_d, True),
            (self.bc_topic, bc_sink, done_b, False),
        ):
            if done:
                md = max(done)
                tail = [r for r in snk if r.get("inOff") == md]
                tail = tail[done.get(md, 0):]
                tail += [r for r in snk if r.get("inOff", -1) > md]
            else:
                tail = list(snk)
            if tail:
                self._durable(lambda t=topic, x=tail, f=fs:
                              t.append_many(x, fence=self.fence,
                                            owner=self.owner, fsync=f))
        self.offset = next_off
        self._reader = None  # re-anchor the tail at the new offset
        self.checkpoint()


class ScribeRole(_Role):
    """Protocol-state folder: deltas → per-doc rolling digest + head
    seq (the scribe/summary role). Its output IS its checkpoint, and
    state+offset commit in one atomic fenced write, so recovery is
    trivially exactly-once."""

    name = "scribe"
    in_topic_name = "deltas"
    out_topic_name = None

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.docs: Dict[str, dict] = {}

    def snapshot_state(self) -> Any:
        return self.docs

    def restore_state(self, state: Any) -> None:
        self.docs = dict(state or {})

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or rec.get("kind") != "op":
            return
        st = self.docs.setdefault(
            rec["doc"], {"seq": 0, "count": 0, "digest": ""}
        )
        payload = json.dumps(
            [st["digest"], canonical_record(rec)], sort_keys=True
        )
        st["digest"] = hashlib.sha256(payload.encode()).hexdigest()
        st["seq"] = max(int(st["seq"]), int(rec["seq"]))
        st["count"] = int(st["count"]) + 1


ROLE_CLASSES = {
    cls.name: cls
    for cls in (DeliRole, ScriptoriumRole, ScribeRole, BroadcasterRole,
                ScriptoriumBroadcasterRole)
}

DELI_IMPLS = ("scalar", "kernel")


def fused_roles(roles: Tuple[str, ...]) -> Tuple[str, ...]:
    """`roles` with the scriptorium+broadcaster pair collapsed into
    the fused durable+broadcast consumer (order preserved, the fused
    role at the first of the pair's positions)."""
    out: List[str] = []
    for r in roles:
        if r in ("scriptorium", "broadcaster"):
            if ScriptoriumBroadcasterRole.name not in out:
                out.append(ScriptoriumBroadcasterRole.name)
        else:
            out.append(r)
    return tuple(out)


FUSED_PIPELINE_ROLES = fused_roles(PIPELINE_ROLES)


def resolve_role_class(role: str, deli_impl: str = "scalar"):
    """Role name -> class; `deli_impl="kernel"` swaps the sequencer for
    the device-batched `deli_kernel.KernelDeliRole` (imported lazily so
    scalar farms never pay the jax import). The summarizer resolves
    lazily too — its merge-tree fold engine only imports jax when a
    doc's contents actually decode as merge-tree ops."""
    if role == "deli" and deli_impl == "kernel":
        from .deli_kernel import KernelDeliRole

        return KernelDeliRole
    if role == "summarizer":
        from .summarizer import SummarizerRole

        return SummarizerRole
    if role == "ingress":
        from .ingress import IngressRole

        return IngressRole
    if role == "retention":
        from .retention import RetentionRole

        return RetentionRole
    return ROLE_CLASSES[role]


def partitioned_role_class(base: type, partition: int) -> type:
    """The sharded-fabric form of a role class: same code, partition-
    suffixed identity. Lease key, heartbeat file, checkpoint key and
    topic pair all become per-partition (`deli-p3` over
    `rawdeltas-p3` → `deltas-p3`), so N partitions of one role are N
    independent fenced exactly-once pipelines over disjoint slices of
    the document space (`server.shard_fabric` owns the slicing)."""
    p = int(partition)
    if p < 0:
        raise ValueError(f"partition must be >= 0, got {partition}")
    attrs = {
        "name": partition_suffix(base.name, p),
        "in_topic_name": partition_suffix(base.in_topic_name, p),
        "out_topic_name": (
            partition_suffix(base.out_topic_name, p)
            if base.out_topic_name else None
        ),
        "partition": p,
        "role_base": base.name,
    }
    # A second output leg (the fused durable+broadcast consumer)
    # partitions along with the primary pair.
    if getattr(base, "bc_topic_name", None):
        attrs["bc_topic_name"] = partition_suffix(base.bc_topic_name, p)
    return type(f"{base.__name__}P{p}", (base,), attrs)


def serve_role(shared_dir: str, role: str, owner: str,
               ttl_s: float = 1.0, batch: int = 512,
               deli_impl: str = "scalar",
               ckpt_interval_s: float = 0.25,
               ckpt_bytes: int = 256 * 1024,
               log_format: Optional[str] = None,
               ckpt_duty: float = 0.2,
               partition: Optional[int] = None,
               deli_devices: Optional[int] = None,
               hb_interval_s: Optional[float] = None,
               summary_ops: Optional[int] = None,
               ingress_partitions: Optional[int] = None,
               ingress_elastic: bool = False,
               device_plane: Optional[str] = None,
               fold_backend: Optional[str] = None) -> None:
    """Child-process entry: run one role until killed/deposed/fenced.
    With `partition`, the role serves that partition's topic pair under
    its partition-suffixed lease (one pinned shard of the fabric —
    `shard_fabric.ShardWorker` is the lease-balanced multi-partition
    form). `deli_devices=N` shards the kernel deli's doc-slot pool
    across an N-device mesh (`--deli-devices`; kernel impl only —
    the scalar deli has no device axis, so asking is a config error).
    `summary_ops` sets the summarizer's emission cadence (summarizer
    role only; env ``FLUID_SUMMARY_OPS`` is the process-wide form).
    `device_plane` ("DOCSxMODEL", `parallel.device_plane`) serves the
    kernel deli on the plane's 1-D docs slice and lays the
    summarizer's folds over the whole 2-D pool; `fold_backend`
    ("kernel"|"overlay") picks the summarizer's merge-tree fold
    engine (``FLUID_FOLD_BACKEND`` is the process-wide form)."""
    if deli_devices is not None and deli_devices > 1 and (
            role != "deli" or deli_impl != "kernel"):
        raise ValueError(
            f"deli_devices={deli_devices} needs role=deli with "
            f"deli_impl='kernel' (got role={role!r}, impl={deli_impl!r})"
        )
    if device_plane is not None and (
            role not in ("deli", "summarizer")
            or (role == "deli" and deli_impl != "kernel")):
        raise ValueError(
            f"device_plane={device_plane!r} serves the kernel deli "
            f"and the summarizer (got role={role!r}, "
            f"impl={deli_impl!r})"
        )
    if fold_backend is not None and role != "summarizer":
        raise ValueError(
            f"fold_backend={fold_backend!r} is a summarizer knob "
            f"(got role={role!r})"
        )
    if summary_ops is not None and role != "summarizer":
        raise ValueError(
            f"summary_ops={summary_ops} is a summarizer knob "
            f"(got role={role!r})"
        )
    if (ingress_partitions is not None or ingress_elastic) \
            and role != "ingress":
        raise ValueError(
            f"ingress_partitions/ingress_elastic are ingress knobs "
            f"(got role={role!r})"
        )
    use_compile_cache()
    cls = resolve_role_class(role, deli_impl)
    if partition is not None:
        cls = partitioned_role_class(cls, partition)
    kw = {}
    if deli_devices is not None and deli_devices > 1:
        kw["deli_devices"] = deli_devices
    if device_plane is not None:
        kw["device_plane"] = device_plane
    if fold_backend is not None:
        kw["fold_backend"] = fold_backend
    if summary_ops is not None:
        kw["summary_ops"] = summary_ops
    if role == "ingress":
        # The front door routes by partition topology; admission knobs
        # themselves ride FLUID_INGRESS_* env (server.ingress).
        kw["n_partitions"] = ingress_partitions or 1
        kw["elastic"] = ingress_elastic
    r = cls(
        shared_dir, owner, ttl_s=ttl_s, batch=batch,
        ckpt_interval_s=ckpt_interval_s, ckpt_bytes=ckpt_bytes,
        log_format=log_format, ckpt_duty=ckpt_duty, **kw,
    )
    track_compiles()  # the role's modules imported jax, if it uses it
    if hb_interval_s is not None:
        # Heartbeat throttle: the default (0 = every step) is the
        # classic liveness contract, but a registry snapshot per
        # record is pure tail latency at high step rates — the
        # open-loop latency driver runs its children at ~0.1s (still
        # 20x inside the staleness threshold; forced heartbeats —
        # degraded flags, fence rejections — always bypass the
        # throttle).
        r.hb_interval_s = hb_interval_s
    print(f"READY {r.name} {owner}", flush=True)
    while True:
        try:
            r.step()
        except FencedError as exc:
            # Recovery-path rejection (step() handles its own): we are
            # a zombie; a successor owns the fence. Stand down loudly.
            r._m_fenced.inc()
            r.heartbeat()  # export the rejection before dying
            print(f"FENCED {role} {owner}: {exc}", flush=True)
            raise SystemExit(EXIT_FENCED)


# ---------------------------------------------------------------------------
# The supervisor
# ---------------------------------------------------------------------------


class ServiceSupervisor:
    """Launches the lambda farm as child processes and keeps it alive.

    Failure detection is two-signal: process exit (`Popen.poll`) and
    heartbeat staleness (a live-but-wedged child — SIGSTOP, deadlock —
    misses its heartbeat and is SIGKILLed before restart; fencing makes
    even a missed kill safe). Every restart spawns a fresh owner
    identity `<role>-g<generation>`, whose lease acquisition waits out
    the dead owner's TTL and advances the fence.
    """

    def __init__(self, shared_dir: str, roles: Tuple[str, ...] = ROLES,
                 ttl_s: float = 0.75, heartbeat_timeout_s: float = 2.0,
                 batch: int = 512, python: Optional[str] = None,
                 spawn_ready_timeout_s: float = 30.0,
                 deli_impl: Optional[str] = None,
                 ckpt_interval_s: float = 0.25,
                 ckpt_bytes: int = 256 * 1024,
                 log_format: Optional[str] = None,
                 ckpt_duty: float = 0.2,
                 deli_devices: Optional[int] = None,
                 child_env: Optional[Dict[str, str]] = None,
                 hb_interval_s: Optional[float] = None,
                 summary_ops: Optional[int] = None,
                 fused_hop: bool = False,
                 ingress: bool = False,
                 retention: bool = False,
                 retention_env: Optional[Dict[str, str]] = None,
                 device_plane: Optional[str] = None,
                 fold_backend: Optional[str] = None):
        """`child_env` adds/overrides spawn-environment variables for
        every child (the chaos harness's seam: it points CHILDREN at a
        disk-fault spec — `queue.DISK_FAULT_ENV` — without poisoning
        its own appends). `hb_interval_s` throttles the children's
        heartbeat-file writes (None keeps the classic every-step
        cadence; forced heartbeats always bypass the throttle).
        `summary_ops` sets the summarizer child's emission cadence
        (records per doc between summaries; None keeps the role
        default / ``FLUID_SUMMARY_OPS``). `fused_hop` collapses the
        scriptorium+broadcaster pair in `roles` into the fused
        durable+broadcast consumer (`ScriptoriumBroadcasterRole`) —
        same topics, same records, one fewer process wake and fsync
        per batch on the downstream hop pair. `ingress` puts the
        supervised admission front door (`server.ingress.IngressRole`)
        in front of the farm: clients submit to the ``ingress`` topic,
        and only admitted records reach ``rawdeltas`` — auth / size /
        rate / backpressure nacks land on the ``nacks`` topic
        instead."""
        if fused_hop:
            roles = fused_roles(tuple(roles))
        if ingress and "ingress" not in roles:
            roles = ("ingress",) + tuple(roles)
        if retention and "retention" not in roles:
            # Sixth role, the retention plane (`server.retention`):
            # summary-driven fenced op-log truncation + castore GC.
            # Opt-in — with it on, readers that need a topic's full
            # prefix must boot from the newest summary instead.
            roles = tuple(roles) + ("retention",)
        self.retention = bool(retention) or "retention" in roles
        self.ingress = bool(ingress) or "ingress" in roles
        self.fused_hop = bool(fused_hop)
        self.shared_dir = shared_dir
        self.child_env = dict(child_env or {})
        if self.retention:
            if default_log_format(log_format) != "columnar":
                raise ValueError(
                    "retention=True needs log_format='columnar' "
                    "(JSONL topics have no truncation header)"
                )
            if "summarizer" not in roles:
                raise ValueError(
                    "retention=True needs the summarizer in roles: "
                    "truncation only reclaims SUMMARY-covered records"
                )
            # The retention child's consumer set is THIS farm's actual
            # deltas consumers — a role that is not in the farm must
            # not block reclaim as a phantom offset-0 checkpoint.
            deltas_consumers = [
                r for r in roles
                if r in ("scriptorium", "broadcaster", "scribe",
                         "summarizer", ScriptoriumBroadcasterRole.name)
            ]
            self.child_env.setdefault(
                "FLUID_RETENTION_CONSUMERS", ",".join(deltas_consumers)
            )
            if self.ingress:
                # With the front door on, the admission topics are
                # growth surfaces too: `ingress` truncates behind the
                # admission role's own input checkpoint, `nacks`
                # behind its producer recovery window (PR 14
                # follow-up — the whole pipeline's disk is bounded,
                # not just the ordered half).
                self.child_env.setdefault(
                    "FLUID_RETENTION_TOPICS",
                    "deltas,rawdeltas,ingress,nacks",
                )
            for k, v in (retention_env or {}).items():
                self.child_env[k] = str(v)
        self.hb_interval_s = hb_interval_s
        self.summary_ops = (
            int(summary_ops) if summary_ops is not None else None
        )
        self.roles = tuple(roles)
        self.ttl_s = ttl_s
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.batch = batch
        self.ckpt_interval_s = ckpt_interval_s
        self.ckpt_bytes = ckpt_bytes
        self.ckpt_duty = ckpt_duty
        self.log_format = default_log_format(log_format)
        self.deli_impl = deli_impl or os.environ.get("FLUID_DELI", "scalar")
        if self.deli_impl not in DELI_IMPLS:
            raise ValueError(
                f"deli_impl {self.deli_impl!r} not in {DELI_IMPLS}"
            )
        # Multi-device deli: shard the kernel deli's [D, C] pool over
        # N devices — the host's chips, or under CPU emulation N
        # forced virtual host devices in the spawn env (`_child_env`).
        self.deli_devices = (
            int(deli_devices) if deli_devices is not None else None
        )
        if self.deli_devices is not None and self.deli_devices > 1 \
                and self.deli_impl != "kernel":
            raise ValueError(
                f"deli_devices={self.deli_devices} needs "
                f"deli_impl='kernel' (the scalar deli has no device "
                f"axis); got {self.deli_impl!r}"
            )
        # 2-D device plane (parallel.device_plane): ONE docs x model
        # mesh serving the kernel deli (docs-axis slice) AND the
        # summarizer folds (whole pool). The parent only PARSES the
        # spec — children build the actual mesh (`_child_env`); the
        # spec also rides the child env (PLANE_ENV) so
        # ranged/partitioned roles inherit it.
        self.device_plane: Optional[str] = None
        self.plane_shape: Optional[Tuple[int, int]] = None
        self.fold_backend = fold_backend
        if fold_backend is not None and fold_backend not in (
                "kernel", "overlay"):
            raise ValueError(
                f"fold_backend {fold_backend!r} not in "
                f"('kernel', 'overlay')"
            )
        if device_plane is not None:
            from ..parallel.device_plane import PLANE_ENV, \
                parse_plane_spec

            if self.deli_impl != "kernel":
                raise ValueError(
                    f"device_plane={device_plane!r} needs "
                    f"deli_impl='kernel' (the scalar deli has no "
                    f"device axis); got {self.deli_impl!r}"
                )
            if self.deli_devices is not None and self.deli_devices > 1:
                raise ValueError(
                    "deli_devices and device_plane are exclusive: "
                    "the plane's docs axis IS the deli's device slice"
                )
            self.plane_shape = parse_plane_spec(device_plane)
            self.device_plane = (
                f"{self.plane_shape[0]}x{self.plane_shape[1]}"
            )
            self.child_env.setdefault(PLANE_ENV, self.device_plane)
        self.python = python or sys.executable
        self.spawn_ready_timeout_s = spawn_ready_timeout_s
        # One chip belongs to one process. Decided from the spawn
        # environment alone (the supervisor never starts a backend):
        # under CPU emulation no child owns a chip; otherwise the one
        # role whose work needs the accelerator gets it, and a layout
        # that needs it in two children is refused here.
        self.chip_role: Optional[str] = None
        if not cpu_pinned():
            need = self._accelerator_roles()
            if len(need) > 1:
                raise ValueError(
                    f"roles {need[0]!r} and {need[1]!r} both need the "
                    f"accelerator, and one chip serves one process; "
                    f"run one of them on the CPU (e.g. "
                    f"deli_impl='scalar' or fold_backend='kernel')"
                )
            self.chip_role = need[0] if need else None
        if self.chip_role is not None and holds_accelerator():
            raise RuntimeError(
                f"this process holds the accelerator, so the "
                f"{self.chip_role!r} child could not take it; start the "
                f"farm before this process touches JAX"
            )
        self.procs: Dict[str, subprocess.Popen] = {}
        self.spawned_at: Dict[str, float] = {}
        self._stdout_tails: Dict[str, str] = {}
        self.generation: Dict[str, int] = {r: 0 for r in self.roles}
        self.restarts: Dict[str, int] = {r: 0 for r in self.roles}
        self.events: List[str] = []
        # Timestamped twin of `events` (the fault/recovery timeline
        # chaos_run renders; events stays the stable string API).
        self.timeline: List[Tuple[float, str]] = []
        self._monitor = None
        os.makedirs(os.path.join(shared_dir, "hb"), exist_ok=True)

    def _event(self, text: str) -> None:
        self.events.append(text)
        self.timeline.append((time.time(), text))

    # ------------------------------------------------------------ spawn

    def _repo_root(self) -> str:
        return os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )))

    def _child_cmd(self, role: str, owner: str) -> List[str]:
        """The child process's argv (the spawn seam subclasses override:
        `shard_fabric.ShardFabricSupervisor` launches lease-balanced
        shard workers through the same monitor/restart machinery).
        -c instead of -m: `-m pkg.mod` would import the package first
        and runpy then re-executes the module as __main__
        (RuntimeWarning + double module state)."""
        cmd = [self.python, "-c",
               "from fluidframework_tpu.server.supervisor import main; "
               "main()",
               "--role", role, "--dir", self.shared_dir,
               "--owner", owner, "--ttl", str(self.ttl_s),
               "--batch", str(self.batch),
               "--impl", self.deli_impl,
               "--log-format", self.log_format,
               "--ckpt-interval", str(self.ckpt_interval_s),
               "--ckpt-bytes", str(self.ckpt_bytes),
               "--ckpt-duty", str(self.ckpt_duty)]
        if self.deli_devices is not None and role == "deli":
            cmd += ["--deli-devices", str(self.deli_devices)]
        if self.device_plane is not None and role in ("deli",
                                                      "summarizer"):
            cmd += ["--device-plane", self.device_plane]
        if self.fold_backend is not None and role == "summarizer":
            cmd += ["--fold-backend", self.fold_backend]
        if self.summary_ops is not None and role == "summarizer":
            cmd += ["--summary-ops", str(self.summary_ops)]
        if self.hb_interval_s is not None:
            cmd += ["--hb-interval", str(self.hb_interval_s)]
        return cmd

    def _hb_file(self, role: str) -> str:
        """Where `role`'s liveness heartbeat lives (subclass seam: the
        shard fabric heartbeats per WORKER, not per role)."""
        return os.path.join(self.shared_dir, "hb", f"{role}.json")

    def _accelerator_roles(self) -> List[str]:
        """The roles whose work needs the accelerator (subclass seam:
        the shard fabric runs a kernel deli in every worker): the
        kernel deli, and a summarizer that folds over a device plane
        or through compiled overlay-pallas kernels."""
        need = []
        if self.deli_impl == "kernel" and "deli" in self.roles:
            need.append("deli")
        if "summarizer" in self.roles:
            from .summarizer import FOLD_INTERPRET_ENV

            interpreted = self.child_env.get(
                FOLD_INTERPRET_ENV, os.environ.get(FOLD_INTERPRET_ENV)
            ) == "1"
            if self.device_plane is not None or (
                    self.fold_backend == "overlay" and not interpreted):
                need.append("summarizer")
        return need

    def _child_env(self, role: str) -> Dict[str, str]:
        """`role`'s spawn environment — the one place that says which
        process may take the chip. `chip_role` inherits this process's
        environment and so the host's accelerator; every other child
        is pinned to the CPU. Under CPU emulation a multi-device deli
        or a device plane forces its device count into every child
        (the XLA flag only acts before the first jax import — exactly
        why it rides the spawn env)."""
        n_forced = None
        if self.plane_shape is not None:
            n_forced = self.plane_shape[0] * self.plane_shape[1]
        elif self.deli_devices is not None and self.deli_devices > 1:
            n_forced = self.deli_devices
        if role == self.chip_role:
            env = dict(os.environ)
        elif n_forced is not None and cpu_pinned():
            env = forced_host_device_env(n_forced)
        else:
            env = cpu_child_env()
        env.update(self.child_env)
        return env

    def _spawn(self, role: str) -> Optional[subprocess.Popen]:
        """Spawn one role child; returns None (and records the event)
        on failure rather than raising — a failed spawn must not kill
        the monitor loop that every OTHER role depends on. poll_once
        retries it on its next pass."""
        import select

        self.generation[role] += 1
        self.spawned_at[role] = time.time()  # paces respawn retries too
        owner = f"{role}-g{self.generation[role]}"
        try:
            proc = subprocess.Popen(
                self._child_cmd(role, owner),
                stdout=subprocess.PIPE, text=True,
                cwd=self._repo_root(),
                env=self._child_env(role),
            )
        except OSError as exc:
            self.procs[role] = None
            self._event(f"spawn {owner} FAILED ({exc!r})")
            return None
        # Bounded READY wait: a child wedged before its banner must
        # not freeze the whole monitor loop. Raw-fd reads, not the
        # buffered text wrapper — bytes the child flushed in the same
        # write as its banner must reach the drain buffer below, not
        # die in a wrapper buffer the fd-level drain never sees.
        fd = proc.stdout.fileno()
        deadline = time.time() + max(self.spawn_ready_timeout_s,
                                     self._heartbeat_bar(role))
        buf = b""
        while b"\n" not in buf:
            left = deadline - time.time()
            if left <= 0:
                break
            ready, _, _ = select.select([fd], [], [], left)
            if not ready:
                break
            chunk = os.read(fd, 4096)
            if not chunk:
                break
            buf += chunk
        banner, _, rest = buf.partition(b"\n")
        line = banner.decode("utf-8", "replace").strip()
        if not line.startswith("READY"):
            try:
                proc.kill()
                proc.wait(timeout=10)
            except OSError:
                pass
            self.procs[role] = None
            self._event(f"spawn {owner} FAILED ({line!r})")
            return None
        # Post-banner output is drained non-blockingly by poll_once: a
        # long-lived child (shard worker) prints a line per deposed or
        # fenced partition, and an undrained 64KB pipe would eventually
        # block the child's print() — a whole-worker stall with no
        # real fault.
        os.set_blocking(fd, False)
        self._stdout_tails[role] = rest.decode("utf-8", "replace")[-2048:]
        self.procs[role] = proc
        self._event(f"spawn {owner}")
        return proc

    def start(self) -> "ServiceSupervisor":
        for role in self.roles:
            # Boot is strict: a farm that cannot even start should say
            # so immediately, not limp along partially supervised.
            if self._spawn(role) is None:
                self.stop()
                raise RuntimeError(
                    f"{role} failed to start: {self.events[-1]}"
                )
        return self

    # ---------------------------------------------------------- monitor

    def _heartbeat_bar(self, role: str) -> float:
        """Seconds `role` may go without a heartbeat (or, at spawn,
        without READY) before it counts as wedged. The chip child
        compiles inside its step, silently, so its bar is sized for
        that compile; process exit is still caught at once."""
        if role == self.chip_role:
            return CHIP_HEARTBEAT_TIMEOUT_S
        return self.heartbeat_timeout_s

    def _heartbeat_age(self, role: str) -> float:
        """Staleness of `role`'s liveness signal. Clamped by the time
        since the current child was spawned: a fresh child that has
        not yet written its first heartbeat (or whose predecessor left
        an old one behind) gets a full grace period instead of an
        instant spurious restart."""
        since_spawn = time.time() - self.spawned_at.get(role, 0.0)
        try:
            with open(self._hb_file(role)) as f:
                hb = json.load(f)
            return min(time.time() - float(hb.get("t", 0)), since_spawn)
        except (OSError, ValueError):
            return since_spawn

    def _drain_stdout(self, role: str) -> None:
        """Pull whatever `role`'s child printed since the last pass into
        a bounded tail buffer (the fd is non-blocking after the banner).
        Only the tail is kept — poll_once quotes the last line when it
        restarts the child."""
        proc = self.procs.get(role)
        if proc is None or proc.stdout is None:
            return
        # os.read, not proc.stdout.read(): buffered text reads over a
        # non-blocking fd raise mid-stream (bpo-13322) instead of
        # returning the partial data, which would leave the pipe full.
        try:
            while True:
                chunk = os.read(proc.stdout.fileno(), 65536)
                if not chunk:
                    break
                tail = (self._stdout_tails.get(role, "")
                        + chunk.decode("utf-8", "replace"))
                self._stdout_tails[role] = tail[-2048:]
        except (OSError, ValueError):
            pass  # no data yet (EAGAIN) or fd already closed

    def poll_once(self) -> List[str]:
        """One supervision pass; returns the events it acted on."""
        acted: List[str] = []
        for role in self.roles:
            proc = self.procs.get(role)
            if proc is None:
                # Previous spawn attempt failed; retry, paced by the
                # lease TTL so a persistent failure can't hot-loop.
                if (role in self.generation
                        and time.time() - self.spawned_at.get(role, 0)
                        >= self.ttl_s):
                    acted.append(f"respawn {role}")
                    self._spawn(role)
                continue
            dead = proc.poll() is not None
            age = self._heartbeat_age(role)
            stale = not dead and age > self._heartbeat_bar(role)
            if not dead and not stale:
                self._drain_stdout(role)
                continue
            if stale:
                # Wedged (or stopped) but alive: kill before restart.
                # Fencing keeps us safe even if the kill were missed.
                try:
                    proc.kill()
                except OSError:
                    pass
                proc.wait(timeout=10)
            self._drain_stdout(role)
            tail = self._stdout_tails.pop(role, "").strip()
            why = (
                f"stale-heartbeat age={age:.2f}s" if stale
                else f"exit={proc.returncode}"
            )
            event = f"restart {role} ({why})" + (
                f" [{tail.splitlines()[-1]}]" if tail else ""
            )
            self.restarts[role] += 1
            self._event(event)
            acted.append(event)
            self._spawn(role)
        return acted

    def supervise(self, duration_s: float,
                  poll_interval_s: float = 0.1) -> None:
        """Run the monitor loop for `duration_s` (the harness's
        foreground mode; production would loop forever)."""
        deadline = time.time() + duration_s
        while time.time() < deadline:
            self.poll_once()
            time.sleep(poll_interval_s)

    # ---------------------------------------------------- observability

    def child_metrics(self) -> Dict[str, dict]:
        """Each role's last heartbeat metrics snapshot (children report
        up through the heartbeat channel; absent/torn files skip)."""
        out: Dict[str, dict] = {}
        for role in self.roles:
            try:
                with open(self._hb_file(role)) as f:
                    hb = json.load(f)
            except (OSError, ValueError):
                continue
            snap = hb.get("metrics")
            if isinstance(snap, dict):
                out[role] = snap
        return out

    def child_devices(self) -> Dict[str, dict]:
        """Each role's last heartbeat-reported device (`platform`,
        `kind`, `count` — `utils.devices.device_report`)."""
        out: Dict[str, dict] = {}
        for role in self.roles:
            dev = self._hb_field(role, "device")
            if isinstance(dev, dict):
                out[role] = dev
        return out

    def collect_metrics(self):
        """A fresh registry merging every child's heartbeat snapshot
        with the supervisor's own gauges — rebuilt per call, so a
        /metrics scrape always reflects the latest heartbeats without
        double counting."""
        from ..utils.metrics import MetricsRegistry

        reg = MetricsRegistry()
        for snap in self.child_metrics().values():
            reg.merge(snap)
        for role in self.roles:
            reg.gauge("supervisor_restarts", role=role).set(
                self.restarts[role]
            )
            proc = self.procs.get(role)
            alive = proc is not None and proc.poll() is None
            reg.gauge("supervisor_child_alive", role=role).set(
                1.0 if alive else 0.0
            )
            reg.gauge("supervisor_heartbeat_age_s", role=role).set(
                round(self._heartbeat_age(role), 3)
            )
        return reg

    def health(self) -> Dict[str, Any]:
        roles: Dict[str, Any] = {}
        ok = True
        for role in self.roles:
            proc = self.procs.get(role)
            alive = proc is not None and proc.poll() is None
            age = self._heartbeat_age(role)
            stale = age > self._heartbeat_bar(role)
            # A child limping through storage-fault backoff reports
            # itself `degraded` in its heartbeat — live (no restart
            # wanted) but worth an operator's eye.
            limping = bool(self._hb_field(role, "degraded"))
            roles[role] = {
                "alive": alive, "heartbeat_age_s": round(age, 3),
                "restarts": self.restarts[role],
                "degraded": limping,
                "device": self._hb_field(role, "device"),
            }
            ok = ok and alive and not stale and not limping
        return {"status": "ok" if ok else "degraded", "roles": roles,
                "deli_impl": self.deli_impl,
                "log_format": self.log_format,
                "fused_hop": self.fused_hop,
                "retention": self.retention,
                "device_plane": self.device_plane}

    def _hb_field(self, role: str, key: str) -> Any:
        """One field off `role`'s last heartbeat (None if absent)."""
        try:
            with open(self._hb_file(role)) as f:
                return json.load(f).get(key)
        except (OSError, ValueError):
            return None

    def child_slow_ops(self) -> List[dict]:
        """The farm's merged slow-op spans: every child's last
        heartbeat-reported flight-recorder buffer (wire-trace mode
        only — nothing feeds the recorders otherwise), slowest first.
        The `/traces` body for a supervised farm."""
        spans: List[dict] = []
        for role in self.roles:
            v = self._hb_field(role, "slow_ops")
            if isinstance(v, list):
                spans.extend(s for s in v if isinstance(s, dict))
        spans.sort(key=lambda s: -float(s.get("e2e_ms", 0.0)))
        return spans

    def serve_metrics(self, host: str = "127.0.0.1", port: int = 0):
        """The farm's live ops endpoint: `/metrics` (+ `/slo`) merges
        the children's heartbeat-reported registries per scrape;
        `/healthz` reports per-role liveness; `/traces` merges the
        children's slow-op buffers. Returns the
        `monitor.MetricsServer`."""
        if self._monitor is None:
            from .monitor import MetricsServer

            self._monitor = MetricsServer(
                registry=self.collect_metrics, health=self.health,
                host=host, port=port, traces=self.child_slow_ops,
            ).start()
        return self._monitor

    def stop(self) -> None:
        if self._monitor is not None:
            self._monitor.stop()
            self._monitor = None
        for role, proc in list(self.procs.items()):
            if proc is None:
                continue
            if proc.poll() is None:
                proc.kill()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        self.procs.clear()


# ---------------------------------------------------------------------------
# child entry
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)

    def _take(flag: str, default: Optional[str] = None) -> Optional[str]:
        if flag in args:
            i = args.index(flag)
            val = args[i + 1]
            del args[i:i + 2]
            return val
        return default

    role = _take("--role")
    shared_dir = _take("--dir")
    owner = _take("--owner") or f"{role}-pid{os.getpid()}"
    ttl = float(_take("--ttl", "1.0"))
    batch = int(_take("--batch", "512"))
    impl = _take("--impl") or os.environ.get("FLUID_DELI", "scalar")
    log_format = _take("--log-format")
    ckpt_interval = float(_take("--ckpt-interval", "0.25"))
    ckpt_bytes = int(_take("--ckpt-bytes", str(256 * 1024)))
    ckpt_duty = float(_take("--ckpt-duty", "0.2"))
    partition_s = _take("--partition")
    devices_s = _take("--deli-devices")
    hb_interval_s = _take("--hb-interval")
    summary_ops_s = _take("--summary-ops")
    device_plane_s = _take("--device-plane")
    fold_backend_s = _take("--fold-backend")
    ingress_parts_s = _take("--ingress-partitions")
    ingress_elastic = "--ingress-elastic" in args
    if ingress_elastic:
        args.remove("--ingress-elastic")
    if (role not in ROLES + (ScriptoriumBroadcasterRole.name, "ingress",
                             "retention")
            or shared_dir is None
            or impl not in DELI_IMPLS
            or (log_format is not None and log_format not in LOG_FORMATS)
            or (partition_s is not None and not partition_s.isdigit())
            or (devices_s is not None and not devices_s.isdigit())
            or (ingress_parts_s is not None
                and not ingress_parts_s.isdigit())
            or (summary_ops_s is not None
                and not summary_ops_s.isdigit())
            or (fold_backend_s is not None
                and fold_backend_s not in ("kernel", "overlay"))):
        print(
            "usage: python -m fluidframework_tpu.server.supervisor "
            "--role {deli|scriptorium|scribe|broadcaster|summarizer"
            "|scriptorium_broadcaster|ingress|retention} "
            "--dir D "
            "[--owner O] [--ttl S] [--batch N] [--impl scalar|kernel] "
            "[--log-format json|columnar] [--partition K] "
            "[--deli-devices N] [--device-plane DxM] "
            "[--fold-backend kernel|overlay] "
            "[--hb-interval S] [--summary-ops N] "
            "[--ingress-partitions N] [--ingress-elastic] "
            "[--ckpt-interval S] [--ckpt-bytes N] [--ckpt-duty F]",
            file=sys.stderr,
        )
        raise SystemExit(2)
    serve_role(shared_dir, role, owner, ttl_s=ttl, batch=batch,
               deli_impl=impl, ckpt_interval_s=ckpt_interval,
               ckpt_bytes=ckpt_bytes, log_format=log_format,
               ckpt_duty=ckpt_duty,
               partition=int(partition_s) if partition_s else None,
               deli_devices=int(devices_s) if devices_s else None,
               hb_interval_s=float(hb_interval_s)
               if hb_interval_s else None,
               summary_ops=int(summary_ops_s) if summary_ops_s else None,
               ingress_partitions=int(ingress_parts_s)
               if ingress_parts_s else None,
               ingress_elastic=ingress_elastic,
               device_plane=device_plane_s,
               fold_backend=fold_backend_s)


if __name__ == "__main__":
    main()
