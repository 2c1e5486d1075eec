"""Sharded ordering fabric: a lease-balanced, multi-partition kernel-
deli farm with fenced partition handoff.

The reference scales routerlicious horizontally by splitting the
document space across Kafka partitions with ZooKeeper arbitrating
consumer ownership (SURVEY.md §2.5). This module is that topology over
the repo's own primitives — partitioning as a first-class subsystem
instead of the single-partition pipeline PRs 1–4 grew:

- **Document-space slicing** — `queue.partition_of` (consistent hash)
  maps every doc to one of N partitions; `ShardRouter` is the ingress
  edge (the lambdas-driver document-router role): one raw/sequenced
  topic pair PER partition (``rawdeltas-p{k}`` → ``deltas-p{k}``),
  boxcar records riding whole with their doc.
- **Lease-balanced ownership** — `ShardWorker` (one OS process) sweeps
  the partition leases (`queue.LeaseManager`, the zookeeper role) and
  runs ONE supervised deli role per owned partition
  (`supervisor.partitioned_role_class` over the scalar `DeliRole` or
  the device-batched `deli_kernel.KernelDeliRole`, either log format).
  Workers announce liveness in ``<dir>/workers/<slot>.json``; each
  targets ``ceil(N / alive_workers)`` partitions, so membership change
  IS the rebalance trigger: a joining worker makes peers shed surplus
  partitions (graceful release → immediate takeover), a dead worker's
  stale heartbeat raises the survivors' target and its expired leases
  are swept up.
- **Fenced handoff, exactly-once** — a partition changes hands through
  the PR-1 machinery unchanged: the new owner's lease carries a higher
  fence, its first output append binds that fence on ``deltas-p{k}``
  (a deposed owner's in-flight batch is REJECTED with `FencedError`),
  the loser's fenced checkpoint — per-doc sequencer state in
  `DocumentSequencer.checkpoint()` format, i.e. a `SeqPool` slice when
  the kernel deli wrote it — is restored by `_Role._recover`, and the
  exactly-once ``inOff`` scan replays the checkpoint→durable gap
  silently. A kill or rebalance mid-boxcar never dups or skips a
  sequence number (tests/test_chaos_recovery.py drives this with
  ``--faults kill,lease`` over the kernel+columnar fabric).
- **Supervision + observability** — `ShardFabricSupervisor` runs W
  workers as monitored children through the `ServiceSupervisor`
  machinery (heartbeat staleness, crash restart, fresh owner identity
  per generation); worker heartbeats carry per-partition-labeled
  metrics (``role="deli", partition="3"``) that the supervisor scrape
  merges into one registry.

`tools/shard_run.py` is the CLI; `tools/partition_worker_main.py` is
a thin wrapper over `ShardWorker`. No benchmark cell measures the
fabric's aggregate throughput yet.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from ..utils.devices import device_report, use_compile_cache
from .columnar_log import (
    LOG_FORMATS,
    ColumnarFileTopic,
    default_log_format,
    make_tail_reader,
    make_topic,
)
from .queue import (
    FencedError,
    LeaseManager,
    RangeLeaseStore,
    doc_hash,
    lease_owners,
    lease_table,
    merge_ranges,
    partition_suffix,
    range_for_doc,
    record_partition,
    split_by_partition,
    split_ranges,
)
from .supervisor import (
    DELI_IMPLS,
    EXIT_FENCED,
    BroadcasterRole,
    ScribeRole,
    ScriptoriumBroadcasterRole,
    ScriptoriumRole,
    ServiceSupervisor,
    _topic_path,
    partitioned_role_class,
    resolve_role_class,
    trace_wire_enabled,
)

# Downstream-stage topologies a ShardWorker can run next to each owned
# deli partition ("the farm's other lambdas, partitioned like deli"):
# "fused" collapses scriptorium+broadcaster into the fused
# durable+broadcast consumer (one deltas read per partition), "split"
# runs them separately (the only shape the ELASTIC fabric supports —
# two-leg predecessor absorption is fused-only machinery that doesn't
# exist; `ranged_role_class` rejects the fused base loudly).
DOWNSTREAM_MODES = {
    "fused": (ScriptoriumBroadcasterRole, ScribeRole),
    "split": (ScriptoriumRole, BroadcasterRole, ScribeRole),
}

__all__ = [
    "AutoscalePolicy",
    "DOWNSTREAM_MODES",
    "MergedDeltasReader",
    "ShardFabricSupervisor",
    "ShardRouter",
    "ShardWorker",
    "control_result",
    "partition_lease_name",
    "range_lease_name",
    "ranged_role_class",
    "raw_topic_name",
    "deltas_topic_name",
    "request_topology_change",
    "serve_shard_worker",
    "spread_doc_names",
    "stage_p99s",
]


def raw_topic_name(partition: int) -> str:
    return partition_suffix("rawdeltas", partition)


def deltas_topic_name(partition: int) -> str:
    return partition_suffix("deltas", partition)


def partition_lease_name(partition: int) -> str:
    """The lease key partition ownership is arbitrated under — the
    partitioned deli role's name (`partitioned_role_class`), so the
    lease, heartbeat, checkpoint and fence all share one identity."""
    return partition_suffix("deli", partition)


def range_lease_name(rid: str) -> str:
    """The elastic twin of `partition_lease_name`: range `rid`'s lease
    key, role name and checkpoint key are all ``deli-{rid}`` — one
    identity per range incarnation, like the static fabric's
    ``deli-p{k}``."""
    return f"deli-{rid}"


# ---------------------------------------------------------------------------
# ranged roles (the elastic fabric's per-range deli)
# ---------------------------------------------------------------------------


class _RangedMixin:
    """Hash-range identity + predecessor absorption for a supervised
    role (the deli, and since the front-door PR any single-out-topic
    downstream stage — scriptorium, broadcaster, scribe — consuming a
    per-range topic).

    A ranged role is a partitioned role whose slice of the document
    space is a hash range ``[lo, hi)`` instead of a modulo class, and
    whose range may have PREDECESSORS — the range(s) a live split or
    merge replaced. The exactly-once story rests on one invariant the
    sequencer already has: **per-document independence**. A document's
    outputs are a pure function of that document's input order, and a
    document's inputs live in exactly one topic at a time (its range's
    raw topic, moving predecessor → successor exactly once, when the
    router observes the new epoch). So the successor may absorb each
    predecessor's tail as a unit — restore the predecessor's final
    fenced checkpoint sliced to this range, bind its (strictly higher,
    fabric-scoped) fence on the predecessor's output topic so the
    deposed owner's in-flight batch is REJECTED, scan for the durable
    output prefix, silently replay it, and emit only the missing tail
    — without reconstructing the wall-clock interleaving across
    ranges, because no document's order ever spans two sources in a
    way the parent-first replay doesn't reproduce.

    Outputs produced from predecessor inputs carry ``inSrc`` (the
    predecessor's rid) next to ``inOff``: inOff values live in a
    per-source offset space, and the recovery scans partition by
    source so a successor crash mid-absorption replays exactly."""

    # Filled in by `ranged_role_class`.
    rid: str = ""
    range_lo: int = 0
    range_hi: int = 0
    pred_rids: tuple = ()
    topo_epoch: int = 0
    # The UNSUFFIXED topic names a predecessor's pair derives from
    # (``{pred_in_base}-{prid}`` → ``{pred_out_base}-{prid}``): the
    # deli reads rawdeltas→deltas, a ranged scriptorium deltas→durable,
    # a ranged scribe deltas→(nothing — pred_out_base None skips every
    # output-side step of the absorption).
    pred_in_base: str = "rawdeltas"
    pred_out_base: Optional[str] = "deltas"

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        # Fences must be comparable ACROSS lease keys (a successor
        # binds on predecessor topics), so ranged roles allocate from
        # the fabric-wide counter instead of the per-key default.
        self.leases = LeaseManager(
            self.leases.dir, self.owner, self.leases.ttl_s,
            self.leases.claim_ttl_s, fence_scope="__fabric__",
        )
        self._preds: Dict[str, dict] = {}
        self._hash_cache: Dict[str, int] = {}
        # Cursor-retirement grace: a predecessor continuously quiescent
        # this long is declared fully absorbed and its cursor dropped
        # from new checkpoints (see `_retire_pred`). Two lease TTLs
        # comfortably covers the only writer that could still land a
        # record there — a router whose topology read raced the commit
        # (routers stat-refresh the epoch record per append).
        self.pred_retire_s = max(1.0, 2.0 * self.leases.ttl_s)
        self._m_preds_retired = self.metrics.counter(
            "shard_pred_cursors_retired_total", **self._metric_labels()
        )
        for prid in self.pred_rids:
            self._add_pred(prid, None)

    # ----------------------------------------------------------- slicing

    def _add_pred(self, prid: str, off: Optional[int]) -> None:
        p = self._preds.get(prid)
        if p is not None:
            if off is not None and (p["off"] is None or off < p["off"]):
                p["off"] = off
            return
        self._preds[prid] = {
            "off": off,
            "raw": make_topic(
                _topic_path(self.shared_dir,
                            f"{self.pred_in_base}-{prid}"),
                self.log_format,
            ),
            "deltas": (
                make_topic(
                    _topic_path(self.shared_dir,
                                f"{self.pred_out_base}-{prid}"),
                    self.log_format,
                ) if self.pred_out_base else None
            ),
            "reader": None,
            # Retirement state: "done" preds are fully absorbed (their
            # cursor is dropped from new checkpoints, replaced by a
            # done_preds tombstone); quiet_since tracks continuous
            # quiescence toward that declaration.
            "done": False,
            "quiet_since": None,
        }

    def _in_range(self, doc_id: str) -> bool:
        h = self._hash_cache.get(doc_id)
        if h is None:
            h = self._hash_cache[doc_id] = doc_hash(doc_id)
        return self.range_lo <= h < self.range_hi

    def _mine(self, rec) -> bool:
        return (isinstance(rec, dict) and isinstance(rec.get("doc"), str)
                and self._in_range(rec["doc"]))

    # ------------------------------------------------------- state shape

    def snapshot_state(self):
        return self._ranged_state(super().snapshot_state())

    def checkpoint_state(self):
        # The base role's state as it writes it (the kernel deli's
        # pre-encoded map), inside the same wrapper.
        base = super().checkpoint_state or super().snapshot_state
        return self._ranged_state(base())

    def _ranged_state(self, docs):
        st = {
            "__ranged__": 1,
            "docs": docs,
            "preds": {prid: p["off"] for prid, p in self._preds.items()
                      if p["off"] is not None and not p["done"]},
            "epoch": self.topo_epoch,
        }
        done = sorted(p for p, e in self._preds.items() if e["done"])
        if done:
            # Tombstones, not cursors: a restart must know these were
            # ABSORBED (skip re-absorption entirely), not merely never
            # seen (which would rescan the predecessor from offset 0).
            st["done_preds"] = done
        return st

    def restore_state(self, state):
        if isinstance(state, dict) and state.get("__ranged__"):
            for prid, off in (state.get("preds") or {}).items():
                self._add_pred(prid, int(off))
            for prid in state.get("done_preds") or ():
                self._add_pred(prid, None)
                self._preds[prid]["done"] = True
            super().restore_state(state.get("docs"))
        else:
            super().restore_state(state)

    # --------------------------------------------------------- recovery

    def _recover(self) -> None:
        if self._preds and self.ckpt.load(self.name) is None:
            # First acquisition of this range: seed a checkpoint-zero
            # from the predecessors' final fenced checkpoints BEFORE
            # normal recovery, so a crash mid-absorption restarts from
            # the exact same state (idempotent by construction).
            self._seed_from_preds()
        super()._recover()
        if self._preds:
            self.checkpoint()

    def _pred_ckpt_key(self, prid: str) -> str:
        """Predecessor `prid`'s checkpoint key for THIS role family
        (``{role_base}-{prid}`` — the deli's is `range_lease_name`)."""
        return f"{self.role_base}-{prid}"

    def _seed_from_preds(self) -> None:
        docs: Dict[str, Any] = {}
        cursors: Dict[str, int] = {}
        for prid in self.pred_rids:
            env = self.ckpt.load(self._pred_ckpt_key(prid))
            st = (env or {}).get("state") or {}
            cursors[prid] = int(st.get("offset", 0))
            inner = st.get("state")
            if isinstance(inner, dict) and inner.get("__ranged__"):
                # The predecessor was itself a successor: inherit its
                # unfinished predecessor cursors (min on conflict —
                # reprocessing below another branch's cursor is
                # silenced by resubmission dedup) so no ancestor tail
                # is ever orphaned, however stale a router gets.
                for gprid, goff in (inner.get("preds") or {}).items():
                    if gprid in self.pred_rids:
                        continue  # a direct pred's own offset wins
                    goff = int(goff)
                    cur = cursors.get(gprid)
                    cursors[gprid] = goff if cur is None else \
                        min(cur, goff)
                inner = inner.get("docs") or {}
            for d, s in (inner or {}).items():
                if self._in_range(d):
                    docs[d] = s
        for prid, off in cursors.items():
            self._add_pred(prid, off)
        self.ckpt.save(
            self.name,
            {"offset": 0, "state": {
                "__ranged__": 1, "docs": docs, "preds": cursors,
                "epoch": self.topo_epoch,
            }},
            fence=self.fence, owner=self.owner,
        )

    def _absorb_predecessors(self) -> None:
        """The `_Role._recover` hook: absorb every predecessor's tail
        (fence bind → durable-prefix scan → silent replay → missing
        tail emitted) BEFORE the own-topic gap replay — a doc's own-
        topic records always postdate its predecessor records, so
        parent-first is the per-document input order (ancestors
        before descendants for the same reason). Retired (done) preds
        are skipped outright — their tombstone in the checkpoint says
        every record they ever held is already absorbed.

        Our fence binds on EVERY live predecessor's output topic
        FIRST, before any scan or emission for ANY of them: in a
        merge→split chain a predecessor may itself be a still-LIVE
        successor (the merged range's role mid-drain of an older
        range) — scanning the old range's re-emissions and only later
        deposing the live consumer would let it land more claimable
        records between the scan and the bind, and this role would
        re-emit them too (a durable-leg duplicate). With every pred
        topic bound up front, every producer that could still emit a
        record this role will claim is demonstrably FencedError-
        deposed before the first scan. (Two sibling successors race
        their binds; the lower fence is rejected, exits, and retries
        under a fresh — higher — lease fence, the usual takeover
        dance.)"""
        preds = self._ordered_preds()
        for prid in preds:
            p = self._preds[prid]
            if p["deltas"] is not None:
                self._durable(lambda t=p["deltas"]: t.append_many(
                    [], fence=self.fence, owner=self.owner
                ))
        for prid in preds:
            self._absorb_pred(prid)

    def _pred_done_counts(self, prid: str, start: int) -> Dict[int, int]:
        """Durable outputs per `prid`-space input offset: the
        predecessor's own (untagged) outputs, plus `inSrc`-tagged
        re-emissions in THIS role's topic and every predecessor topic
        (a predecessor that was itself a successor may have died with
        tagged outputs beyond its checkpointed cursor)."""
        done: Dict[int, int] = {}

        def scan(topic, tagged: bool):
            entries, _ = topic.read_entries(0)
            for _i, r in entries:
                if not isinstance(r, dict) or r.get("inOff", -1) < start:
                    continue
                if tagged:
                    if r.get("inSrc") != prid:
                        continue
                elif r.get("inSrc") is not None:
                    continue
                if not self._mine(r):
                    # ALWAYS slice by range, tagged or not: a
                    # predecessor that was itself a successor holds
                    # tagged records for docs OUTSIDE this child's
                    # range (its range was wider), and counting them
                    # would inflate max_done past this range's true
                    # durable prefix — a clipped record of ours would
                    # then never be re-emitted.
                    continue
                done[r["inOff"]] = done.get(r["inOff"], 0) + 1

        scan(self._preds[prid]["deltas"], tagged=False)
        scan(self.out_topic, tagged=True)
        for orid, op in self._preds.items():
            if orid != prid and op["deltas"] is not None:
                scan(op["deltas"], tagged=True)
        return done

    def _absorb_pred(self, prid: str) -> None:
        p = self._preds[prid]
        if p["off"] is None:
            p["off"] = 0  # predecessor died before its first checkpoint
        if p["deltas"] is None:
            # Output-less role (scribe): state+offset commit atomically
            # in the checkpoint, so absorption is just a silent fold of
            # the pred tail — nothing to fence-bind or re-emit.
            gap, next_off = p["raw"].read_entries(p["off"])
            sink: List[dict] = []
            for i, rec in gap:
                if self._mine(rec):
                    self.process(i, rec, sink)
            self.flush_batch(sink)
            p["off"] = next_off
            p["reader"] = None
            return
        # Our fence is already bound on this (and every) pred topic by
        # `_absorb_predecessors`' pre-pass, so the deposed owner's
        # in-flight batch is rejected and the scan below sees the
        # final durable prefix.
        done = self._pred_done_counts(prid, p["off"])
        gap, next_off = p["raw"].read_entries(p["off"])
        mine = [(i, rec) for i, rec in gap if self._mine(rec)]
        out: List[dict] = []
        live = mine
        if done:
            max_done = max(done)
            sink: List[dict] = []
            for i, rec in mine:
                if i <= max_done:
                    self.process(i, rec, sink)  # silent: already durable
            self.flush_batch(sink)
            # Only the LAST durable input can have been clipped
            # mid-append; re-emit exactly its missing suffix.
            tail = [r for r in sink if r.get("inOff") == max_done]
            out.extend(tail[done.get(max_done, 0):])
            live = [(i, rec) for i, rec in mine if i > max_done]
        sink2: List[dict] = []
        for i, rec in live:
            self.process(i, rec, sink2)
        self.flush_batch(sink2)
        out.extend(sink2)
        for r in out:
            r["inSrc"] = prid
        if out:
            self._durable(lambda: self.out_topic.append_many(
                out, fence=self.fence, owner=self.owner
            ))
        p["off"] = next_off
        p["reader"] = None

    # ------------------------------------------------------ steady state

    def step(self, idle_sleep: float = 0.01) -> int:
        """One quantum with the happens-before the range chain needs:
        per document, predecessor-topic records strictly precede
        own-topic records (the router moves a doc exactly once per
        epoch), so the OWN batch is read first but processed LAST —
        buffered while every predecessor tail is drained to
        quiescence. Any pred record of a doc in the buffered batch was
        appended before the doc's own record, hence before the drain
        started, hence is consumed by it; processing pred-then-buffer
        therefore reproduces every doc's true input order no matter
        how the wall clock interleaved the topics."""
        if self.fence is None or not self._ordered_preds():
            # No predecessors left to watch (none ever, or all retired
            # as fully absorbed): the classic single-topic quantum —
            # retirement also removes the per-step pred tail polls.
            return super().step(idle_sleep)
        self._renew_or_die()
        if self._reader is None or self._reader.next_line != self.offset:
            self._reader = make_tail_reader(self.in_topic, self.offset)
        # 1. READ (don't process) one own-topic batch. The batch-start
        # byte anchor (`_Role._in_pos`) is captured HERE and restored
        # after the pred drains below clobber it to None.
        in_pos0 = getattr(self._reader, "_pos", None)
        if self.ingest_batches and hasattr(self._reader, "poll_batches"):
            units = self._reader.poll_batches(self.batch)
        else:
            units = [("rec", i, rec)
                     for i, rec in self._reader.poll(self.batch)]
        # 2. Drain every predecessor past the read point.
        pred_moved = self._pump_preds()
        # 3. Process the buffered own batch.
        self._in_pos = in_pos0
        out: List[dict] = []
        moved = 0
        for unit in units:
            if unit[0] == "batch":
                moved += unit[2].n
                self.process_batch(unit[1], unit[2], out)
            else:
                moved += 1
                self.process(unit[1], unit[2], out)
        next_off = self._reader.next_line
        if not moved:
            if next_off != self.offset:
                self.offset = next_off
                self._ckpt_dirty = True
            try:
                self.maybe_checkpoint()
            except FencedError as exc:
                self._fenced_exit(exc)
            self.heartbeat()
            if not pred_moved:
                self._idle_wait(idle_sleep)
            return pred_moved
        self.flush_batch(out)
        try:
            if self.out_topic is not None:
                self._ckpt_pending_bytes += self._durable(
                    lambda: self.out_topic.append_many(
                        out, fence=self.fence, owner=self.owner
                    )
                )
            self.offset = next_off
            self._ckpt_dirty = True
            self.maybe_checkpoint()
        except FencedError as exc:
            self._fenced_exit(exc)
        self._m_records.inc(moved)
        self.heartbeat()
        return moved + pred_moved

    def _fenced_exit(self, exc: FencedError) -> None:
        self._m_fenced.inc()
        self.heartbeat(force=True)
        print(f"FENCED {self.name} {self.owner}: {exc}", flush=True)
        raise SystemExit(EXIT_FENCED)

    def _ordered_preds(self) -> List[str]:
        """LIVE (non-retired) predecessors oldest-first (birth epoch
        off the rid tag): in a chain — grandparent inherited from a
        split-of-a-split — the older range's records precede the
        newer's per doc, so drains run ancestors before descendants."""
        def birth(rid: str) -> int:
            head, sep, tail = rid.rpartition("-e")
            return int(tail) if sep and tail.isdigit() else 1

        return sorted(
            (p for p, e in self._preds.items() if not e["done"]),
            key=birth,
        )

    def _retire_pred(self, prid: str) -> None:
        """Declare `prid` fully absorbed and drop its cursor from new
        checkpoints (ROADMAP item-2 follow-up). Two facts make this
        safe: (1) the topology history marks every pred DEAD by
        construction — this role only exists because the epoch commit
        replaced them, and a committed range never returns (a merge
        recreating its bounds is a fresh incarnation with a fresh
        rid); (2) the pred's raw topic has been continuously quiescent
        for `pred_retire_s` (two lease TTLs past the last record),
        which outlasts the only straggler writer possible — a router
        whose per-append topology stat raced the commit. From here the
        checkpoint carries a tombstone instead of a cursor, restarts
        skip re-absorption, and the steady-state pump stops polling
        the dead tail."""
        p = self._preds[prid]
        p["done"] = True
        p["reader"] = None
        self._ckpt_dirty = True
        self._m_preds_retired.inc()

    def _pump_preds(self) -> int:
        """Drain every predecessor tail to QUIESCENCE: full passes
        over the preds (oldest epoch first) until one pass delivers
        nothing — every pred record appended before that pass began is
        then consumed, which is what the buffered-own-batch ordering
        rests on. Lease renewal stays live inside the loop (a huge
        absorb must not let the lease lapse)."""
        total = 0
        while True:
            pass_moved = 0
            for prid in self._ordered_preds():
                pass_moved += self._pump_one_pred(prid)
            total += pass_moved
            if pass_moved == 0:
                return total

    def _pump_one_pred(self, prid: str) -> int:
        p = self._preds[prid]
        if p["done"] or p["off"] is None:
            return 0  # retired / absorbed at recovery before any pump
        taken = 0
        while True:
            reader = p["reader"]
            if reader is None or reader.next_line != p["off"]:
                reader = p["reader"] = make_tail_reader(
                    p["raw"], p["off"]
                )
            entries = reader.poll(self.batch)
            if not entries:
                if reader.next_line != p["off"]:
                    p["off"] = reader.next_line
                    self._ckpt_dirty = True
                    p["quiet_since"] = None  # junk lines still count
                elif taken == 0:
                    # A fully quiet pass: start (or continue) the
                    # retirement clock; past the grace, the cursor is
                    # dropped from future checkpoints.
                    now = time.time()
                    if p["quiet_since"] is None:
                        p["quiet_since"] = now
                    elif now - p["quiet_since"] >= self.pred_retire_s:
                        self._retire_pred(prid)
                else:
                    p["quiet_since"] = None
                return taken
            p["quiet_since"] = None
            # Pred records have no own-topic byte anchor: a manifest
            # emitted from this drain carries byteOff None (readers
            # fall back to the unbounded backward scan).
            self._in_pos = None
            out: List[Any] = []
            src_emit = isinstance(self.out_topic, ColumnarFileTopic)
            if src_emit:
                # Columnar out topic: the frame-level FLAG_SRC stamp
                # (`append_many(src=prid)`) carries the inSrc tag, so
                # a columnar-emitting role (the kernel deli) keeps its
                # `encode_columns` fast path through a pred drain —
                # elastic splits no longer force the `_dict_emit`
                # fallback (ROADMAP item-1 follow-up b). Dict-path
                # strays in the same flush pick the tag up at decode
                # identically.
                for i, rec in entries:
                    if self._mine(rec):
                        self.process(i, rec, out)
                self.flush_batch(out)
            else:
                # JSON out topic: per-record dict tagging (there is no
                # frame to carry the tag).
                self._dict_emit = True
                try:
                    for i, rec in entries:
                        if self._mine(rec):
                            self.process(i, rec, out)
                    self.flush_batch(out)
                finally:
                    self._dict_emit = False
                for r in out:
                    r["inSrc"] = prid
            try:
                if out and self.out_topic is not None:
                    if src_emit:
                        self._ckpt_pending_bytes += self._durable(
                            lambda: self.out_topic.append_many(
                                out, fence=self.fence,
                                owner=self.owner, src=prid,
                            )
                        )
                    else:
                        self._ckpt_pending_bytes += self._durable(
                            lambda: self.out_topic.append_many(
                                out, fence=self.fence, owner=self.owner
                            )
                        )
                p["off"] = reader.next_line
                self._ckpt_dirty = True
                self.maybe_checkpoint()
            except FencedError as exc:
                self._fenced_exit(exc)
            taken += len(entries)
            self._renew_or_die()
            self.heartbeat()


def ranged_role_class(base: type, entry: dict, epoch: int) -> type:
    """The elastic form of `partitioned_role_class`: same role code,
    hash-range identity. Lease key, heartbeat file, checkpoint key and
    topic pair all derive from the base role's names + the range id
    (the deli's ``deli-{rid}`` over ``rawdeltas-{rid}`` →
    ``deltas-{rid}``; a ranged scriptorium's ``scriptorium-{rid}``
    over ``deltas-{rid}`` → ``durable-{rid}``), the role only touches
    documents hashing into ``[lo, hi)``, and the entry's `preds` name
    the range(s) it absorbs (split parent / merge parents)."""
    if getattr(base, "bc_topic_name", None):
        raise ValueError(
            f"{base.__name__} has a second output leg "
            f"({base.bc_topic_name!r}): two-leg predecessor absorption "
            f"is not implemented — run the split "
            f"scriptorium+broadcaster pair on the elastic fabric"
        )
    rid = entry["rid"]
    return type(
        f"{base.__name__}Range", (_RangedMixin, base), {
            "name": f"{base.name}-{rid}",
            "in_topic_name": f"{base.in_topic_name}-{rid}",
            "out_topic_name": (f"{base.out_topic_name}-{rid}"
                               if base.out_topic_name else None),
            "partition": rid,  # metric label: {role: base, partition: rid}
            "role_base": base.name,
            "rid": rid,
            "range_lo": int(entry["lo"]),
            "range_hi": int(entry["hi"]),
            "pred_rids": tuple(entry.get("preds") or ()),
            "topo_epoch": int(epoch),
            "pred_in_base": base.in_topic_name,
            "pred_out_base": base.out_topic_name,
        },
    )


# ---------------------------------------------------------------------------
# topology-change control channel
# ---------------------------------------------------------------------------


def _control_dir(shared_dir: str) -> str:
    return os.path.join(shared_dir, "control")


def request_topology_change(shared_dir: str, cmd: dict) -> str:
    """Stage a split/merge command for the worker that owns the target
    range (the fabric's admin channel — the supervisor's
    `request_split`/`request_merge` and the chaos harness both write
    here). Returns the command id; `control_result` reports completion
    (the executing worker writes a ``.done`` marker with the new
    epoch)."""
    d = _control_dir(shared_dir)
    os.makedirs(d, exist_ok=True)
    cid = f"cmd-{time.time_ns():020d}-{os.getpid()}"
    tmp = os.path.join(d, f".{cid}.tmp")
    with open(tmp, "w") as f:
        json.dump(cmd, f)
    os.replace(tmp, os.path.join(d, f"{cid}.json"))
    return cid


def control_result(shared_dir: str, cmd_id: str) -> Optional[dict]:
    try:
        with open(os.path.join(_control_dir(shared_dir),
                               f"{cmd_id}.done.json")) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def spread_doc_names(n_docs: int, n_partitions: int,
                     prefix: str = "doc") -> List[str]:
    """`n_docs` deterministic doc names that cover the partitions as
    evenly as the hash allows (scan names, round-robin the partition
    quota — the workload builders' answer to small-N hash clumping;
    real traffic gets the same balance from volume)."""
    from .queue import partition_of

    if n_partitions <= 1:
        return [f"{prefix}{i}" for i in range(n_docs)]
    per = {p: 0 for p in range(n_partitions)}
    quota = math.ceil(n_docs / n_partitions)
    out: List[str] = []
    i = 0
    while len(out) < n_docs and i < 10_000 * max(1, n_docs):
        name = f"{prefix}{i}"
        i += 1
        p = partition_of(name, n_partitions)
        if per[p] < quota:
            per[p] += 1
            out.append(name)
    return out


# ---------------------------------------------------------------------------
# ingress router
# ---------------------------------------------------------------------------


class ShardRouter:
    """The fabric's ingress edge: appends each raw record to its doc's
    partition topic (the document-router role). Boxcar-aware — a wire
    boxcar names one doc and rides whole, so its atomicity survives
    routing. Appends are grouped per partition per call (one fenced
    frame/lock per partition, not per record), and arrival order is
    preserved WITHIN each partition — the only order the per-document
    sequencing contract needs, since a doc lives in exactly one
    partition.

    `elastic=True` routes by ``(epoch, hash(doc))`` instead of
    ``doc % N``: the live hash-range topology (`queue.RangeLeaseStore`)
    is re-read whenever its record changes on disk, so a split/merge
    redirects NEW records to the child ranges within one append — and
    any record a momentarily-stale router still lands on a retired
    range's topic is absorbed by the successor's predecessor tail
    (`_RangedMixin`), so staleness costs latency, never order."""

    def __init__(self, shared_dir: str, n_partitions: int,
                 log_format: Optional[str] = None,
                 elastic: bool = False):
        if n_partitions < 1:
            raise ValueError(f"n_partitions must be >= 1: {n_partitions}")
        self.shared_dir = shared_dir
        self.n_partitions = n_partitions
        self.log_format = default_log_format(log_format)
        self.elastic = bool(elastic)
        if self.elastic:
            self.store = RangeLeaseStore(shared_dir, "__router__")
            self.topology = self.store.ensure_topology(n_partitions)
            self._topo_sig: Optional[tuple] = None
            self._topic_cache: Dict[str, Any] = {}
            self.topics: List[Any] = []  # static-mode surface only
        else:
            self.topics = [
                make_topic(_topic_path(shared_dir, raw_topic_name(p)),
                           self.log_format)
                for p in range(n_partitions)
            ]

    # ------------------------------------------------- topology refresh

    def _refresh(self) -> None:
        """Adopt a newer topology epoch if the record changed on disk
        (one stat per call — the epoch flip is visible within one
        append, no polling thread)."""
        if not self.elastic:
            return
        try:
            st = os.stat(self.store.topology_path)
        except OSError:
            return
        sig = (st.st_mtime_ns, st.st_size)
        if sig != self._topo_sig:
            topo = self.store.read_topology()
            if topo is not None:
                self.topology = topo
            self._topo_sig = sig

    def _topic(self, name: str):
        t = self._topic_cache.get(name)
        if t is None:
            t = self._topic_cache[name] = make_topic(
                _topic_path(self.shared_dir, name), self.log_format
            )
        return t

    # ----------------------------------------------------------- routing

    def partition(self, rec: Any) -> int:
        return record_partition(rec, self.n_partitions)

    def split(self, records: List[Any]) -> Dict[int, List[Any]]:
        """Records grouped by partition, input order preserved within
        each group (pure routing — no I/O)."""
        return split_by_partition(records, self.n_partitions)

    def split_elastic(self, records: List[Any]) -> Dict[str, List[Any]]:
        """Records grouped by live range id under the CURRENT epoch
        (doc-less junk pins to the first range — any single consistent
        home keeps offsets deterministic)."""
        out: Dict[str, List[Any]] = {}
        for rec in records:
            doc = rec.get("doc") if isinstance(rec, dict) else None
            if isinstance(doc, str):
                entry = range_for_doc(self.topology, doc)
            else:
                entry = self.topology["ranges"][0]
            out.setdefault(entry["rid"], []).append(rec)
        return out

    def append(self, records: List[Any],
               fence: Optional[int] = None,
               owner: Optional[str] = None) -> Dict[Any, int]:
        """Route + append one ingress batch; returns records appended
        per partition (keyed by index, or by range id when elastic).
        `fence`/`owner` gate every leg's append (the supervised
        ingress role routes under its own fence, so a deposed front
        door's in-flight batch is rejected on the topic).

        Elastic appends are epoch-rechecked AFTER landing: if the
        topology moved while this batch was in flight (a router stalled
        between its refresh and its appends can outlive even the
        pred-cursor retirement grace — the one hole pure tail-draining
        can't cover), the batch is re-routed under the new epoch. The
        duplicate delivery is safe by construction: if a successor
        still drains the old topic, resubmission dedup silences the
        second copy (per-client clientSeq); if the old range's cursor
        was already retired, the first copy is simply never read and
        the re-route is the only live one. Bounded: one re-route per
        epoch change observed, and epochs only advance."""
        counts: Dict[Any, int] = {}
        if self.elastic:
            self._refresh()
            for _ in range(64):  # paranoia bound; epochs move rarely
                epoch = self.topology["epoch"]
                by_rid = self.split_elastic(records)
                rid_to_raw = {e["rid"]: e["raw"]
                              for e in self.topology["ranges"]}
                counts = {}
                for rid, recs in by_rid.items():
                    self._topic(rid_to_raw[rid]).append_many(
                        recs, fence=fence, owner=owner
                    )
                    counts[rid] = len(recs)
                self._refresh()
                if self.topology["epoch"] == epoch:
                    return counts
            return counts
        for p, recs in self.split(records).items():
            self.topics[p].append_many(recs, fence=fence, owner=owner)
            counts[p] = len(recs)
        return counts

    # ------------------------------------------------------ read surface

    def stage_topic_names(self, base: str = "deltas") -> List[str]:
        """Every topic name stage `base` has EVER written across this
        fabric — live ranges plus retired ones (topology history), so
        records written under epoch E stay readable after E+1. The
        per-partition downstream stages share the naming rule
        (``durable-p{k}`` / ``broadcast-{rid}`` ...), so one helper
        serves every stage's merged read surface."""
        if self.elastic:
            self._refresh()
            return [f"{base}-{rid}"
                    for rid in self.topology.get("history", [])]
        return [partition_suffix(base, p)
                for p in range(self.n_partitions)]

    def deltas_topic_names(self) -> List[str]:
        return self.stage_topic_names("deltas")

    def deltas_topics(self) -> List[Any]:
        """Every partition's sequenced-output topic (the merged read
        surface convergence checks and catch-up readers use)."""
        if self.elastic:
            return [self._topic(n) for n in self.deltas_topic_names()]
        return [
            make_topic(_topic_path(self.shared_dir, deltas_topic_name(p)),
                       self.log_format)
            for p in range(self.n_partitions)
        ]

    def live_raw_topics(self) -> List[Any]:
        """The LIVE ranges' ingress topics (fault-injection surface)."""
        if self.elastic:
            self._refresh()
            return [self._topic(e["raw"])
                    for e in self.topology["ranges"]]
        return list(self.topics)

    def merged_reader(self, base: str = "deltas") -> "MergedDeltasReader":
        return MergedDeltasReader(self, base=base)


class MergedDeltasReader:
    """The merged catch-up read: one cursor PER RANGE TOPIC across the
    whole topology history, polled incrementally. A split or merge
    adds cursors (new ranges) without invalidating old ones, so a
    consumer riding this surface sees every record exactly once no
    matter how often N changes mid-stream — re-reading every file from
    zero per poll would be O(file²) at bench scale. Retired ranges'
    topics quiesce once their successor binds, so each costs one
    empty incremental poll per pass; history grows only by
    operator-initiated epochs, which bounds the per-poll fan-out.

    `base` picks the stage surface: "deltas" (default) reads the
    sequenced stream; "durable"/"broadcast" read the per-partition
    downstream legs the same elastic way (the catch-up surface a
    split hands a range's downstream legs over on)."""

    def __init__(self, router: ShardRouter, base: str = "deltas"):
        self.router = router
        self.base = base
        self._readers: Dict[str, Any] = {}

    def poll(self, max_count_per_range: Optional[int] = None
             ) -> List[Any]:
        out: List[Any] = []
        for name in self.router.stage_topic_names(self.base):
            reader = self._readers.get(name)
            if reader is None:
                reader = self._readers[name] = make_tail_reader(
                    self.router._topic(name) if self.router.elastic
                    else make_topic(
                        _topic_path(self.router.shared_dir, name),
                        self.router.log_format,
                    ),
                    0,
                )
            out.extend(v for _i, v in reader.poll(max_count_per_range))
        return out


# ---------------------------------------------------------------------------
# the worker
# ---------------------------------------------------------------------------


class ShardWorker:
    """One fabric node: sweeps partition leases toward its fair share
    and pumps a supervised deli role per owned partition.

    Balance is emergent, not orchestrated: each worker computes
    ``target = ceil(n_partitions / alive_workers)`` from the worker
    heartbeat directory and (a) gracefully RELEASES surplus partitions
    — final fenced checkpoint, then lease release with expires=0 so the
    successor takes over without waiting out the TTL — and (b) acquires
    free/expired partitions up to target. Ownership changes always run
    through the fence: the successor's recovery (`_Role._recover`)
    binds its higher fence on the output topic FIRST, so anything the
    deposed owner still has in flight is rejected, then restores the
    fenced checkpoint and closes the append-vs-checkpoint window with
    the exactly-once ``inOff`` scan."""

    def __init__(self, shared_dir: str, slot: str,
                 owner: Optional[str] = None, n_partitions: int = 4,
                 deli_impl: Optional[str] = None,
                 log_format: Optional[str] = None, ttl_s: float = 1.0,
                 batch: int = 512, max_partitions: Optional[int] = None,
                 ckpt_interval_s: float = 0.25,
                 ckpt_bytes: int = 256 * 1024, ckpt_duty: float = 0.2,
                 worker_ttl_s: Optional[float] = None,
                 deli_devices: Optional[int] = None,
                 elastic: bool = False, summarize: bool = False,
                 summary_ops: Optional[int] = None,
                 downstream: Optional[str] = None,
                 device_plane: Optional[str] = None,
                 plane_column: Optional[int] = None):
        """`elastic=True` swaps fixed modulo-N partitions for the
        hash-range topology (`queue.RangeLeaseStore`): the worker
        sweeps RANGE leases toward its fair share of the LIVE range
        set (which changes epoch to epoch), executes staged
        split/merge commands for ranges it owns, and releases any
        role whose range a committed topology change retired.
        `n_partitions` then only seeds the bootstrap topology.

        `summarize=True` runs a per-partition summary service next to
        each owned deli (`summarizer.SummarizerRole` under
        `partitioned_role_class`: ``deltas-p{k}`` → ``summaries-p{k}``
        + content-addressed blobs in the shared store), following deli
        ownership for locality but fenced under its own
        ``summarizer-p{k}`` lease. On the ELASTIC fabric the
        summarizer is ranged like the deli (`ranged_role_class` over
        the same topology entry, ``deltas-{rid}`` →
        ``summaries-{rid}``): its per-doc fold state is a flat map, so
        a split/merge successor ABSORBS its predecessors' fold dicts
        sliced to its hash range through the generic predecessor
        machinery — summaries ride every topology
        (`SummaryIndex(topics=router.stage_topic_names("summaries"))`
        is the merged manifest read surface).

        `downstream` ("fused" | "split") promotes the farm's OTHER
        lambdas to per-partition supervised consumers riding deli
        ownership: each owned partition gets its own
        ``deltas-p{k}``-consuming scriptorium+broadcaster (fused or
        split) and scribe under their own fenced leases — the
        routerlicious every-stage-partitioned topology. On the
        ELASTIC fabric the stages are ranged like the deli
        (`ranged_role_class` over the same topology entry): a split
        hands each range's durable/broadcast legs to the successors
        through the same predecessor-absorption machinery,
        exactly-once. Elastic + "fused" is a loud config error (the
        fused role's two output legs have no two-leg absorption)."""
        self.summarize = bool(summarize)
        self.summary_ops = summary_ops
        if downstream is not None and downstream not in DOWNSTREAM_MODES:
            raise ValueError(
                f"downstream {downstream!r} not in "
                f"{sorted(DOWNSTREAM_MODES)}"
            )
        if downstream == "fused" and elastic:
            raise ValueError(
                "downstream='fused' is static-partition only: the "
                "fused consumer's two output legs have no two-leg "
                "predecessor absorption — use downstream='split' on "
                "the elastic fabric"
            )
        self.downstream = downstream
        self.shared_dir = shared_dir
        self.slot = slot
        self.owner = owner or slot
        self.n_partitions = int(n_partitions)
        self.elastic = bool(elastic)
        self.deli_impl = deli_impl or os.environ.get("FLUID_DELI", "scalar")
        if self.deli_impl not in DELI_IMPLS:
            raise ValueError(
                f"deli_impl {self.deli_impl!r} not in {DELI_IMPLS}"
            )
        # Multi-device deli per partition: every owned partition's
        # kernel role shards its pool over the same process-wide
        # N-device mesh — "one partition = one worker process" and
        # "one doc slab = one device" compose, they don't compete.
        self.deli_devices = (
            int(deli_devices) if deli_devices is not None else None
        )
        if self.deli_devices is not None and self.deli_devices > 1 \
                and self.deli_impl != "kernel":
            raise ValueError(
                f"deli_devices={self.deli_devices} needs "
                f"deli_impl='kernel'; got {self.deli_impl!r}"
            )
        # 2-D device plane: one partition = one worker = one mesh
        # slice — this worker's delis order documents on model column
        # `plane_column` (default: a stable hash of the worker slot)
        # while its summarizers' folds span the whole plane.
        self.device_plane = device_plane
        if device_plane is not None:
            if self.deli_impl != "kernel":
                raise ValueError(
                    f"device_plane={device_plane!r} needs "
                    f"deli_impl='kernel'; got {self.deli_impl!r}"
                )
            if self.deli_devices is not None and self.deli_devices > 1:
                raise ValueError(
                    "deli_devices and device_plane are exclusive on "
                    "a worker: the plane's docs axis IS the deli's "
                    "device slice"
                )
        self.plane_column = plane_column
        self.log_format = default_log_format(log_format)
        self.ttl_s = ttl_s
        self.batch = batch
        self.max_partitions = max_partitions
        self.ckpt_interval_s = ckpt_interval_s
        self.ckpt_bytes = ckpt_bytes
        self.ckpt_duty = ckpt_duty
        # A worker is presumed dead once its heartbeat is older than
        # this (decoupled from the per-partition lease TTL: membership
        # flaps should be rarer than lease renewals).
        self.worker_ttl_s = worker_ttl_s or 3.0 * ttl_s
        self.workers_dir = os.path.join(shared_dir, "workers")
        self.leases_dir = os.path.join(shared_dir, "leases")
        os.makedirs(self.workers_dir, exist_ok=True)
        # Read-only ownership probe (owner_of takes no claim).
        self._probe = LeaseManager(self.leases_dir, self.owner, ttl_s)
        if self.elastic:
            self.store: Optional[RangeLeaseStore] = RangeLeaseStore(
                shared_dir, self.owner, ttl_s
            )
            self.topology: Optional[dict] = self.store.ensure_topology(
                self.n_partitions
            )
        else:
            self.store = None
            self.topology = None
        # Role keys: partition ints (static) or range ids (elastic).
        self.roles: Dict[Any, Any] = {}
        # Per-partition summary services (summarize=True): mirror deli
        # ownership, own fenced lease per partition.
        self.summ_roles: Dict[Any, Any] = {}
        # Per-partition downstream stages (downstream=): key -> list of
        # role instances, mirroring deli ownership like summarizers.
        self.down_roles: Dict[Any, List[Any]] = {}
        self.events: List[str] = []
        self._hb_t = 0.0
        self._sweep_t = 0.0
        from ..utils.metrics import get_registry

        self.metrics = get_registry()
        self._m_owned = self.metrics.gauge(
            "shard_partitions_owned", worker=self.slot
        )
        self._m_handoffs = self.metrics.counter(
            "shard_partition_releases_total", worker=self.slot
        )
        self._m_drops = self.metrics.counter(
            "shard_partition_deposed_total", worker=self.slot
        )

    # -------------------------------------------------------- membership

    def _event(self, text: str) -> None:
        self.events.append(text)

    def _hb_path(self) -> str:
        return os.path.join(self.workers_dir, f"{self.slot}.json")

    def heartbeat(self) -> None:
        """Worker-level liveness + the fabric's metrics channel: ONE
        snapshot of this process's registry (per-partition labels keep
        every owned partition's series distinct), so the supervisor
        scrape merges one file per worker with no double counting.
        `degraded` lists partitions currently inside a storage-fault
        retry budget (ENOSPC/stall backoff) — limping, not dead — for
        `ShardFabricSupervisor.health()` to surface. In wire-trace
        mode the worker's slow-op flight-recorder buffer rides along
        too (the per-partition broadcaster stages — fused or split —
        run in THIS process and feed the process recorder, each span
        tagged with its partition), so `/traces` is populated on the
        elastic fabric exactly like the classic farm."""
        tmp = self._hb_path() + f".tmp.{os.getpid()}"
        hb = {
            "t": time.time(), "slot": self.slot, "owner": self.owner,
            "pid": os.getpid(),
            "partitions": sorted(
                p for p, r in self.roles.items()
                if r.fence is not None
            ),
            "degraded": sorted(
                p for p, r in self.roles.items()
                if getattr(r, "degraded", False)
            ),
            "epoch": (self.topology or {}).get("epoch"),
            "device": device_report(),
            "metrics": self.metrics.snapshot(),
        }
        if trace_wire_enabled():
            from ..utils.metrics import get_flight_recorder

            spans = get_flight_recorder().snapshot()
            if spans:
                hb["slow_ops"] = spans
        with open(tmp, "w") as f:
            # One string from the C encoder: `json.dump` streams through
            # the pure-Python one, several times slower per instrument.
            f.write(json.dumps(hb))
        os.replace(tmp, self._hb_path())
        self._hb_t = time.time()

    def alive_workers(self, now: Optional[float] = None) -> int:
        """Workers with a fresh heartbeat (self always counts)."""
        now = time.time() if now is None else now
        alive = 0
        saw_self = False
        try:
            names = os.listdir(self.workers_dir)
        except OSError:
            names = []
        for fn in names:
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.workers_dir, fn)) as f:
                    hb = json.load(f)
            except (OSError, ValueError):
                continue
            if now - float(hb.get("t", 0)) <= self.worker_ttl_s:
                alive += 1
                if fn == f"{self.slot}.json":
                    saw_self = True
        return alive if saw_self else alive + 1

    def _keys(self) -> List[Any]:
        """The current partition key space: fixed indices, or the live
        range ids of the topology epoch this worker last read."""
        if not self.elastic:
            return list(range(self.n_partitions))
        return [e["rid"] for e in self.topology["ranges"]]

    def _lease_name(self, key: Any) -> str:
        return (range_lease_name(key) if self.elastic
                else partition_lease_name(key))

    def _entry(self, rid: str) -> dict:
        return next(e for e in self.topology["ranges"]
                    if e["rid"] == rid)

    def target_partitions(self) -> int:
        """This worker's fair share of the partition space (the LIVE
        range count when elastic — a split raises everyone's target,
        a merge lowers it: capacity follows the topology)."""
        t = math.ceil(len(self._keys()) / max(1, self.alive_workers()))
        if self.max_partitions is not None:
            t = min(t, self.max_partitions)
        return t

    # ------------------------------------------------------- role plumbing

    def _make_role(self, key: Any):
        base = resolve_role_class("deli", self.deli_impl)
        if self.elastic:
            cls = ranged_role_class(
                base, self._entry(key), self.topology["epoch"]
            )
        else:
            cls = partitioned_role_class(base, key)
        kw = {}
        if self.deli_devices is not None and self.deli_devices > 1:
            kw["deli_devices"] = self.deli_devices
        if self.device_plane is not None:
            # One worker = one mesh slice: every deli this worker
            # runs orders on the SAME model column of the plane
            # (explicit column, or a stable hash of the worker slot).
            from ..parallel.device_plane import plane_column_of, \
                resolve_plane

            kw["device_plane"] = self.device_plane
            kw["plane_column"] = (
                self.plane_column if self.plane_column is not None
                else plane_column_of(
                    self.slot,
                    resolve_plane(self.device_plane).model,
                )
            )
        role = cls(
            self.shared_dir, self.owner, ttl_s=self.ttl_s,
            batch=self.batch, ckpt_interval_s=self.ckpt_interval_s,
            ckpt_bytes=self.ckpt_bytes, log_format=self.log_format,
            ckpt_duty=self.ckpt_duty, **kw,
        )
        # The WORKER heartbeat (whole-registry snapshot, throttled) is
        # the fabric's liveness/metrics channel; per-partition role
        # heartbeats are debugging surface only, so throttle their
        # per-step registry-snapshot writes to the same cadence.
        role.hb_interval_s = self.ttl_s / 3
        return role

    def _make_summ_role(self, key: Any):
        from .summarizer import SummarizerRole

        if self.elastic:
            # Ranged summarizer: same topology entry as the deli, so a
            # split/merge successor seeds from the predecessors' final
            # fold checkpoints sliced to its range and re-emits only
            # the clipped manifest tail (the `_RangedMixin` contract;
            # the old "static-partition only" ValueError is gone).
            cls = ranged_role_class(
                SummarizerRole, self._entry(key), self.topology["epoch"]
            )
        else:
            cls = partitioned_role_class(SummarizerRole, key)
        kw = {}
        if self.summary_ops is not None:
            kw["summary_ops"] = self.summary_ops
        if self.device_plane is not None:
            # Summarizer folds span the WHOLE plane (the sequencers
            # tile it column-wise) — both tenants, one chip pool.
            kw["device_plane"] = self.device_plane
        role = cls(
            self.shared_dir, self.owner, ttl_s=self.ttl_s,
            batch=self.batch, ckpt_interval_s=self.ckpt_interval_s,
            ckpt_bytes=self.ckpt_bytes, log_format=self.log_format,
            ckpt_duty=self.ckpt_duty, **kw,
        )
        role.hb_interval_s = self.ttl_s / 3
        return role

    def _sweep_summarizers(self) -> None:
        """Summarizers follow deli ownership (the partition's deltas
        live here anyway); their own lease/fence keeps a deposed
        worker's late manifest append rejected like any other role."""
        for k in list(self.summ_roles):
            if k not in self.roles:
                self._release_summ(k, "deli released")
        for k in self.roles:
            if k not in self.summ_roles:
                self.summ_roles[k] = self._make_summ_role(k)

    def _release_summ(self, key: Any, why: str) -> None:
        role = self.summ_roles.pop(key, None)
        if role is None:
            return
        role.close_doorbell()
        if role.fence is not None:
            try:
                role.checkpoint()
            except (FencedError, OSError):
                pass
            role.leases.release(role.name)
        self._event(f"released summarizer {self._kname(key)} ({why})")

    def _make_down_roles(self, key: Any) -> List[Any]:
        roles = []
        for base in DOWNSTREAM_MODES[self.downstream]:
            if self.elastic:
                cls = ranged_role_class(
                    base, self._entry(key), self.topology["epoch"]
                )
            else:
                cls = partitioned_role_class(base, key)
            role = cls(
                self.shared_dir, self.owner, ttl_s=self.ttl_s,
                batch=self.batch, ckpt_interval_s=self.ckpt_interval_s,
                ckpt_bytes=self.ckpt_bytes, log_format=self.log_format,
                ckpt_duty=self.ckpt_duty,
            )
            role.hb_interval_s = self.ttl_s / 3
            roles.append(role)
        return roles

    def _sweep_downstream(self) -> None:
        """Downstream stages follow deli ownership (the partition's
        deltas are written here anyway); each stage holds its OWN
        fenced lease (``scriptorium-p{k}`` / ``broadcaster-{rid}`` ...)
        so a deposed worker's late downstream append is rejected like
        any other role's."""
        for k in list(self.down_roles):
            if k not in self.roles:
                self._release_down(k, "deli released")
        for k in self.roles:
            if k not in self.down_roles:
                self.down_roles[k] = self._make_down_roles(k)

    def _release_down(self, key: Any, why: str) -> None:
        roles = self.down_roles.pop(key, None)
        if not roles:
            return
        for role in roles:
            role.close_doorbell()
            if role.fence is not None:
                try:
                    role.checkpoint()
                except (FencedError, OSError):
                    pass
                role.leases.release(role.name)
        self._event(f"released downstream {self._kname(key)} ({why})")

    def _release(self, key: Any, why: str) -> None:
        """Graceful fenced handoff: final checkpoint under our (still
        valid) fence, then release with expires=0 — the successor's
        next sweep takes over immediately, restores this checkpoint,
        and its recovery scan replays any durable gap silently."""
        role = self.roles.pop(key, None)
        if role is None:
            return
        role.close_doorbell()
        if role.fence is not None:
            try:
                role.checkpoint()
            except (FencedError, OSError):
                pass  # a successor already holds the fence: its state wins
            role.leases.release(role.name)
            # Count only REAL handoffs: dropping a role instance that
            # never acquired its lease released nothing.
            self._m_handoffs.inc()
        self._event(f"released {self._kname(key)} ({why})")

    @staticmethod
    def _kname(key: Any) -> str:
        return f"p{key}" if isinstance(key, int) else str(key)

    def sweep(self) -> None:
        """One balance pass: (elastic) adopt the newest topology epoch,
        execute staged split/merge commands, retire dead ranges; then
        shed surplus, prune lost races, acquire toward target."""
        if self.elastic:
            topo = self.store.read_topology()
            if topo is not None and (
                    self.topology is None
                    or topo["epoch"] != self.topology["epoch"]):
                self._event(f"topology epoch {topo['epoch']}")
                self.topology = topo
            self._process_controls()
            # A committed split/merge retires its source range(s):
            # release NOW (final fenced checkpoint) instead of pumping
            # until the successor's fence rejects us.
            live = set(self._keys())
            for k in [k for k in self.roles if k not in live]:
                self._release(k, "topology-retired")
        keys = self._keys()
        target = self.target_partitions()
        # Shed surplus (highest key first: deterministic, so two
        # overfull workers don't thrash the same partition).
        while len(self.roles) > target:
            self._release(sorted(self.roles)[-1], "rebalance")
        # Prune instances that never acquired while a live foreign
        # owner holds the lease (we lost the race).
        for p, role in list(self.roles.items()):
            if role.fence is None:
                owner = self._probe.owner_of(self._lease_name(p))
                if owner is not None and owner != self.owner:
                    self.roles.pop(p)
                    role.close_doorbell()
        # Acquire free/expired partitions up to target, scanning from a
        # slot-dependent start so peers spread instead of colliding.
        if len(self.roles) < target and keys:
            # crc32, not hash(): per-process salt would make the scan
            # start differ between a worker and its restarted self.
            start = zlib.crc32(self.slot.encode()) % len(keys)
            for i in range(len(keys)):
                if len(self.roles) >= target:
                    break
                p = keys[(start + i) % len(keys)]
                if p in self.roles:
                    continue
                owner = self._probe.owner_of(self._lease_name(p))
                if owner is None or owner == self.owner:
                    self.roles[p] = self._make_role(p)
        if self.summarize:
            self._sweep_summarizers()
        if self.downstream:
            self._sweep_downstream()
        self._m_owned.set(len(self.roles))
        self._sweep_t = time.time()

    # --------------------------------------------- split/merge execution

    def _process_controls(self) -> None:
        """Execute staged topology commands for ranges this worker
        owns (`request_topology_change` writes them; whoever owns the
        target executes and writes the ``.done`` marker). A lost
        commit CAS leaves the command pending for the next sweep."""
        cdir = _control_dir(self.shared_dir)
        try:
            names = sorted(os.listdir(cdir))
        except OSError:
            return
        for fn in names:
            if not fn.endswith(".json") or fn.endswith(".done.json"):
                continue
            path = os.path.join(cdir, fn)
            done_path = path[:-len(".json")] + ".done.json"
            if os.path.exists(done_path):
                continue
            try:
                with open(path) as f:
                    cmd = json.load(f)
            except (OSError, ValueError):
                continue
            op = cmd.get("op") if isinstance(cmd, dict) else None
            if op == "split":
                self._control_split(cmd, done_path)
            elif op == "merge":
                self._control_merge(cmd, done_path)
            else:
                self._done_marker(done_path, error=f"unknown op {op!r}")

    def _done_marker(self, done_path: str, **result) -> None:
        tmp = done_path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({
                "by": self.owner,
                "epoch": (self.topology or {}).get("epoch"),
                **result,
            }, f)
        os.replace(tmp, done_path)

    def _control_split(self, cmd: dict, done_path: str) -> None:
        live = {e["rid"] for e in self.topology["ranges"]}
        rid = cmd.get("rid")
        if rid is None:
            # No target named: capacity follows load — split the widest
            # range this worker owns.
            owned = [k for k, r in self.roles.items()
                     if r.fence is not None]
            if not owned:
                return
            rid = max(owned, key=lambda k: (
                self._entry(k)["hi"] - self._entry(k)["lo"]
            ))
        if rid not in live:
            self._done_marker(done_path, error=f"range {rid} not live")
            return
        role = self.roles.get(rid)
        if role is None or role.fence is None:
            return  # not ours (yet): the owner executes
        try:
            # The parent's FINAL fenced checkpoint — what both children
            # seed from. Written BEFORE the topology commit, so a crash
            # between the two leaves the old epoch fully intact.
            role.checkpoint()
        except (FencedError, OSError):
            self.roles.pop(rid, None)
            self._m_drops.inc()
            return
        topo2 = split_ranges(self.topology, rid, cmd.get("at"))
        if self.store.commit_topology(topo2, self.topology["epoch"]):
            role.leases.release(role.name)
            self.roles.pop(rid, None)
            self._m_handoffs.inc()
            self.topology = self.store.read_topology()
            self._event(
                f"split {rid} -> epoch {self.topology['epoch']}"
            )
            self._done_marker(done_path, op="split", rid=rid)
        else:
            self.topology = self.store.read_topology() or self.topology

    def _control_merge(self, cmd: dict, done_path: str) -> None:
        rids = cmd.get("rids") or []
        if len(rids) != 2:
            self._done_marker(done_path, error=f"merge needs 2 rids: "
                                               f"{rids}")
            return
        live = {e["rid"]: e for e in self.topology["ranges"]}
        if rids[0] not in live or rids[1] not in live:
            self._done_marker(done_path,
                              error=f"ranges {rids} not all live")
            return
        a, b = sorted(rids, key=lambda r: live[r]["lo"])
        if live[a]["hi"] != live[b]["lo"]:
            self._done_marker(done_path,
                              error=f"ranges {rids} not adjacent")
            return
        role_a = self.roles.get(a)
        if role_a is None or role_a.fence is None:
            # Executor rule: the LEFT range's owner executes; the right
            # owner hands its range off the moment it sees the command
            # so the executor's acquisition never waits out a TTL.
            if b in self.roles:
                self._release(b, "merge-handoff")
            return
        lm = self.store.leases
        role_b = self.roles.get(b)
        if role_b is not None and role_b.fence is not None:
            # We own both: final-checkpoint b and KEEP its lease bound
            # through the commit — releasing first would open a window
            # where a peer's sweep acquires the about-to-retire range
            # (try_acquire's already-ours short-circuit would hand the
            # released lease back without re-arming it).
            try:
                role_b.checkpoint()
            except (FencedError, OSError):
                pass  # a successor's fence already won: its state stands
            self.roles.pop(b, None)
            self._m_handoffs.inc()
            self._event(f"released {self._kname(b)} (merge-handoff)")
        else:
            if role_b is not None:
                self.roles.pop(b, None)  # never acquired: nothing held
            if lm.try_acquire(range_lease_name(b)) is None:
                return  # right owner hasn't handed off yet: next sweep
        try:
            role_a.checkpoint()  # the left parent's final checkpoint
        except (FencedError, OSError):
            self.roles.pop(a, None)
            self._m_drops.inc()
            lm.release(range_lease_name(b))
            return
        topo2 = merge_ranges(self.topology, a, b)
        if self.store.commit_topology(topo2, self.topology["epoch"]):
            role_a.leases.release(role_a.name)
            self.roles.pop(a, None)
            self._m_handoffs.inc()
            self.topology = self.store.read_topology()
            self._event(
                f"merge {a}+{b} -> epoch {self.topology['epoch']}"
            )
            self._done_marker(done_path, op="merge", rids=[a, b])
        else:
            self.topology = self.store.read_topology() or self.topology
        lm.release(range_lease_name(b))

    # -------------------------------------------------------------- pump

    def step(self) -> int:
        """One fabric quantum: pump every owned partition once, then
        (throttled) heartbeat + rebalance sweep. Returns records
        moved. A deposed/fenced partition drops OUT of this worker —
        never the worker itself: the other partitions it owns must
        keep sequencing (contrast `serve_role`, where the process IS
        the partition)."""
        moved = 0
        for p, role in list(self.roles.items()):
            try:
                moved += role.step(idle_sleep=0)
            except SystemExit as exc:
                self.roles.pop(p, None)
                role.close_doorbell()
                self._m_drops.inc()
                self._event(f"dropped {self._kname(p)} (exit={exc.code})")
            except FencedError as exc:
                self.roles.pop(p, None)
                role.close_doorbell()
                self._m_drops.inc()
                self._event(f"dropped {self._kname(p)} (fenced: {exc})")
        for p, role in list(self.summ_roles.items()):
            try:
                moved += role.step(idle_sleep=0)
            except (SystemExit, FencedError) as exc:
                self.summ_roles.pop(p, None)
                role.close_doorbell()
                self._event(
                    f"dropped summarizer {self._kname(p)} ({exc})"
                )
        for p, roles in list(self.down_roles.items()):
            for role in list(roles):
                try:
                    moved += role.step(idle_sleep=0)
                except (SystemExit, FencedError) as exc:
                    # Drop the whole partition's downstream set (the
                    # deposed stage's siblings released gracefully):
                    # the key leaves down_roles, so the next sweep
                    # recreates fresh instances while we still own the
                    # deli — a single deposed stage must not leave the
                    # partition's durable leg unowned forever.
                    roles.remove(role)
                    role.close_doorbell()
                    self._event(
                        f"dropped downstream {role.name} ({exc})"
                    )
                    self._release_down(p, f"{role.name} deposed")
                    break  # the key's remaining roles just released
        now = time.time()
        if now - self._sweep_t > self.ttl_s / 2:
            self.sweep()
        if now - self._hb_t > self.ttl_s / 3:
            self.heartbeat()
        return moved

    def idle_wait(self, timeout_s: float) -> None:
        """The worker's idle quantum: wait on ALL owned partitions'
        input-topic doorbells at once (any append wakes the next
        step), bounded by `timeout_s` so the sweep/heartbeat cadence
        and the poll fallback are unaffected."""
        from .queue import wait_doorbells

        import itertools

        bells = [b for b in (
            r.doorbell() for r in itertools.chain(
                self.roles.values(), self.summ_roles.values(),
                *self.down_roles.values(),
            )
        ) if b is not None]
        if bells:
            # Bounded stretch (the _Role.bell_wait_s rationale), capped
            # so the sweep/heartbeat cadence (ttl/2, ttl/3) still runs
            # on time.
            wait_doorbells(
                bells, min(max(timeout_s, 0.05), self.ttl_s / 6)
            )
        else:
            time.sleep(timeout_s)

    def stop(self) -> None:
        """Graceful exit: hand every partition off now instead of
        making successors wait out the lease TTL."""
        for p in sorted(self.summ_roles):
            self._release_summ(p, "shutdown")
        for p in sorted(self.down_roles):
            self._release_down(p, "shutdown")
        for p in sorted(self.roles):
            self._release(p, "shutdown")
        try:
            os.remove(self._hb_path())
        except OSError:
            pass


def serve_shard_worker(shared_dir: str, slot: str,
                       owner: Optional[str] = None,
                       idle_sleep: float = 0.01, **kw) -> None:
    """Child-process entry: run one shard worker until killed."""
    use_compile_cache()
    w = ShardWorker(shared_dir, slot, owner=owner, **kw)
    w.heartbeat()
    w.sweep()
    # Bare "READY <slot>" when slot IS the owner (the standalone CLI
    # contract tools/partition_worker_main.py keeps); supervised
    # children append their generation owner for the event log.
    banner = f"READY {slot}" + (
        f" {w.owner}" if w.owner != slot else ""
    )
    print(banner, flush=True)
    while True:
        if w.step() == 0:
            w.idle_wait(idle_sleep)


# ---------------------------------------------------------------------------
# load-driven autoscaling
# ---------------------------------------------------------------------------


def stage_p99s(snap: dict, stage: str
               ) -> Tuple[Optional[float], Dict[str, float]]:
    """(farm_p99, {partition: p99}) for one wire-trace stage off a
    metrics snapshot. Per-partition series come straight from the
    ``op_stage_ms{stage=...,partition=k}`` histograms the ranged roles
    observe; the FARM-WIDE quantile is estimated over the bucket-wise
    SUM of every matching histogram (label-less classic series
    included), so it stays one quantile of one distribution rather
    than a quantile of quantiles. Beyond-last-bucket estimates are
    dropped, not faked."""
    from ..utils.metrics import histogram_quantile

    merged: Optional[dict] = None
    per: Dict[str, float] = {}
    for h in snap.get("histograms", ()):
        if (h.get("name") != "op_stage_ms"
                or (h.get("labels") or {}).get("stage") != stage
                or not h.get("count")):
            continue
        part = h["labels"].get("partition")
        if part is not None:
            v = histogram_quantile(h, 0.99)
            if v != float("inf"):
                per[part] = v
        if merged is None:
            merged = {"buckets": list(h["buckets"]),
                      "counts": list(h["counts"]),
                      "count": int(h["count"])}
        elif merged["buckets"] == list(h["buckets"]):
            merged["counts"] = [a + b for a, b in
                                zip(merged["counts"], h["counts"])]
            merged["count"] += int(h["count"])
    farm = None
    if merged is not None and merged["count"]:
        v = histogram_quantile(merged, 0.99)
        if v != float("inf"):
            farm = v
    return farm, per


class AutoscalePolicy:
    """The closed autoscaling loop: a supervisor-side policy watching
    per-partition deli throughput (``role_records_total{role="deli",
    partition=rid}`` rates off the merged worker-heartbeat registry)
    and the farm's ``/slo`` p99 (``op_stage_ms`` quantiles, wire-trace
    runs), issuing `request_split` on sustained HOT ranges and
    `request_merge` on sustained COLD adjacent pairs over the EXISTING
    control channel — capacity follows load, no human in the loop.

    Anti-flap machinery, all three layers deliberate:

    - **hysteresis** — the split threshold (`split_rate`) sits well
      above the merge threshold (`merge_rate`), so a range oscillating
      near either line never qualifies for both;
    - **sustain** — a range must hold its hot/cold verdict for
      `sustain_s` continuous seconds before the policy acts (one
      bursty pump is not load);
    - **min-interval** — at most one topology change per
      `min_interval_s`, and never while a previously issued command is
      still pending, so the fabric always finishes absorbing one epoch
      before the policy can stage the next.

    Pure decision logic: `observe()` takes the sampled state and
    returns at most one staged command dict — the supervisor owns the
    sampling cadence and the control-channel write, and the chaos
    harness gates a policy-driven split mid-boxcar bit-identical like
    any operator-driven one."""

    def __init__(self, split_rate: float = 2000.0,
                 merge_rate: float = 50.0, sustain_s: float = 3.0,
                 min_interval_s: float = 10.0, max_ranges: int = 16,
                 min_ranges: int = 1,
                 p99_hot_ms: Optional[float] = None,
                 p99_stage: str = "submit_to_stamp",
                 p99_per_partition: bool = False):
        """`split_rate`/`merge_rate`: records/s per range above/below
        which a range counts hot/cold. `p99_hot_ms` (optional): when
        the farm-wide `op_stage_ms{stage=p99_stage}` p99 exceeds it,
        the HIGHEST-rate range counts hot too — the latency-driven
        trigger for load the rate threshold alone misses (one huge doc
        in an otherwise quiet range). Needs wire tracing to populate;
        None disables the latency trigger.

        `p99_per_partition=True` sharpens the latency trigger to the
        PER-RANGE quantiles (the ``op_stage_ms{stage=...,partition=k}``
        series the ranged roles observe into their worker heartbeats):
        a range whose OWN p99 exceeds `p99_hot_ms` counts hot,
        regardless of the farm-wide quantile or where the record rate
        is highest — one hot range in a quiet farm triggers its own
        split instead of hiding inside a healthy farm-wide p99 (or
        splitting the wrong, merely-busiest, range)."""
        if merge_rate >= split_rate:
            raise ValueError(
                f"hysteresis requires merge_rate < split_rate "
                f"(got {merge_rate} >= {split_rate})"
            )
        self.split_rate = float(split_rate)
        self.merge_rate = float(merge_rate)
        self.sustain_s = float(sustain_s)
        self.min_interval_s = float(min_interval_s)
        self.max_ranges = int(max_ranges)
        self.min_ranges = int(min_ranges)
        self.p99_hot_ms = p99_hot_ms
        self.p99_stage = p99_stage
        self.p99_per_partition = bool(p99_per_partition)
        self._last_sample: Optional[Tuple[float, Dict[str, float]]] = None
        self.hot_since: Dict[str, float] = {}
        self.cold_since: Dict[str, float] = {}
        # None until the FIRST action: min-interval paces actions
        # apart, it must not delay the first one.
        self.last_action_t: Optional[float] = None
        self.actions: List[dict] = []  # staged commands, for operators

    # ------------------------------------------------------------ sample

    def rates(self, now: float,
              counts: Dict[str, float]) -> Optional[Dict[str, float]]:
        """Per-range records/s from successive counter samples (None
        until two samples exist). Clamped at zero: a restarted worker
        resets its counters and a raw diff would go negative."""
        prev = self._last_sample
        self._last_sample = (now, dict(counts))
        if prev is None:
            return None
        dt = now - prev[0]
        if dt <= 0:
            return None
        return {
            rid: max(0.0, (counts.get(rid, 0.0) - prev[1].get(rid, 0.0))
                     ) / dt
            for rid in counts
        }

    # ------------------------------------------------------------ decide

    def observe(self, now: float, rates: Dict[str, float],
                topo: dict,
                p99_ms: Optional[float] = None,
                p99_by_partition: Optional[Dict[str, float]] = None,
                ) -> Optional[dict]:
        """Fold one sample; returns a command dict ({"op": "split",
        "rid": ...} / {"op": "merge", "rids": [...]}) when the policy
        fires, else None. The caller stages it and must not call
        `observe` with a pending unexecuted command. `p99_by_partition`
        (range id -> that range's own stage p99, ms) feeds the
        `p99_per_partition` trigger; ignored otherwise."""
        ranges = sorted(topo["ranges"], key=lambda e: e["lo"])
        live = {e["rid"] for e in ranges}
        for d in (self.hot_since, self.cold_since):
            for rid in [r for r in d if r not in live]:
                d.pop(rid)
        hottest = max(rates, key=lambda r: rates[r]) if rates else None
        latency_hot = (
            not self.p99_per_partition
            and self.p99_hot_ms is not None and p99_ms is not None
            and p99_ms > self.p99_hot_ms
        )
        per_p99 = p99_by_partition or {}
        for rid in live:
            rate = rates.get(rid, 0.0)
            own_p99 = per_p99.get(rid)
            own_hot = (
                self.p99_per_partition and self.p99_hot_ms is not None
                and own_p99 is not None and own_p99 > self.p99_hot_ms
            )
            if rate > self.split_rate or own_hot \
                    or (latency_hot and rid == hottest):
                self.hot_since.setdefault(rid, now)
            else:
                self.hot_since.pop(rid, None)
            if rate < self.merge_rate:
                self.cold_since.setdefault(rid, now)
            else:
                self.cold_since.pop(rid, None)
        if self.last_action_t is not None \
                and now - self.last_action_t < self.min_interval_s:
            return None
        # Split the longest-sustained hot range first.
        hot = [(now - t0, rid) for rid, t0 in self.hot_since.items()
               if now - t0 >= self.sustain_s]
        if hot and len(ranges) < self.max_ranges:
            _, rid = max(hot)
            self.last_action_t = now
            self.hot_since.pop(rid, None)
            cmd = {"op": "split", "rid": rid, "why": "autoscale-hot"}
            self.actions.append({"t": now, **cmd})
            return cmd
        # Merge the first adjacent pair that is cold on BOTH sides.
        if len(ranges) > max(1, self.min_ranges):
            for a, b in zip(ranges, ranges[1:]):
                if a["hi"] != b["lo"]:
                    continue
                ta = self.cold_since.get(a["rid"])
                tb = self.cold_since.get(b["rid"])
                if ta is None or tb is None:
                    continue
                if min(now - ta, now - tb) >= self.sustain_s:
                    self.last_action_t = now
                    self.cold_since.pop(a["rid"], None)
                    self.cold_since.pop(b["rid"], None)
                    cmd = {"op": "merge",
                           "rids": [a["rid"], b["rid"]],
                           "why": "autoscale-cold"}
                    self.actions.append({"t": now, **cmd})
                    return cmd
        return None


# ---------------------------------------------------------------------------
# the fabric supervisor
# ---------------------------------------------------------------------------


class ShardFabricSupervisor(ServiceSupervisor):
    """W shard workers as supervised children over N partitions.

    Reuses the `ServiceSupervisor` monitor machinery wholesale (process
    exit + heartbeat staleness, paced respawn, fresh owner identity per
    generation) — a "role" here is a worker SLOT (``shard-w0``…), its
    heartbeat the worker file `ShardWorker.heartbeat` writes. A
    restarted worker re-enters the lease sweep and the fabric
    rebalances around it; per-partition metrics ride the worker
    heartbeats and merge at `collect_metrics` exactly like the classic
    farm's role metrics."""

    def __init__(self, shared_dir: str, n_workers: int = 2,
                 n_partitions: int = 4,
                 max_partitions: Optional[int] = None,
                 worker_ttl_s: Optional[float] = None,
                 elastic: bool = False, summarize: bool = False,
                 downstream: Optional[str] = None,
                 ingress: bool = False,
                 autoscale: Any = None,
                 **kw):
        """`downstream` ("fused"|"split") runs per-partition
        scriptorium/broadcaster/scribe consumers inside each worker
        (see `ShardWorker`). `ingress=True` adds the supervised
        admission front door (`server.ingress.IngressRole`) as an
        extra child routing the ``ingress`` topic into the fabric's
        raw partitions. `autoscale` (an `AutoscalePolicy`, or True
        for defaults; elastic only) closes the scaling loop: the
        supervisor samples per-partition throughput each monitor pass
        and stages policy-driven splits/merges on the control
        channel."""
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1: {n_workers}")
        self.n_partitions = int(n_partitions)
        self.max_partitions = max_partitions
        self.worker_ttl_s = worker_ttl_s
        self.elastic = bool(elastic)
        self.summarize = bool(summarize)
        if downstream is not None and downstream not in DOWNSTREAM_MODES:
            raise ValueError(
                f"downstream {downstream!r} not in "
                f"{sorted(DOWNSTREAM_MODES)}"
            )
        if downstream == "fused" and self.elastic:
            raise ValueError(
                "downstream='fused' is static-partition only "
                "(use 'split' on the elastic fabric)"
            )
        self.downstream = downstream
        if autoscale and not self.elastic:
            raise ValueError(
                "autoscale needs elastic=True (the policy issues "
                "live range splits/merges)"
            )
        self.autoscale: Optional[AutoscalePolicy] = (
            autoscale if isinstance(autoscale, AutoscalePolicy)
            else (AutoscalePolicy() if autoscale else None)
        )
        self._autoscale_t = 0.0
        self._autoscale_pending: Optional[str] = None
        roles = tuple(f"shard-w{i}" for i in range(n_workers))
        if ingress:
            roles = ("ingress",) + roles
        self.ingress_enabled = bool(ingress)
        super().__init__(shared_dir, roles=roles, **kw)
        os.makedirs(os.path.join(shared_dir, "workers"), exist_ok=True)
        if self.elastic:
            # Bootstrap the topology before any child spawns, so the
            # router/workers/harness all adopt one epoch-1 record.
            self.store: Optional[RangeLeaseStore] = RangeLeaseStore(
                shared_dir, "__supervisor__"
            )
            self.store.ensure_topology(self.n_partitions)
        else:
            self.store = None

    def _accelerator_roles(self) -> List[str]:
        """Every worker slot runs a kernel deli when deli_impl is
        "kernel" (the front door never computes on a device)."""
        if self.deli_impl != "kernel":
            return []
        return [r for r in self.roles if r.startswith("shard-w")]

    def _child_cmd(self, role: str, owner: str) -> List[str]:
        if role == "ingress":
            # The front door is a classic supervised role child
            # (server.supervisor main), pointed at the fabric's
            # partition topology so its router writes the same raw
            # topics the workers consume.
            cmd = [self.python, "-c",
                   "from fluidframework_tpu.server.supervisor import "
                   "main; main()",
                   "--role", "ingress", "--dir", self.shared_dir,
                   "--owner", owner, "--ttl", str(self.ttl_s),
                   "--batch", str(self.batch),
                   "--log-format", self.log_format,
                   "--ckpt-interval", str(self.ckpt_interval_s),
                   "--ckpt-bytes", str(self.ckpt_bytes),
                   "--ckpt-duty", str(self.ckpt_duty),
                   "--ingress-partitions", str(self.n_partitions)]
            if self.elastic:
                cmd += ["--ingress-elastic"]
            if self.hb_interval_s is not None:
                cmd += ["--hb-interval", str(self.hb_interval_s)]
            return cmd
        cmd = [self.python, "-c",
               "from fluidframework_tpu.server.shard_fabric import main; "
               "main()",
               "--dir", self.shared_dir, "--slot", role,
               "--owner", owner,
               "--partitions", str(self.n_partitions),
               "--ttl", str(self.ttl_s), "--batch", str(self.batch),
               "--impl", self.deli_impl,
               "--log-format", self.log_format,
               "--ckpt-interval", str(self.ckpt_interval_s),
               "--ckpt-bytes", str(self.ckpt_bytes),
               "--ckpt-duty", str(self.ckpt_duty)]
        if self.max_partitions is not None:
            cmd += ["--max-partitions", str(self.max_partitions)]
        if self.worker_ttl_s is not None:
            cmd += ["--worker-ttl", str(self.worker_ttl_s)]
        if self.deli_devices is not None:
            cmd += ["--deli-devices", str(self.deli_devices)]
        if self.device_plane is not None:
            # One worker = one mesh slice: worker i orders on model
            # column i (mod model) of the shared plane.
            cmd += ["--device-plane", self.device_plane]
            try:
                col = int(role.rsplit("w", 1)[1])
            except (IndexError, ValueError):
                col = 0
            cmd += ["--plane-column", str(col)]
        if self.elastic:
            cmd += ["--elastic"]
        if self.summarize:
            cmd += ["--summarize"]
            if self.summary_ops is not None:
                cmd += ["--summary-ops", str(self.summary_ops)]
        if self.downstream:
            cmd += ["--downstream", self.downstream]
        return cmd

    def _hb_file(self, role: str) -> str:
        if role == "ingress":
            # The front door heartbeats like a classic role child, not
            # a worker slot.
            return os.path.join(self.shared_dir, "hb", "ingress.json")
        return os.path.join(self.shared_dir, "workers", f"{role}.json")

    def partition_owners(self) -> Dict[str, str]:
        """Live {``deli-p{k}`` | ``deli-{rid}``: owner} — the
        operator's ownership view (`queue.lease_owners` over the
        fabric's lease directory)."""
        return lease_owners(os.path.join(self.shared_dir, "leases"))

    def partition_leases(self) -> Dict[str, dict]:
        """The full lease view — owner AND fence/expiry per partition
        (`queue.lease_table`): the fence is how a reader tells a stale
        pre-split owner from the live one."""
        return lease_table(os.path.join(self.shared_dir, "leases"))

    def topology(self) -> Optional[dict]:
        """The live hash-range topology record (None when static)."""
        return self.store.read_topology() if self.elastic else None

    def request_split(self, rid: Optional[str] = None,
                      at: Optional[int] = None) -> str:
        """Stage a live split of range `rid` (default: the owner's
        widest range) at hash `at` (default: midpoint). Returns the
        command id; the owning worker executes it on its next sweep
        and `control_result(shared_dir, cmd_id)` reports the new
        epoch."""
        if not self.elastic:
            raise ValueError("request_split needs elastic=True")
        cmd: Dict[str, Any] = {"op": "split"}
        if rid is not None:
            cmd["rid"] = rid
        if at is not None:
            cmd["at"] = int(at)
        return request_topology_change(self.shared_dir, cmd)

    def request_merge(self, rid_a: str, rid_b: str) -> str:
        """Stage a live merge of adjacent ranges `rid_a`/`rid_b`."""
        if not self.elastic:
            raise ValueError("request_merge needs elastic=True")
        return request_topology_change(
            self.shared_dir, {"op": "merge", "rids": [rid_a, rid_b]}
        )

    def control_result(self, cmd_id: str) -> Optional[dict]:
        return control_result(self.shared_dir, cmd_id)

    # ------------------------------------------------------- autoscaling

    def poll_once(self) -> List[str]:
        acted = super().poll_once()
        if self.autoscale is not None:
            self.autoscale_tick()
        return acted

    def autoscale_tick(self, force: bool = False) -> Optional[str]:
        """One autoscale sample/decide pass, throttled to ~half the
        lease TTL (`force` bypasses the throttle, for tests): sample
        per-partition deli record counters off the worker heartbeats,
        wait out any previously staged command (one epoch change in
        flight at a time — the fabric must finish absorbing it), and
        stage at most one policy-driven split/merge on the control
        channel. Returns the staged command id, if any."""
        pol = self.autoscale
        if pol is None:
            return None
        now = time.time()
        if not force and now - self._autoscale_t < max(
                0.25, self.ttl_s / 2):
            return None
        self._autoscale_t = now
        if self._autoscale_pending is not None:
            if self.control_result(self._autoscale_pending) is None:
                return None  # previous command still executing
            self._autoscale_pending = None
        topo = self.topology()
        if topo is None:
            return None
        counts: Dict[str, float] = {}
        for snap in self.child_metrics().values():
            for c in snap.get("counters", ()):
                if (c.get("name") == "role_records_total"
                        and c.get("labels", {}).get("role") == "deli"):
                    rid = c["labels"].get("partition")
                    if rid is not None:
                        counts[rid] = (counts.get(rid, 0.0)
                                       + float(c["value"]))
        rates = pol.rates(now, counts)
        if rates is None:
            return None  # need two samples for a rate
        p99 = None
        p99_by_part: Optional[Dict[str, float]] = None
        if pol.p99_hot_ms is not None:
            snap = self.collect_metrics().snapshot()
            p99, p99_by_part = stage_p99s(snap, pol.p99_stage)
        cmd = pol.observe(now, rates, topo, p99_ms=p99,
                          p99_by_partition=p99_by_part)
        if cmd is None:
            return None
        why = cmd.pop("why", "autoscale")
        cid = request_topology_change(self.shared_dir, cmd)
        self._autoscale_pending = cid
        self._event(
            f"autoscale: staged {cmd.get('op')} "
            f"{cmd.get('rid') or cmd.get('rids')} ({why})"
        )
        return cid

    def degraded_partitions(self) -> List[str]:
        """Partitions currently limping through a storage-fault retry
        budget: the `degraded` lists the worker heartbeats export,
        UNION the fresh per-role heartbeats — a role stuck inside its
        backoff cannot return to the worker loop, so its own forced
        heartbeat (`_Role._durable`) is the prompt signal; worker
        heartbeats catch up between steps. Role files older than the
        heartbeat timeout are ignored (a crashed role must not pin
        the fabric degraded forever)."""
        out = set()
        for role in self.roles:
            try:
                with open(self._hb_file(role)) as f:
                    hb = json.load(f)
            except (OSError, ValueError):
                continue
            out.update(str(p) for p in hb.get("degraded") or [])
        hb_dir = os.path.join(self.shared_dir, "hb")
        now = time.time()
        try:
            names = os.listdir(hb_dir)
        except OSError:
            names = []
        for fn in names:
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(hb_dir, fn)) as f:
                    hb = json.load(f)
            except (OSError, ValueError):
                continue
            if (hb.get("degraded")
                    and now - float(hb.get("t", 0))
                    <= self.heartbeat_timeout_s):
                out.add(fn[:-len(".json")])
        return sorted(out)

    def health(self) -> Dict[str, Any]:
        h = super().health()
        owners = self.partition_owners()
        topo = self.topology()
        expected = (len(topo["ranges"]) if topo is not None
                    else self.n_partitions)
        h["n_partitions"] = expected
        h["partition_owners"] = owners
        h["partition_leases"] = self.partition_leases()
        if topo is not None:
            h["epoch"] = topo["epoch"]
            h["ranges"] = [e["rid"] for e in topo["ranges"]]
        limping = self.degraded_partitions()
        h["degraded_partitions"] = limping
        h["downstream"] = self.downstream
        h["ingress"] = self.ingress_enabled
        if self.autoscale is not None:
            h["autoscale_actions"] = len(self.autoscale.actions)
        # Degraded until every partition has a live owner (boot,
        # takeover, split/merge windows — unowned partitions buffer,
        # not lose) and none is inside a storage-fault retry budget:
        # an operator should see either gap.
        if len(owners) < expected or limping:
            h["status"] = "degraded"
        return h

    def collect_metrics(self):
        reg = super().collect_metrics()
        leases = self.partition_leases()
        topo = self.topology()
        reg.gauge("shard_partitions_total").set(
            len(topo["ranges"]) if topo is not None
            else self.n_partitions
        )
        reg.gauge("shard_partitions_owned_live").set(len(leases))
        if topo is not None:
            reg.gauge("shard_topology_epoch").set(topo["epoch"])
        if self.autoscale is not None:
            reg.gauge("shard_autoscale_actions").set(
                len(self.autoscale.actions)
            )
        for name, info in leases.items():
            # The lease FENCE next to the owner (satellite of the
            # lease_table fix): a scrape can tell a stale pre-split
            # owner's series from the live one's.
            reg.gauge("shard_partition_fence", partition=name).set(
                info["fence"]
            )
        return reg


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

    elastic = "--elastic" in args
    if elastic:
        args.remove("--elastic")
    summarize = "--summarize" in args
    if summarize:
        args.remove("--summarize")
    summary_ops_s = _take("--summary-ops")
    downstream = _take("--downstream")
    shared_dir = _take("--dir")
    slot = _take("--slot")
    owner = _take("--owner")
    n_partitions = int(_take("--partitions", "4"))
    ttl = float(_take("--ttl", "1.0"))
    batch = int(_take("--batch", "512"))
    impl = _take("--impl") or os.environ.get("FLUID_DELI", "scalar")
    log_format = _take("--log-format")
    ckpt_interval = float(_take("--ckpt-interval", "0.25"))
    ckpt_bytes = int(_take("--ckpt-bytes", str(256 * 1024)))
    ckpt_duty = float(_take("--ckpt-duty", "0.2"))
    max_p = _take("--max-partitions")
    worker_ttl = _take("--worker-ttl")
    devices_s = _take("--deli-devices")
    device_plane_s = _take("--device-plane")
    plane_col_s = _take("--plane-column")
    if (shared_dir is None or slot is None or args
            or impl not in DELI_IMPLS
            or (log_format is not None and log_format not in LOG_FORMATS)
            or (devices_s is not None and not devices_s.isdigit())
            or (plane_col_s is not None and not plane_col_s.isdigit())
            or (downstream is not None
                and downstream not in DOWNSTREAM_MODES)
            or (summary_ops_s is not None
                and not summary_ops_s.isdigit())):
        print(
            "usage: python -m fluidframework_tpu.server.shard_fabric "
            "--dir D --slot S [--owner O] [--partitions N] [--ttl S] "
            "[--batch N] [--impl scalar|kernel] "
            "[--log-format json|columnar] [--max-partitions K] "
            "[--worker-ttl S] [--deli-devices N] "
            "[--device-plane DxM] [--plane-column K] [--elastic] "
            "[--summarize] [--summary-ops N] [--downstream fused|split] "
            "[--ckpt-interval S] [--ckpt-bytes N] [--ckpt-duty F]",
            file=sys.stderr,
        )
        raise SystemExit(2)
    serve_shard_worker(
        shared_dir, slot, owner=owner, n_partitions=n_partitions,
        deli_impl=impl, log_format=log_format, ttl_s=ttl, batch=batch,
        max_partitions=int(max_p) if max_p else None,
        ckpt_interval_s=ckpt_interval, ckpt_bytes=ckpt_bytes,
        ckpt_duty=ckpt_duty,
        worker_ttl_s=float(worker_ttl) if worker_ttl else None,
        deli_devices=int(devices_s) if devices_s else None,
        elastic=elastic, summarize=summarize,
        summary_ops=int(summary_ops_s) if summary_ops_s else None,
        downstream=downstream,
        device_plane=device_plane_s,
        plane_column=int(plane_col_s) if plane_col_s else None,
    )


if __name__ == "__main__":
    main()
