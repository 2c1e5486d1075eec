"""Summary service: the scriptorium-offload summarizer role.

Per PAPER.md the hot path is merge-tree apply *and* the summary /
catch-up read side: persistence is periodic summaries in git-like
storage, and real collaborative traffic is mostly joins and reads —
yet until this module every client joining a document replayed the
entire op log, and the overlay/merge-tree kernel had no live consumer.

`SummarizerRole` is a supervised farm lambda (`server.supervisor._Role`
machinery: fenced lease, heartbeat, exactly-once ``inOff`` recovery)
that consumes the sequenced **deltas** stream and periodically emits
**fenced summary records**:

- the summary **blob** — a replayable per-doc state snapshot — is
  content-addressed into the shared `castore.ContentAddressedStore`
  behind a `historian.HistorianCache` (immutable blobs, LRU budget);
- a small **manifest** ``{doc, seq, msn, count, form, handle, off}``
  is appended (fenced, with ``inOff``) to the ``summaries`` topic, so
  readers discover the newest summary ≤ seq by tailing ONE topic
  (`SummaryIndex`).

Two blob forms, decided per document from its first op:

- ``"mergetree"`` — op contents parse as merge-tree wire ops
  (`protocol.mergetree_ops`). The role folds the doc's ops through the
  vectorized merge-tree kernel (`core.kernel_replica.KernelReplica`
  over `ops.mergetree_kernel`; several docs folding in the same pump
  are STACKED and dispatched through the vmapped
  `apply_op_batch_docs_jit` — one device call across the doc axis,
  the `overlay_replay.stack_replicas` idiom applied to the live
  stream). The blob serializes the **canonical row form** of the
  table at the fold point: settled rows (ins ≤ msn, not removed)
  coalesced into maximal equal-prop runs, tombstones below the window
  dropped (zamboni), above-window rows kept with their semantic
  fields, adjacent rows with identical semantic fields merged. The
  canonical form is a pure function of the op prefix — NOT of pump
  boundaries, checkpoint timing, or restart history — which is what
  makes the content-addressed handle stable across crashes: after
  every emission the live replica is REBUILT from the serialized rows
  (the restart path runs on every cadence), so an interrupted and an
  uninterrupted summarizer are byte-identical by construction.
  Blob size is O(document + collab window), independent of log
  length — the flat-cold-join property the catch-up bench gates.
- ``"ops"`` — generic contents (no merge-tree structure to compact):
  the blob carries the canonical records themselves. Correct (and the
  boundary between summary and tail is still exactly-once checked),
  but O(log); mixed/undecodable docs freeze their summaries rather
  than emit garbage.

**Safety argument** (why summary + tail == full replay): the fold
point of a summary at record k uses record k's stamped ``msn``. Every
op sequenced after k carries ``refSeq >= msn_at_its_sequencing >=
msn_k`` (deli nacks stale refSeqs and msn is monotone), so a tombstone
removed at/below ``msn_k`` is invisible to every later perspective and
a row inserted at/below ``msn_k`` is visible to every later
perspective — exactly the zamboni/compaction safety contract
`KernelReplica.compact` rests on, applied at a recorded point. The
differential gates (tests/test_summarizer.py, the chaos
summarizer-kill run) check it bit-for-bit via document-state digests.

Readers: `SummaryIndex` (manifest tailer), `read_catchup` (nearest
summary + op tail off the deltas topic), `SummaryReplica` (boots from
a blob — or cold — and applies tail records), `state_digest` (the
GOLDEN-style form two boots are compared in).
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict, List, Optional, Tuple

from .castore import ContentAddressedStore
from .columnar_log import make_tail_reader, make_topic
from .historian import HistorianCache
from .supervisor import _Role, canonical_record

__all__ = [
    "FOLD_BACKENDS",
    "FOLD_BACKEND_ENV",
    "FOLD_INTERPRET_ENV",
    "SUMMARY_OPS_ENV",
    "SummarizerRole",
    "SummaryIndex",
    "SummaryReplica",
    "open_summary_store",
    "read_catchup",
    "state_digest",
    "summarize_document",
]

# Default emission cadence: one summary per doc every N sequenced
# records (override per role via summary_ops=, or process-wide via the
# env — the supervisor's child_env seam carries it to farm children).
SUMMARY_OPS_ENV = "FLUID_SUMMARY_OPS"
DEFAULT_SUMMARY_OPS = 256

# Merge-tree fold backend (`fold_backend=` / env): "kernel" is the
# vmapped row-model kernel (`apply_op_batch_docs_jit`), "overlay" the
# O(collab window) overlay-pallas engine (`core.overlay_fold`; its
# speed against the vmapped replay is not measured on the chip yet).
# Canonical row serialization is backend-invariant BY CONTRACT, so
# blobs and content-addressed handles are bit-identical either way —
# gated by tests/test_device_plane.py on every host. Asking for "overlay" where pallas cannot lower (CPU host, no
# interpreter requested) raises; the role never switches backends.
FOLD_BACKEND_ENV = "FLUID_FOLD_BACKEND"
# "1": run the overlay backend through the pallas INTERPRETER — the
# CPU-CI correctness mode (slow, bit-identical), used by the chaos /
# differential gates on hosts without a TPU.
FOLD_INTERPRET_ENV = "FLUID_FOLD_INTERPRET"
FOLD_BACKENDS = ("kernel", "overlay")

# Fold-engine shape knobs (uniform across docs so the stacked vmapped
# dispatch can group them; a doc that outgrows the uniform capacity
# simply folds through the same kernel un-stacked).
_CHUNK = 128
_MIN_CAP = 512


def _pow2(n: int, lo: int = _MIN_CAP) -> int:
    c = lo
    while c < n:
        c *= 2
    return c


def _summary_ops_default() -> int:
    try:
        return max(1, int(os.environ.get(SUMMARY_OPS_ENV, "")))
    except ValueError:
        return DEFAULT_SUMMARY_OPS


def _fold_backend_default() -> str:
    b = os.environ.get(FOLD_BACKEND_ENV, "").strip() or "kernel"
    if b not in FOLD_BACKENDS:
        raise ValueError(
            f"{FOLD_BACKEND_ENV}={b!r} not in {FOLD_BACKENDS}"
        )
    return b


def _fold_interpret_default() -> bool:
    return os.environ.get(FOLD_INTERPRET_ENV, "") == "1"


_store_seq = 0


def open_summary_store(shared_dir: str,
                       budget_bytes: int = 64 * 1024 * 1024
                       ) -> HistorianCache:
    """The farm's summary store: a durable content-addressed store
    under ``<shared_dir>/store`` fronted by the historian cache
    (immutable blobs LRU-cache; every process — summarizer children,
    catch-up readers, benches — opens the same directory). Each open
    gets its own metrics label: distinct caches (different dirs, or a
    role and a reader side by side) must not fold into one gauge."""
    global _store_seq
    _store_seq += 1
    return HistorianCache(
        ContentAddressedStore(
            prefer_native=False,
            directory=os.path.join(shared_dir, "store"),
        ),
        blob_budget_bytes=budget_bytes,
        name=f"summary{_store_seq}",
    )


# ---------------------------------------------------------------------------
# merge-tree fold engine
# ---------------------------------------------------------------------------


def _decode_mt_op(contents: Any):
    """Merge-tree wire op, or None when the contents carry no
    merge-tree structure (the generic-doc detection rule)."""
    if not isinstance(contents, dict) or "type" not in contents:
        return None
    try:
        from ..protocol.mergetree_ops import op_from_json

        return op_from_json(contents)
    except (KeyError, ValueError, TypeError):
        return None


def _boot_mergetree(rows: List[list], msn: int):
    """Build a live `KernelReplica` from serialized canonical rows —
    THE restart path, also run after every emission so interrupted and
    uninterrupted summarizers proceed from the identical state."""
    import numpy as np

    from ..core.kernel_replica import KernelReplica, TextArena
    from ..ops.mergetree_kernel import (
        NOT_REMOVED,
        PROP_ABSENT,
        SegmentTable,
    )
    from ..protocol.constants import NO_CLIENT

    import jax.numpy as jnp

    rep = KernelReplica(initial="", chunk_size=_CHUNK, capacity=_MIN_CAP)
    n = len(rows)
    cap = _pow2(n + 2 * _CHUNK + 8)
    buf_start = np.zeros(cap, np.int32)
    length = np.zeros(cap, np.int32)
    ins_seq = np.zeros(cap, np.int32)
    ins_client = np.full(cap, NO_CLIENT, np.int32)
    rem_seq = np.full(cap, NOT_REMOVED, np.int32)
    rem_clients = np.full((cap, rep.n_removers), NO_CLIENT, np.int32)
    props = np.full((cap, rep.n_prop_keys), PROP_ABSENT, np.int32)
    parts: List[str] = []
    off = 0
    for i, (seg, ins, icl, rem, rcl, prow) in enumerate(rows):
        buf_start[i] = off
        length[i] = len(seg)
        ins_seq[i] = ins
        ins_client[i] = icl
        if rem is not None:
            rem_seq[i] = rem
            rem_clients[i, : len(rcl)] = rcl
        if prow:
            for k, v in prow.items():
                props[i, rep.props.key_id(k)] = rep.props.value_id(v)
        parts.append(seg)
        off += len(seg)
    rep.arena = TextArena("".join(parts))
    rep.capacity = cap
    rep.table = SegmentTable(
        n_rows=jnp.int32(n),
        buf_start=jnp.asarray(buf_start),
        length=jnp.asarray(length),
        ins_seq=jnp.asarray(ins_seq),
        ins_client=jnp.asarray(ins_client),
        rem_seq=jnp.asarray(rem_seq),
        rem_clients=jnp.asarray(rem_clients),
        props=jnp.asarray(props),
        error=jnp.int32(0),
    )
    rep.min_seq = rep._applied_min_seq = int(msn)
    rep._pending_rows_bound = n
    return rep


def _encode_fold(rep, records: List[dict]) -> None:
    """Encode canonical op records into the replica's pending rows
    (`kernel_replica.encode_op` — the same encoder every kernel
    replica consumer uses). Join/leave/noop records advance msn only."""
    from ..core.kernel_replica import encode_op
    from ..protocol.messages import MessageType, SequencedMessage

    for rec in records:
        if rec.get("type") == "op":
            op = _decode_mt_op(rec.get("contents"))
            if op is None:
                raise ValueError(f"non-mergetree contents at seq "
                                 f"{rec.get('seq')}")
            msg = SequencedMessage(
                int(rec["seq"]), int(rec["msn"]), int(rec["client"]),
                int(rec.get("clientSeq", 0)), int(rec.get("refSeq", 0)),
                MessageType.OP, op,
            )
            encode_op(rep, op, msg)
        rep.current_seq = int(rec["seq"])
        rep.min_seq = max(rep.min_seq, int(rec["msn"]))


def _place_fold_stack(tables, stacked, plane):
    """Lay a stacked kernel fold over the 2-D device plane: the doc
    axis shards on ``docs`` and the TABLE row/segment axis on
    ``model`` (`PartitionSpec('docs', 'model')` — XLA partitions the
    row-axis gathers with model-axis collectives), batch columns ride
    the doc axis replicated over model. Skipped (None) when the
    shapes don't divide the grid — placement is an optimization, the
    fold is bit-identical either way."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    K = int(tables.n_rows.shape[0])
    cap = int(tables.buf_start.shape[1])
    if K % plane.docs or cap % plane.model:
        return None
    mesh = plane.mesh

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))

    def put_table(a):
        spec = (P("docs") if a.ndim == 1
                else P("docs", "model", *([None] * (a.ndim - 2))))
        return put(a, spec)

    def put_batch(a):
        return put(a, P("docs", *([None] * (a.ndim - 1))))

    return (
        jax.tree_util.tree_map(put_table, tables),
        jax.tree_util.tree_map(put_batch, stacked),
    )


def _fold_jobs(jobs: List[tuple], plane=None) -> None:
    """Drain the pending encoded rows of several replicas through the
    merge-tree kernel, STACKING same-shape replicas into one vmapped
    `apply_op_batch_docs_jit` dispatch per round — the docs axis is
    embarrassingly parallel, so K summarizing docs cost one device
    call, not K (the `stack_replicas` idiom on the live stream).
    `plane` (a `parallel.device_plane.DevicePlane`) lays the stacked
    dispatch over the 2-D pool: docs on the ``docs`` axis, table rows
    on ``model``."""
    import jax
    import jax.numpy as jnp

    from ..ops.mergetree_kernel import (
        apply_op_batch_docs_jit,
        apply_op_batch_jit,
    )

    reps = [rep for rep, _ in jobs]
    while any(r._encoded for r in reps):
        groups: Dict[tuple, list] = {}
        for r in reps:
            if not r._encoded:
                continue
            r._ensure_capacity()
            groups.setdefault((r.capacity, r.chunk_size), []).append(r)
        for (_cap, chunk_b), grp in groups.items():
            chunks = []
            for r in grp:
                chunks.append(r._encoded[:chunk_b])
                del r._encoded[:chunk_b]
            batches = [r._build_batch(c) for r, c in zip(grp, chunks)]
            if len(grp) == 1:
                grp[0].table = apply_op_batch_jit(grp[0].table, batches[0])
            else:
                stack = lambda *xs: jnp.stack(xs)  # noqa: E731
                tables = jax.tree_util.tree_map(
                    stack, *[r.table for r in grp]
                )
                stacked = jax.tree_util.tree_map(stack, *batches)
                if plane is not None:
                    placed = _place_fold_stack(tables, stacked, plane)
                    if placed is not None:
                        tables, stacked = placed
                out = apply_op_batch_docs_jit(tables, stacked)
                for i, r in enumerate(grp):
                    r.table = jax.tree_util.tree_map(
                        lambda a, _i=i: a[_i], out
                    )
            for r, c in zip(grp, chunks):
                r._applied_min_seq = c[-1][10]
                r._applied_since_compact = True
                if (r._pending_rows_bound
                        > r.capacity * r.compact_watermark):
                    # The zamboni watermark `KernelReplica._flush_chunks`
                    # applies: without it a long fold accumulates
                    # tombstones/splits and the O(capacity)-per-op
                    # kernel goes quadratic in log length. Deterministic
                    # (a pure function of the fold sequence), and the
                    # canonical row serialization is invariant under
                    # compaction history by construction.
                    r.compact()


def _canonical_rows(rep, msn: int) -> List[list]:
    """The canonical serialized row form of a replica's table at fold
    msn `msn` — a pure function of the document's op prefix:

    - tombstones removed at/below `msn` are dropped (zamboni: invisible
      to every refSeq >= msn perspective);
    - rows inserted at/below `msn` normalize (ins_seq, ins_client) to
      (UNIVERSAL_SEQ, NO_CLIENT) — their visibility is certain for
      every future perspective, so the real stamps carry no semantics;
    - adjacent rows whose semantic fields all match merge into maximal
      runs, erasing split/chunk/checkpoint history from the bytes.

    Each row: ``[text, ins_seq, ins_client, rem_seq|None,
    rem_clients|None, props|None]``."""
    import jax
    import numpy as np

    from ..core.overlay_fold import merge_canonical_rows
    from ..ops.mergetree_kernel import NOT_REMOVED, raise_kernel_errors
    from ..protocol.constants import NO_CLIENT, UNIVERSAL_SEQ

    t = jax.tree_util.tree_map(np.asarray, rep.table)
    raise_kernel_errors(int(t.error))
    text = rep.arena.snapshot()
    raw: List[tuple] = []
    for i in range(int(t.n_rows)):
        rem = int(t.rem_seq[i])
        removed = rem != NOT_REMOVED
        if removed and rem <= msn:
            continue  # zamboni: tombstone below the window
        seg = text[int(t.buf_start[i]): int(t.buf_start[i])
                   + int(t.length[i])]
        ins = int(t.ins_seq[i])
        icl = int(t.ins_client[i])
        if ins <= msn:
            ins, icl = UNIVERSAL_SEQ, NO_CLIENT
        rcl = (sorted(int(c) for c in t.rem_clients[i]
                      if int(c) != NO_CLIENT) if removed else None)
        props = rep.props.decode_row(t.props[i])
        raw.append((seg, ins, icl, rem if removed else None, rcl,
                    props))
    # The merge rule is SHARED with the overlay fold backend
    # (`core.overlay_fold.merge_canonical_rows`) — one definition, so
    # the two backends cannot drift apart on the bytes.
    return merge_canonical_rows(raw)


# ---------------------------------------------------------------------------
# the supervised role
# ---------------------------------------------------------------------------


class SummarizerRole(_Role):
    """deltas → summaries: the scriptorium-offload summary lambda.

    Composes with the whole PR-1 machinery unchanged: fenced lease,
    heartbeat, checkpoint cadence, and the exactly-once ``inOff``
    recovery — manifests are ordinary outputs of their trigger input
    line, so a crash between the manifest append and the checkpoint
    replays silently and re-emits only the clipped tail. Blob puts are
    content-addressed (idempotent), so recovery re-putting a blob is a
    no-op with the same handle: **restarts cannot fork a summary**.

    Runs per-partition under `partitioned_role_class` (``deltas-p{k}``
    → ``summaries-p{k}``) for the static sharded fabric, and per RANGE
    under `shard_fabric.ranged_role_class` on the ELASTIC fabric: the
    fold state is a flat per-doc map, so a split/merge successor
    absorbs its predecessors' fold dicts sliced to its hash range
    through the generic `_RangedMixin` machinery (seed from the final
    fenced checkpoints, fence-bind on the pred manifest topics, silent
    replay of the durable prefix, missing manifests re-emitted
    exactly-once) — summaries ride every topology.

    Manifests additionally carry ``byteOff`` — the LOGICAL deltas-topic
    byte position at the start of the trigger's input batch (None when
    the emission came from recovery replay or a predecessor drain,
    where no own-topic anchor exists). It is a hard lower bound for
    the catch-up tail seek (`read_catchup` feeds it to the backward
    scans as ``stop_at``), stable under op-log truncation.

    Around each emission round the role PINS the summary store
    (`server.retention.write_pin`) until the round's manifests are
    durably appended: the retention plane's castore GC never sweeps a
    blob newer than the oldest live pin, closing the put→manifest
    race without coordinating with the sweeper."""

    name = "summarizer"
    in_topic_name = "deltas"
    out_topic_name = "summaries"

    def __init__(self, *a, summary_ops: Optional[int] = None,
                 store=None, historian_budget: int = 64 * 1024 * 1024,
                 fold_backend: Optional[str] = None,
                 device_plane=None,
                 fold_interpret: Optional[bool] = None,
                 **kw):
        super().__init__(*a, **kw)
        self.summary_ops = int(summary_ops or _summary_ops_default())
        if self.summary_ops < 1:
            raise ValueError(f"summary_ops must be >= 1: {summary_ops}")
        # Fold backend + device plane (resolved LAZILY: both touch jax
        # and the role must construct cheaply in scalar/no-mergetree
        # farms). `device_plane` is a spec/`DevicePlane`; None falls
        # back to the process env (`parallel.device_plane.PLANE_ENV`)
        # so supervised children inherit the farm plane.
        requested = fold_backend or _fold_backend_default()
        if requested not in FOLD_BACKENDS:
            raise ValueError(
                f"fold_backend {requested!r} not in {FOLD_BACKENDS}"
            )
        self._fold_backend_requested = requested
        self._fold_backend: Optional[str] = None
        self.fold_interpret = (
            bool(fold_interpret) if fold_interpret is not None
            else _fold_interpret_default()
        )
        self._plane_arg = device_plane
        self._plane_resolved = False
        self._plane = None
        self.store = store if store is not None else open_summary_store(
            self.shared_dir, historian_budget
        )
        # doc -> fold dict (JSON-serializable; live replicas cached
        # separately and rebuilt lazily from the serialized rows).
        self.docs: Dict[str, dict] = {}
        self._reps: Dict[str, Any] = {}
        # (doc, line_idx, window_upto, seq, msn, count) — the pending
        # emission points of this pump, folded/emitted in flush_batch.
        self._triggers: List[tuple] = []
        m = self.metrics
        labels = self._metric_labels()
        self._m_summaries = m.counter("summaries_emitted_total", **labels)
        self._m_blob_bytes = m.counter("summary_blob_bytes_total",
                                       **labels)
        self._m_fold_ops = m.counter("summary_fold_ops_total", **labels)
        self._m_stacked = m.counter("summary_stacked_folds_total",
                                    **labels)
        self._m_frozen = m.counter("summary_docs_frozen_total", **labels)
        self._m_docs = m.gauge("summary_docs", **labels)
        self._m_build_ms = m.histogram("summary_build_ms", **labels)
        self._m_plane_folds = m.counter("summary_plane_folds_total",
                                        **labels)

    # --------------------------------------------------- fold backend

    def fold_backend(self) -> str:
        """The fold backend, checked once: "overlay" runs only where
        the overlay-pallas engine can (a TPU backend, or the
        interpreter when `fold_interpret` asks for the CPU-CI
        correctness mode); asking for it anywhere else raises — the
        summarizer never switches backends behind the caller's back."""
        if self._fold_backend is None:
            backend = self._fold_backend_requested
            if backend == "overlay":
                from ..core.overlay_fold import overlay_available

                if not overlay_available(self.fold_interpret):
                    import jax

                    raise RuntimeError(
                        f"fold_backend=overlay cannot run on the "
                        f"{jax.default_backend()!r} backend without "
                        f"the interpreter (fold_interpret=False); run "
                        f"on a TPU, set fold_interpret=True, or ask "
                        f"for fold_backend='kernel'"
                    )
            self._fold_backend = backend
            self.metrics.gauge(
                "summary_fold_backend", backend=backend,
                **self._metric_labels()
            ).set(1)
        return self._fold_backend

    def device_plane(self):
        """The farm's 2-D device plane (None when unconfigured):
        explicit arg wins, else the process env — the supervisor's
        child_env seam (`--device-plane`/`FLUID_DEVICE_PLANE`)."""
        if not self._plane_resolved:
            from ..parallel.device_plane import resolve_plane

            self._plane = resolve_plane(self._plane_arg, env=True)
            self._plane_resolved = True
        return self._plane

    def _boot_rep(self, rows: List[list], msn: int):
        if self.fold_backend() == "overlay":
            from ..core.overlay_fold import boot_overlay

            return boot_overlay(rows, msn,
                                interpret=self.fold_interpret)
        return _boot_mergetree(rows, msn)

    def _dispatch_fold(self, fold_jobs: List[tuple]) -> None:
        plane = self.device_plane()
        if plane is not None:
            self._m_plane_folds.inc()
        if self.fold_backend() == "overlay":
            from ..core.overlay_fold import fold_jobs_overlay

            fold_jobs_overlay(fold_jobs, plane=plane,
                              interpret=self.fold_interpret)
        else:
            _fold_jobs(fold_jobs, plane=plane)

    def _rows_of(self, rep, msn: int) -> List[list]:
        """Canonical rows at `msn` — backend-dispatched, identical
        bytes by contract (the content-addressed no-fork invariant)."""
        if self.fold_backend() == "overlay":
            return rep.canonical_rows(msn)
        return _canonical_rows(rep, msn)

    # ------------------------------------------------------------ state

    def snapshot_state(self) -> Any:
        # A FLAT {doc: fold} map — the shape `_RangedMixin` slices by
        # hash range when an elastic successor absorbs this role's
        # final checkpoint (every ranged role's state contract).
        return dict(self.docs)

    def restore_state(self, state: Any) -> None:
        state = dict(state or {})
        if set(state) == {"docs"} and isinstance(state["docs"], dict) \
                and all(isinstance(v, dict) and "count" in v
                        for v in state["docs"].values()):
            # Pre-retention checkpoint shape ({"docs": {...}}): unwrap.
            state = dict(state["docs"])
        self.docs = state
        self._reps = {}
        self._triggers = []

    # ------------------------------------------------------------- fold

    def _fold(self, doc: str) -> dict:
        f = self.docs.get(doc)
        if f is None:
            f = self.docs[doc] = {
                "seq": 0, "msn": 0, "count": 0, "engine": None,
                "window": [], "records": [],
                "base": 0, "base_msn": 0, "rows": [],
                "last": None,
            }
            self._m_docs.set(len(self.docs))
        return f

    def _rep(self, doc: str, f: dict):
        rep = self._reps.get(doc)
        if rep is None:
            rep = self._reps[doc] = self._boot_rep(
                f["rows"], f["base_msn"]
            )
        return rep

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or rec.get("kind") != "op" \
                or "doc" not in rec:
            return  # nacks / junk: summaries fold sequenced ops only
        f = self._fold(rec["doc"])
        f["seq"] = max(int(f["seq"]), int(rec["seq"]))
        f["msn"] = max(int(f["msn"]), int(rec["msn"]))
        f["count"] = int(f["count"]) + 1
        c = canonical_record(rec)
        if f["engine"] is None and rec.get("type") == "op":
            f["engine"] = ("mergetree"
                           if _decode_mt_op(rec.get("contents"))
                           is not None else "ops")
            if f["engine"] == "ops":
                # Generic doc: the whole history is the state.
                f["records"].extend(f["window"])
                f["window"] = []
        if f["engine"] == "ops":
            f["records"].append(c)
        else:  # mergetree / undecided / frozen: buffer the window
            f["window"].append(c)
        if f["engine"] in ("mergetree", "ops") and \
                f["count"] % self.summary_ops == 0:
            # Snapshot the fold-prefix lengths AT the trigger: records
            # later in the same pump belong to the NEXT summary, and a
            # blob cut anywhere else would depend on pump boundaries —
            # the determinism the content-addressed no-fork contract
            # rests on. A cadence point reached while the engine is
            # still UNDECIDED (>= summary_ops joins/leaves before the
            # first op) is skipped outright — an engine decided later
            # in the same pump would otherwise emit an empty blob, and
            # one decided in a later pump would leave a dangling
            # trigger; both deterministic only by accident. Skipping
            # is itself deterministic (a pure function of the
            # stream), and join/leave records carry no summarizable
            # state beyond the (seq, msn, count) head.
            self._triggers.append((
                rec["doc"], line_idx, len(f["window"]),
                len(f["records"]), f["seq"], f["msn"], f["count"],
                # The input batch's start byte (logical; None in
                # recovery replay / pred drains): every record of this
                # doc below it is at/below this summary's seq, so the
                # manifest's byteOff bounds the catch-up tail seek.
                self._in_pos,
            ))

    # ------------------------------------------------------- emission

    def _freeze(self, doc: str, f: dict, why: str) -> None:
        """A doc whose stream stopped folding (undecodable op, kernel
        error, prop overflow): stop emitting summaries for it — a
        frozen doc falls back to longer tails, never to a wrong
        summary. Loud in the metrics, not in the stream."""
        f["engine"] = "frozen"
        f["window"] = []
        f["rows"] = []
        self._reps.pop(doc, None)
        self._m_frozen.inc()
        print(f"summarizer: froze {doc} ({why})", flush=True)

    def flush_batch(self, out: List[dict]) -> None:
        if not self._triggers:
            return
        import time as _time

        from .retention import write_pin

        # GC epoch pin: blobs put from here on may not be referenced
        # by a durable manifest yet — the retention sweeper spares
        # everything newer than this instant until the pin clears
        # (after this round's outputs are appended, or on expiry if we
        # die — recovery's silent replay re-puts the blobs before the
        # clipped manifests are re-emitted, so expiry is safe).
        self._pin_t = write_pin(self.shared_dir, self.name)
        self._pin_hb = self._pin_t
        self._pinned = True
        t0 = _time.perf_counter()
        triggers, self._triggers = self._triggers, []
        consumed: Dict[str, int] = {}
        # Group consecutive triggers of DISTINCT docs into one stacked
        # fold round; a doc triggering twice in one pump starts a new
        # round (its second fold depends on its first).
        i = 0
        while i < len(triggers):
            round_jobs: List[tuple] = []
            round_docs: set = set()
            j = i
            while j < len(triggers) and triggers[j][0] not in round_docs:
                round_docs.add(triggers[j][0])
                round_jobs.append(triggers[j])
                j += 1
            self._emit_round(round_jobs, consumed, out)
            i = j
        self._m_build_ms.observe((_time.perf_counter() - t0) * 1000.0)

    def _refresh_pin(self) -> None:
        # Heartbeat the GC pin mid-round: rewriting with the ORIGINAL
        # floor keeps blobs put earlier in the round covered while the
        # file mtime proves this writer is alive — a round longer than
        # retention.PIN_TTL_S must not lose its early puts to the sweep.
        # Time-gated: liveness runs on the TTL clock, so the hot
        # emission path only pays the file rewrite every TTL/4, not
        # per blob put.
        if getattr(self, "_pinned", False):
            import time as _time

            from .retention import PIN_TTL_S, write_pin

            now = _time.time()
            if now - getattr(self, "_pin_hb", 0.0) < PIN_TTL_S / 4.0:
                return
            self._pin_hb = now
            write_pin(self.shared_dir, self.name, self._pin_t)

    def _unpin(self) -> None:
        if getattr(self, "_pinned", False):
            from .retention import clear_pin

            clear_pin(self.shared_dir, self.name)
            self._pinned = False

    def _append_outputs(self, out: List[dict]) -> int:
        n = super()._append_outputs(out)
        # The round's manifests are durable: release the GC pin.
        self._unpin()
        return n

    def _take_checkpoint(self, background: bool) -> None:
        super()._take_checkpoint(background)
        # Recovery and pred drains append outside `_append_outputs`;
        # both checkpoint right after, so the pin never outlives the
        # round however the manifests landed.
        self._unpin()

    def _emit_round(self, round_jobs: List[tuple],
                    consumed: Dict[str, int], out: List[dict]) -> None:
        self._refresh_pin()
        fold_jobs: List[tuple] = []
        for doc, _line, upto, _rupto, _seq, msn, _count, _bo \
                in round_jobs:
            f = self.docs[doc]
            if f["engine"] != "mergetree":
                continue
            done = consumed.get(doc, 0)
            take = f["window"][: upto - done]
            rep = self._rep(doc, f)
            try:
                _encode_fold(rep, take)
            except (ValueError, TypeError) as exc:
                self._freeze(doc, f, repr(exc))
                continue
            self._m_fold_ops.inc(len(take))
            fold_jobs.append((rep, take))
        if len(fold_jobs) > 1:
            self._m_stacked.inc(len(fold_jobs))
        if fold_jobs:
            self._dispatch_fold(fold_jobs)
        for doc, line_idx, upto, rec_upto, seq, msn, count, byte_off \
                in round_jobs:
            f = self.docs[doc]
            if f["engine"] == "frozen":
                continue
            done = consumed.get(doc, 0)
            if f["engine"] == "mergetree":
                rep = self._reps.get(doc)
                if rep is None:
                    continue  # froze mid-round
                try:
                    rows = self._rows_of(rep, msn)
                except RuntimeError as exc:  # kernel error flag
                    self._freeze(doc, f, repr(exc))
                    continue
                del f["window"][: upto - done]
                consumed[doc] = upto
                f["rows"] = rows
                f["base"] = seq
                f["base_msn"] = msn
                # Rebuild from the serialized form — the restart path,
                # exercised every cadence, so a crashed-and-restored
                # summarizer can never diverge from this one.
                self._reps[doc] = self._boot_rep(rows, msn)
                blob = {"form": "mergetree", "doc": doc, "seq": seq,
                        "msn": msn, "count": count, "rows": rows}
            elif f["engine"] == "ops":
                blob = {"form": "ops", "doc": doc, "seq": seq,
                        "msn": msn, "count": count,
                        "records": list(f["records"][:rec_upto])}
            else:
                continue  # undecided: nothing but joins/leaves yet
            payload = json.dumps(
                blob, sort_keys=True, separators=(",", ":")
            ).encode()
            self._refresh_pin()
            handle = self._durable(lambda: self.store.put(payload))
            f["last"] = {"seq": seq, "handle": handle}
            self._m_summaries.inc()
            self._m_blob_bytes.inc(len(payload))
            out.append({
                "kind": "summary", "doc": doc, "seq": seq, "msn": msn,
                "count": count, "form": blob["form"], "handle": handle,
                "bytes": len(payload), "off": line_idx,
                # Byte-offset hint for the O(tail) catch-up seek
                # (None: recovery replay / pred drain — readers fall
                # back to the unbounded backward scan). byteTopic
                # names the byte space: on the elastic fabric a
                # ranged summarizer's offsets are meaningless in any
                # OTHER range's topic, so readers use the floor only
                # when the topic they scan matches.
                "byteOff": byte_off,
                "byteTopic": self.in_topic_name,
                "inOff": line_idx,
            })


# ---------------------------------------------------------------------------
# readers: manifest index, boot replica, catch-up
# ---------------------------------------------------------------------------


class SummaryIndex:
    """Tail of the ``summaries`` topic(s): newest manifest per doc ≤ a
    requested seq. One topic read answers every reader — the discovery
    surface of the summary service. `partitions` adds the static
    fabric's ``summaries-p{k}`` siblings to the tail set."""

    def __init__(self, shared_dir: str, log_format: Optional[str] = None,
                 partitions: int = 1,
                 topics: Optional[List[str]] = None):
        """`topics` names the manifest topics explicitly (the ELASTIC
        fabric's per-range ``summaries-{rid}`` set across the topology
        history — `ShardRouter.stage_topic_names("summaries")`);
        `partitions` keeps the static fabric's ``summaries-p{k}``
        shorthand."""
        import threading

        from .queue import partition_suffix

        if topics is not None:
            names = list(topics)
        else:
            names = ["summaries"]
            if partitions > 1:
                names += [partition_suffix("summaries", k)
                          for k in range(partitions)]
        self._readers = [
            make_tail_reader(make_topic(
                os.path.join(shared_dir, "topics", f"{n}.jsonl"),
                log_format,
            ))
            for n in names
        ]
        # doc -> manifests sorted by seq (appends are seq-monotone per
        # doc within a topic; merged across topics defensively). One
        # index is shared across FarmReadServer's session THREADS: the
        # tail readers' read-modify-write and the manifest lists go
        # under a lock, or racing polls double-deliver or strand
        # reader positions.
        self.manifests: Dict[str, List[dict]] = {}
        self._lock = threading.Lock()

    def poll(self) -> int:
        n = 0
        with self._lock:
            for r in self._readers:
                for _, rec in r.poll():
                    if not isinstance(rec, dict) or \
                            rec.get("kind") != "summary":
                        continue
                    lst = self.manifests.setdefault(rec["doc"], [])
                    lst.append(rec)
                    if len(lst) > 1 and lst[-2]["seq"] > rec["seq"]:
                        lst.sort(key=lambda m: m["seq"])
                    n += 1
        return n

    def nearest(self, doc: str, seq: Optional[int] = None
                ) -> Optional[dict]:
        """Newest manifest for `doc` with ``manifest.seq <= seq``
        (no bound: the newest overall)."""
        with self._lock:
            lst = list(self.manifests.get(doc) or ())
        if not lst:
            return None
        if seq is None:
            return lst[-1]
        best = None
        for m in lst:
            if m["seq"] <= seq:
                best = m
            else:
                break
        return best


class SummaryReplica:
    """A reader-side replica booted from a summary blob (or cold).

    The join path under test: boot from ``blob`` then
    ``apply_records(tail)`` must be bit-identical — per
    `state_digest` — to a cold boot applying the full log. Cold boots
    decide their engine exactly like the summarizer (first op's
    contents), so the differential compares like with like."""

    def __init__(self, blob: Optional[dict] = None):
        self.form = blob["form"] if blob else None
        self.seq = int(blob["seq"]) if blob else 0
        self.msn = int(blob["msn"]) if blob else 0
        self.count = int(blob.get("count", 0)) if blob else 0
        self._rep = None
        self.records: List[dict] = []
        # Canonical records seen before the engine is decided (a cold
        # boot's joins/leaves ahead of the first op).
        self._prefix: List[dict] = []
        if blob is None:
            return
        if self.form == "mergetree":
            self._rep = _boot_mergetree(blob["rows"], self.msn)
        elif self.form == "ops":
            self.records = [dict(r) for r in blob["records"]]
        else:
            raise ValueError(f"unknown summary form {self.form!r}")

    def apply_records(self, records: List[dict]) -> int:
        """Apply sequenced wire records (kind == "op") past the boot
        point; duplicates at/below the current seq drop (the reader's
        half of the exactly-once boundary). Merge-tree folding batches
        the whole call into chunked kernel dispatches."""
        pending_mt: List[dict] = []
        n = 0
        for rec in records:
            if not isinstance(rec, dict) or rec.get("kind") != "op":
                continue
            if int(rec["seq"]) <= self.seq:
                continue
            c = canonical_record(rec)
            if self.form is None and rec.get("type") == "op":
                self.form = ("mergetree"
                             if _decode_mt_op(rec.get("contents"))
                             is not None else "ops")
                if self.form == "ops":
                    self.records.extend(self._prefix)
                else:
                    pending_mt.extend(self._prefix)
                self._prefix = []
            if self.form == "mergetree":
                pending_mt.append(c)
            elif self.form == "ops":
                self.records.append(c)
            else:  # undecided: joins/leaves before the first op
                self._prefix.append(c)
            self.seq = int(rec["seq"])
            self.msn = max(self.msn, int(rec["msn"]))
            self.count += 1
            n += 1
        if pending_mt:
            if self._rep is None:
                self._rep = _boot_mergetree([], 0)
            _encode_fold(self._rep, pending_mt)
            _fold_jobs([(self._rep, pending_mt)])
        return n

    # ------------------------------------------------------------ state

    def get_text(self) -> str:
        return self._rep.get_text() if self._rep is not None else ""

    def char_spans(self) -> List[tuple]:
        if self._rep is None:
            return []
        from ..testing.farm import char_spans

        return char_spans(self._rep.annotated_spans())

    def state_digest(self) -> str:
        return state_digest(self)


def state_digest(replica: SummaryReplica) -> str:
    """The GOLDEN-style digest two boots are compared in: document
    state (char-level, so segmentation history is invisible) for
    merge-tree docs, the canonical record stream for generic docs —
    plus the (seq, msn, count) head so a tail boundary off-by-one can
    never hide."""
    if replica.form == "mergetree":
        body: Any = [replica.get_text(), replica.char_spans()]
    else:
        body = replica.records
    payload = json.dumps(
        [replica.seq, replica.msn, replica.count, replica.form, body],
        sort_keys=True, ensure_ascii=True, default=str,
    )
    return hashlib.sha256(payload.encode()).hexdigest()


def _tail_records_reverse(path: str, doc: str, base: int,
                          upto: Optional[int],
                          stop_at: Optional[int] = None) -> List[dict]:
    """`doc`'s op records with ``base < seq [<= upto]`` read BACKWARD
    from the topic's end — O(tail + interleave), not O(log): per-doc
    seqs are append-monotone, so the first own-doc record at/below
    `base` bounds the scan. JSONL topics only (a frame log needs the
    forward walk); the torn-tail rule holds — a final line without
    its newline is never consumed.

    ``stop_at`` (a manifest's ``byteOff`` — a line boundary) floors
    the walk: every own-doc record below it is at/below `base`, so
    the seek is O(tail) even with zero own-doc interleave."""
    stop = max(0, int(stop_at)) if isinstance(stop_at, int) else 0
    out: List[dict] = []
    try:
        f = open(path, "rb")
    except OSError:
        return out
    with f:
        f.seek(0, os.SEEK_END)
        pos = f.tell()
        stop = min(stop, pos)
        block = 1 << 16
        carry = b""
        first = True
        while pos > stop:
            step = min(block, pos - stop)
            pos -= step
            f.seek(pos)
            data = f.read(step) + carry
            parts = data.split(b"\n")
            carry = parts[0]  # partial first line: joins the next block
            lines = parts[1:]
            if first:
                first = False
                if lines and not data.endswith(b"\n"):
                    lines.pop()  # torn tail: invisible until complete
            for raw in reversed(lines):
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    rec = json.loads(raw)
                except ValueError:
                    continue  # sealed junk from a crashed writer
                if not isinstance(rec, dict) or rec.get("doc") != doc \
                        or rec.get("kind") != "op":
                    continue
                s = int(rec["seq"])
                if s <= base:
                    out.reverse()
                    return out
                if upto is None or s <= upto:
                    out.append(rec)
            block = min(block * 2, 1 << 22)
        # Floor reached (file start, or the byteOff line boundary):
        # carry is the (complete) first line of the scanned region —
        # a non-aligned stop leaves a partial line, which simply
        # fails to parse and is skipped (records below the floor are
        # at/below `base` by the byteOff contract anyway).
        raw = carry.strip()
        if raw:
            try:
                rec = json.loads(raw)
                if isinstance(rec, dict) and rec.get("doc") == doc \
                        and rec.get("kind") == "op" \
                        and int(rec["seq"]) > base \
                        and (upto is None or int(rec["seq"]) <= upto):
                    out.append(rec)
            except ValueError:
                pass
    out.reverse()
    return out


def read_catchup(shared_dir: str, doc: str,
                 log_format: Optional[str] = None,
                 seq: Optional[int] = None,
                 index: Optional[SummaryIndex] = None,
                 store=None,
                 deltas_topic: str = "deltas") -> dict:
    """Answer a cold join from the farm's topics: nearest summary ≤
    `seq` (manifest + blob) plus the op tail past it off the deltas
    topic — the read that replaces full-log replay. Returns
    ``{"manifest", "blob", "ops"}`` (manifest/blob None when no
    summary exists yet — the tail is then the whole log).

    With a summary present the tail is read BACKWARD from the topic's
    end (O(tail), so the join cost is flat in log length) on BOTH
    log formats: JSONL via the line scan below,
    columnar via the frame-chaining scan
    (`columnar_log.tail_records_reverse`), which falls back to the
    forward skip from the manifest's `off` only when it cannot anchor
    (a pre-sidecar or JSON-era-prefix file)."""
    from .columnar_log import ColumnarFileTopic, tail_records_reverse

    idx = index or SummaryIndex(shared_dir, log_format)
    idx.poll()
    man = idx.nearest(doc, seq)
    blob = None
    swept = False
    if man is not None:
        st = store or open_summary_store(shared_dir)
        try:
            blob = json.loads(st.get(man["handle"]).decode())
        except KeyError:
            # Castore GC swept this manifest's blob: it fell below
            # the doc's retention root set (only the newest
            # ``keep_summaries`` manifests stay referenced, while a
            # quiet doc can hold the manifest-topic cut back far
            # enough for older ones to stay discoverable). Fall to
            # the full-replay path — honest only while the op log
            # still holds the doc's whole history, checked below.
            man, swept = None, True
    topic = make_topic(
        os.path.join(shared_dir, "topics", f"{deltas_topic}.jsonl"),
        log_format,
    )
    if man is None and (swept or seq is not None):
        # No usable summary at/below the requested seq. A replay from
        # logical 0 silently resumes at the truncation base, so if the
        # doc IS summarized (its covered prefix may be physically
        # reclaimed) and the log has a cut, partial state would come
        # back as if complete — refuse loudly instead. Docs with no
        # summary at all never pass the retention coverage clamp, so
        # their history is structurally intact whatever the base.
        base_gone = (topic.base_offsets()[0] > 0
                     if hasattr(topic, "base_offsets") else False)
        if base_gone and (swept or idx.nearest(doc) is not None):
            raise LookupError(
                f"catchup({doc!r}, seq={seq}): state below the "
                f"retention horizon — the nearest summary blob was "
                f"garbage-collected and/or the covered op prefix was "
                f"truncated; only the newest summaries are retained"
            )
    base = int(man["seq"]) if man is not None else 0
    ops = None
    if man is not None:
        # The manifest's byteOff (when present) floors the backward
        # walk: O(tail) however sparse the doc's records are in the
        # interleave, truncation-stable (logical bytes) — but ONLY in
        # the byte space it was stamped against (`byteTopic`). A
        # pred-era manifest read through the merged elastic index
        # against a successor range's topic would floor the walk at a
        # foreign offset and silently drop tail ops; mismatch falls
        # back to the unbounded (still correct) scan.
        stop = man.get("byteOff")
        stop = (stop if isinstance(stop, int)
                and man.get("byteTopic") == deltas_topic else None)
        if isinstance(topic, ColumnarFileTopic):
            ops = tail_records_reverse(topic, doc, base, seq,
                                       stop_at=stop)
        else:
            ops = _tail_records_reverse(topic.path, doc, base, seq,
                                        stop_at=stop)
    if ops is None:
        # The manifest's `off` (its trigger's input line) bounds the
        # forward scan: records at/below it are covered.
        reader = make_tail_reader(
            topic, int(man["off"]) + 1 if man is not None else 0
        )
        ops = [
            rec for _, rec in reader.poll()
            if isinstance(rec, dict) and rec.get("kind") == "op"
            and rec.get("doc") == doc and int(rec["seq"]) > base
            and (seq is None or int(rec["seq"]) <= seq)
        ]
    return {"manifest": man, "blob": blob, "ops": ops}


# ---------------------------------------------------------------------------
# in-proc summarizer agent (the LocalServer / tinylicious twin)
# ---------------------------------------------------------------------------


def summarize_document(server, registry, doc_id: str) -> Tuple[str, int]:
    """The reference's summarizer-client shape for the in-proc
    `LocalServer`: resolve the document headless (no join — the
    catch-up tail applies without connecting), upload the runtime
    summary, and point the doc's ref at it, so every later
    `Loader.resolve` boots from this summary plus only the op tail.
    Returns ``(handle, base_seq)``."""
    from ..drivers.local_driver import LocalDriver
    from ..loader.container import Loader

    loader = Loader(LocalDriver(server), registry)
    c = loader.resolve(doc_id, connect=False)
    try:
        wire = c.runtime.summarize().to_json()
        base_seq = int(c.runtime.current_seq)
    finally:
        c.close()
    handle = server.upload_summary(wire)
    server.storage.set_ref(doc_id, handle)
    return handle, base_seq
