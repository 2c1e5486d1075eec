"""Batched TPU deli: the vmap'd sequencer kernel wired into the LIVE
ordering pipeline.

The scalar deli (`lambdas.DeliLambda` in-proc, `supervisor.DeliRole`
in the supervised farm) tickets one raw record at a time through a
per-document `DocumentSequencer`. This module re-expresses that hot
loop the way BASELINE config 5 demands (10k docs x 64 clients batched
per kernel call): a pump drains the raw topic in micro-batches, maps
string doc-ids to dense document slots, packs the submissions into
columnar `SeqBatch` arrays, runs the vmap'd
`ops.sequencer_kernel.sequence_batch` over the document axis on
device, and scatters the stamped messages / nacks back out via ONE
`append_many` per pump.

Division of labor (the correctness spine):

- **Decisions on device** — stamp/nack/skip verdicts (including boxcar
  aborts and resubmission dedup) come from the kernel, bit-identical
  to the scalar oracle by the differential gates
  (tests/test_sequencer_kernel.py, tests/test_deli_kernel.py).
- **Bookkeeping from results** — the host keeps a per-doc mirror
  (head seq, MSN, connected clients' ref/client seqs) updated ONLY
  from kernel verdicts, never by re-deriving decisions. The mirror
  makes checkpoints pure host work (no [D, C] device pulls) in the
  SAME format as `DocumentSequencer.checkpoint()`, so scalar and
  kernel delis restore each other's checkpoints — the scalar path is
  both the oracle and the fallback.

Doc slots grow by doubling and evict for free: parking a document just
frees its slot (the mirror is authoritative for parked docs); touching
it again reloads the state row (`SEQ_POOL_LOAD_PROGRAM`) before the
next kernel call. A `KernelDeliRole` bounds residency by what it
observes (`KernelDeliRole.residency_budget`), and its pool shrinks to
that budget once it holds twice as many rows.

Two frontends wrap the shared `PackedDeliCore`:

- `KernelDeliLambda` — drop-in for the in-proc `DeliLambda`
  (`LocalServer(deli_impl="kernel")` or env `FLUID_DELI=kernel`):
  same deltas entries (`SequencedMessage`/`NackMessage`), same
  checkpoint shape, boxcar atomicity and the system-message control
  path included.
- `KernelDeliRole` — drop-in for the supervised farm's `DeliRole`
  (`--impl kernel`): same wire records with per-record `inOff`, so PR
  1's fenced exactly-once recovery (scan the output topic for the
  durable prefix, silently replay the gap) composes unchanged — a
  supervisor restart mid-batch must not re-stamp, and the chaos
  harness proves it converges bit-identical to the scalar golden.

This module imports jax at import time by design; the scalar paths
(`lambdas`, `supervisor`) import it lazily so scalar farms never pay
the cost.
"""

from __future__ import annotations

import functools
import json
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..ops import sequencer_kernel as _sk
from ..ops.sequencer_kernel import (
    NO_GROUP,
    SUB_JOIN,
    SUB_LEAVE,
    SUB_OP,
    SUB_SYSTEM,
)
from ..protocol import record_batch as _rb
from ..protocol.messages import (
    MessageType,
    NackMessage,
    SequencedMessage,
    trace_submit_ts,
)
from ..utils.metrics import get_registry, named_program, span
from .log import LogConsumer, MessageLog
from .queue import EncodedJson
from .sequencer import (
    NACK_FUTURE_REFSEQ,
    NACK_STALE_REFSEQ,
    NACK_UNKNOWN_CLIENT,
    future_refseq_reason,
    out_of_order_reason,
    stale_refseq_reason,
)
from .supervisor import _Role

__all__ = [
    "KernelDeliLambda",
    "KernelDeliRole",
    "PackedDeliCore",
    "RESIDENT_FLOOR",
    "RESIDENT_HORIZON_S",
    "SEQ_POOL_LOAD_PROGRAM",
    "SeqPool",
]

SYSTEM_CLIENT = -1  # mirrors lambdas.SYSTEM_CLIENT (import would cycle)

# The residency rule of every `KernelDeliRole`: the pool holds at most
# the power of two covering twice the documents its pumps touched over
# the last RESIDENT_HORIZON_S seconds, and never fewer than
# RESIDENT_FLOOR (a partition of up to that many documents keeps them
# all resident, so the rule parks nothing there).
RESIDENT_HORIZON_S = 1.0
RESIDENT_FLOOR = 16384

# The XLA module name of the pool's row reload (``jit_seq_pool_load``):
# profiler readers find its device time by this name.
SEQ_POOL_LOAD_PROGRAM = "seq_pool_load"
LOAD_MIN_ROWS = 8  # the smallest reload bucket


@functools.partial(jax.jit, donate_argnums=0)
@named_program(SEQ_POOL_LOAD_PROGRAM)
def _load_rows(state, idx, seq, min_seq, connected, ref_seq, client_seq):
    """Write whole state rows: row ``idx[i]`` takes row i of every
    field. Padding rows carry an index past the last row and are
    dropped."""
    def put(a, v):
        return a.at[idx].set(v, mode="drop")

    return _sk.SequencerState(
        seq=put(state.seq, seq), min_seq=put(state.min_seq, min_seq),
        connected=put(state.connected, connected),
        ref_seq=put(state.ref_seq, ref_seq),
        client_seq=put(state.client_seq, client_seq),
    )


def _pow2(n: int, lo: int = 8) -> int:
    p = lo
    while p < n:
        p <<= 1
    return p


def _mul_of(n: int, m: int) -> int:
    """n rounded UP to a multiple of m (the doc-axis shard constraint:
    every device owns the same number of slot rows)."""
    return n if m <= 1 else ((n + m - 1) // m) * m


def mesh_for_devices(deli_devices: Optional[int]):
    """The device mesh a `deli_devices=N` seam resolves to: None for
    the single-device pool (N absent / 1), else the process-wide
    shared 1-D docs mesh over the default backend's first N devices
    (`parallel.mesh.make_docs_mesh` raises when they do not exist)."""
    if deli_devices is None or int(deli_devices) <= 1:
        return None
    from ..parallel.mesh import shared_docs_mesh

    return shared_docs_mesh(int(deli_devices))


def mesh_for_plane(device_plane, plane_column: Optional[int] = None,
                   partition_key=None, env: bool = False):
    """The sequencer's TYPED SLICE of a 2-D device plane
    (`parallel.device_plane.DevicePlane`): a 1-D docs mesh over one
    model column — one partition = one worker = one mesh slice. The
    column is explicit (`plane_column`), derived from the partition
    key (stable hash), or 0; `env=True` lets farm children inherit
    the supervisor's plane from ``FLUID_DEVICE_PLANE`` with no argv
    plumbing. Returns None when no plane is configured."""
    from ..parallel.device_plane import plane_column_of, resolve_plane

    plane = resolve_plane(device_plane, env=env)
    if plane is None:
        return None
    if plane_column is None:
        plane_column = (plane_column_of(partition_key, plane.model)
                        if partition_key is not None else 0)
    return plane.seq_mesh(plane_column)


def _nack_reason(code: int, ref: int, msn: int, head: int, cseq: int,
                 expected: Optional[int]) -> str:
    """The scalar sequencer's nack wording (shared helpers in
    server/sequencer.py), reconstructed from the kernel verdict + host
    mirror (codes are the contract; text is for humans)."""
    if code == NACK_UNKNOWN_CLIENT:
        return "unknown client"
    if code == NACK_STALE_REFSEQ:
        return stale_refseq_reason(ref, msn)
    if code == NACK_FUTURE_REFSEQ:
        return future_refseq_reason(ref, head)
    if expected is not None:
        return out_of_order_reason(cseq, expected)
    return f"clientSeq {cseq} out of order"


def _doc_state(doc_id: str, h: dict) -> dict:
    """One mirror entry in `DocumentSequencer.checkpoint()` format."""
    return {
        "doc_id": doc_id,
        "seq": h["seq"],
        "min_seq": h["min_seq"],
        "clients": {
            str(cid): {
                "ref_seq": rc[0], "client_seq": rc[1], "last_update": 0.0,
            }
            for cid, rc in h["clients"].items()
        },
    }


def _encode_doc(doc_id: str, h: dict) -> bytes:
    """``, "<doc_id>": <state>``: the item `json.dumps` writes for this
    document inside the checkpoint's per-doc map, after the separator
    that precedes every item but the first. Integer fields format
    directly; any other value (or key) takes `json.dumps` itself."""
    seq, msn = h["seq"], h["min_seq"]
    if type(doc_id) is str and type(seq) is int and type(msn) is int:
        try:
            clients = ", ".join([
                f'"{cid}": {{"ref_seq": {r}, "client_seq": {c}, '
                f'"last_update": 0.0}}'
                if type(cid) is int and type(r) is int and type(c) is int
                else None  # join raises: the json.dumps path below
                for cid, (r, c) in h["clients"].items()])
        except (TypeError, ValueError):
            pass
        else:
            k = json.dumps(doc_id)
            return (f', {k}: {{"doc_id": {k}, "seq": {seq}, "min_seq": '
                    f'{msn}, "clients": {{{clients}}}}}').encode()
    item = json.dumps({doc_id: _doc_state(doc_id, h)})
    return (", " + item[1:-1]).encode()


class SeqPool:
    """Dense [D, C] kernel-state pool with doc-slot grow/evict and
    scalar-format checkpoints.

    The device state is authoritative for VERDICTS; `docs` is the host
    mirror (seq head, MSN, per-client ref/client seqs) maintained from
    verdicts, authoritative for CHECKPOINTS and for parked (evicted)
    documents. Slots are recycled: parking costs nothing (the row is
    overwritten on the next load), touching a parked doc queues a row
    reload that flushes in one batched write before the next run.

    Eviction victims come off two queues of the resident documents that
    the current pump has not touched, each oldest-touched first:
    MSN-cold documents (MSN caught up with the head) before lagging
    ones. A document's MSN and head change only in a pump that touches
    it, so `begin` files the previous pump's documents once, and a pick
    is O(1).

    Checkpoints encode incrementally: every mirror write marks its
    document dirty, and `encode_docs` re-encodes only those, reusing
    every other document's encoded item from the last checkpoint.
    Likewise the compaction sweep visits only the documents whose
    column map gained a column or whose clients lost one.
    """

    def __init__(self, n_docs: int = 8, n_clients: int = 8,
                 max_resident: Optional[int] = None, mesh=None,
                 labels: Optional[Dict[str, str]] = None):
        """`mesh` (a 1-D `jax.sharding.Mesh` over a ``docs`` axis, see
        `parallel.mesh.make_docs_mesh`/`shared_docs_mesh`) shards the
        `[D, C]` pool across its devices: `n_docs` is kept a multiple
        of ``mesh.size`` (every device owns an equal slab of slot
        rows), the kernel call is the shard_map'd
        `ops.sequencer_kernel.sharded_sequence_fn`, and verdicts
        gather once per chunk. The host mirror, slot allocation,
        grow/evict/park, and checkpoint format are IDENTICAL to the
        single-device pool — sharding only changes where slot rows
        live. `labels` label the pool's counters (a role's
        ``{role: name}``)."""
        self.mesh = mesh
        self._n_shards = int(mesh.size) if mesh is not None else 1
        self.n_docs = _mul_of(max(1, n_docs), self._n_shards)
        self.n_clients = _pow2(max(2, n_clients), lo=2)
        self.state = _sk.make_state(self.n_docs, self.n_clients)
        self._placed = False  # host-side state edits re-place lazily
        # Logical slot -> physical state row. Identity until a PLACED
        # grow: doubling a sharded pool in place keeps every existing
        # row on its shard (each device pads its own slab locally — no
        # host round-trip, no cross-device traffic), which renumbers
        # the row space per shard; the mirror/free-list keep stable
        # LOGICAL slots and this map translates at the kernel
        # boundary (pack + row scatter).
        self._phys = np.arange(self.n_docs, dtype=np.int64)
        self.max_resident = max_resident
        # doc_id -> {"slot": int|None, "seq", "min_seq",
        #            "clients": {cid: [ref_seq, client_seq]}, "t": lru}
        self.docs: Dict[str, dict] = {}
        self.slot_owner: Dict[int, str] = {}
        self.free: List[int] = list(range(self.n_docs - 1, -1, -1))
        # Queued row reloads, (slot, mirror entry); an entry whose doc
        # no longer owns the slot (parked since) is dropped at flush.
        self._loads: List[Tuple[int, dict]] = []
        # Logical slots whose row has held a document: a new document
        # on a never-used row needs no reload (the row is still zero).
        self._used = np.zeros(self.n_docs, bool)
        self._need_clients = self.n_clients
        self._clock = 0
        # Documents touched this pump, in touch order, and how many of
        # them were MSN-cold when touched; the victim queues (doc ->
        # None, oldest touch first) hold every other resident doc.
        self._active: Dict[str, None] = {}
        self._active_cold = 0
        self._cold: "OrderedDict[str, None]" = OrderedDict()
        self._warm: "OrderedDict[str, None]" = OrderedDict()
        # Checkpoint encoding: each document's encoded item
        # (`_encode_doc`; keyed in `docs`' order, since an entry is
        # made wherever a document is) and the documents whose mirror
        # entry changed since; the mirror entries the compaction sweep
        # must visit (id -> entry).
        self._items: Dict[str, Optional[bytes]] = {}
        self._dirty: set = set()
        self._compact: Dict[int, dict] = {}
        # Pool instrumentation (per-event counters here; occupancy
        # gauges are refreshed once per kernel pump by the core).
        m = get_registry()
        labels = labels or {}
        self._m_grows = m.counter("deli_pool_grows_total", **labels)
        self._m_shrinks = m.counter("deli_pool_shrinks_total", **labels)
        self._m_evicts = m.counter("deli_pool_evictions_total", **labels)
        self._m_loads = m.counter("deli_pool_loads_total", **labels)
        # ROADMAP (e)/(c) observability: which policy picked each
        # eviction victim, how cold the resident set looked at decision
        # time, and how many client columns compaction reclaimed.
        self._m_evict_policy = {
            p: m.counter("deli_pool_evictions_by_policy_total", policy=p,
                         **labels)
            for p in ("msn_cold", "lru")
        }
        self._m_cold = m.gauge("deli_pool_cold_resident_docs", **labels)
        self._m_reclaims = m.counter("deli_pool_col_reclaims_total",
                                     **labels)
        self._m_compactions = m.counter("deli_pool_compactions_total",
                                        **labels)

    # ------------------------------------------------------------ slots

    def begin(self) -> None:
        """Start a pump: file the previous pump's resident documents at
        the young end of their victim queue (their touches are the
        latest), cold or lagging by the MSN their verdicts left."""
        docs, cold, warm = self.docs, self._cold, self._warm
        for doc_id in self._active:
            h = docs.get(doc_id)
            if h is not None and h["slot"] is not None:
                (cold if h["min_seq"] >= h["seq"] else warm)[doc_id] = None
        self._active.clear()
        self._active_cold = 0

    def touch(self, doc_id: str) -> dict:
        """Resident host-mirror entry for `doc_id` (its `"slot"` is the
        kernel row; `"cmap"` maps client ids to dense columns —
        column 0 is the never-connected SCRATCH column that ops from
        unknown/foreign client ids address, so any id — negative,
        huge — gets the oracle's unknown-client verdict without
        aliasing a real client's state)."""
        h = self.docs.get(doc_id)
        new = h is None
        if new:
            h = {"slot": None, "seq": 0, "min_seq": 0, "clients": {},
                 "cmap": {}, "t": 0}
            self.docs[doc_id] = h
            self._items[doc_id] = None
            self._dirty.add(doc_id)
        elif len(h["cmap"]) > 2 * len(h["clients"]) + 8:
            # Live compaction trigger (ROADMAP (c)): a high-churn doc
            # whose column map has outgrown its live clients reclaims
            # departed clients' columns. Safe here — touch() runs once
            # per doc per pump, BEFORE any of this pump's submissions
            # read the map.
            self.compact_doc(doc_id)
        if h["slot"] is None:
            slot = self._alloc()
            h["slot"] = slot
            self.slot_owner[slot] = doc_id
            if not new or self._used[slot]:
                self._loads.append((slot, h))
            self._used[slot] = True
        elif doc_id in self._cold:  # active: no longer a victim
            del self._cold[doc_id]
        else:
            self._warm.pop(doc_id, None)
        self._clock += 1
        h["t"] = self._clock
        active = self._active
        if doc_id in active:
            del active[doc_id]  # touched again: to the young end
        elif h["min_seq"] >= h["seq"]:
            self._active_cold += 1
        active[doc_id] = None
        return h

    def col_of_join(self, h: dict, cid) -> int:
        """The client's dense column, assigned on first join (columns
        are per-doc-monotone, like the scalar per-doc client dict). A
        new column makes the doc a compaction candidate: the join may
        not take."""
        cmap = h["cmap"]
        col = cmap.get(cid)
        if col is None:
            col = cmap[cid] = len(cmap) + 1  # col 0 is scratch
            self._compact[id(h)] = h
        return col

    def _victim(self) -> Optional[Tuple[str, str]]:
        """(doc, policy) of the next eviction: the least recently
        touched MSN-cold document, else the least recently touched
        one (ROADMAP (e): a doc whose MSN caught its head is quiescent
        — every connected client acked everything, or none remain).
        None when every resident doc is active."""
        for queue, policy in ((self._cold, "msn_cold"),
                              (self._warm, "lru")):
            if queue:
                return next(iter(queue)), policy
        return None

    def _alloc(self) -> int:
        # Soft resident budget: once resident docs reach max_resident,
        # every new residency first parks the victim and reuses its
        # slot — the cap holds except when a single pump's active set
        # exceeds it (actives can't be parked; the pool then grows to
        # cover the pump).
        if (self.max_resident is not None
                and len(self.slot_owner) >= self.max_resident):
            self._m_cold.set(len(self._cold) + self._active_cold)
            victim = self._victim()
            if victim is not None:
                self.park(*victim)
        if not self.free:
            old = self.n_docs
            self.n_docs = _mul_of(max(8, old * 2), self._n_shards)
            self.free.extend(range(self.n_docs - 1, old - 1, -1))
            self._used = np.concatenate(
                [self._used, np.zeros(self.n_docs - old, bool)])
            self._m_grows.inc()
        return self.free.pop()

    def park(self, doc_id: str, policy: Optional[str] = None) -> None:
        """Evict a document's slot. Free: the host mirror is already
        complete, so the stale device row is simply abandoned until the
        slot's next occupant reloads over it (a reload still queued for
        the doc is dropped at flush, since the doc no longer owns the
        slot). `policy` records which rule picked the victim (msn_cold
        / lru) for the pool gauges."""
        h = self.docs[doc_id]
        slot = h["slot"]
        if slot is None:
            return
        h["slot"] = None
        self.slot_owner.pop(slot, None)
        self.free.append(slot)
        if doc_id in self._cold:
            del self._cold[doc_id]
        else:
            self._warm.pop(doc_id, None)
        self._m_evicts.inc()
        if policy is not None:
            self._m_evict_policy[policy].inc()

    def fit(self, budget: int) -> None:
        """Hold at most `budget` documents resident from the next
        residency on; a pool with at least twice `budget` rows parks
        victims down to the budget and shrinks to `budget` rows, the
        documents it keeps reloaded into the first rows. Call between
        pumps (`begin` first): a pump's active documents never park,
        and a pool still holding more than `budget` of them keeps its
        rows."""
        self.max_resident = budget
        rows = _mul_of(max(LOAD_MIN_ROWS, budget), self._n_shards)
        if self.n_docs < 2 * rows:
            return
        while len(self.slot_owner) > rows:
            victim = self._victim()
            if victim is None:
                return
            self.park(*victim)
        keep = list(self.slot_owner.values())
        for slot, doc_id in enumerate(keep):
            self.docs[doc_id]["slot"] = slot
        self.slot_owner = dict(enumerate(keep))
        self.n_docs = rows
        self.free = list(range(rows - 1, len(keep) - 1, -1))
        self._used = np.zeros(rows, bool)
        self._used[:len(keep)] = True
        self._phys = np.arange(rows, dtype=np.int64)
        self.state = _sk.make_state(rows, self.n_clients)
        self._placed = False
        self._loads = [(slot, self.docs[d]) for slot, d in enumerate(keep)]
        self._m_shrinks.inc()

    # ------------------------------------------------- column compaction

    def compact_doc(self, doc_id: str) -> int:
        """Reclaim departed clients' columns in this doc's client-id →
        dense-column map (ROADMAP (c)): the map is rebuilt over LIVE
        clients only (relative column order preserved, so the rebuild
        is deterministic), and a resident doc queues a full row reload
        so the device row matches the new layout before the next
        kernel call. Returns the number of columns reclaimed."""
        h = self.docs.get(doc_id)
        return 0 if h is None else self._compact_entry(h)

    def _compact_entry(self, h: dict) -> int:
        cmap = h["cmap"]
        live = h["clients"]
        reclaimed = len(cmap) - len(live)
        if reclaimed <= 0:
            return 0
        h["cmap"] = {
            cid: i + 1  # col 0 stays the never-connected scratch column
            for i, cid in enumerate(sorted(live, key=cmap.__getitem__))
        }
        if h["slot"] is not None:
            self._loads.append((h["slot"], h))
        self._m_reclaims.inc(reclaimed)
        self._m_compactions.inc()
        return reclaimed

    def compact_all(self) -> int:
        """Checkpoint-time sweep: compact every doc's column map (the
        restart-free form of the checkpoint/restore compaction). Only
        the candidates can hold a departed client's column: a doc's map
        outgrows its live clients only where it gained a column or its
        clients lost one."""
        candidates, self._compact = self._compact, {}
        return sum(map(self._compact_entry, candidates.values()))

    def resident_docs(self) -> int:
        return len(self.slot_owner)

    def note_client(self, client_id: int) -> None:
        if client_id >= self._need_clients:
            self._need_clients = client_id + 1

    # -------------------------------------------------------- device ops

    def _place(self, state):
        """Lay every per-doc array out across the mesh (leading docs
        axis sharded, everything else replicated per row)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(self.mesh, PartitionSpec("docs"))
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sh), state
        )

    def _grow_placed(self, old_d: int, old_c: int, new_c: int) -> bool:
        """Grow an ALREADY-PLACED sharded pool in place (the deferred
        GROW scatter — PR 6 follow-up closed): each device pads ITS
        OWN slab with fresh empty rows/columns (`jnp.pad` on the
        shard's committed buffer runs device-local), and the assembled
        array reuses those buffers — no host round-trip, no
        cross-device transfer, no full-pool re-place. The row space
        renumbers per shard (shard s owns rows [s*r1, (s+1)*r1) after
        the grow), so `_phys` remaps every logical slot to its new
        physical row ON ITS OLD SHARD — untouched rows never move.
        Returns False when the layout can't do it (not placed, shards
        not addressable) and the caller falls back to the classic
        grow_state + full re-place."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        S = self._n_shards
        new_d = self.n_docs
        r0, r1 = old_d // S, new_d // S
        sh = NamedSharding(self.mesh, PartitionSpec("docs"))
        new_fields = {}
        for name in self.state._fields:
            arr = getattr(self.state, name)
            try:
                shards = list(arr.addressable_shards)
            except AttributeError:
                return False  # host array: not actually placed
            if len(shards) != S:
                return False
            parts: List[Any] = [None] * S
            for s in shards:
                row0 = (s.index[0].start or 0) if s.index else 0
                widths = [(0, r1 - r0)]
                if arr.ndim > 1:
                    widths.append((0, new_c - old_c))
                parts[row0 // r0] = jnp.pad(s.data, widths)
            if any(p is None for p in parts):
                return False
            new_fields[name] = jax.make_array_from_single_device_arrays(
                (new_d,) + arr.shape[1:] if arr.ndim == 1
                else (new_d, new_c) + arr.shape[2:], sh, parts
            )
        # Remap: logical slot l at old physical row p (shard p//r0,
        # local p%r0) keeps its shard at row (p//r0)*r1 + p%r0; the
        # NEW logical ids [old_d, new_d) fill each shard's fresh
        # locals [r0, r1).
        phys = self._phys[:old_d]
        new_phys = np.empty(new_d, np.int64)
        new_phys[:old_d] = (phys // r0) * r1 + (phys % r0)
        grow_per = r1 - r0
        for s in range(S):
            base_l = old_d + s * grow_per
            new_phys[base_l: base_l + grow_per] = np.arange(
                s * r1 + r0, s * r1 + r1
            )
        self._phys = new_phys
        self.state = _sk.SequencerState(**new_fields)
        return True

    def _scatter_rows_placed(self, idx, updates) -> bool:
        """Scoped re-place (PR-6 follow-up (b)): scatter the loaded
        rows into an ALREADY-PLACED pool per shard, rebuilding only
        the device slabs that own a touched row and reusing every
        other shard's buffer as-is (`make_array_from_single_device_
        arrays` keeps untouched buffers by identity — nothing is
        re-transferred). Returns False when the layout doesn't allow
        it (not placed yet, or a shard's rows aren't host-addressable)
        and the caller falls back to the full `_place`."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        rows = self.n_docs // self._n_shards
        by_shard: Dict[int, List[int]] = {}
        for i, slot in enumerate(idx):
            by_shard.setdefault(int(slot) // rows, []).append(i)
        sh = NamedSharding(self.mesh, PartitionSpec("docs"))
        new_fields = {}
        for name, vals in updates.items():
            arr = getattr(self.state, name)
            try:
                shards = list(arr.addressable_shards)
            except AttributeError:
                return False  # host array: not placed yet
            if len(shards) != self._n_shards:
                return False  # partial addressability: full re-place
            parts = []
            for s in shards:
                row0 = (s.index[0].start or 0) if s.index else 0
                sel = by_shard.get(row0 // rows)
                if not sel:
                    parts.append(s.data)  # reused by identity
                    continue
                local = np.array(s.data)  # pull ONE shard, not the pool
                for i in sel:
                    local[int(idx[i]) - row0] = vals[i]
                parts.append(jax.device_put(local, s.device))
            new_fields[name] = jax.make_array_from_single_device_arrays(
                arr.shape, sh, parts
            )
        self.state = self.state._replace(**new_fields)
        return True

    def prepare(self) -> int:
        """Grow the packed state to the logical (D, C), flush queued
        doc-row loads in one batched write, and (sharded pools) place
        the result across the mesh — the kernel's in/out specs then
        keep it sharded between pumps for free. Returns the rows
        loaded. One device, the write is `SEQ_POOL_LOAD_PROGRAM` at a
        power-of-two row bucket (`warm_loads` compiles them all). An
        already-placed sharded pool takes the SCOPED scatter path
        instead: only the shards owning a reloaded row are rebuilt,
        the rest keep their buffers (growth still re-places everything
        — a new shape means new buffers no matter what)."""
        need_c = _pow2(self._need_clients, self.n_clients)
        d, c = self.state.connected.shape
        if self.n_docs != d or need_c != c:
            if not (self.mesh is not None and self._placed
                    and self._grow_placed(d, c, need_c)):
                # Classic path (scalar, or first placement still
                # pending): zero-pad on the host and re-place below;
                # the appended rows are the new physical tail, so the
                # logical map extends as identity.
                self.state = _sk.grow_state(self.state, self.n_docs,
                                            need_c)
                self._placed = False
                if len(self._phys) < self.n_docs:
                    self._phys = np.concatenate([
                        self._phys,
                        np.arange(len(self._phys), self.n_docs,
                                  dtype=np.int64),
                    ])
            self.n_clients = need_c
        # One load per slot still owned by its doc (the latest queued).
        loads = {slot: h for slot, h in self._loads if h["slot"] == slot}
        self._loads = []
        if not loads:
            if self.mesh is not None and not self._placed:
                self.state = self._place(self.state)
                self._placed = True
            return 0
        idx, updates = self._rows(loads)
        self._m_loads.inc(len(idx))
        if (self.mesh is not None and self._placed
                and self._scatter_rows_placed(idx, updates)):
            return len(idx)
        self.state = self._load(idx, updates)
        if self.mesh is not None:
            # The host-side scatter loses the docs layout; re-place
            # before the next kernel call (one batched transfer).
            self.state = self._place(self.state)
            self._placed = True
        return len(idx)

    def _rows(self, loads: Dict[int, dict]):
        """(physical rows, field -> row values) of the state rows the
        mirror entries in `loads` (slot -> entry) hold, built as
        arrays."""
        n, C = len(loads), self.n_clients
        hs = list(loads.values())
        idx = self._phys[np.fromiter(loads, np.int64, n)]
        seqv = np.fromiter((h["seq"] for h in hs), np.int32, n)
        minv = np.fromiter((h["min_seq"] for h in hs), np.int32, n)
        counts = np.fromiter((len(h["clients"]) for h in hs), np.int64, n)
        flat = np.array([(cmap[cid], r, cs) for h in hs
                         for cmap in (h["cmap"],)
                         for cid, (r, cs) in h["clients"].items()],
                        np.int64).reshape(-1, 3)
        row = np.repeat(np.arange(n), counts)
        col = flat[:, 0]
        conn = np.zeros((n, C), bool)
        ref = np.zeros((n, C), np.int32)
        cseq = np.zeros((n, C), np.int32)
        conn[row, col] = True
        ref[row, col] = flat[:, 1]
        cseq[row, col] = flat[:, 2]
        return idx, {"seq": seqv, "min_seq": minv, "connected": conn,
                     "ref_seq": ref, "client_seq": cseq}

    def _load(self, idx, updates):
        """The state with `updates` written at rows `idx`: one
        `SEQ_POOL_LOAD_PROGRAM` call, padded to a power-of-two bucket
        whose padding rows index past the last row (dropped)."""
        import jax.numpy as jnp

        n = len(idx)
        b = _pow2(n, LOAD_MIN_ROWS)
        d = self.state.seq.shape[0]
        pidx = np.full(b, d, np.int32)
        pidx[:n] = idx
        cols = []
        for name in _sk.SequencerState._fields:
            v = updates[name]
            pad = np.zeros((b,) + v.shape[1:], v.dtype)
            pad[:n] = v
            cols.append(jnp.asarray(pad))
        return _load_rows(self.state, jnp.asarray(pidx), *cols)

    def warm_loads(self) -> None:
        """Compile the row reload at every bucket a flush can take at
        the pool's current shape (one device; a placed sharded pool
        reloads on the host and compiles nothing)."""
        if self.mesh is not None:
            return
        saved = self.state
        d = saved.seq.shape[0]
        b = LOAD_MIN_ROWS
        while True:
            self.state = _sk.make_state(d, self.n_clients)
            idx = np.full(min(b, d), d, np.int64)
            rows = {name: np.zeros((len(idx),) + getattr(
                        self.state, name).shape[1:],
                        getattr(self.state, name).dtype)
                    for name in _sk.SequencerState._fields}
            jax.block_until_ready(self._load(idx, rows))
            if b >= d:
                break
            b <<= 1
        self.state = saved

    def run_chunk(self, kind, client, cseq, ref, groups, dedup: bool,
                  aborted=None):
        """One device call; `aborted` threads the boxcar-abort tracker
        across a pump's chunks. Returns (SeqResult as numpy, tracker).
        Sharded pools run the shard_map'd kernel — same abort/dedup
        semantics, doc rows resident on their owning device — and the
        verdict gather is the single device_get below."""
        if aborted is None:
            aborted = _sk.no_aborts(self.n_docs)
        self.state, aborted, res = self._sequence(
            self.state, kind, client, cseq, ref, groups, dedup, aborted)
        return jax.device_get(res), aborted

    def _sequence(self, state, kind, client, cseq, ref, groups,
                  dedup: bool, aborted):
        """One sequencer call on `state` (donated): the shard_map'd
        program over the mesh, else the one-device program."""
        import jax.numpy as jnp

        batch = _sk.SeqBatch(
            kind=jnp.asarray(kind), client=jnp.asarray(client),
            client_seq=jnp.asarray(cseq), ref_seq=jnp.asarray(ref),
        )
        if self.mesh is not None:
            fn = _sk.sharded_sequence_fn(self.mesh, dedup=bool(dedup))
            return fn(state, aborted, batch, jnp.asarray(groups))
        return _sk.sequence_batch_grouped(
            state, batch, jnp.asarray(groups), dedup, aborted)

    def warm_sequencer(self, max_cols: int, dedup: bool) -> None:
        """Compile the sequencer at every batch width a pump can take
        at the pool's current shape ([D, B], B a power of two from 8
        to `max_cols`), on a throwaway state."""
        d, c = self.state.connected.shape
        b = 8
        while b <= max_cols:
            state = _sk.make_state(d, c)
            if self.mesh is not None:
                state = self._place(state)
            z = np.zeros((d, b), np.int32)
            jax.block_until_ready(self._sequence(
                state, z + _sk.SUB_PAD, z, z, z, z + NO_GROUP, dedup,
                _sk.no_aborts(d)))
            b *= 2

    # ---------------------------------------------------- verdict mirror

    def head(self, doc_id: str) -> int:
        return self.docs[doc_id]["seq"]

    def connected_clients(self, doc_id: str) -> set:
        h = self.docs.get(doc_id)
        return set(h["clients"]) if h else set()

    def expected_cseq(self, doc_id: str, client_id: int) -> Optional[int]:
        st = self.docs[doc_id]["clients"].get(client_id)
        return st[1] + 1 if st is not None else None

    def apply_join(self, doc_id: str, cid: int, seq: int, msn: int) -> None:
        h = self.docs[doc_id]
        h["clients"][cid] = [seq - 1, 0]
        h["seq"], h["min_seq"] = seq, msn
        self._dirty.add(doc_id)

    def apply_leave(self, doc_id: str, cid: int, seq: int, msn: int) -> None:
        h = self.docs[doc_id]
        h["clients"].pop(cid, None)
        h["seq"], h["min_seq"] = seq, msn
        self._dirty.add(doc_id)
        self._compact[id(h)] = h

    def apply_op(self, doc_id: str, cid: int, seq: int, msn: int,
                 cseq: int, ref: int) -> None:
        h = self.docs[doc_id]
        h["clients"][cid] = [ref, cseq]
        h["seq"], h["min_seq"] = seq, msn
        self._dirty.add(doc_id)

    def apply_stamp(self, doc_id: str, seq: int, msn: int) -> None:
        h = self.docs[doc_id]
        h["seq"], h["min_seq"] = seq, msn
        self._dirty.add(doc_id)

    def mark_dirty(self, doc_ids) -> None:
        """Mark documents whose mirror entries the caller wrote itself
        (the columnar emission's bulk update)."""
        self._dirty.update(doc_ids)

    # -------------------------------------------------------- checkpoint

    def checkpoint_docs(self) -> dict:
        """Per-doc state in `DocumentSequencer.checkpoint()` format —
        scalar and kernel delis restore each other's checkpoints."""
        return {doc_id: _doc_state(doc_id, h)
                for doc_id, h in self.docs.items()}

    def encode_docs(self) -> Tuple[EncodedJson, int]:
        """``json.dumps(checkpoint_docs())`` as an `EncodedJson` whose
        parts are the documents' encoded items, and the number of
        documents re-encoded: only those marked dirty since the last
        call; every other item is the one encoded before."""
        docs, items, dirty = self.docs, self._items, self._dirty
        if len(items) != len(docs):
            raise RuntimeError("a document entered the mirror unmarked")
        for doc_id in dirty:
            items[doc_id] = _encode_doc(doc_id, docs[doc_id])
        self._dirty = set()
        parts = [b"{"]
        parts.extend(items.values())
        if len(parts) > 1:
            parts[1] = parts[1][2:]  # no separator before the first item
        parts.append(b"}")
        return EncodedJson(parts), len(dirty)

    def restore_docs(self, docs: Optional[dict]) -> None:
        for doc_id, st in (docs or {}).items():
            clients = {
                int(cid): [int(v["ref_seq"]), int(v["client_seq"])]
                for cid, v in st["clients"].items()
            }
            self.docs[doc_id] = {
                "slot": None, "seq": int(st["seq"]),
                "min_seq": int(st["min_seq"]), "clients": clients,
                "cmap": {cid: i + 1 for i, cid in enumerate(clients)},
                "t": 0,
            }
            self._items.setdefault(doc_id, None)
            self._dirty.add(doc_id)
            self.note_client(len(clients) + 1)


class _FlatResults:
    """Kernel verdicts for one pump, aligned with the submission index
    `add()`/`add_columns()` returned. Two shapes share the class: flat
    Python lists (the dict-emission path — one vectorized array→list
    conversion, then plain indexing) or numpy arrays
    (``run(as_arrays=True)`` — the columnar-emission path, where
    verdicts flow into `record_batch.ColumnarRecords` columns as array
    slices without ever becoming per-record Python values)."""

    __slots__ = ("seq", "msn", "nack", "skipped")

    def __init__(self, seq, msn, nack, skipped):
        self.seq = seq
        self.msn = msn
        self.nack = nack
        self.skipped = skipped


class PackedDeliCore:
    """Shared pack → kernel → verdict engine for both deli frontends.

    Per pump: `begin()`, then `touch`/`add` append submissions to flat
    columnar lists (per-record cost: a few list appends); `run()` does
    the rest VECTORIZED — per-doc column assignment via a stable
    argsort cumulative count, [D, B] scatter and verdict gather via
    fancy indexing — executes the chunks in order (the boxcar-abort
    tracker threads across chunks, so groups may span them), and
    returns verdicts aligned with the submission indices."""

    def __init__(self, n_docs: int = 8, n_clients: int = 8,
                 max_resident: Optional[int] = None, max_cols: int = 256,
                 dedup: bool = False, mesh=None, kernel_span=None,
                 prepare_span=None,
                 labels: Optional[Dict[str, str]] = None):
        """`kernel_span` (a `utils.metrics.Span`) times each sequencer
        call through its verdict fetch, `prepare_span` the pool's row
        reloads before it (n = rows loaded); the role passes its own
        ``<role>.kernel`` and ``<role>.pool.prepare``, and its metric
        `labels` for the pool's counters."""
        self.pool = SeqPool(n_docs, n_clients, max_resident, mesh=mesh,
                            labels=labels)
        self._sp_kernel = kernel_span or span("deli.kernel")
        self._sp_prepare = prepare_span or span("deli.pool.prepare")
        self.max_cols = max(8, max_cols)
        self.dedup = dedup
        # Submissions accumulate as ORDERED segments: lists of
        # per-record tuples (`add`) interleaved with pre-columnized
        # (n, 6) arrays (`add_columns` — the bulk ingest surface for
        # producers that already hold columns; the live roles still
        # add() per record because emission needs a per-record plan,
        # see the ROADMAP pre-columnized-emission follow-up). run()
        # concatenates them into the six 1-D columns
        # `ops.sequencer_kernel.pack_submissions` packs from.
        self._segments: List[Any] = []
        self._n_subs = 0
        self._gctr: Dict[int, int] = {}
        # Kernel-path instrumentation: one histogram observation + a
        # handful of gauge/counter updates PER PUMP (never per record:
        # the benchmark's untraced runs, whose end-to-end metrics carry
        # this cost, are what a regression shows in).
        m = get_registry()
        self._m_pump = m.histogram(
            "deli_pump_records",
            buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384),
            impl="kernel",
        )
        self._m_nacks = m.counter("deli_nacks_total", impl="kernel")
        self._m_skips = m.counter("deli_dedup_skips_total", impl="kernel")
        self._m_resident = m.gauge("deli_pool_resident_docs")
        self._m_slots = m.gauge("deli_pool_doc_slots")
        self._m_fill = m.gauge("deli_pool_fill_ratio")
        self._m_cols = m.gauge("deli_pool_client_cols")
        self._m_devices = m.gauge("deli_pool_devices")

    def begin(self) -> None:
        self.pool.begin()
        self._segments = []
        self._n_subs = 0
        self._gctr = {}

    def touch(self, doc_id: str) -> dict:
        """The doc's host-mirror entry (slot + client column map)."""
        return self.pool.touch(doc_id)

    def add(self, slot: int, kind: int, client: int = 0, cseq: int = 0,
            ref: int = 0, group: int = NO_GROUP) -> int:
        """Queue one submission; `client` is the doc's dense COLUMN
        (from the cmap / `col_of_join`, 0 = scratch). Returns the
        submission's verdict index."""
        pool = self.pool
        if client >= pool._need_clients:
            pool._need_clients = client + 1
        segs = self._segments
        if not segs or not isinstance(segs[-1], list):
            segs.append([])
        segs[-1].append((slot, kind, client, cseq, ref, group))
        j = self._n_subs
        self._n_subs = j + 1
        return j

    def add_columns(self, slot, kind, client, cseq, ref,
                    group=NO_GROUP) -> int:
        """Bulk-queue PRE-COLUMNIZED submissions: equal-length 1-D
        sequences (or scalars, broadcast) of doc slots, SUB_* kinds,
        dense client columns, clientSeqs and refSeqs — the shape the
        columnar record-batch codec hands over, appended without
        per-record tuple packing. Returns the first verdict index
        (submission i's verdict is at return + i)."""
        slot = np.asarray(slot, np.int64)
        n = slot.shape[0]
        cols = np.empty((n, 6), np.int64)
        cols[:, 0] = slot
        cols[:, 1] = kind
        cols[:, 2] = client
        cols[:, 3] = cseq
        cols[:, 4] = ref
        cols[:, 5] = group
        if n:
            self.pool.note_client(int(cols[:, 2].max()))
        self._segments.append(cols)
        j = self._n_subs
        self._n_subs = j + n
        return j

    def new_group(self, slot: int) -> int:
        """A fresh boxcar group id, unique per doc within this pump."""
        g = self._gctr.get(slot, 0)
        self._gctr[slot] = g + 1
        return g

    def add_boxcar(self, slot: int, ops: List[Tuple[int, int, int]]):
        """Pack one atomic boxcar: `ops` is [(column, cseq, ref)]; a
        nack masks out the group's tail (an unknown client's op rides
        the scratch column — col 0 — and nacks like the oracle).
        Returns the verdict indices."""
        g = self.new_group(slot)
        add = self.add
        return [add(slot, SUB_OP, col, cs, rf, g) for col, cs, rf in ops]

    def warm_up(self) -> None:
        """Flush queued reloads, then compile every program a pump can
        run at the pool's current shape: the sequencer at each batch
        width and the row reload at each bucket."""
        with self._sp_prepare as sp:
            sp.n = self.pool.prepare()
        self.pool.warm_sequencer(self.max_cols, self.dedup)
        self.pool.warm_loads()

    def run(self, as_arrays: bool = False) -> _FlatResults:
        pool = self.pool
        with self._sp_prepare as sp:
            sp.n = pool.prepare()
        n = self._n_subs
        if n == 0:
            if as_arrays:
                z32 = np.zeros(0, np.int32)
                return _FlatResults(z32, z32, z32, np.zeros(0, bool))
            return _FlatResults([], [], [], [])
        parts = [
            np.asarray(s, np.int64).reshape(-1, 6) for s in self._segments
        ]
        cols6 = parts[0] if len(parts) == 1 else np.concatenate(parts)
        self._segments = []
        self._n_subs = 0
        self._gctr = {}
        seq_o = np.empty(n, np.int32)
        msn_o = np.empty(n, np.int32)
        nack_o = np.empty(n, np.int32)
        skip_o = np.empty(n, bool)
        aborted = None
        # Dense [D, B] packing lives with the kernel now
        # (`pack_submissions` accepts the pre-columnized 1-D arrays
        # directly); chunks execute in order so the boxcar-abort
        # tracker threads across them.
        for sel, sl, ic, kind, client, cseq, ref, grp in \
                _sk.pack_submissions(
                    # Logical doc slots -> physical state rows (the
                    # placed-grow renumbering seam; identity on
                    # scalar / never-grown pools).
                    pool._phys[cols6[:, 0]],
                    cols6[:, 1], cols6[:, 2], cols6[:, 3],
                    cols6[:, 4], cols6[:, 5], pool.n_docs, self.max_cols,
                ):
            with self._sp_kernel as k:
                k.n = len(sl)
                res, aborted = pool.run_chunk(
                    kind, client, cseq, ref, grp, self.dedup, aborted
                )
            seq_o[sel] = res.seq[sl, ic]
            msn_o[sel] = res.min_seq[sl, ic]
            nack_o[sel] = res.nack[sl, ic]
            skip_o[sel] = res.skipped[sl, ic]
        self._m_pump.observe(n)
        nacks = int(np.count_nonzero(nack_o))
        if nacks:
            self._m_nacks.inc(nacks)
        skips = int(np.count_nonzero(skip_o))
        if skips:
            self._m_skips.inc(skips)
        resident = pool.resident_docs()
        self._m_resident.set(resident)
        self._m_slots.set(pool.n_docs)
        self._m_fill.set(resident / pool.n_docs if pool.n_docs else 0.0)
        self._m_cols.set(pool.n_clients)
        self._m_devices.set(pool._n_shards)
        if as_arrays:
            return _FlatResults(seq_o, msn_o, nack_o, skip_o)
        return _FlatResults(
            seq_o.tolist(), msn_o.tolist(), nack_o.tolist(), skip_o.tolist()
        )


# ---------------------------------------------------------------------------
# in-proc frontend (LocalServer)
# ---------------------------------------------------------------------------


class KernelDeliLambda:
    """Drop-in for `lambdas.DeliLambda`: same topics, same deltas
    entries, same checkpoint shape — sequencing decisions on device.

    Select with `LocalServer(deli_impl="kernel")` or `FLUID_DELI=kernel`;
    the scalar `DeliLambda` is the oracle (tests/test_deli_kernel.py
    drives both with identical traffic) and the fallback."""

    def __init__(self, log: MessageLog, checkpoint: Optional[dict] = None,
                 max_pump: int = 8192, n_docs: int = 8, n_clients: int = 8,
                 max_resident: Optional[int] = None, max_cols: int = 256,
                 raw_topic: str = "rawdeltas",
                 deli_devices: Optional[int] = None,
                 device_plane=None, plane_column: Optional[int] = None):
        """`raw_topic` names the ingress topic (the sharded
        LocalServer's per-partition ``rawdeltas-p{k}`` form).
        `deli_devices=N` shards the doc-slot pool across an N-device
        mesh (`LocalServer(deli_devices=N)` passes it through);
        `device_plane` instead takes the sequencer's 1-D slice of the
        shared 2-D plane (`parallel.device_plane`, model column
        `plane_column`). Either way the checkpoint shape is
        topology-free, so restores interop across scalar ⇄
        single-device ⇄ sharded ⇄ plane-sliced freely."""
        if device_plane is not None and deli_devices is not None \
                and int(deli_devices) > 1:
            raise ValueError(
                "deli_devices and device_plane are exclusive: the "
                "plane's seq_mesh IS the deli's device slice"
            )
        mesh = mesh_for_devices(deli_devices)
        if mesh is None:
            mesh = mesh_for_plane(device_plane, plane_column)
        self.core = PackedDeliCore(
            n_docs, n_clients, max_resident, max_cols, dedup=False,
            mesh=mesh,
        )
        offset = 0
        if checkpoint:
            from .supervisor import unwrap_ranged_state

            offset = checkpoint["offset"]
            self.core.pool.restore_docs(
                unwrap_ranged_state(checkpoint["docs"])
            )
        self.consumer = LogConsumer(log.topic(raw_topic), offset)
        self.deltas = log.topic("deltas")
        self.max_pump = max_pump
        self._m_stage = get_registry().histogram(
            "op_stage_ms", stage="submit_to_stamp"
        )

    def pump(self, max_count: Optional[int] = None) -> int:
        """Drain up to `max_count` raw records (micro-batch cap: a deep
        backlog yields between pumps instead of starving the caller)."""
        cap = self.max_pump if max_count is None else max_count
        raws = self.consumer.poll(cap)
        if not raws:
            return 0
        out = self._process(raws)
        if out:
            self.deltas.append_many(out)
        return len(raws)

    def _process(self, raws: List[dict]) -> List[dict]:
        core = self.core
        pool = core.pool
        core.begin()
        touch, add, col_of_join = core.touch, core.add, pool.col_of_join
        docs_cache: Dict[str, tuple] = {}  # touch once per doc per pump
        plan: List[tuple] = []
        append = plan.append
        for raw in raws:
            if not isinstance(raw, dict) or not raw.get("doc"):
                continue  # journal LOST_RECORD placeholder / junk
            doc_id = raw["doc"]
            ent = docs_cache.get(doc_id)
            if ent is None:
                h = touch(doc_id)
                ent = docs_cache[doc_id] = (h["slot"], h)
            slot, h = ent
            cmap = h["cmap"]
            kind = raw["kind"]
            if kind == "join":
                cid = raw["client"]
                append((doc_id, add(slot, SUB_JOIN, col_of_join(h, cid)),
                        "join", cid, None))
            elif kind == "leave":
                cid = raw["client"]
                # Unknown client -> scratch column -> nothing stamped.
                append((doc_id, add(slot, SUB_LEAVE, cmap.get(cid, 0)),
                        "leave", cid, None))
            elif kind == "control":
                append((doc_id, add(slot, SUB_SYSTEM), "sys",
                        raw["type"], raw["contents"]))
            elif kind == "boxcar":
                cid = raw["client"]
                msgs = raw["msgs"]
                col = cmap.get(cid, 0)
                handles = core.add_boxcar(
                    slot, [(col, m.client_seq, m.ref_seq) for m in msgs]
                )
                for hd, m in zip(handles, msgs):
                    append((doc_id, hd, "op", cid, m))
            else:  # client op; unknown -> scratch column -> 403 nack
                cid = raw["client"]
                msg = raw["msg"]
                append((doc_id, add(slot, SUB_OP, cmap.get(cid, 0),
                                    msg.client_seq, msg.ref_seq),
                        "op", cid, msg))
        res = core.run()

        out: List[dict] = []
        emit = out.append
        seqs, msns, nacks, skips = res.seq, res.msn, res.nack, res.skipped
        apply_op = pool.apply_op
        ts = time.time()
        observe_stage = self._m_stage.observe
        for doc_id, handle, tag, a, b in plan:
            if tag == "op":
                if skips[handle]:
                    continue
                seq, msn, nack = seqs[handle], msns[handle], nacks[handle]
                if nack:
                    reason = _nack_reason(
                        nack, b.ref_seq, msn, pool.head(doc_id),
                        b.client_seq, pool.expected_cseq(doc_id, a),
                    )
                    emit({"doc": doc_id, "kind": "nack", "client": a,
                          "msg": NackMessage(a, b.client_seq, nack, reason)})
                    continue
                apply_op(doc_id, a, seq, msn, b.client_seq, b.ref_seq)
                # Same op-lifecycle trace contract as the scalar deli
                # (traces are observability-only: excluded from journal
                # encoding and every digest form).
                tr = [("stamp", ts)]
                sub = trace_submit_ts(b.metadata)
                if sub is not None:
                    tr.insert(0, ("submit", sub))
                    observe_stage((ts - sub) * 1000.0)
                emit({"doc": doc_id, "kind": "op",
                      "msg": SequencedMessage(
                          seq, msn, a, b.client_seq, b.ref_seq,
                          b.type, b.contents, b.metadata, b.address, ts,
                          tr)})
            elif tag == "join":
                seq, msn = seqs[handle], msns[handle]
                pool.apply_join(doc_id, a, seq, msn)
                emit({"doc": doc_id, "kind": "op",
                      "msg": SequencedMessage(
                          seq, msn, a, 0, seq - 1,
                          MessageType.CLIENT_JOIN, a, None, None, ts,
                          [("stamp", ts)])})
            elif tag == "leave":
                seq, msn = seqs[handle], msns[handle]
                if seq == 0:
                    continue  # unknown client: oracle stamps nothing
                pool.apply_leave(doc_id, a, seq, msn)
                emit({"doc": doc_id, "kind": "op",
                      "msg": SequencedMessage(
                          seq, msn, a, 0, seq - 1,
                          MessageType.CLIENT_LEAVE, a, None, None, ts,
                          [("stamp", ts)])})
            else:  # sys
                seq, msn = seqs[handle], msns[handle]
                pool.apply_stamp(doc_id, seq, msn)
                emit({"doc": doc_id, "kind": "op",
                      "msg": SequencedMessage(
                          seq, msn, SYSTEM_CLIENT, 0, seq - 1,
                          a, b, None, None, ts, [("stamp", ts)])})
        return out

    def checkpoint(self) -> dict:
        """Same shape as `DeliLambda.checkpoint()` (offset + per-doc
        `DocumentSequencer` states): restart may switch impls freely.
        Checkpoint time doubles as the column-compaction sweep
        (ROADMAP (c)) — the state written never names departed
        clients, and the pool reclaims their columns on the spot."""
        self.core.pool.compact_all()
        return {
            "offset": self.consumer.checkpoint(),
            "docs": self.core.pool.checkpoint_docs(),
        }


# ---------------------------------------------------------------------------
# supervised-farm frontend (exactly-once recovery)
# ---------------------------------------------------------------------------

# Wire `type` codes the emit columns stamp (the K_SEQ_OP type column).
_TC_OP = _rb._TYPE_CODE["op"]
_TC_JOIN = _rb._TYPE_CODE["join"]
_TC_LEAVE = _rb._TYPE_CODE["leave"]


class _ScalarEmit:
    """Scalar-record accumulator for the columnar emission path: the
    records that still need per-record handling (nacks with their
    reason text, joins/leaves, dict-ingested strays, boxcar members)
    land as COLUMNS in stream order, so one pump's whole output is
    `ColumnarRecords` parts end to end — never a per-record wire
    dict. `flush()` closes the current accumulation into a part
    appended to `out` (called before every vectorized span so parts
    splice back in exact stream order)."""

    __slots__ = ("docs", "doc_of", "kind", "tc", "didx", "client",
                 "cseq", "ref", "seq", "msn", "inoff", "blobs")

    def __init__(self):
        self.docs: List[str] = []
        self.doc_of: Dict[str, int] = {}
        self.kind: List[int] = []
        self.tc: List[int] = []
        self.didx: List[int] = []
        self.client: List[int] = []
        self.cseq: List[int] = []
        self.ref: List[int] = []
        self.seq: List[int] = []
        self.msn: List[int] = []
        self.inoff: List[int] = []
        self.blobs: List[bytes] = []

    def _doc(self, doc: str) -> int:
        di = self.doc_of.get(doc)
        if di is None:
            di = self.doc_of[doc] = len(self.docs)
            self.docs.append(doc)
        return di

    def op(self, doc: str, tc: int, cid: int, cseq: int, ref: int,
           seq: int, msn: int, inoff: int, contents: Any) -> None:
        self.kind.append(_rb.K_SEQ_OP)
        self.tc.append(tc)
        self.didx.append(self._doc(doc))
        self.client.append(cid)
        self.cseq.append(cseq)
        self.ref.append(ref)
        self.seq.append(seq)
        self.msn.append(msn)
        self.inoff.append(inoff)
        self.blobs.append(_rb._dumps(contents))  # JsonBlob rides raw

    def member(self, doc: str, tc: int, cid: int, seq: int, msn: int,
               inoff: int) -> None:
        # join/leave wire shape: clientSeq 0, refSeq seq-1, contents=cid
        self.op(doc, tc, cid, 0, seq - 1, seq, msn, inoff, cid)

    def nack(self, doc: str, cid: int, cseq: int, code: int,
             reason: str, inoff: int) -> None:
        self.kind.append(_rb.K_NACK)
        self.tc.append(_rb._NO_TYPE)
        self.didx.append(self._doc(doc))
        self.client.append(cid)
        self.cseq.append(cseq)
        self.ref.append(0)
        self.seq.append(code)  # code rides the seq column
        self.msn.append(0)
        self.inoff.append(inoff)
        self.blobs.append(_rb._dumps(reason))

    def flush(self, out: List[Any]) -> None:
        n = len(self.kind)
        if not n:
            return
        blob_off = np.zeros(n + 1, np.uint32)
        blob_off[1:] = np.cumsum([len(b) for b in self.blobs])
        out.append(_rb.ColumnarRecords(
            self.docs, self.kind, self.tc, self.didx, self.client,
            self.cseq, self.ref, self.seq, self.msn, self.inoff,
            blob_off, b"".join(self.blobs),
        ))
        self.__init__()


class KernelDeliRole(_Role):
    """Drop-in for `supervisor.DeliRole` with device-batched ticketing.

    `process()` buffers validated records; `flush_batch()` (called by
    the supervision step AND the recovery gap-replay) packs them, runs
    the kernel, and emits the same wire records as the scalar role —
    each carrying its input offset (`inOff`), so the fenced
    exactly-once recovery contract (PR 1) holds unchanged: a restart
    mid-batch scans the durable output prefix and silently replays the
    gap through the same kernel path without re-emitting.

    Over a columnar op-log (`--log-format columnar`) the role ingests
    whole `RecordBatch` frames (`process_batch`): doc ids come from the
    batch dictionary, the int fields straight off the codec's columns,
    and standalone ops' `contents` stay PRE-ENCODED JSON blobs end to
    end when the output topic is columnar too — zero per-record JSON
    decode on the deli hot path (ROADMAP (a)/(d)). Wire boxcar records
    sequence atomically through the kernel's group machinery, matching
    the scalar role's schema-rev semantics bit for bit (their packed
    ops decode once per boxcar — per-op blob pass-through inside a
    boxcar needs a nested-offset codec rev, noted in ROADMAP)."""

    name = "deli"
    in_topic_name = "rawdeltas"
    out_topic_name = "deltas"
    ingest_batches = True  # _Role.step feeds RecordBatch frames whole

    def __init__(self, *a, mesh=None, deli_devices: Optional[int] = None,
                 device_plane=None, plane_column: Optional[int] = None,
                 **kw):
        """`mesh` (a ready 1-D docs mesh) or `deli_devices=N` (resolved
        via the process-wide shared mesh) shards the pool across
        devices; the wire records, `inOff` recovery contract and
        checkpoint format are identical either way, so the fenced
        exactly-once machinery and the shard fabric compose unchanged
        — a fabric partition worker may run each partition's deli over
        its own device slice. `device_plane`/`plane_column` instead
        take the sequencer's 1-D slice of the shared 2-D plane
        (`parallel.device_plane`; the column defaults to a stable
        hash of the partition key — one partition = one mesh slice),
        falling back to the ``FLUID_DEVICE_PLANE`` env so supervised
        children inherit the farm plane."""
        if device_plane is not None and deli_devices is not None \
                and int(deli_devices) > 1:
            raise ValueError(
                "deli_devices and device_plane are exclusive: the "
                "plane's seq_mesh IS the deli's device slice"
            )
        super().__init__(*a, **kw)
        self.mesh = mesh if mesh is not None else \
            mesh_for_devices(deli_devices)
        if self.mesh is None and (deli_devices is None
                                  or int(deli_devices) <= 1):
            self.mesh = mesh_for_plane(
                device_plane, plane_column,
                partition_key=self.partition, env=True,
            )
        labels = self._metric_labels()
        self._sp_kernel = span(f"{self.span_prefix}.kernel", **labels)
        self._sp_prepare = span(f"{self.span_prefix}.pool.prepare",
                                **labels)
        self._sp_emit = span(f"{self.span_prefix}.emit", **labels)
        self.core = self._new_core()
        # Documents the pumps touched, oldest touch first, with the
        # time of that touch (`residency_budget`'s working set).
        self._recent: "OrderedDict[str, float]" = OrderedDict()
        self._pending: List[tuple] = []  # ("rec", off, dict) |
        #                                 ("cols", start_off, RecordBatch)
        self._m_ckpt_docs: Optional[tuple] = None  # built at checkpoint
        # Blob pass-through is only legal when the output topic can
        # carry raw JSON bytes (a columnar sibling); a JSON out topic
        # needs decoded values for its json.dumps.
        from .columnar_log import ColumnarFileTopic

        self.out_columnar = isinstance(self.out_topic, ColumnarFileTopic)

    # ------------------------------------------------------------ state

    def snapshot_state(self) -> Any:
        """The per-doc map (`SeqPool.checkpoint_docs`) after the
        column-compaction sweep (ROADMAP (c)): the snapshot never names
        departed clients. `checkpoint` writes the same state from
        `checkpoint_state`."""
        self.core.pool.compact_all()
        return self.core.pool.checkpoint_docs()

    def checkpoint_state(self) -> Any:
        """`snapshot_state` pre-encoded (`SeqPool.encode_docs`): the
        sweep, then only the documents changed since the last
        checkpoint are encoded again. Counts both on
        ``deli_checkpoint_docs_{encoded,reused}_total{role}``."""
        pool = self.core.pool
        pool.compact_all()
        enc, n = pool.encode_docs()
        if self._m_ckpt_docs is None:
            labels = self._metric_labels()
            self._m_ckpt_docs = tuple(
                self.metrics.counter(f"deli_checkpoint_docs_{k}_total",
                                     **labels)
                for k in ("encoded", "reused"))
        self._m_ckpt_docs[0].inc(n)
        self._m_ckpt_docs[1].inc(len(pool.docs) - n)
        self.ckpt_docs_encoded = n
        return enc

    def restore_state(self, state: Any) -> None:
        from .supervisor import unwrap_ranged_state

        core = self._new_core()
        core.pool.restore_docs(unwrap_ranged_state(state))
        self.core = core

    def _new_core(self) -> PackedDeliCore:
        return PackedDeliCore(dedup=True, mesh=self.mesh,
                              kernel_span=self._sp_kernel,
                              prepare_span=self._sp_prepare,
                              labels=self._metric_labels())

    # -------------------------------------------------------- residency

    def residency_budget(self, now: Optional[float] = None) -> int:
        """The resident budget the role derives from what its pumps
        touched: the power of two covering twice the documents touched
        over the last `RESIDENT_HORIZON_S` seconds (`time.monotonic`),
        at least `RESIDENT_FLOOR`. A pump's active set is never parked
        (`SeqPool._alloc`), so one larger than the budget grows the
        pool to cover it."""
        now = time.monotonic() if now is None else now
        recent = self._recent
        cut = now - RESIDENT_HORIZON_S
        while recent and next(iter(recent.values())) < cut:
            recent.popitem(last=False)
        return max(RESIDENT_FLOOR, _pow2(2 * len(recent)))

    def warm_up(self) -> None:
        """Fit the pool to the residency budget now, then compile every
        program a pump can run at that size (`PackedDeliCore.warm_up`):
        a pump after this compiles nothing until the pool's shape
        changes."""
        self.core.pool.begin()
        self.core.pool.fit(self.residency_budget())
        self.core.warm_up()

    # ------------------------------------------------------------- pump

    def process(self, line_idx: int, rec: Any, out: List[dict]) -> None:
        if not isinstance(rec, dict) or "doc" not in rec:
            return  # foreign/junk record: consume and move on
        if rec.get("kind") not in ("join", "leave", "op", "boxcar"):
            return
        self._pending.append(("rec", line_idx, rec))

    def process_batch(self, start_line: int, batch: Any,
                      out: List[dict]) -> None:
        """Columnar ingest: queue one `RecordBatch` whole (records
        numbered start_line..start_line+n-1)."""
        self._pending.append(("cols", start_line, batch))

    def _plan_op(self, plan, add, line_idx, doc, slot, col, cid, cseq,
                 ref, contents, group=NO_GROUP, sub_ts=None,
                 adm_ts=None):
        # `sub_ts`/`adm_ts` thread the client submit stamp (ingress
        # "tr_sub") and the front door's admission stamp ("tr_adm")
        # through the plan tuple so wire-trace mode can stamp/observe
        # at emit time — the kernel twin of the scalar role's span
        # coverage (PR 9 follow-up b; admit_to_stamp from ISSUE 13).
        plan.append((line_idx, doc, "op",
                     (cid, cseq, ref, contents, sub_ts, adm_ts),
                     add(slot, SUB_OP, col, cseq, ref, group)))

    def flush_batch(self, out: List[dict]) -> None:
        if not self._pending:
            return
        core = self.core
        pool = core.pool
        core.begin()
        now = time.monotonic()
        pool.fit(self.residency_budget(now))
        touch, add, col_of_join = core.touch, core.add, pool.col_of_join
        docs_cache: Dict[str, tuple] = {}  # touch once per doc per pump
        plan: List[tuple] = []
        shadow: Dict[str, set] = {}
        # Columnar emission (the pre-columnized emit path): legal when
        # the out topic carries raw frames and nothing downstream needs
        # per-record wire dicts — wire tracing adds a side "tr" key
        # (generic schema), recovery's silent replay and the ranged
        # fabric's predecessor drains post-process dict records
        # (inOff filters, inSrc tags).
        emit_cols = (self.out_columnar and not self.trace_wire
                     and not self._recovering and not self._dict_emit)

        def doc_entry(doc):
            ent = docs_cache.get(doc)
            if ent is None:
                h = touch(doc)
                ent = docs_cache[doc] = (h["slot"], h)
            return ent

        def plan_record(line_idx, rec):
            doc = rec["doc"]
            slot, h = doc_entry(doc)
            kind = rec["kind"]
            cid = rec["client"]
            if kind == "op":
                # Unknown/foreign client id -> scratch column -> the
                # oracle's unknown-client nack, no state aliasing.
                self._plan_op(
                    plan, add, line_idx, doc, slot,
                    h["cmap"].get(cid, 0), cid, rec["clientSeq"],
                    rec.get("refSeq", 0), rec.get("contents"),
                    sub_ts=rec.get("tr_sub"),
                    adm_ts=rec.get("tr_adm"),
                )
            elif kind == "boxcar":
                plan_boxcar(line_idx, doc, slot, h, cid, [
                    (op["clientSeq"], op.get("refSeq", 0),
                     op.get("contents"))
                    for op in rec.get("ops") or []
                ], sub_ts=rec.get("tr_sub"),
                    adm_ts=rec.get("tr_adm"))
            elif kind == "join":
                conn = shadow.get(doc)
                if conn is None:
                    conn = shadow[doc] = pool.connected_clients(doc)
                if cid in conn:
                    return  # duplicate join (at-least-once ingress)
                conn.add(cid)
                plan.append((line_idx, doc, "join", cid,
                             add(slot, SUB_JOIN, col_of_join(h, cid))))
            else:  # leave
                conn = shadow.get(doc)
                if conn is None:
                    conn = shadow[doc] = pool.connected_clients(doc)
                conn.discard(cid)
                plan.append((line_idx, doc, "leave", cid,
                             add(slot, SUB_LEAVE, h["cmap"].get(cid, 0))))

        def plan_boxcar(line_idx, doc, slot, h, cid, ops, sub_ts=None,
                        adm_ts=None):
            # One atomic group: a nack masks the group's tail in-kernel
            # (resubmission dedup stays per-op and silent).
            col = h["cmap"].get(cid, 0)
            g = core.new_group(slot)
            for cseq, ref, contents in ops:
                self._plan_op(plan, add, line_idx, doc, slot, col, cid,
                              cseq, ref, contents, group=g,
                              sub_ts=sub_ts, adm_ts=adm_ts)

        passthrough = self.out_columnar
        for ent in self._pending:
            if ent[0] == "rec":
                plan_record(ent[1], ent[2])
                continue
            self._plan_cols(plan, ent[2], ent[1], doc_entry,
                            plan_record, plan_boxcar, passthrough)
        self._pending = []
        recent = self._recent
        for doc in docs_cache:
            recent.pop(doc, None)
            recent[doc] = now
        res = core.run(as_arrays=emit_cols)
        with self._sp_emit:
            if emit_cols:
                self._emit_columns(plan, res, out)
            else:
                self._emit_dicts(plan, res, out)

    # ------------------------------------------------- columnar ingest

    # Below this, a K_RAW_OP run takes the per-record tuple path: the
    # per-run fixed cost (unique-doc touch, array builds, one emit
    # part per span) only amortizes over real runs — a join-interleaved
    # stream decomposes into length-1 "runs" that would otherwise pay
    # it per record.
    MIN_OP_RUN = 16

    def _plan_cols(self, plan, rb, base, doc_entry, plan_record,
                   plan_boxcar, passthrough) -> None:
        """Queue one ingested `RecordBatch`: homogeneous K_RAW_OP runs
        (at least `MIN_OP_RUN` long) go through
        `PackedDeliCore.add_columns` as arrays (doc slots via one
        touch per unique doc, dense client columns via one cmap probe
        per record — no plan tuples, no per-record blob handles),
        everything else (joins/leaves/boxcars/generic strays, short op
        runs) through the per-record plan."""
        n = rb.n
        if n == 0:
            return
        docs = rb.docs
        kinds_l = None
        cseqs_l = refs_l = None
        for run_is_op, lo, hi in _sk.mask_runs(rb.kind == _rb.K_RAW_OP):
            if run_is_op and hi - lo >= self.MIN_OP_RUN:
                self._plan_op_run(plan, rb, lo, hi, base, doc_entry)
                continue
            if kinds_l is None:
                kinds_l = rb.kind.tolist()
                doci = rb.doc_idx.tolist()
                clients = rb.client.tolist()
            for i in range(lo, hi):
                k = kinds_l[i]
                if k == _rb.K_RAW_OP:
                    if cseqs_l is None:
                        cseqs_l = rb.client_seq.tolist()
                        refs_l = rb.ref_seq.tolist()
                    doc = docs[doci[i]]
                    slot, h = doc_entry(doc)
                    cid = clients[i]
                    contents: Any = _rb.JsonBlob(rb.blob(i))
                    if not passthrough:
                        contents = contents.value
                    self._plan_op(
                        plan, self.core.add, base + i, doc, slot,
                        h["cmap"].get(cid, 0), cid, cseqs_l[i],
                        refs_l[i], contents,
                    )
                elif k == _rb.K_RAW_BOXCAR:
                    doc = docs[doci[i]]
                    slot, h = doc_entry(doc)
                    # v2 frames: per-op ints off the nested columns,
                    # per-op contents as raw-blob handles end to end.
                    ops = rb.boxcar(i)
                    if not passthrough:
                        ops = [
                            (cs, rf, c.value
                             if isinstance(c, _rb.JsonBlob) else c)
                            for cs, rf, c in ops
                        ]
                    plan_boxcar(base + i, doc, slot, h, clients[i],
                                ops)
                elif k in (_rb.K_RAW_JOIN, _rb.K_RAW_LEAVE):
                    plan_record(base + i, {
                        "kind": "join" if k == _rb.K_RAW_JOIN
                        else "leave",
                        "doc": docs[doci[i]], "client": clients[i],
                    })
                else:
                    # Generic / foreign record inside the frame: decode
                    # this one record and route it the legacy way.
                    rec = rb.record(i)
                    if isinstance(rec, dict) and "doc" in rec and \
                            rec.get("kind") in ("join", "leave", "op",
                                                "boxcar"):
                        plan_record(base + i, rec)

    def _plan_op_run(self, plan, rb, lo, hi, base, doc_entry) -> None:
        """Bulk-queue one contiguous K_RAW_OP run [lo, hi) through
        `add_columns` — the pre-columnized ingest half finally on the
        live path."""
        docs = rb.docs
        doci = rb.doc_idx[lo:hi]
        slot_of: Dict[int, int] = {}
        h_of: Dict[int, dict] = {}
        for d in np.unique(doci).tolist():
            slot, h = doc_entry(docs[d])
            slot_of[d] = slot
            h_of[d] = h
        m = hi - lo
        doci_l = doci.tolist()
        clients_l = rb.client[lo:hi].tolist()
        slots = np.fromiter((slot_of[d] for d in doci_l), np.int64, m)
        cols = np.fromiter(
            (h_of[d]["cmap"].get(c, 0)
             for d, c in zip(doci_l, clients_l)),
            np.int64, m,
        )
        j0 = self.core.add_columns(
            slots, SUB_OP, cols, rb.client_seq[lo:hi],
            rb.ref_seq[lo:hi],
        )
        plan.append((base, None, "run", (j0, rb, lo, hi, h_of), None))

    # ----------------------------------------------------- emission

    def _emit_dicts(self, plan, res, out: List[dict]) -> None:
        """The per-record wire-dict emission (the differential-oracle
        shape, and the path recovery / tracing / ranged drains use)."""
        pool = self.core.pool
        emit = out.append
        seqs, msns, nacks, skips = res.seq, res.msn, res.nack, res.skipped
        apply_op = pool.apply_op
        # Wire-trace stamps: ONE clock read per flush (the kernel
        # role's whole-pump philosophy — KernelDeliLambda stamps the
        # same way), serving both the record stamp and the
        # submit_to_stamp observe so the two surfaces agree exactly.
        trace = self.trace_wire
        now = time.time() if trace else 0.0

        def emit_op(line_idx, doc, cid, cseq, ref, contents, sub_ts,
                    adm_ts, handle):
            if skips[handle]:
                return  # deduped resubmission / aborted boxcar tail
            seq, msn, nack = seqs[handle], msns[handle], nacks[handle]
            if nack:
                emit({"kind": "nack", "doc": doc, "client": cid,
                      "clientSeq": cseq, "code": nack,
                      "reason": _nack_reason(
                          nack, ref, msn, pool.head(doc), cseq,
                          pool.expected_cseq(doc, cid)),
                      "inOff": line_idx})
                return
            apply_op(doc, cid, seq, msn, cseq, ref)
            rec = {"kind": "op", "doc": doc, "seq": seq, "msn": msn,
                   "client": cid, "clientSeq": cseq, "refSeq": ref,
                   "type": "op", "contents": contents,
                   "inOff": line_idx}
            if trace:
                tr = {"stamp": now}
                if isinstance(sub_ts, (int, float)):
                    tr["sub"] = sub_ts
                    if not self._recovering:
                        # Recovery's silent replay must not be
                        # re-observed (crash-spanning durations) —
                        # the scalar role's rule, kernel-side.
                        self._observe_stage(
                            "submit_to_stamp",
                            (now - sub_ts) * 1000.0,
                        )
                if isinstance(adm_ts, (int, float)):
                    # The front door's admission stamp: same flush
                    # clock read, same recovery-silent rule — the
                    # scalar role's admit_to_stamp, kernel-side.
                    tr["adm"] = adm_ts
                    if not self._recovering:
                        self._observe_stage(
                            "admit_to_stamp",
                            (now - adm_ts) * 1000.0,
                        )
                rec["tr"] = tr
            emit(rec)

        for line_idx, doc, tag, payload, handle in plan:
            if tag == "op":
                cid, cseq, ref, contents, sub_ts, adm_ts = payload
                emit_op(line_idx, doc, cid, cseq, ref, contents,
                        sub_ts, adm_ts, handle)
            elif tag == "run":
                j0, rb, lo, hi, _h_of = payload
                docs = rb.docs
                doci = rb.doc_idx
                clients = rb.client
                cseqs = rb.client_seq
                refs = rb.ref_seq
                for i in range(lo, hi):
                    contents: Any = _rb.JsonBlob(rb.blob(i))
                    if not self.out_columnar:
                        contents = contents.value
                    emit_op(line_idx + i, docs[int(doci[i])],
                            int(clients[i]), int(cseqs[i]),
                            int(refs[i]), contents, None, None,
                            j0 + i - lo)
            elif tag == "join":
                seq, msn = seqs[handle], msns[handle]
                pool.apply_join(doc, payload, seq, msn)
                rec = {"kind": "op", "doc": doc, "seq": seq, "msn": msn,
                       "client": payload, "clientSeq": 0,
                       "refSeq": seq - 1,
                       "type": "join", "contents": payload,
                       "inOff": line_idx}
                if trace:
                    rec["tr"] = {"stamp": now}
                emit(rec)
            else:  # leave
                seq, msn = seqs[handle], msns[handle]
                if seq == 0:
                    continue  # unknown client: nothing stamped
                pool.apply_leave(doc, payload, seq, msn)
                rec = {"kind": "op", "doc": doc, "seq": seq, "msn": msn,
                       "client": payload, "clientSeq": 0,
                       "refSeq": seq - 1,
                       "type": "leave", "contents": payload,
                       "inOff": line_idx}
                if trace:
                    rec["tr"] = {"stamp": now}
                emit(rec)

    def _emit_columns(self, plan, res, out: List[Any]) -> None:
        """The pre-columnized emission: verdict arrays flow into
        `ColumnarRecords` parts (ingest blob bytes pass through as
        whole heap spans), appended to `out` in exact stream order —
        `ColumnarFileTopic.append_many` splices them into one frame
        with zero per-record classification. The host mirror updates
        from flat column lists (bookkeeping-from-results, no wire
        dicts); nack reasons stay per-record (rare, text-only)."""
        pool = self.core.pool
        seqs, msns, nacks, skips = res.seq, res.msn, res.nack, res.skipped
        sc = _ScalarEmit()
        for line_idx, doc, tag, payload, handle in plan:
            if tag == "run":
                self._emit_run(payload, res, sc, out, line_idx)
            elif tag == "op":
                if skips[handle]:
                    continue
                seq = int(seqs[handle])
                msn = int(msns[handle])
                nack = int(nacks[handle])
                cid, cseq, ref, contents, _sub, _adm = payload
                if nack:
                    sc.nack(doc, cid, cseq, nack, _nack_reason(
                        nack, ref, msn, pool.head(doc), cseq,
                        pool.expected_cseq(doc, cid)), line_idx)
                    continue
                pool.apply_op(doc, cid, seq, msn, cseq, ref)
                sc.op(doc, _TC_OP, cid, cseq, ref, seq, msn, line_idx,
                      contents)
            elif tag == "join":
                seq = int(seqs[handle])
                msn = int(msns[handle])
                pool.apply_join(doc, payload, seq, msn)
                sc.member(doc, _TC_JOIN, payload, seq, msn, line_idx)
            else:  # leave
                seq = int(seqs[handle])
                msn = int(msns[handle])
                if seq == 0:
                    continue  # unknown client: nothing stamped
                pool.apply_leave(doc, payload, seq, msn)
                sc.member(doc, _TC_LEAVE, payload, seq, msn, line_idx)
        sc.flush(out)

    def _emit_run(self, payload, res, sc: _ScalarEmit, out: List[Any],
                  base: int) -> None:
        """Emit one ingested K_RAW_OP run: contiguous ACCEPTED spans
        become `ColumnarRecords` parts — verdict columns sliced
        straight off the kernel result, contents blobs one heap memcpy
        per span — while nacked records (rare) take the scalar path in
        place, so the output order is exactly the scalar role's."""
        j0, rb, lo, hi, h_of = payload
        m = hi - lo
        seqs = res.seq[j0:j0 + m]
        msns = res.msn[j0:j0 + m]
        nacks = res.nack[j0:j0 + m]
        skips = res.skipped[j0:j0 + m]
        # 0 = dropped (dedup), 1 = accepted, 2 = nacked.
        cat = np.where(skips, 0,
                       np.where(nacks == 0, 1, 2)).astype(np.int8)
        pool = self.core.pool
        for c, a, b in _sk.mask_runs(cat):
            if c == 0:
                continue  # deduped resubmissions: nothing emitted
            rows = slice(lo + a, lo + b)
            if c == 1:
                off = rb._blob_off[lo + a:lo + b + 1]
                heap = bytes(rb._heap[off[0]:off[-1]])
                seq64 = seqs[a:b].astype(np.int64)
                msn64 = msns[a:b].astype(np.int64)
                w = b - a
                part = _rb.ColumnarRecords(
                    rb.docs,
                    np.full(w, _rb.K_SEQ_OP, np.uint8),
                    np.full(w, _TC_OP, np.uint8),
                    rb.doc_idx[rows],
                    rb.client[rows], rb.client_seq[rows],
                    rb.ref_seq[rows],
                    seq64, msn64,
                    np.arange(base + lo + a, base + lo + b,
                              dtype=np.int64),
                    (off - off[0]).astype(np.uint32), heap,
                )
                sc.flush(out)  # strays before this span keep order
                out.append(part)
                # Mirror update from flat columns (last write wins per
                # (doc, client) — order-equivalent within a span of
                # plain ops, and spans run in stream order).
                span_docs = rb.doc_idx[rows]
                pool.mark_dirty([rb.docs[d] for d in
                                 np.unique(span_docs).tolist()])
                for d, cl, cs, rf, sq, mn in zip(
                        span_docs.tolist(),
                        rb.client[rows].tolist(),
                        rb.client_seq[rows].tolist(),
                        rb.ref_seq[rows].tolist(),
                        seq64.tolist(), msn64.tolist()):
                    h = h_of[d]
                    h["clients"][cl] = [rf, cs]
                    h["seq"] = sq
                    h["min_seq"] = mn
            else:
                docs = rb.docs
                for i in range(lo + a, lo + b):
                    j = i - lo
                    doc = docs[int(rb.doc_idx[i])]
                    cid = int(rb.client[i])
                    cseq = int(rb.client_seq[i])
                    ref = int(rb.ref_seq[i])
                    nk = int(nacks[j])
                    msn = int(msns[j])
                    sc.nack(doc, cid, cseq, nk, _nack_reason(
                        nk, ref, msn, pool.head(doc), cseq,
                        pool.expected_cseq(doc, cid)), base + i)
