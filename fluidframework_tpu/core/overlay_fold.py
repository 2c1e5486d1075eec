"""Overlay-pallas fold backend for the summary service.

The serving summarizer (`server.summarizer.SummarizerRole`) folds
merge-tree docs through the vectorized ROW-MODEL kernel
(`ops.mergetree_kernel.apply_op_batch_docs_jit`) — O(capacity) vector
work per op. The in-tree overlay engine replays the same semantics at
O(collab window) per op, but until this module it had no live
consumer on the summary
path. `OverlayFoldReplica` is the summarizer-shaped driver:

- **boot from canonical rows** (`boot_overlay`) — the restart path,
  identical in contract to `summarizer._boot_mergetree`: settled rows
  (ins normalized to UNIVERSAL_SEQ, not removed) become the settled
  text/props space, everything else (unsettled inserts, tombstones
  above the window) boots as overlay TEXT rows over a fresh arena.
- **fold rounds** through the fused overlay replay
  (`ops.overlay_pallas.replay_fused`): one device dispatch per round
  per doc, per-chunk zamboni folds riding the dispatch, fold records
  pulled once per round and applied to the host settled state
  (`core.overlay_replay.reconstruct_settled`, incremental form).
  Several docs folding in one emission round STACK over the 2-D
  device plane (`parallel.device_plane.DevicePlane`): the stacked doc
  axis tiles ``PartitionSpec(('docs', 'model'))`` — the summarizer's
  half of the one-chip-pool composition (the sequencer holds the
  ``docs`` axis of the same mesh).
- **canonical serialization** (`canonical_rows`) — bit-identical to
  `summarizer._canonical_rows` over the kernel table BY CONTRACT: the
  same normalization (tombstones <= msn dropped, settled ins
  normalized to (UNIVERSAL_SEQ, NO_CLIENT), adjacent equal-semantic
  rows merged maximally) applied to the overlay state, so blob bytes
  and content-addressed handles are backend-invariant. The
  differential gates (tests/test_device_plane.py) hold the two
  backends byte-equal on every host; `overlay_available` is the loud-fallback probe for hosts
  where pallas cannot lower (CPU children run the interpreter via
  ``FLUID_FOLD_INTERPRET`` for correctness gates).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "OverlayFoldReplica",
    "boot_overlay",
    "fold_jobs_overlay",
    "merge_canonical_rows",
    "overlay_available",
]

# Fold-engine shape knobs: chunk mirrors the summarizer's kernel-fold
# chunk; the window is the overlay table's unsettled-row capacity
# (pallas tiling wants multiples of 1024) and grows ahead of need.
_CHUNK = 128
_MIN_WINDOW = 1024
_PK = 4  # max prop pairs per encoded op (KernelReplica default)
_KR = 4  # removers per row (KernelReplica default)
_KK = 8  # prop keys (KernelReplica default)


def merge_canonical_rows(raw_rows) -> List[list]:
    """THE canonical-row merge rule, shared by both fold backends:
    adjacent rows whose semantic fields all match coalesce into
    maximal runs, erasing split/chunk/engine history from the bytes.
    `raw_rows` yields ``(text, ins, icl, rem|None, rcl|None, props)``
    tuples in document order."""
    out: List[list] = []
    last_key: Optional[tuple] = None
    for seg, ins, icl, rem, rcl, props in raw_rows:
        key = (ins, icl, rem, tuple(rcl) if rcl else None,
               json.dumps(props, sort_keys=True))
        if key == last_key and out:
            out[-1][0] += seg
        else:
            out.append([seg, ins, icl, rem, rcl, props])
            last_key = key
    return out


# ---------------------------------------------------------------------------
# availability probe
# ---------------------------------------------------------------------------

def overlay_available(interpret: bool = False) -> bool:
    """Whether the overlay-pallas fold can run here: the interpreter
    runs anywhere; compiled Mosaic kernels lower only on a TPU backend.
    A platform check, not a probe — a lowering error on the TPU is a
    bug to surface, not a reason to pick another backend."""
    if interpret:
        return True
    import jax

    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# the replica
# ---------------------------------------------------------------------------


class OverlayFoldReplica:
    """Overlay-engine twin of the summarizer's `KernelReplica` fold
    state: same encode surface (`kernel_replica.encode_op` writes into
    `_encoded` through the arena/prop-interner attrs), same
    boot-from-rows restart contract, same canonical serialization —
    different engine underneath."""

    def __init__(self, interpret: bool = False,
                 window: int = _MIN_WINDOW):
        import jax.numpy as jnp  # noqa: F401  (asserts jax present)

        from ..ops.overlay_pallas import make_overlay_table
        from .kernel_replica import PropInterner, TextArena

        self.interpret = bool(interpret)
        self.chunk_size = _CHUNK
        self.max_prop_pairs = _PK
        self.n_removers = _KR
        self.n_prop_keys = _KK
        self.window = int(window)
        self.arena = TextArena("")
        self.props = PropInterner(_KK)
        self.table = make_overlay_table(self.window, _KR, _KK)
        # Host settled state (text/props/attr as np arrays of
        # codepoints / interned ids), advanced per round from the fold
        # records — the `OverlayDeviceReplica.reconstruct_settled`
        # walk in incremental form.
        self.settled_t = np.zeros(0, np.int32)
        self.settled_p = np.zeros((0, _KK), np.int32)
        self.settled_a = np.zeros(0, np.int32)
        # encode_op contract fields.
        self._encoded: List[tuple] = []
        self._pending_rows_bound = 0
        # _encode_fold contract fields.
        self.min_seq = 0
        self.current_seq = 0
        self._applied_min_seq = 0

    # --------------------------------------------------------- capacity

    def _ensure_window(self, need: int) -> None:
        """Grow the overlay table's row capacity ahead of a round (the
        `KernelReplica._ensure_capacity` role): padding preserves every
        field's empty-row sentinel, in 1024-row steps (pallas tiling).
        """
        if need <= self.window:
            return
        import jax.numpy as jnp

        from ..ops.mergetree_kernel import NOT_REMOVED, PROP_ABSENT
        from ..protocol.constants import NO_CLIENT

        new_w = self.window
        while new_w < need:
            new_w += _MIN_WINDOW
        pad = new_w - self.window
        t = self.table
        self.table = t._replace(
            anchor=jnp.pad(t.anchor, (0, pad)),
            buf_start=jnp.pad(t.buf_start, (0, pad)),
            length=jnp.pad(t.length, (0, pad)),
            ins_seq=jnp.pad(t.ins_seq, (0, pad)),
            ins_client=jnp.pad(t.ins_client, (0, pad),
                               constant_values=NO_CLIENT),
            rem_seq=jnp.pad(t.rem_seq, (0, pad),
                            constant_values=NOT_REMOVED),
            rem_clients=jnp.pad(t.rem_clients, ((0, pad), (0, 0)),
                                constant_values=NO_CLIENT),
            props=jnp.pad(t.props, ((0, pad), (0, 0)),
                          constant_values=PROP_ABSENT),
        )
        self.window = new_w

    # ------------------------------------------------------------ round

    def build_round(self) -> Optional[dict]:
        """Drain `_encoded` into one padded fold-round job: columnar
        OpBatch host arrays (NOOP-padded to whole chunks), the
        per-chunk MSN fold schedule (each chunk folds at its last real
        row's msn — semantics-free boundaries, the zamboni watermark
        riding the dispatch), a fresh per-round fold log, and the
        window sized so ERR_CAPACITY cannot fire for this round's row
        bound. Returns None when nothing is pending."""
        from ..ops.mergetree_kernel import (
            NO_KEY,
            OP_NOOP,
            PROP_ABSENT,
        )
        from ..protocol.constants import NO_CLIENT

        rows = self._encoded
        if not rows:
            return None
        self._encoded = []
        n = len(rows)
        B = self.chunk_size
        n_chunks = -(-n // B)
        pad = n_chunks * B
        self._ensure_window(int(self._rows_now()) + 4 * n + 64)
        op_type = np.full(pad, OP_NOOP, np.int32)
        pos1 = np.zeros(pad, np.int32)
        pos2 = np.zeros(pad, np.int32)
        seq = np.zeros(pad, np.int32)
        ref = np.zeros(pad, np.int32)
        client = np.full(pad, NO_CLIENT, np.int32)
        buf = np.zeros(pad, np.int32)
        ilen = np.zeros(pad, np.int32)
        pkeys = np.full((pad, _PK), NO_KEY, np.int32)
        pvals = np.full((pad, _PK), PROP_ABSENT, np.int32)
        msns = np.zeros(n_chunks, np.int32)
        for i, (t, p1, p2, s, r, c, b, ln, ks, vs, msn) in \
                enumerate(rows):
            op_type[i], pos1[i], pos2[i] = t, p1, p2
            seq[i], ref[i], client[i], buf[i], ilen[i] = s, r, c, b, ln
            for j, (k, v) in enumerate(zip(ks, vs)):
                pkeys[i, j], pvals[i, j] = k, v
            msns[i // B] = msn
        self._applied_min_seq = rows[-1][10]
        self._pending_rows_bound = 0
        return {
            "rep": self,
            "window": self.window,
            "n": n,
            "n_chunks": n_chunks,
            "batch": (op_type, pos1, pos2, seq, ref, client, buf, ilen,
                      pkeys, pvals),
            "msns": msns,
            # Worst case: every fold emits at most `window` records
            # (only table rows fold), one fold per chunk.
            "log_cap": (n_chunks + 1) * self.window,
        }

    def _rows_now(self) -> int:
        return int(self.table.n_rows)

    def apply_round(self, table, log, counts) -> None:
        """Fold a finished round's outputs back into this replica:
        adopt the table and replay the round's fold records into the
        host settled state (one reconstruct epoch per chunk)."""
        from .overlay_replay import reconstruct_settled

        self.table = table
        counts_l = [int(c) for c in np.asarray(counts)]
        total = sum(counts_l)
        if total:
            stream_text = np.frombuffer(
                self.arena.snapshot().encode("utf-32-le"), np.uint32
            ).astype(np.int32)
            self.settled_t, self.settled_p, self.settled_a = \
                reconstruct_settled(
                    self.settled_t, stream_text,
                    np.asarray(log)[:total], counts_l, _KK,
                    initial_props=self.settled_p,
                    initial_attr=self.settled_a,
                )
        if len(self.settled_t) != int(self.table.settled_len):
            raise RuntimeError(
                f"overlay fold settled desync: host "
                f"{len(self.settled_t)} != device "
                f"{int(self.table.settled_len)}"
            )

    def fold_pending(self) -> None:
        """Single-replica round (the unstacked path — also the
        defensive flush `canonical_rows` takes if encoded rows are
        still pending)."""
        job = self.build_round()
        if job is None:
            return
        _run_rounds([job], plane=None, interpret=self.interpret)

    # -------------------------------------------------- serialization

    def _check_invariants(self, t) -> None:
        """Host-side structural invariants of the overlay table
        (`overlay_ref.OverlayDoc.verify_invariants`' serving twin),
        checked BEFORE every serialization: a corrupt table — however
        it got that way — must freeze the doc loudly (RuntimeError →
        the role's freeze path, longer tails), never ship a wrong
        content-addressed blob."""
        from ..ops.mergetree_kernel import NOT_REMOVED
        from ..ops.overlay_pallas import SETTLED_BASE
        from ..protocol.constants import NO_CLIENT

        n = int(t.n_rows)
        if n < 0 or n > self.window:
            raise RuntimeError(f"overlay n_rows corrupt: {n}")
        if n == 0:
            return
        length = t.length[:n]
        anchor = t.anchor[:n]
        is_span = t.buf_start[:n] >= SETTLED_BASE
        removed = t.rem_seq[:n] != NOT_REMOVED
        has_removers = (t.rem_clients[:n] != NO_CLIENT).any(axis=1)
        S = int(t.settled_len)
        consume = np.where(is_span, length, 0)
        end = anchor + consume
        bad = (
            (length <= 0).any()
            or (anchor < 0).any() or (end > S).any()
            or (n > 1 and (anchor[1:] < end[:-1]).any())
            or bool((removed != has_removers).any())
            or (t.ins_seq[:n] < 0).any()
            or (t.ins_client[:n] < NO_CLIENT).any()
        )
        if bad:
            raise RuntimeError(
                "overlay table failed structural invariants at "
                "serialization (corrupt row state); freezing the doc "
                "rather than shipping a wrong summary"
            )

    def canonical_rows(self, msn: int) -> List[list]:
        """The canonical serialized row form at fold msn `msn` —
        byte-identical to `summarizer._canonical_rows` over the kernel
        table for the same op prefix (the backend-invariance contract
        the content-addressed handles rest on). Runs the final zamboni
        fold at `msn` first, so the table holds only rows the window
        still needs."""
        import jax
        import jax.numpy as jnp

        from ..ops.mergetree_kernel import (
            NOT_REMOVED,
            PROP_DELETE,
            PROP_ABSENT,
            raise_kernel_errors,
        )
        from ..ops.overlay_pallas import SETTLED_BASE, fold_device
        from ..ops.overlay_ref import merge_span_props
        from ..protocol.constants import NO_CLIENT, UNIVERSAL_SEQ

        self.fold_pending()
        self.table, records, n_rec = fold_device(
            self.table, jnp.int32(msn)
        )
        self.apply_round(self.table, np.asarray(records),
                         [int(n_rec)])
        t = jax.tree_util.tree_map(np.asarray, self.table)
        raise_kernel_errors(int(t.error))
        self._check_invariants(t)
        arena_text = self.arena.snapshot()
        decode = self.props.decode_row
        settled_t, settled_p = self.settled_t, self.settled_p
        raw: List[tuple] = []

        def emit_settled(lo: int, hi: int) -> None:
            # Settled content: ins normalized by construction; split
            # into maximal equal-prop runs (the canonical merge below
            # re-merges across row boundaries with the full key).
            i = lo
            while i < hi:
                j = i + 1
                while j < hi and np.array_equal(settled_p[j],
                                                settled_p[i]):
                    j += 1
                raw.append((
                    "".join(map(chr, settled_t[i:j].tolist())),
                    UNIVERSAL_SEQ, NO_CLIENT, None, None,
                    decode(settled_p[i]),
                ))
                i = j

        cursor = 0
        for i in range(int(t.n_rows)):
            a = int(t.anchor[i])
            if a > cursor:
                emit_settled(cursor, a)
                cursor = a
            rem = int(t.rem_seq[i])
            removed = rem != NOT_REMOVED
            ln = int(t.length[i])
            is_span = int(t.buf_start[i]) >= SETTLED_BASE
            if removed and rem <= msn:
                # Tombstone below the window: zamboni (the final fold
                # above dropped these; defensive for exactness).
                if is_span:
                    cursor = a + ln
                continue
            rcl = (sorted(int(c) for c in t.rem_clients[i]
                          if int(c) != NO_CLIENT) if removed else None)
            if is_span:
                # Removed settled text (a live span cannot survive the
                # fold — spans fold unconditionally): per-position
                # merged props split into runs, insert identity is
                # settled == universal.
                merged = merge_span_props(
                    settled_p[a: a + ln], t.props[i]
                )
                k = 0
                while k < ln:
                    k2 = k + 1
                    while k2 < ln and np.array_equal(merged[k2],
                                                     merged[k]):
                        k2 += 1
                    raw.append((
                        "".join(map(chr,
                                    settled_t[a + k: a + k2].tolist())),
                        UNIVERSAL_SEQ, NO_CLIENT, rem, rcl,
                        decode(merged[k]),
                    ))
                    k = k2
                cursor = a + ln
            else:
                b = int(t.buf_start[i])
                seg = arena_text[b: b + ln]
                ins = int(t.ins_seq[i])
                icl = int(t.ins_client[i])
                if ins <= msn:
                    ins, icl = UNIVERSAL_SEQ, NO_CLIENT
                row_p = np.asarray(t.props[i]).copy()
                row_p[row_p == PROP_DELETE] = PROP_ABSENT
                raw.append((seg, ins, icl, rem if removed else None,
                            rcl, decode(row_p)))
        emit_settled(cursor, len(settled_t))
        return merge_canonical_rows(raw)


def boot_overlay(rows: List[list], msn: int,
                 interpret: bool = False) -> OverlayFoldReplica:
    """Build a live overlay fold replica from serialized canonical
    rows — THE restart path, run after every emission exactly like
    `summarizer._boot_mergetree` so interrupted and uninterrupted
    summarizers proceed from the identical state."""
    import jax.numpy as jnp

    from ..ops.mergetree_kernel import NOT_REMOVED, PROP_ABSENT
    from ..ops.overlay_pallas import make_overlay_table
    from ..protocol.constants import NO_CLIENT, UNIVERSAL_SEQ

    rep = OverlayFoldReplica(interpret=interpret)
    n = len(rows)
    need_w = _MIN_WINDOW
    while need_w < n + 2 * _CHUNK + 8:
        need_w += _MIN_WINDOW
    W = need_w
    anchor = np.zeros(W, np.int32)
    buf = np.zeros(W, np.int32)
    length = np.zeros(W, np.int32)
    iseq = np.zeros(W, np.int32)
    icl_a = np.full(W, NO_CLIENT, np.int32)
    rseq = np.full(W, NOT_REMOVED, np.int32)
    rcl_a = np.full((W, _KR), NO_CLIENT, np.int32)
    props_a = np.full((W, _KK), PROP_ABSENT, np.int32)
    settled_t: List[int] = []
    settled_p: List[np.ndarray] = []
    m = 0
    for seg, ins, icl, rem, rcl, prow in rows:
        prow_ids = np.full(_KK, PROP_ABSENT, np.int32)
        if prow:
            for k, v in prow.items():
                prow_ids[rep.props.key_id(k)] = rep.props.value_id(v)
        if rem is None and ins <= msn:
            # Settled run: text/props join the settled space directly
            # (ins is UNIVERSAL_SEQ in canonical form; <= msn keeps
            # the rule identical to the kernel boot's semantics).
            settled_t.extend(ord(c) for c in seg)
            settled_p.extend([prow_ids] * len(seg))
            continue
        # Window TEXT row: unsettled insert or an above-window
        # tombstone; anchor = current settled position, text in the
        # arena. Normalized-identity tombstones keep
        # (UNIVERSAL_SEQ, NO_CLIENT) — visible to every perspective,
        # exactly the settled-content rule.
        anchor[m] = len(settled_t)
        buf[m] = rep.arena.append(seg)
        length[m] = len(seg)
        iseq[m] = UNIVERSAL_SEQ if ins <= msn else ins
        icl_a[m] = NO_CLIENT if ins <= msn else icl
        if rem is not None:
            rseq[m] = rem
            if rcl:
                rcl_a[m, : len(rcl)] = rcl
        props_a[m] = prow_ids
        m += 1
    rep.window = W
    rep.settled_t = np.asarray(settled_t, np.int32)
    rep.settled_p = (
        np.stack(settled_p) if settled_p
        else np.zeros((0, _KK), np.int32)
    )
    rep.settled_a = np.zeros(len(settled_t), np.int32)
    rep.table = make_overlay_table(W, _KR, _KK)._replace(
        n_rows=jnp.int32(m),
        anchor=jnp.asarray(anchor),
        buf_start=jnp.asarray(buf),
        length=jnp.asarray(length),
        ins_seq=jnp.asarray(iseq),
        ins_client=jnp.asarray(icl_a),
        rem_seq=jnp.asarray(rseq),
        rem_clients=jnp.asarray(rcl_a),
        props=jnp.asarray(props_a),
        settled_len=jnp.int32(len(settled_t)),
    )
    rep.min_seq = rep._applied_min_seq = int(msn)
    rep._pending_rows_bound = m
    return rep


# ---------------------------------------------------------------------------
# stacked rounds over the device plane
# ---------------------------------------------------------------------------


_STACKED_FN_CACHE: Dict[tuple, Any] = {}


def _stacked_fold_fn(mesh, chunk: int, interpret: bool):
    """Compile the stacked whole-round fold: `lax.map` over the
    stacked doc axis running the fused overlay replay per doc. With a
    device plane the map body shard_maps over BOTH mesh axes — the
    stacked doc axis tiles ``P(('docs', 'model'))``, so K docs spread
    over the whole pool (the `parallel.mesh.sharded_overlay_replay
    _multi` idiom on the 2-D plane). Cached process-wide per (mesh,
    chunk, interpret) — paired with `shared_plane`, every summarizer
    round in a process reuses ONE compiled callable per shape instead
    of re-tracing per emission."""
    key = (mesh, chunk, bool(interpret))
    cached = _STACKED_FN_CACHE.get(key)
    if cached is not None:
        return cached
    import jax

    from ..ops.mergetree_kernel import OpBatch
    from ..ops.overlay_pallas import OverlayTable, replay_fused

    def local(tables, ops, logs, counts, msns):
        def one(args):
            t, o, log, cnt, msn = args
            return replay_fused(t, o, log, cnt, msn, chunk, interpret)

        return jax.lax.map(one, (tables, ops, logs, counts, msns))

    if mesh is None:
        fn = _STACKED_FN_CACHE[key] = jax.jit(local)
        return fn
    from jax.sharding import PartitionSpec as P

    docs = P(("docs", "model"))
    table_specs = OverlayTable(
        n_rows=docs, anchor=docs, buf_start=docs, length=docs,
        ins_seq=docs, ins_client=docs, rem_seq=docs, rem_clients=docs,
        props=docs, settled_len=docs, error=docs,
    )
    op_specs = OpBatch(
        op_type=docs, pos1=docs, pos2=docs, seq=docs, ref_seq=docs,
        client=docs, buf_start=docs, ins_len=docs, prop_keys=docs,
        prop_vals=docs,
    )
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(table_specs, op_specs, docs, docs, docs),
        out_specs=(table_specs, docs, docs, docs),
        check_vma=False,
    )
    jitted = _STACKED_FN_CACHE[key] = jax.jit(fn)
    return jitted


def _run_rounds(jobs: List[dict], plane=None,
                interpret: bool = False) -> None:
    """Execute fold-round jobs: singletons run the fused replay
    directly; same-shape groups stack into ONE dispatch (padded with
    empty dummy replicas up to the plane size so the shard_map's doc
    axis divides the mesh). Outputs unstack back into each replica."""
    import jax
    import jax.numpy as jnp

    from ..ops.mergetree_kernel import OpBatch

    def job_device_inputs(job):
        (op_type, pos1, pos2, seq, ref, client, buf, ilen,
         pkeys, pvals) = job["batch"]
        batch = OpBatch(
            op_type=jnp.asarray(op_type), pos1=jnp.asarray(pos1),
            pos2=jnp.asarray(pos2), seq=jnp.asarray(seq),
            ref_seq=jnp.asarray(ref), client=jnp.asarray(client),
            buf_start=jnp.asarray(buf), ins_len=jnp.asarray(ilen),
            prop_keys=jnp.asarray(pkeys), prop_vals=jnp.asarray(pvals),
        )
        log = jnp.zeros((job["log_cap"], 5 + _KK), jnp.int32)
        counts = jnp.zeros(job["n_chunks"], jnp.int32)
        return batch, log, counts, jnp.asarray(job["msns"])

    # Group by the shapes stacking requires to be uniform; chunk
    # padding inside a group re-folds at the same msn — idempotent
    # (nothing new settles, nothing new drops), so padded chunks are
    # semantics-free.
    groups: Dict[tuple, List[dict]] = {}
    for job in jobs:
        groups.setdefault((job["window"],), []).append(job)
    for _key, grp in groups.items():
        # Singletons ride the SAME undonated jitted map as groups
        # (stack of one): the overlay fold never donates a live
        # replica's table buffers — `replay_fused`'s donation only
        # exists inside the traced map body, where it is inert.
        # Uniform chunk count / log cap across the group (pad by
        # repeating the last chunk's msn — an msn-idempotent no-op).
        n_chunks = max(j["n_chunks"] for j in grp)
        log_cap = max(j["log_cap"] for j in grp)
        for j in grp:
            (op_type, pos1, pos2, seq, ref, client, buf, ilen,
             pkeys, pvals) = j["batch"]
            pad = n_chunks * _CHUNK - len(op_type)
            if pad:
                from ..ops.mergetree_kernel import (
                    NO_KEY,
                    OP_NOOP,
                    PROP_ABSENT,
                )
                from ..protocol.constants import NO_CLIENT

                j["batch"] = (
                    np.concatenate([op_type,
                                    np.full(pad, OP_NOOP, np.int32)]),
                    np.concatenate([pos1, np.zeros(pad, np.int32)]),
                    np.concatenate([pos2, np.zeros(pad, np.int32)]),
                    np.concatenate([seq, np.zeros(pad, np.int32)]),
                    np.concatenate([ref, np.zeros(pad, np.int32)]),
                    np.concatenate([client,
                                    np.full(pad, NO_CLIENT, np.int32)]),
                    np.concatenate([buf, np.zeros(pad, np.int32)]),
                    np.concatenate([ilen, np.zeros(pad, np.int32)]),
                    np.concatenate([pkeys,
                                    np.full((pad, _PK), NO_KEY,
                                            np.int32)]),
                    np.concatenate([pvals,
                                    np.full((pad, _PK), PROP_ABSENT,
                                            np.int32)]),
                )
            if j["n_chunks"] < n_chunks:
                j["msns"] = np.concatenate([
                    j["msns"],
                    np.full(n_chunks - j["n_chunks"], j["msns"][-1],
                            np.int32),
                ])
            j["n_chunks"] = n_chunks
            j["log_cap"] = log_cap
        real = len(grp)
        mesh = plane.mesh if plane is not None else None
        if mesh is not None:
            # Pad the stack to a mesh multiple with empty dummies so
            # the shard_map's doc axis divides the device grid.
            size = plane.size
            while len(grp) % size:
                grp.append(_dummy_job(grp[0]))
        stack = lambda *xs: jnp.stack(xs)  # noqa: E731
        tables = jax.tree_util.tree_map(
            stack, *[j["rep"].table if j["rep"] is not None
                     else j["table"] for j in grp]
        )
        devs = [job_device_inputs(j) for j in grp]
        opss = jax.tree_util.tree_map(stack, *[d[0] for d in devs])
        logs = jnp.stack([d[1] for d in devs])
        countss = jnp.stack([d[2] for d in devs])
        msnss = jnp.stack([d[3] for d in devs])
        fn = _stacked_fold_fn(mesh, _CHUNK, interpret)
        out_tables, out_logs, out_counts, _cursors = fn(
            tables, opss, logs, countss, msnss
        )
        out_logs = np.asarray(out_logs)
        out_counts = np.asarray(out_counts)
        for d, j in enumerate(grp[:real]):
            rep = j["rep"]
            table = jax.tree_util.tree_map(
                lambda a, _d=d: a[_d], out_tables
            )
            rep.apply_round(table, out_logs[d], out_counts[d])


def _dummy_job(like: dict) -> dict:
    """An empty padding replica shaped like `like` (rep=None: outputs
    are discarded)."""
    from ..ops.mergetree_kernel import (
        NO_KEY,
        OP_NOOP,
        PROP_ABSENT,
    )
    from ..ops.overlay_pallas import make_overlay_table
    from ..protocol.constants import NO_CLIENT

    pad = like["n_chunks"] * _CHUNK
    return {
        "rep": None,
        "table": make_overlay_table(like["window"], _KR, _KK),
        "window": like["window"],
        "n": 0,
        "n_chunks": like["n_chunks"],
        "batch": (
            np.full(pad, OP_NOOP, np.int32), np.zeros(pad, np.int32),
            np.zeros(pad, np.int32), np.zeros(pad, np.int32),
            np.zeros(pad, np.int32), np.full(pad, NO_CLIENT, np.int32),
            np.zeros(pad, np.int32), np.zeros(pad, np.int32),
            np.full((pad, _PK), NO_KEY, np.int32),
            np.full((pad, _PK), PROP_ABSENT, np.int32),
        ),
        "msns": np.zeros(like["n_chunks"], np.int32),
        "log_cap": like["log_cap"],
    }


def fold_jobs_overlay(jobs: List[Tuple[Any, list]], plane=None,
                      interpret: bool = False) -> None:
    """Drain the pending encoded rows of several overlay replicas —
    the `summarizer._fold_jobs` twin for the overlay backend: each
    replica's round is ONE fused replay dispatch, and same-shape
    replicas stack across the device plane (K summarizing docs tile
    the 2-D pool in one dispatch instead of K)."""
    round_jobs: List[dict] = []
    for rep, _take in jobs:
        job = rep.build_round()
        if job is not None:
            round_jobs.append(job)
    if round_jobs:
        _run_rounds(round_jobs, plane=plane, interpret=interpret)
