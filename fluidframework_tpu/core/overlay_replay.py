"""Overlay replay engine: the O(collab window) TPU fast path.

`OverlayDeviceReplica` plays the same role as
`core.columnar_replay.ColumnarReplica` (consume a pre-decoded columnar
op stream, converge on the final document state) but drives the
overlay pallas kernel (`ops.overlay_pallas`): the device table holds
only UNSETTLED rows — per-op kernel work scales with the collaboration
window (a few thousand rows) instead of the table capacity (131k),
which is the reference's O(log n) B-tree + partial-lengths bound
(mergeTree.ts:1397, partialLengths.ts:256) re-expressed for the VPU.

Settled content never occupies device memory as rows: each per-chunk
fold appends its settled/dropped rows to a preallocated HBM record log
(one `dynamic_update_slice`, donated/in-place), and the host
reconstructs the settled text+props once, AFTER the timed region, by
replaying the log epoch-by-epoch (`reconstruct_settled`) — the
snapshot role, off the hot path, like the reference's snapshot write
(snapshotV1.ts:30). This also removes the round-2 VMEM scale cliff:
document length is unbounded by the window table; only the collab
window itself must fit (ERR_CAPACITY flags if it doesn't).

The steady-state loop performs ZERO host<->device transfers and no
blocking syncs: the (NOOP-padded) stream uploads once, each chunk is
one `replay_chunk_step` dispatch, and errors ride the table scalar,
checked at the end.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.mergetree_kernel import (
    NO_CLIENT,
    NO_KEY,
    NOT_REMOVED,
    OP_NOOP,
    PROP_ABSENT,
    PROP_DELETE,
    OpBatch,
    raise_kernel_errors,
)
from ..ops.overlay_pallas import (
    OVERLAY_PROGRAM,
    REC_DROP_SPAN,
    REC_NONE,
    REC_SETTLE_SPAN,
    REC_SETTLE_TEXT,
    OverlayTable,
    make_overlay_table,
    replay_chunk_step,
    replay_fused,
)
from ..ops.overlay_ref import (
    SETTLED_BASE,
    OverlayDoc,
    OverlayReplica,
    merge_span_props,
)
from ..testing.synthetic import ColumnarStream
from ..utils.metrics import named_program, span


def reconstruct_settled(
    initial_text: np.ndarray,
    stream_text: np.ndarray,
    log: np.ndarray,
    counts: List[int],
    n_prop_keys: int,
    initial_props: Optional[np.ndarray] = None,
    initial_attr: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Replay the fold log into the final settled (text, props, attr).

    Each epoch's records are in storage (== coordinate) order with
    anchors in that epoch's settled space — exactly the walk
    `overlay_ref.OverlayDoc.fold` performs in-place; here it runs once
    per epoch over the logged rows instead (same codes, same
    PROP_DELETE tombstone semantics; `attr` carries each settled
    position's insert-attribution key, record column 4).

    `initial_props`/`initial_attr` seed the settled props/attr arrays
    (defaults: all-absent / zero) — the INCREMENTAL form
    `core.overlay_fold.OverlayFoldReplica` applies per emission round,
    where the initial settled state carries real props from earlier
    rounds instead of a fresh load."""
    KK = n_prop_keys
    settled_t = np.asarray(initial_text, np.int32)
    settled_p = (
        np.asarray(initial_props, np.int32).copy()
        if initial_props is not None
        else np.full((len(settled_t), KK), PROP_ABSENT, np.int32)
    )
    settled_a = (
        np.asarray(initial_attr, np.int32).copy()
        if initial_attr is not None
        else np.zeros(len(settled_t), np.int32)
    )
    off = 0
    for cnt in counts:
        recs = log[off: off + cnt]
        off += cnt
        if cnt == 0:
            continue
        pieces_t: List[np.ndarray] = []
        pieces_p: List[np.ndarray] = []
        pieces_a: List[np.ndarray] = []
        cursor = 0
        for r in recs:
            a = int(r[0])
            code = int(r[1])
            b = int(r[2])
            ln = int(r[3])
            iseq = int(r[4])
            props = r[5:]
            pieces_t.append(settled_t[cursor:a])
            pieces_p.append(settled_p[cursor:a])
            pieces_a.append(settled_a[cursor:a])
            cursor = a
            if code == REC_SETTLE_TEXT:
                pieces_t.append(stream_text[b: b + ln])
                row = props.copy()
                row[row == PROP_DELETE] = PROP_ABSENT
                pieces_p.append(np.broadcast_to(row, (ln, KK)).copy())
                pieces_a.append(np.full(ln, iseq, np.int32))
            elif code == REC_DROP_SPAN:
                cursor = a + ln
            elif code == REC_SETTLE_SPAN:
                pieces_t.append(settled_t[a: a + ln])
                pieces_p.append(
                    merge_span_props(settled_p[a: a + ln], props)
                )
                pieces_a.append(settled_a[a: a + ln])
                cursor = a + ln
            elif code == REC_NONE:
                pass  # dropped text row: reconstructs to nothing
            else:
                raise ValueError(f"bad fold-log code {code}")
        pieces_t.append(settled_t[cursor:])
        pieces_p.append(settled_p[cursor:])
        pieces_a.append(settled_a[cursor:])
        settled_t = np.concatenate(pieces_t) if pieces_t else (
            np.zeros(0, np.int32)
        )
        settled_p = (
            np.concatenate(pieces_p)
            if pieces_p else np.zeros((0, KK), np.int32)
        )
        settled_a = (
            np.concatenate(pieces_a)
            if pieces_a else np.zeros(0, np.int32)
        )
    return settled_t, settled_p, settled_a


@functools.lru_cache(maxsize=None)
def _stream_step_fn(B: int, interpret: bool, n_ops_seg: int,
                    n_chunks_seg: int, shapes: tuple):
    """ONE cached jitted executable per segment shape: unpack the
    packed host->device transfer + the whole fused replay — one
    dispatch per segment rides the wire, and the big carries (table,
    log, counts) are donated so XLA updates them in place. Cached at
    module level so fresh replicas (bench repeats) reuse the compiled
    executable."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    @named_program(OVERLAY_PROGRAM + "_segment")
    def step(table, log, counts, dev, epoch0):
        offs = [0]
        for k in shapes:
            offs.append(offs[-1] + n_ops_seg * max(k, 1))
        fields = []
        for k, o0, o1 in zip(shapes, offs, offs[1:]):
            f = dev[o0:o1]
            if k:
                f = f.reshape(n_ops_seg, k)
            fields.append(f)
        batch = OpBatch(*fields)
        msns = dev[offs[-1]: offs[-1] + n_chunks_seg]
        return replay_fused(
            table, batch, log, counts, msns, B, interpret,
            epoch0=epoch0,
        )

    return step


class OverlayDeviceReplica:
    """Device-resident overlay replica driven by columnar op arrays.

    Same output surface as `ColumnarReplica` / `OverlayReplica`
    (get_text / annotated_spans / check_errors) so the digest gates
    compare all engines directly. `interpret=True` runs the pallas
    kernel through the interpreter so CPU tests gate it bit-for-bit.
    """

    def __init__(
        self,
        stream: ColumnarStream,
        initial_len: int = 0,
        chunk_size: int = 2048,
        window: int = 8192,
        n_removers: int = 4,
        n_prop_keys: int = 8,
        interpret: bool = False,
        log_cap: Optional[int] = None,
    ):
        self.stream = stream
        self.chunk_size = chunk_size
        self.window = window
        self.n_removers = n_removers
        self.n_prop_keys = n_prop_keys
        self.interpret = interpret
        self.initial_len = initial_len

        n = len(stream)
        self.n_chunks = -(-n // chunk_size) if n else 0
        # Every row ever created folds (or survives) exactly once; ~3
        # rows/op (insert + split tails / gap spans) bounds the log.
        self.log_cap = log_cap or (3 * n + 4 * window)
        # The feed's layer spans: device allocations here, padding
        # (`prepare_host`), the upload (`prepare`) or per-segment puts.
        alloc, self._sp_pad, self._sp_upload, self._sp_put = (
            span(f"overlay.{phase}")
            for phase in ("alloc", "pad", "upload", "segment_put"))
        with alloc:
            self.table = make_overlay_table(
                window, n_removers, n_prop_keys, settled_len=initial_len
            )
            self.log = jnp.zeros((self.log_cap, 5 + n_prop_keys),
                                 jnp.int32)
            self.counts = jnp.zeros(max(self.n_chunks, 1), jnp.int32)
            self.cursor = jnp.int32(0)
        self.chunks_done = 0
        self._doc: Optional[OverlayDoc] = None
        self._dev: Optional[OpBatch] = None

    # -------------------------------------------------------------- replay

    def prepare(self) -> None:
        """Upload the (NOOP-padded) op stream and per-chunk MSN
        schedule to the device — the load phase, outside the timed
        replay region (the reference replay tool likewise pre-parses
        recorded op files before its timed loop,
        packages/tools/replay-tool/src/replayMessages.ts)."""
        if getattr(self, "_dev", None) is not None:
            return
        self.prepare_host()
        with self._sp_upload:
            self._dev = OpBatch(*(jnp.asarray(a) for a in self._host))
            self._msn_by_chunk = jnp.asarray(self._host_msn)

    def prepare_host(self) -> None:
        """Decode the stream into padded HOST arrays only (the
        streaming-ingress load phase: nothing touches the device; the
        replay itself feeds segments in)."""
        if getattr(self, "_host", None) is not None:
            return
        with self._sp_pad:
            self._pad_host()

    def _pad_host(self) -> None:
        s = self.stream
        n = len(s)
        B = self.chunk_size
        pad = self.n_chunks * B

        def up(a: np.ndarray, fill: int = 0) -> np.ndarray:
            out = np.full(pad, fill, np.int32)
            out[:n] = a
            return out

        self._host = OpBatch(
            op_type=up(s.op_type, OP_NOOP),
            pos1=up(s.pos1), pos2=up(s.pos2),
            seq=up(s.seq), ref_seq=up(s.ref_seq),
            client=up(s.client, NO_CLIENT),
            buf_start=up(s.buf_start), ins_len=up(s.ins_len),
            prop_keys=up(s.prop_key, NO_KEY)[:, None],
            prop_vals=up(s.prop_val, PROP_ABSENT)[:, None],
        )
        # Applied MSN at each chunk's end (the fold perspective).
        ends = np.minimum(np.arange(1, self.n_chunks + 1) * B, n) - 1
        self._host_msn = s.min_seq[ends].astype(np.int32)

    def replay_streaming(self, n_segments: int = 8) -> None:
        """Replay with INGEST IN THE LOOP: the op stream lives on the
        host and feeds the device segment by segment, each segment's
        transfer (async `jax.device_put`) overlapping the previous
        segment's fused replay — the alfred→deli→merge pipeline
        running concurrently end-to-end (SURVEY §2.6 row 4;
        localOrderer.ts:245 pipelines per-doc over Kafka the same
        way) instead of the pre-staged load phase."""
        self.prepare_host()
        if not self.n_chunks:
            return
        n_segments = max(1, min(n_segments, self.n_chunks))
        seg_chunks = -(-self.n_chunks // n_segments)
        B = self.chunk_size

        def seg_slice(si: int):
            lo_c = si * seg_chunks
            hi_c = min(lo_c + seg_chunks, self.n_chunks)
            lo, hi = lo_c * B, hi_c * B
            # ONE packed transfer per segment (each transfer pays its
            # own latency; 10 small puts would serialize).
            packed = np.concatenate(
                [np.ascontiguousarray(a[lo:hi]).reshape(-1)
                 for a in self._host]
                + [self._host_msn[lo_c:hi_c]]
            ).astype(np.int32)
            with self._sp_put:
                dev = jax.device_put(packed)
            return lo_c, hi - lo, hi_c - lo_c, dev

        shapes = tuple(
            (a.shape[1] if a.ndim > 1 else 0) for a in self._host
        )

        n_live = -(-self.n_chunks // seg_chunks)
        nxt = seg_slice(0)
        for si in range(n_live):
            lo_c, n_ops_seg, n_chunks_seg, dev = nxt
            if si + 1 < n_live:
                nxt = seg_slice(si + 1)  # async: overlaps the replay
            step = _stream_step_fn(
                B, self.interpret, n_ops_seg, n_chunks_seg, shapes
            )
            self.table, self.log, self.counts, self.cursor = step(
                self.table, self.log, self.counts, dev,
                jnp.int32(lo_c),
            )
        self.chunks_done = self.n_chunks
        self._doc = None

    def replay(self, limit_chunks: Optional[int] = None) -> None:
        """Replay the stream. Full replays run as ONE fused device
        dispatch (`replay_fused`); `limit_chunks` runs the incremental
        per-chunk form instead (compile warm-up with identical shapes
        — share the same stream)."""
        self.prepare()
        if limit_chunks is None and self.n_chunks:
            self.table, self.log, self.counts, self.cursor = replay_fused(
                self.table, self._dev, self.log, self.counts,
                self._msn_by_chunk, self.chunk_size, self.interpret,
            )
            self.chunks_done = self.n_chunks
            self._doc = None
            return
        for ci in range(self.n_chunks):
            if limit_chunks is not None and ci >= limit_chunks:
                break
            self.table, self.log, self.counts, self.cursor = (
                replay_chunk_step(
                    self.table, self._dev, jnp.int32(ci * self.chunk_size),
                    self.chunk_size, self._msn_by_chunk[ci], self.log,
                    self.counts, self.cursor, jnp.int32(ci),
                    self.interpret,
                )
            )
            self.chunks_done = ci + 1
        self._doc = None

    # ------------------------------------------------------------- output

    def check_errors(self) -> None:
        raise_kernel_errors(int(self.table.error))

    def _materialize(self) -> OverlayDoc:
        """Pull the table + fold log once and rebuild the final
        overlay document host-side (off the timed path)."""
        if self._doc is not None:
            return self._doc
        cursor = int(self.cursor)
        if cursor + self.window > self.log_cap:
            raise RuntimeError(
                f"fold log overflow ({cursor} + {self.window} rows > "
                f"cap {self.log_cap}); raise log_cap"
            )
        counts = np.asarray(self.counts)[: self.chunks_done].tolist()
        log = np.asarray(self.log[:cursor])
        settled_t, settled_p, settled_a = reconstruct_settled(
            self.stream.text[: self.initial_len], self.stream.text,
            log, counts, self.n_prop_keys,
        )
        doc = OverlayDoc(settled_t, self.n_removers, self.n_prop_keys)
        doc.settled_props = settled_p
        doc.settled_attr = settled_a
        t = self.table
        m = int(t.n_rows)
        doc.anchor = np.asarray(t.anchor[:m])
        doc.buf = np.asarray(t.buf_start[:m])
        doc.length = np.asarray(t.length[:m])
        doc.iseq = np.asarray(t.ins_seq[:m])
        doc.iclient = np.asarray(t.ins_client[:m])
        doc.rseq = np.asarray(t.rem_seq[:m])
        doc.rcl = np.asarray(t.rem_clients[:m])
        doc.props = np.asarray(t.props[:m])
        doc.error = int(t.error)
        stream_text = np.asarray(self.stream.text, np.int32)

        def row_text(i: int) -> np.ndarray:
            b = int(doc.buf[i])
            ln = int(doc.length[i])
            if b >= SETTLED_BASE:
                a = b - SETTLED_BASE
                return doc.settled_text[a: a + ln]
            return stream_text[b: b + ln]

        doc._row_text = row_text  # type: ignore[assignment]
        self._doc = doc
        return doc

    def _shim(self) -> OverlayReplica:
        shim = OverlayReplica.__new__(OverlayReplica)
        shim.doc = self._materialize()
        shim.stream = self.stream
        return shim

    def get_text(self) -> str:
        return OverlayReplica.get_text(self._shim())

    def annotated_spans(self):
        return OverlayReplica.annotated_spans(self._shim())

    def attribution_spans(self):
        """(run_length, insert-attribution key) runs over the visible
        document — settled keys ride the fold log's ins_seq column,
        unsettled rows derive theirs from the table's ins_seq."""
        return OverlayReplica.attribution_spans(self._shim())

    def verify_invariants(self) -> None:
        self._materialize().verify_invariants()


def stack_replicas(reps: List["OverlayDeviceReplica"]):
    """Stack prepared replicas into the leading-docs-axis input layout
    of `parallel.mesh.sharded_overlay_replay`:
    ``(tables, ops, logs, counts, msn_by_chunk)``."""
    stack = lambda *xs: jnp.stack(xs)
    return (
        jax.tree_util.tree_map(stack, *[r.table for r in reps]),
        jax.tree_util.tree_map(stack, *[r._dev for r in reps]),
        jnp.stack([r.log for r in reps]),
        jnp.stack([r.counts for r in reps]),
        jnp.stack([r._msn_by_chunk for r in reps]),
    )


def restore_shard(
    rep: "OverlayDeviceReplica", out_tables, out_logs, out_counts,
    cursors, d: int,
) -> "OverlayDeviceReplica":
    """Load document `d`'s sharded-replay outputs into `rep` so its
    host-side readout (get_text / annotated_spans / check_errors)
    reflects the mesh run."""
    rep.table = jax.tree_util.tree_map(lambda a: a[d], out_tables)
    rep.log = out_logs[d]
    rep.counts = out_counts[d]
    rep.cursor = cursors[d]
    rep.chunks_done = rep.n_chunks
    rep._doc = None
    return rep


class OverlayKernelMessageReplica:
    """SequencedMessage-driven overlay DEVICE replica: the pallas
    overlay kernel behind the same message surface as
    `overlay_ref.OverlayMessageReplica`, so the farm differential
    tests (real concurrency: lagging refSeqs, tie-breaks, overlapping
    removes, multi-pair annotations) gate the KERNEL bit-for-bit
    against the scalar oracle. Reuses `KernelReplica`'s op encoder
    (text arena + prop interner)."""

    def __init__(self, initial: str = "", chunk_size: int = 64,
                 window: int = 1024, n_removers: int = 4,
                 n_prop_keys: int = 8, max_prop_pairs: int = 4,
                 interpret: bool = True):
        from .kernel_replica import PropInterner, TextArena

        self.arena = TextArena("")
        self.props = PropInterner(n_prop_keys)
        self.chunk_size = chunk_size
        self.window = window
        self.n_removers = n_removers
        self.n_prop_keys = n_prop_keys
        self.max_prop_pairs = max_prop_pairs
        self.interpret = interpret
        self.initial = initial
        self._initial_np = np.asarray([ord(c) for c in initial], np.int32)
        self.table = make_overlay_table(
            window, n_removers, n_prop_keys, settled_len=len(initial)
        )
        self._rows: List[tuple] = []
        self._epochs: List[Tuple[np.ndarray, int]] = []
        self._doc: Optional[OverlayDoc] = None

    def apply_messages(self, msgs) -> None:
        from .kernel_replica import EncoderState, encode_op
        from ..protocol.messages import MessageType

        enc = EncoderState(self.arena, self.props, self.max_prop_pairs)
        msn = 0
        for msg in msgs:
            if msg.type == MessageType.OP and msg.contents is not None:
                encode_op(enc, msg.contents, msg)
                self._rows.extend(enc._encoded)
                if enc._encoded:
                    msn = enc._encoded[-1][10]
                enc._encoded = []
            else:
                msn = max(msn, msg.minimum_sequence_number)
            while len(self._rows) >= self.chunk_size:
                self._flush(self._rows[: self.chunk_size])
                self._rows = self._rows[self.chunk_size:]
        if self._rows:
            self._flush(self._rows)
            self._rows = []
        else:
            self._fold_only(msn)
        self._doc = None

    def _flush(self, rows: List[tuple]) -> None:
        from ..ops.overlay_pallas import fold_device, overlay_apply_chunk

        B = self.chunk_size
        PK = self.max_prop_pairs
        cols = {
            "op_type": (OP_NOOP, 0), "pos1": (0, 1), "pos2": (0, 2),
            "seq": (0, 3), "ref_seq": (0, 4), "client": (NO_CLIENT, 5),
            "buf_start": (0, 6), "ins_len": (0, 7),
        }
        arrs = {}
        for name, (fill, j) in cols.items():
            a = np.full(B, fill, np.int32)
            a[: len(rows)] = [r[j] for r in rows]
            arrs[name] = jnp.asarray(a)
        pk = np.full((B, PK), NO_KEY, np.int32)
        pv = np.full((B, PK), PROP_ABSENT, np.int32)
        for i, r in enumerate(rows):
            ks, vs = r[8], r[9]
            pk[i, : len(ks)] = ks
            pv[i, : len(vs)] = vs
        batch = OpBatch(
            prop_keys=jnp.asarray(pk), prop_vals=jnp.asarray(pv), **arrs
        )
        self.table = overlay_apply_chunk(
            self.table, batch, self.interpret
        )
        msn = rows[-1][10]
        self.table, records, n_rec = fold_device(
            self.table, jnp.int32(msn)
        )
        self._epochs.append((np.asarray(records), int(n_rec)))

    def _fold_only(self, msn: int) -> None:
        from ..ops.overlay_pallas import fold_device

        self.table, records, n_rec = fold_device(
            self.table, jnp.int32(msn)
        )
        self._epochs.append((np.asarray(records), int(n_rec)))

    # ------------------------------------------------------------- output

    def check_errors(self) -> None:
        raise_kernel_errors(int(self.table.error))

    def _materialize(self) -> OverlayDoc:
        if self._doc is not None:
            return self._doc
        arena_text = np.asarray(
            [ord(c) for c in self.arena.snapshot()], np.int32
        )
        counts = [n for _, n in self._epochs]
        log = (
            np.concatenate([r[:n] for r, n in self._epochs])
            if self._epochs else np.zeros((0, 5 + self.n_prop_keys),
                                          np.int32)
        )
        settled_t, settled_p, settled_a = reconstruct_settled(
            self._initial_np, arena_text, log, counts, self.n_prop_keys
        )
        doc = OverlayDoc(settled_t, self.n_removers, self.n_prop_keys)
        doc.settled_props = settled_p
        doc.settled_attr = settled_a
        t = self.table
        m = int(t.n_rows)
        doc.anchor = np.asarray(t.anchor[:m])
        doc.buf = np.asarray(t.buf_start[:m])
        doc.length = np.asarray(t.length[:m])
        doc.iseq = np.asarray(t.ins_seq[:m])
        doc.iclient = np.asarray(t.ins_client[:m])
        doc.rseq = np.asarray(t.rem_seq[:m])
        doc.rcl = np.asarray(t.rem_clients[:m])
        doc.props = np.asarray(t.props[:m])
        doc.error = int(t.error)

        def row_text(i: int) -> np.ndarray:
            b = int(doc.buf[i])
            ln = int(doc.length[i])
            if b >= SETTLED_BASE:
                a = b - SETTLED_BASE
                return doc.settled_text[a: a + ln]
            return arena_text[b: b + ln]

        doc._row_text = row_text  # type: ignore[assignment]
        self._doc = doc
        return doc

    def verify_invariants(self) -> None:
        self._materialize().verify_invariants()

    def _doc_order(self):
        shim = OverlayReplica.__new__(OverlayReplica)
        shim.doc = self._materialize()
        return OverlayReplica._doc_order(shim)

    def get_text(self) -> str:
        return "".join(
            "".join(map(chr, t)) for t, _ in self._doc_order()
        )

    def annotated_spans(self):
        spans: List[Tuple[str, Optional[dict]]] = []
        for text, props in self._doc_order():
            for j in range(len(text)):
                row = np.asarray(props[j])
                p = self.props.decode_row(
                    np.where(row == PROP_DELETE, PROP_ABSENT, row)
                )
                spans.append((chr(int(text[j])), p))
        return spans
