"""ctypes adapter for the native host merge engine (hostmerge.cpp).

`NativeMergeEngine` exposes the surface of `core.mergetree
.MergeTreeEngine` that interactive clients use — insert/remove/
annotate (local pending or sequenced remote), ack, MSN window +
zamboni, perspective queries, reconnect regeneration — backed by the
C++ segment list. Semantics are a faithful port of the oracle
(differentially farm-tested, tests/test_native_engine.py); the win is
the constant factor on the per-op document walks that dominate the
interactive path.

Property keys/values are interned to int32 on this side (`None`
encodes as the PROP_DELETE sentinel, matching the reference's
null-deletes convention); content items are int32 (codepoints for
text, handles for permutation vectors).

`make_merge_engine()` picks native when the compiler/library is
available and falls back to the Python oracle engine otherwise, the
same convention as the content store (server/castore.py).
"""

from __future__ import annotations

import ctypes
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..native import load_hostmerge
from ..protocol.constants import NON_COLLAB_CLIENT, UNASSIGNED_SEQ
from ..protocol.mergetree_ops import (
    AnnotateOp,
    GroupOp,
    InsertOp,
    MergeTreeDeltaType,
    MergeTreeOp,
    RemoveOp,
)

PROP_DELETE = -2  # interned encoding of None (must match hostmerge.cpp)

_I32 = ctypes.c_int32


def _arr(values) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(values, np.int32))


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(_I32))


class _PropCoder:
    """Bidirectional key/value <-> int32 interning (unbounded; the
    kernel-side PropInterner is capacity-bound by KK, this one serves
    the host engine)."""

    def __init__(self):
        self._key2id: Dict[str, int] = {}
        self._keys: List[str] = []
        self._val2id: Dict[Any, int] = {}
        self._vals: List[Any] = []

    def key_id(self, key: str) -> int:
        kid = self._key2id.get(key)
        if kid is None:
            kid = len(self._keys)
            self._key2id[key] = kid
            self._keys.append(key)
        return kid

    def val_id(self, value: Any) -> int:
        if value is None:
            return PROP_DELETE
        vid = self._val2id.get(value)
        if vid is None:
            vid = len(self._vals)
            self._val2id[value] = vid
            self._vals.append(value)
        return vid

    def encode(self, props: Optional[dict]) -> Tuple[np.ndarray, np.ndarray]:
        if not props:
            return _arr([]), _arr([])
        keys = [self.key_id(k) for k in props]
        vals = [self.val_id(v) for v in props.values()]
        return _arr(keys), _arr(vals)

    def decode(self, pairs) -> Optional[dict]:
        out = {}
        for k, v in pairs:
            out[self._keys[k]] = self._vals[v]
        return out or None


class _PendingView:
    """Read-only view of the C++ pending FIFO exposing the bits
    callers use (`pending[-1]` as op metadata, truthiness, length)."""

    def __init__(self, eng: "NativeMergeEngine"):
        self._eng = eng

    def __len__(self) -> int:
        return int(self._eng._lib.hm_pending_count(self._eng._ptr))

    def __bool__(self) -> bool:
        return len(self) > 0

    def __getitem__(self, idx: int) -> int:
        if idx != -1:
            raise IndexError("pending view exposes [-1] only")
        gid = int(self._eng._lib.hm_pending_last_id(self._eng._ptr))
        if gid < 0:
            raise IndexError("no pending ops")
        return gid


class NativeMergeEngine:
    """C++-backed merge engine with the MergeTreeEngine surface used
    by CollabClient and PermutationVector."""

    # Staging buffers shrink per-op ctypes marshalling: content/prop
    # arrays are copied into preallocated numpy buffers whose pointers
    # are cached once (numpy's .ctypes.data_as costs ~10us per call).
    _STAGE = 1 << 16

    def __init__(self, local_client_id: int = NON_COLLAB_CLIENT,
                 lib: Optional[ctypes.CDLL] = None):
        self._lib = lib or load_hostmerge()
        if self._lib is None:
            raise RuntimeError("hostmerge library unavailable")
        self._ptr = ctypes.c_void_p(self._lib.hm_new(local_client_id))
        self._props = _PropCoder()
        self._is_text = True
        self._content_buf = np.empty(self._STAGE, np.int32)
        self._content_ptr = _ptr(self._content_buf)
        self._pk_buf = np.empty(64, np.int32)
        self._pk_ptr = _ptr(self._pk_buf)
        self._pv_buf = np.empty(64, np.int32)
        self._pv_ptr = _ptr(self._pv_buf)

    def __del__(self):
        ptr, self._ptr = getattr(self, "_ptr", None), None
        if ptr and getattr(self, "_lib", None) is not None:
            self._lib.hm_free(ptr)

    # ------------------------------------------------------ attributes

    @property
    def local_client_id(self) -> int:
        return int(self._lib.hm_local_client(self._ptr))

    @local_client_id.setter
    def local_client_id(self, cid: int) -> None:
        self._lib.hm_set_identity(
            self._ptr, cid, int(self._lib.hm_collaborating(self._ptr))
        )

    @property
    def collaborating(self) -> bool:
        return bool(self._lib.hm_collaborating(self._ptr))

    @collaborating.setter
    def collaborating(self, v: bool) -> None:
        self._lib.hm_set_identity(
            self._ptr, self.local_client_id, int(bool(v))
        )

    @property
    def current_seq(self) -> int:
        return int(self._lib.hm_current_seq(self._ptr))

    @current_seq.setter
    def current_seq(self, v: int) -> None:
        self._lib.hm_set_current_seq(self._ptr, v)

    @property
    def min_seq(self) -> int:
        return int(self._lib.hm_min_seq(self._ptr))

    @min_seq.setter
    def min_seq(self, v: int) -> None:
        self._lib.hm_set_min_seq(self._ptr, v)

    @property
    def pending(self) -> _PendingView:
        return _PendingView(self)

    # ------------------------------------------------------ mutations

    def _stage_content(self, content: Any) -> int:
        """Copy content items into the staging buffer; returns count."""
        n = len(content)
        if n > len(self._content_buf):
            self._content_buf = np.empty(
                max(n, 2 * len(self._content_buf)), np.int32
            )
            self._content_ptr = _ptr(self._content_buf)
        if isinstance(content, str):
            self._is_text = True
            if n:
                self._content_buf[:n] = np.frombuffer(
                    content.encode("utf-32-le"), np.int32
                )
            return n
        self._is_text = False
        self._content_buf[:n] = content
        return n

    def _stage_props(self, props: Optional[dict]) -> int:
        if not props:
            return 0
        coder = self._props
        for i, (k, v) in enumerate(props.items()):
            self._pk_buf[i] = coder.key_id(k)
            self._pv_buf[i] = coder.val_id(v)
        return len(props)

    def load(self, content: Any, props: Optional[dict] = None) -> None:
        n = self._stage_content(content)
        if props:
            raise NotImplementedError("native load with props")
        self._lib.hm_load(self._ptr, self._content_ptr, n)

    def insert(self, pos: int, content: Any, ref_seq: int, client_id: int,
               seq: int, props: Optional[dict] = None) -> None:
        n = self._stage_content(content)
        clean = (
            {k: v for k, v in props.items() if v is not None}
            if props else None
        )
        nk = self._stage_props(clean)
        rc = self._lib.hm_insert(
            self._ptr, pos, self._content_ptr, n, ref_seq, client_id,
            seq, self._pk_ptr, self._pv_ptr, nk,
        )
        if rc != 0:
            raise ValueError(
                f"insert pos {pos} beyond visible length at perspective "
                f"({ref_seq},{client_id})"
            )

    def remove_range(self, start: int, end: int, ref_seq: int,
                     client_id: int, seq: int) -> None:
        rc = self._lib.hm_remove(
            self._ptr, start, end, ref_seq, client_id, seq
        )
        if rc != 0:
            raise AssertionError(f"bad remove range [{start},{end})")

    def annotate_range(self, start: int, end: int, props: Dict[str, Any],
                       ref_seq: int, client_id: int, seq: int) -> None:
        nk = self._stage_props(props)
        rc = self._lib.hm_annotate(
            self._ptr, start, end, self._pk_ptr, self._pv_ptr, nk,
            ref_seq, client_id, seq,
        )
        if rc != 0:
            raise AssertionError(f"bad annotate range [{start},{end})")

    def ack(self, seq: int) -> None:
        if self._lib.hm_ack(self._ptr, seq) != 0:
            raise IndexError("ack with empty pending FIFO")

    def update_min_seq(self, min_seq: int) -> None:
        # Monotone by construction on every call path (callers pass
        # max(min_seq, msn)); the C++ zamboni is idempotent regardless.
        self._lib.hm_update_min_seq(self._ptr, min_seq)

    def apply_sequenced(self, msg) -> None:
        """Apply one remote `SequencedMessage` (passive-replica path:
        route by op type, advance current_seq and the MSN window —
        the replay_passive loop's per-message body)."""
        op = msg.contents
        if isinstance(op, InsertOp):
            self.insert(op.pos, op.text, msg.ref_seq, msg.client_id,
                        msg.sequence_number)
        elif isinstance(op, RemoveOp):
            self.remove_range(op.start, op.end, msg.ref_seq,
                              msg.client_id, msg.sequence_number)
        elif isinstance(op, AnnotateOp):
            self.annotate_range(op.start, op.end, op.props, msg.ref_seq,
                                msg.client_id, msg.sequence_number)
        else:
            raise TypeError(f"unsupported sequenced op {type(op)!r}")
        self.current_seq = msg.sequence_number
        self.update_min_seq(
            max(self.min_seq, msg.minimum_sequence_number)
        )

    def apply_sequenced_batch(self, msgs) -> None:
        """Apply a run of `SequencedMessage`s in ONE native call
        (hm_apply_batch — the client.ts:858 applyMsg loop with the
        Python/ctypes frame cost paid per BATCH, not per message).
        Own-client messages ack the pending FIFO, remote ops apply at
        their perspectives; the MSN advances once at batch end, which
        is semantics-preserving (zamboni timing never changes visible
        state; min_seq only enters visibility on the local-perspective
        read path, which no remote apply or ack touches)."""
        from ..protocol.messages import MessageType

        kind: List[int] = []
        pos1: List[int] = []
        pos2: List[int] = []
        ref: List[int] = []
        cli: List[int] = []
        seq: List[int] = []
        aoff: List[int] = []
        alen: List[int] = []
        chunks: List[str] = []
        items_mode = False
        item_chunks: List[List[int]] = []
        pk: List[int] = []
        pv: List[int] = []
        poff: List[int] = [0]
        coder = self._props
        local = self.local_client_id
        final_msn = self.min_seq
        cursor = 0

        def row(k, p1=0, p2=0, r=0, c=0, s=0, ao=0, al=0):
            kind.append(k)
            pos1.append(p1)
            pos2.append(p2)
            ref.append(r)
            cli.append(c)
            seq.append(s)
            aoff.append(ao)
            alen.append(al)
            poff.append(len(pk))

        for msg in msgs:
            if msg.minimum_sequence_number > final_msn:
                final_msn = msg.minimum_sequence_number
            sq = msg.sequence_number
            if msg.type != MessageType.OP or msg.contents is None:
                row(4, s=sq)
                continue
            ops = (
                msg.contents.ops
                if isinstance(msg.contents, GroupOp)
                else (msg.contents,)
            )
            for op in ops:
                if msg.client_id == local:
                    row(3, s=sq)
                elif isinstance(op, InsertOp):
                    if op.text is not None:
                        content_len = len(op.text)
                        chunks.append(op.text)
                    else:
                        content_len = len(op.seg)
                        items_mode = True
                        item_chunks.append(list(op.seg))
                    if op.props:
                        for k, v in op.props.items():
                            if v is None:
                                continue
                            pk.append(coder.key_id(k))
                            pv.append(coder.val_id(v))
                    row(0, p1=op.pos, r=msg.ref_seq, c=msg.client_id,
                        s=sq, ao=cursor, al=content_len)
                    cursor += content_len
                elif isinstance(op, RemoveOp):
                    row(1, p1=op.start, p2=op.end, r=msg.ref_seq,
                        c=msg.client_id, s=sq)
                elif isinstance(op, AnnotateOp):
                    for k, v in op.props.items():
                        pk.append(coder.key_id(k))
                        pv.append(coder.val_id(v))
                    row(2, p1=op.start, p2=op.end, r=msg.ref_seq,
                        c=msg.client_id, s=sq)
                else:
                    raise TypeError(f"unsupported op {type(op)!r}")

        if chunks:
            self._is_text = True
        if items_mode:
            if chunks:
                raise TypeError("mixed str/item inserts in one batch")
            arena = _arr([x for ch in item_chunks for x in ch])
            self._is_text = False
        else:
            joined = "".join(chunks)
            arena = (
                np.frombuffer(joined.encode("utf-32-le"), np.int32)
                if joined else _arr([])
            )
        rc = self._lib.hm_apply_batch(
            self._ptr, len(kind), _ptr(_arr(kind)), _ptr(_arr(pos1)),
            _ptr(_arr(pos2)), _ptr(_arr(ref)), _ptr(_arr(cli)),
            _ptr(_arr(seq)), _ptr(np.ascontiguousarray(arena)),
            _ptr(_arr(aoff)), _ptr(_arr(alen)), _ptr(_arr(pk)),
            _ptr(_arr(pv)), _ptr(_arr(poff)), final_msn,
        )
        if rc != 0:
            raise ValueError(
                f"apply_sequenced_batch failed at row {-rc - 1} "
                f"(kind {kind[-rc - 1]}, seq {seq[-rc - 1]})"
            )

    def pack_settled(self) -> None:
        """Merge adjacent fully-settled same-props segments (the
        zamboni.ts:19 packParent role; run length capped in C++).
        PASSIVE replicas only: pending local groups may hold pointers
        into merged-away tails."""
        if len(self.pending):
            raise RuntimeError(
                "pack_settled on an engine with pending local ops"
            )
        self._lib.hm_pack_settled(self._ptr)

    def verify_invariants(self) -> None:
        """Exhaustive structural verification in the C++ engine (the
        MergeTreeEngine.verify_invariants role; violation codes are
        documented at hostmerge.cpp hm_verify)."""
        code = int(self._lib.hm_verify(self._ptr))
        assert code == 0, f"native engine invariant violation #{code}"

    # -------------------------------------------------------- queries

    def visible_length(self, ref_seq: int, client_id: int) -> int:
        return int(
            self._lib.hm_visible_length(self._ptr, ref_seq, client_id)
        )

    def _items(self) -> np.ndarray:
        n = int(self._lib.hm_get_items(self._ptr, None, 0))
        out = np.empty(max(n, 1), np.int32)
        self._lib.hm_get_items(self._ptr, _ptr(out), n)
        return out[:n]

    def get_text(self) -> str:
        if not self._is_text:
            raise TypeError("non-text engine: use get_items()")
        return "".join(map(chr, self._items()))

    def get_items(self) -> List[int]:
        return self._items().tolist()

    def item_at(self, pos: int, ref_seq: int, client_id: int) -> int:
        v = int(self._lib.hm_item_at(self._ptr, pos, ref_seq, client_id))
        if v < 0:
            raise IndexError(f"position {pos} beyond visible length")
        return v

    def position_of_item(self, item: int, ref_seq: int,
                         client_id: int) -> Optional[int]:
        v = int(self._lib.hm_position_of_item(
            self._ptr, item, ref_seq, client_id
        ))
        return None if v < 0 else v

    def enable_attribution(self) -> None:
        """Track per-position insert attribution (attribution key =
        insert seq; the attributionCollection.ts/attributionPolicy.ts
        role). Existing content backfills: loaded text to key 0,
        sequenced segments to their seq, pending locals assigned on
        ack. Runs survive splits, zamboni and settled-run packing."""
        self._lib.hm_enable_attr(self._ptr)

    def attribution_spans(self) -> List[Tuple[int, int]]:
        """(run_length, attribution key) runs over the visible
        document, adjacent equal keys merged."""
        n = int(self._lib.hm_attr_spans(self._ptr, None, 0))
        buf = np.empty(max(n, 1), np.int32)
        self._lib.hm_attr_spans(self._ptr, _ptr(buf), n)
        out: List[Tuple[int, int]] = []
        for i in range(0, n, 2):
            ln, key = int(buf[i]), int(buf[i + 1])
            if out and out[-1][1] == key:
                out[-1] = (out[-1][0] + ln, key)
            else:
                out.append((ln, key))
        return out

    def annotated_spans(self) -> List[Tuple[Any, Optional[dict]]]:
        n = int(self._lib.hm_spans(self._ptr, None, 0))
        buf = np.empty(max(n, 1), np.int32)
        self._lib.hm_spans(self._ptr, _ptr(buf), n)
        out: List[Tuple[Any, Optional[dict]]] = []
        i = 0
        while i < n:
            ln = int(buf[i]); i += 1
            items = buf[i: i + ln]; i += ln
            np_ = int(buf[i]); i += 1
            pairs = [
                (int(buf[i + 2 * j]), int(buf[i + 2 * j + 1]))
                for j in range(np_)
            ]
            i += 2 * np_
            content: Any = (
                "".join(map(chr, items)) if self._is_text else items.tolist()
            )
            out.append((content, self._props.decode(pairs)))
        return out

    # ---------------------------------------------- reconnect / rebase

    def regenerate_pending(
        self, grps: List[int], original: MergeTreeOp
    ) -> Tuple[Optional[MergeTreeOp], List[int]]:
        """Rebase pending local ops for resubmission after reconnect
        (contract of MergeTreeEngine.regenerate_pending; `grps` are
        native group ids)."""
        gids = _arr(grps)
        # Regeneration MUTATES the pending FIFO (group splitting), so
        # the buffer is sized up front: each sub-op costs 5 header
        # ints, sub-op count is bounded by the segment count, and
        # insert payloads by the total content.
        cap = (
            5 * (int(self._lib.hm_segment_count(self._ptr)) + len(gids) + 1)
            + int(self._lib.hm_content_total(self._ptr))
        )
        buf = np.empty(cap, np.int32)
        n = int(self._lib.hm_regenerate(self._ptr, _ptr(gids), len(gids),
                                        _ptr(buf), cap))
        if n < 0:
            raise KeyError(f"unknown pending group in {grps}")
        assert n <= cap
        ops: List[MergeTreeOp] = []
        out_groups: List[int] = []
        i = 0
        ins_props = original.props if isinstance(original, InsertOp) else None
        while i < n:
            kind, gid, a, b = (int(buf[i]), int(buf[i + 1]), int(buf[i + 2]),
                               int(buf[i + 3]))
            ni = int(buf[i + 4])
            items = buf[i + 5: i + 5 + ni]
            i += 5 + ni
            out_groups.append(gid)
            if kind == MergeTreeDeltaType.INSERT:
                if self._is_text:
                    ops.append(InsertOp(
                        pos=a, text="".join(map(chr, items)),
                        props=ins_props,
                    ))
                else:
                    ops.append(InsertOp(
                        pos=a, seg=items.tolist(), props=ins_props
                    ))
            elif kind == MergeTreeDeltaType.REMOVE:
                ops.append(RemoveOp(start=a, end=b))
            else:
                pn = int(self._lib.hm_group_props(self._ptr, gid, None, 0))
                pbuf = np.empty(max(pn, 1), np.int32)
                self._lib.hm_group_props(self._ptr, gid, _ptr(pbuf), pn)
                pairs = [
                    (int(pbuf[2 * j]), int(pbuf[2 * j + 1]))
                    for j in range(pn // 2)
                ]
                ops.append(AnnotateOp(
                    start=a, end=b, props=self._props.decode(pairs) or {}
                ))
        if not ops:
            return None, []
        if len(ops) == 1:
            return ops[0], out_groups
        return GroupOp(ops=ops), out_groups


def native_available() -> bool:
    return load_hostmerge() is not None


def make_merge_engine(local_client_id: int = NON_COLLAB_CLIENT,
                      prefer_native: bool = True):
    """Native engine when available, Python oracle engine otherwise."""
    if prefer_native and native_available():
        return NativeMergeEngine(local_client_id)
    from .mergetree import MergeTreeEngine

    return MergeTreeEngine(local_client_id=local_client_id)
