"""SharedMatrix: a collaborative 2D grid.

Mirrors packages/dds/matrix (src/matrix.ts:80): the row order and the
column order are each a *merge-tree replica* over opaque handles
(`PermutationVector extends Client`, src/permutationvector.ts:151) —
inserting/removing rows or columns is a sequence insert/remove, reusing
all of the merge-tree's conflict resolution; cells live in a sparse
store keyed by (row_handle, col_handle) (src/sparsearray2d.ts:57) so
cell values survive row/column moves without rewrites.

Handles are replica-local storage names (each replica allocates its
own); convergence is judged on the (position → value) mapping, exactly
as the reference.

setCell conflict policy: last sequenced writer wins with pending-local
shadowing per cell (reference matrix conflict-resolution; the
productSet/bspSet machinery for undo-aware set semantics is not yet
ported — see framework undo-redo task).

Wire ops (`contents`):
- {"type": "insertRows"/"removeRows"/"insertCols"/"removeCols",
   "pos": p, "count": n}
- {"type": "setCell", "row": r, "col": c, "value": v}  (positions at
   the sender's perspective)
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from ..core.mergetree import MergeTreeEngine, Segment, VisCategory
from ..protocol.constants import NON_COLLAB_CLIENT, UNASSIGNED_SEQ, UNIVERSAL_SEQ
from ..protocol.messages import SequencedMessage
from ..runtime.channel import ChannelFactory, ChannelStorage
from ..runtime.shared_object import SharedObject
from ..runtime.summary import SummaryTreeBuilder


class PermutationVector:
    """One axis's order: a merge-tree over handle items
    (reference PermutationVector, permutationvector.ts:151). Runs on
    the native C++ engine when available (core/native_engine.py — the
    interactive hot path), falling back to the Python oracle engine."""

    def __init__(self):
        from ..core.native_engine import make_merge_engine

        self.engine = make_merge_engine(NON_COLLAB_CLIENT)
        self._next_handle = 0

    def alloc(self, count: int) -> List[int]:
        out = list(range(self._next_handle, self._next_handle + count))
        self._next_handle += count
        return out

    # ---- perspective-resolved queries

    def handle_at(self, pos: int, ref_seq: int, client_id: int) -> int:
        """The handle at visible position `pos` of a perspective."""
        eng = self.engine
        if hasattr(eng, "item_at"):
            return eng.item_at(pos, ref_seq, client_id)
        remaining = pos
        for seg in eng.segments:
            cat, length = eng._vis(seg, ref_seq, client_id)
            if cat == VisCategory.SKIP or length == 0:
                continue
            if remaining < length:
                return seg.content[remaining]
            remaining -= length
        raise IndexError(f"position {pos} beyond visible length")

    def local_handle_at(self, pos: int) -> int:
        return self.handle_at(
            pos, self.engine.current_seq, self.engine.local_client_id
        )

    def length(self) -> int:
        return self.engine.visible_length(
            self.engine.current_seq, self.engine.local_client_id
        )

    def handles(self) -> List[int]:
        return self.engine.get_items()

    def position_of_handle(self, handle: int) -> Optional[int]:
        """Current local visible position of a handle, or None if its
        row/col is no longer visible."""
        eng = self.engine
        if hasattr(eng, "position_of_item"):
            return eng.position_of_item(
                handle, eng.current_seq, eng.local_client_id
            )
        pos = 0
        for seg in eng.segments:
            cat, length = eng._vis(
                seg, eng.current_seq, eng.local_client_id
            )
            if cat == VisCategory.SKIP or length == 0:
                continue
            if handle in seg.content:
                return pos + seg.content.index(handle)
            pos += length
        return None


class SharedMatrix(SharedObject):
    def initialize_local_core(self) -> None:
        self.rows = PermutationVector()
        self.cols = PermutationVector()
        self._cells: Dict[Tuple[int, int], Any] = {}
        self._pending_cells: Dict[Tuple[int, int], int] = {}

    def on_connected(self) -> None:
        cid = self.runtime.client_id
        for pv in (self.rows, self.cols):
            pv.engine.local_client_id = cid
            pv.engine.collaborating = True
            pv.engine.current_seq = self.runtime.container.current_seq

    # --------------------------------------------------------------- shape

    @property
    def row_count(self) -> int:
        return self.rows.length()

    @property
    def col_count(self) -> int:
        return self.cols.length()

    def _axis_insert(self, pv: PermutationVector, pos: int, count: int, op_type: str) -> None:
        handles = pv.alloc(count)
        eng = pv.engine
        if eng.collaborating:
            eng.insert(pos, handles, eng.current_seq, eng.local_client_id, UNASSIGNED_SEQ)
            self.submit_local_message(
                {"type": op_type, "pos": pos, "count": count},
                {"axis": "rows" if pv is self.rows else "cols",
                 "group": eng.pending[-1]},
            )
        else:
            eng.insert(pos, handles, UNIVERSAL_SEQ, NON_COLLAB_CLIENT, UNIVERSAL_SEQ)
        self.emit(
            "localAxisInsert",
            "rows" if pv is self.rows else "cols", handles,
        )

    def _axis_remove(self, pv: PermutationVector, pos: int, count: int, op_type: str) -> None:
        axis = "rows" if pv is self.rows else "cols"
        # Capture for undo (the productSet/bspSet role: removed
        # region's identity + cell payload) — one pass over the cell
        # map, not O(count x other-axis).
        handles = [pv.local_handle_at(p) for p in range(pos, pos + count)]
        hs = set(handles)
        hi = 0 if pv is self.rows else 1
        cells = {k: v for k, v in self._cells.items() if k[hi] in hs}
        eng = pv.engine
        if eng.collaborating:
            eng.remove_range(pos, pos + count, eng.current_seq, eng.local_client_id, UNASSIGNED_SEQ)
            self.submit_local_message(
                {"type": op_type, "pos": pos, "count": count},
                {"axis": axis, "group": eng.pending[-1]},
            )
        else:
            eng.remove_range(pos, pos + count, UNIVERSAL_SEQ, NON_COLLAB_CLIENT, UNIVERSAL_SEQ)
        self.emit("localAxisRemove", axis, pos, handles, cells)

    def insert_rows(self, pos: int, count: int = 1) -> None:
        self._axis_insert(self.rows, pos, count, "insertRows")

    def remove_rows(self, pos: int, count: int = 1) -> None:
        self._axis_remove(self.rows, pos, count, "removeRows")

    def insert_cols(self, pos: int, count: int = 1) -> None:
        self._axis_insert(self.cols, pos, count, "insertCols")

    def remove_cols(self, pos: int, count: int = 1) -> None:
        self._axis_remove(self.cols, pos, count, "removeCols")

    # --------------------------------------------------------------- cells

    def get_cell(self, row: int, col: int) -> Any:
        key = (self.rows.local_handle_at(row), self.cols.local_handle_at(col))
        return self._cells.get(key)

    def set_cell(self, row: int, col: int, value: Any) -> None:
        key = (self.rows.local_handle_at(row), self.cols.local_handle_at(col))
        had = key in self._cells
        prev = self._cells.get(key)
        self._cells[key] = value
        if self.rows.engine.collaborating:
            self._pending_cells[key] = self._pending_cells.get(key, 0) + 1
            self.submit_local_message(
                {"type": "setCell", "row": row, "col": col, "value": value},
                {"key": key},
            )
        self.emit("localCellSet", key, had, prev)
        self.emit("cellChanged", row, col, True)

    def set_cell_by_handle(self, key, value: Any) -> None:
        """Set a cell addressed by its stable (row, col) HANDLES —
        the undo path's addressing, immune to concurrent permutation.
        No-op if either handle's row/col is no longer visible (the
        cell died with its axis; reference matrix undo skips too)."""
        r = self.rows.position_of_handle(key[0])
        c = self.cols.position_of_handle(key[1])
        if r is None or c is None:
            return
        self.set_cell(r, c, value)

    def to_dense(self) -> List[List[Any]]:
        """The visible grid (row-major), for assertions and export."""
        rh = self.rows.handles()
        ch = self.cols.handles()
        return [[self._cells.get((r, c)) for c in ch] for r in rh]

    # --------------------------------------------------------------- apply

    def process_core(self, msg: SequencedMessage, local: bool, local_metadata: Any) -> None:
        op = msg.contents
        kind = op["type"]
        if kind == "setCell":
            if local:
                key = local_metadata["key"]
                n = self._pending_cells.get(key, 0) - 1
                if n <= 0:
                    self._pending_cells.pop(key, None)
                else:
                    self._pending_cells[key] = n
            else:
                key = (
                    self.rows.handle_at(op["row"], msg.ref_seq, msg.client_id),
                    self.cols.handle_at(op["col"], msg.ref_seq, msg.client_id),
                )
                if self._pending_cells.get(key, 0) == 0:
                    self._cells[key] = op["value"]
                    # Event positions are RECEIVER-local (the sender's
                    # row/col indices mean nothing at this replica) —
                    # and resolving them costs two engine walks, so
                    # only do it when someone is listening.
                    if self._listeners.get("cellChanged"):
                        r = self.rows.position_of_handle(key[0])
                        c = self.cols.position_of_handle(key[1])
                        if r is not None and c is not None:
                            self.emit("cellChanged", r, c, False)
        else:
            pv = self.rows if "Rows" in kind else self.cols
            eng = pv.engine
            if local:
                eng.ack(msg.sequence_number)
            elif kind.startswith("insert"):
                eng.insert(
                    op["pos"], pv.alloc(op["count"]), msg.ref_seq,
                    msg.client_id, msg.sequence_number,
                )
            else:
                eng.remove_range(
                    op["pos"], op["pos"] + op["count"], msg.ref_seq,
                    msg.client_id, msg.sequence_number,
                )
        # Advance both axes' collaboration windows (the MSN advance —
        # which runs zamboni — only when it actually moved).
        seq = msg.sequence_number
        msn = msg.minimum_sequence_number
        for pv in (self.rows, self.cols):
            eng = pv.engine
            eng.current_seq = seq
            if msn > eng.min_seq:
                eng.update_min_seq(msn)

    def resubmit(self, content: Any, local_metadata: Any) -> None:
        """Reconnect replay with rebase: structural ops regenerate
        their positions from their pending merge-tree groups (the
        sequence DDS's regeneratePendingOp applied per axis); setCell
        re-targets by handle at the current perspective (dropped if the
        row/col has since been removed)."""
        op = content
        kind = op["type"]
        if kind == "setCell":
            key = local_metadata["key"]
            r = self.rows.position_of_handle(key[0])
            c = self.cols.position_of_handle(key[1])
            if r is None or c is None:
                # Target row/col is gone: the write is moot; clear the
                # pending shadow it held.
                n = self._pending_cells.get(key, 0) - 1
                if n <= 0:
                    self._pending_cells.pop(key, None)
                else:
                    self._pending_cells[key] = n
                return
            self.submit_local_message(
                {"type": "setCell", "row": r, "col": c, "value": op["value"]},
                local_metadata,
            )
            return
        pv = self.rows if local_metadata["axis"] == "rows" else self.cols
        grps = local_metadata["group"]
        grps = grps if isinstance(grps, list) else [grps]
        from ..protocol.mergetree_ops import GroupOp, InsertOp, RemoveOp

        regenerated, new_groups = pv.engine.regenerate_pending(
            grps,
            InsertOp(pos=op["pos"]) if kind.startswith("insert")
            else RemoveOp(start=op["pos"], end=op["pos"] + op["count"]),
        )
        if regenerated is None:
            return
        subs = regenerated.ops if isinstance(regenerated, GroupOp) else [regenerated]
        # Each regenerated sub-op submits as its own message (each pops
        # one per-segment pending group on ack), carrying ITS OWN
        # replacement group as metadata so a second reconnect can find
        # it in the pending FIFO (stale-group metadata silently dropped
        # resubmissions — advisor finding, round 1).
        for sub, g in zip(subs, new_groups):
            if isinstance(sub, InsertOp):
                mop = {"type": kind, "pos": sub.pos, "count": len(sub.seg or sub.text)}
            else:
                mop = {"type": kind, "pos": sub.start, "count": sub.end - sub.start}
            self.submit_local_message(
                mop, {"axis": local_metadata["axis"], "group": g}
            )

    def apply_stashed_op(self, content: Any) -> Any:
        op = content
        kind = op["type"]
        if kind == "setCell":
            self.set_cell(op["row"], op["col"], op["value"])
        elif kind == "insertRows":
            self.insert_rows(op["pos"], op["count"])
        elif kind == "removeRows":
            self.remove_rows(op["pos"], op["count"])
        elif kind == "insertCols":
            self.insert_cols(op["pos"], op["count"])
        elif kind == "removeCols":
            self.remove_cols(op["pos"], op["count"])
        return None

    # ----------------------------------------------------------- summaries

    def summarize_core(self):
        """Positional snapshot of the visible grid (reference matrix
        snapshot: permutation vectors + cell payload). Unsettled merge
        metadata inside the collab window is not persisted — summaries
        are taken on quiescent replicas (ContainerRuntime refuses dirty
        summarize)."""
        dense = self.to_dense()
        cells = [
            [r, c, row_vals[c]]
            for r, row_vals in enumerate(dense)
            for c in range(len(row_vals))
            if row_vals[c] is not None
        ]
        header = {
            "rowCount": self.row_count,
            "colCount": self.col_count,
            "currentSeq": self.rows.engine.current_seq,
            "minSeq": self.rows.engine.min_seq,
        }
        return (
            SummaryTreeBuilder()
            .add_json_blob("header", header)
            .add_json_blob("cells", cells)
            .summary
        )

    def load_core(self, storage: ChannelStorage) -> None:
        self.initialize_local_core()
        header = json.loads(storage.read("header"))
        for pv, n in ((self.rows, header["rowCount"]), (self.cols, header["colCount"])):
            pv.engine.current_seq = header["currentSeq"]
            pv.engine.min_seq = header["minSeq"]
            if n:
                pv.engine.load(pv.alloc(n))
        rh, ch = self.rows.handles(), self.cols.handles()
        for r, c, v in json.loads(storage.read("cells")):
            self._cells[(rh[r], ch[c])] = v


class MatrixFactory(ChannelFactory):
    type_name = "https://graph.microsoft.com/types/sharedmatrix"
    channel_class = SharedMatrix
