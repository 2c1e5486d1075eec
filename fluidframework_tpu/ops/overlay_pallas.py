"""Pallas TPU kernel for the overlay merge-tree: O(collab window)/op.

Device execution of exactly the semantics specified by
`ops.overlay_ref.OverlayDoc` (the numpy executable spec; see that
module's docstring for the representation and its invariants). The
round-2 chunk kernel (ops/mergetree_pallas.py) keeps EVERY segment row
in VMEM and pays ~10 full-table vector passes per op — O(capacity) =
131k rows of work per op no matter how small the live collaboration
window is. Here the VMEM table holds ONLY unsettled rows (a few
thousand on the bench mix); settled content is a virtual coordinate
space represented by one scalar ``S`` whose text/props live off-kernel
in an append-only fold log. Per-op vector work scales with the window,
the way the reference bounds per-op work to O(log n) with its B-tree +
partial-lengths cache (mergeTree.ts:1397 insertSegments,
partialLengths.ts:256).

Execution shape, per chunk of B sequenced ops:

1. `_overlay_chunk_kernel` (pallas): the overlay columns live in VMEM
   as (W/128, 128) int32 tiles for the whole chunk; a `fori_loop`
   applies ops back-to-back with pure vector-domain bodies (one-hot
   masks, log-doubling cumsums, masked suffix shifts — the idioms
   proven in mergetree_pallas.py). Op-type branches use `pl.when` on
   SMEM scalars so inserts skip range work and vice versa. The one
   per-op vector->scalar crossing is the gap-materialization count of
   range ops (a dynamic `fori_loop` inserts exactly that many span
   rows; see overlay_ref.py "gap materialization").
2. `fold_device` (plain XLA): the settle-merge (overlay_ref.fold /
   the zamboni role, zamboni.ts:19). Folding rows leave the table
   (payload sorts, not gathers — an XLA gather lowers to ~100ns/elem
   on TPU, see ops/zamboni.py), survivors re-anchor by prefix sums,
   and the folded rows are emitted as a dense record block.
3. `replay_chunk_step` (one jit): kernel + fold + append of the fold
   records into a preallocated HBM log (`lax.dynamic_update_slice`,
   donated so XLA updates in place). The host replay loop performs
   zero device syncs; `core.overlay_replay.OverlayDeviceReplica`
   reconstructs the settled document from the log after the timed
   region.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..protocol.constants import NO_CLIENT
from ..utils.metrics import named_program
from .mergetree_kernel import (
    ERR_BAD_POS,
    ERR_CAPACITY,
    ERR_REMOVERS,
    NO_KEY,
    NOT_REMOVED,
    OP_ANNOTATE,
    OP_INSERT,
    OP_REMOVE,
    OpBatch,
    PROP_ABSENT,
    PROP_DELETE,
)
from .mergetree_pallas import (
    LANES,
    _flat_idx,
    _roll1_flat,
    _row_idx,
)
from .overlay_ref import SETTLED_BASE
from .zamboni import _pack_partition

# Fold-record type codes (column 1 of a log record).
REC_NONE = 0  # dropped text row: nothing to reconstruct (kept in the
#               block so one stable partition serves table + records)
REC_SETTLE_TEXT = 1  # unsettled insert becomes settled text at anchor
REC_DROP_SPAN = 2  # settled coords [anchor, anchor+len) excised
REC_SETTLE_SPAN = 3  # props merge into settled [anchor, anchor+len)


class OverlayTable(NamedTuple):
    """Device overlay state: unsettled rows + the settled length."""

    n_rows: jnp.ndarray  # int32 scalar
    anchor: jnp.ndarray  # int32[W] settled coordinate the row sits at
    buf_start: jnp.ndarray  # int32[W]; >= SETTLED_BASE marks span rows
    length: jnp.ndarray  # int32[W]
    ins_seq: jnp.ndarray  # int32[W] (0 for span rows)
    ins_client: jnp.ndarray  # int32[W]
    rem_seq: jnp.ndarray  # int32[W] (NOT_REMOVED if live)
    rem_clients: jnp.ndarray  # int32[W, KR]
    props: jnp.ndarray  # int32[W, KK]
    settled_len: jnp.ndarray  # int32 scalar: S
    error: jnp.ndarray  # int32 scalar ERR_* flags


def make_overlay_table(
    window: int, n_removers: int = 4, n_prop_keys: int = 8,
    settled_len: int = 0,
) -> OverlayTable:
    return OverlayTable(
        n_rows=jnp.int32(0),
        anchor=jnp.zeros(window, jnp.int32),
        buf_start=jnp.zeros(window, jnp.int32),
        length=jnp.zeros(window, jnp.int32),
        ins_seq=jnp.zeros(window, jnp.int32),
        ins_client=jnp.full(window, NO_CLIENT, jnp.int32),
        rem_seq=jnp.full(window, NOT_REMOVED, jnp.int32),
        rem_clients=jnp.full((window, n_removers), NO_CLIENT, jnp.int32),
        props=jnp.full((window, n_prop_keys), PROP_ABSENT, jnp.int32),
        settled_len=jnp.int32(settled_len),
        error=jnp.int32(0),
    )


def _overlay_chunk_kernel(
    # scalars / op columns (SMEM)
    nrows_in_ref, err_in_ref, nops_ref, s_ref,
    op_type_ref, pos1_ref, pos2_ref, seq_ref, client_ref,
    buf_ref, ilen_ref, pkey_ref, pval_ref, ref_seq_ref,
    # table columns in (VMEM)
    t_anchor_in, t_buf_in, t_len_in, t_iseq_in, t_iclient_in, t_rseq_in,
    t_rcl_in, t_props_in,
    # table columns out (VMEM) + scalars out (SMEM)
    t_anchor, t_buf, t_len, t_iseq, t_iclient, t_rseq, t_rcl, t_props,
    nrows_out_ref, err_out_ref,
    # scratch: stacked table + gap staging (VMEM), scalars (SMEM)
    T, G, nlive_ref, err_ref,
):
    """FUSED per-op form (round 4). Semantics identical to the round-3
    kernel / overlay_ref.OverlayDoc.apply (differential farm gates);
    the execution shape is redesigned for the serial-latency bound the
    round-3 profile exposed (per-op cost was ~window-independent —
    dominated by the NUMBER of dependent small vector ops, not data):

    - ONE perspective pass per op (visibility + prefix scan), with
      ``pre``/``vis``/``skip`` kept as scratch COLUMNS of the stacked
      table so split fixups and the covered phase never recompute the
      scan (the incremental-partial-lengths role, partialLengths.ts:256).
    - All landing/split indices move to the SCALAR domain via full
      reductions (jnp.min over one-hot masks) instead of mask cumsums
      + vector broadcasts.
    - The whole table is ONE stacked (C, W8, 128) tensor; a segment
      split + row insert is one or two dest-based masked rolls of the
      full stack (insertingWalk's memmove, mergeTree.ts:1740) — a few
      big instructions instead of ~20 per-column roll sequences.
    - Rows live in a packed prefix tracked by an SMEM ``n_live``
      scalar (no live column; capacity checks are scalar compares).
    """
    KR = t_rcl_in.shape[0]
    KK = t_props_in.shape[0]
    B = pos1_ref.shape[0]
    PK = pkey_ref.shape[0] // B
    shape = t_len_in.shape
    window = shape[0] * LANES
    flat = _flat_idx(shape)
    S = s_ref[0]
    W = jnp.int32(window)
    IMIN = jnp.int32(-2147483647)

    # Stacked column layout.
    A_, B_, L_, IS_, IC_, RS_ = 0, 1, 2, 3, 4, 5
    RC0 = 6
    PP0 = RC0 + KR
    PRE_ = PP0 + KK
    VIS_ = PRE_ + 1

    T[A_] = t_anchor_in[...]
    T[B_] = t_buf_in[...]
    T[L_] = t_len_in[...]
    T[IS_] = t_iseq_in[...]
    T[IC_] = t_iclient_in[...]
    T[RS_] = t_rseq_in[...]
    for k in range(KR):
        T[RC0 + k] = t_rcl_in[k]
    for k in range(KK):
        T[PP0 + k] = t_props_in[k]
    T[PRE_] = jnp.zeros(shape, jnp.int32)
    T[VIS_] = jnp.zeros(shape, jnp.int32)
    nlive_ref[0] = nrows_in_ref[0]
    err_ref[0] = err_in_ref[0]

    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    # Upper-triangular ones: the lane-inclusive prefix sum becomes ONE
    # MXU matmul (v @ U) instead of a log2(128)-step roll chain. Exact:
    # every partial sum is an integer below 2^24 (document length bound
    # 2^23), representable in f32; HIGHEST precision avoids the bf16
    # fast path. Hoisted out of the op loop.
    U_tri = (
        jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    ).astype(jnp.float32)
    row_i = _row_idx(shape)

    def cumsum_and_total(v):
        """(exclusive flat prefix sum, grand total) of int32 tiles."""
        inc = jax.lax.dot(
            v.astype(jnp.float32), U_tri,
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.int32)
        totals = jnp.broadcast_to(inc[:, LANES - 1:], shape)
        s = 1
        rt = totals
        while s < shape[0]:
            rt = rt + jnp.where(row_i >= s, pltpu.roll(rt, s, 0), 0)
            s *= 2
        row_excl = jnp.where(row_i > 0, pltpu.roll(rt, 1, 0), 0)
        return (inc - v) + row_excl, rt[shape[0] - 1, 0]

    def at(ci, j):
        """Scalar value of stacked column `ci` at flat row `j`: one
        dynamic-sublane (1, LANES) load + a lane-only reduce — far
        cheaper than a full-window masked reduce. `j` is clamped;
        out-of-range results are selected away by callers."""
        jc = jnp.minimum(j, W - 1)
        row = T[ci, pl.ds(jc // LANES, 1), :]
        return jnp.max(jnp.where(lane1 == jc % LANES, row, IMIN))

    def at_g(gref, ci, j):
        row = gref[ci, pl.ds(j // LANES, 1), :]
        return jnp.max(jnp.where(lane1 == j % LANES, row, IMIN))

    def first_idx(mask):
        """Index of the first set row, or W when none."""
        return jnp.min(jnp.where(mask, flat, W))

    def roll_from(thr):
        """Dest-based masked roll of the WHOLE stack: row j takes row
        j-1 for j >= thr (insertingWalk's memmove as ~4 wide ops).
        Row max(thr,1)-1 keeps its value; the opened slot holds a
        stale copy the caller overwrites. thr >= W: full no-op mask
        (callers pl.when-guard to skip the work entirely)."""
        v = T[...]
        w = pltpu.roll(v, 1, 2)
        carry = pltpu.roll(w, 1, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
        rolled = jnp.where(lane == 0, carry, w)
        T[...] = jnp.where(flat[None] >= thr, rolled, v)

    def vis_pass(r, c):
        """The ONE perspective pass (overlay_ref._visibility + _pre;
        mergeTree.ts:916 nodeLength, partialLengths.ts:256): writes
        pre/vis scratch columns, returns (skip, dsum). Note vis > 0
        implies ~skip, so downstream phases that only touch visible
        rows never need skip."""
        nl = nlive_ref[0]
        live = flat < nl
        rseq = T[RS_]
        removed = rseq != NOT_REMOVED
        tomb = removed & (rseq <= r)
        ins_vis = (T[IC_] == c) | (T[IS_] <= r)
        among = jnp.any(T[RC0:PP0] == c, axis=0)
        skip = (~live) | tomb | (removed & ~ins_vis)
        visible = (~skip) & ins_vis & ~(removed & among)
        vis = jnp.where(visible, T[L_], 0)
        is_span = T[B_] >= SETTLED_BASE
        consume = jnp.where(live & is_span, T[L_], 0)
        delta = vis - consume
        excl, dsum = cumsum_and_total(delta)
        T[PRE_] = T[A_] + excl
        T[VIS_] = vis
        return skip, dsum

    def clear_new_row(ohn):
        """Remover/prop columns of a freshly opened slot."""
        oh3 = ohn[None]
        T[RC0:PP0] = jnp.where(oh3, NO_CLIENT, T[RC0:PP0])
        T[PP0:PRE_] = jnp.where(oh3, PROP_ABSENT, T[PP0:PRE_])

    def set1(ci, oh, val):
        T[ci] = jnp.where(oh, val, T[ci])

    def body(i, _):
        otype = op_type_ref[i]
        pos1 = pos1_ref[i]
        pos2 = pos2_ref[i]
        oseq = seq_ref[i]
        orefseq = ref_seq_ref[i]
        oclient = client_ref[i]
        obuf = buf_ref[i]
        oilen = ilen_ref[i]

        is_ins = otype == OP_INSERT
        is_rem = otype == OP_REMOVE
        is_ann = otype == OP_ANNOTATE
        is_range = is_rem | is_ann

        @pl.when(is_ins)
        def _():
            # Landing (overlay_ref._apply_insert / insertingWalk +
            # breakTie, mergeTree.ts:1740,:1719) fused with the
            # boundary split: both indices resolve in pre-split
            # coordinates from the single perspective pass. An inside
            # row (pre < pos < pre+vis) always precedes every landing
            # row (pre >= pos; pre is non-decreasing), so ONE reduce
            # finds whichever applies, and the row's scalars serve
            # both cases.
            skip, dsum = vis_pass(orefseq, oclient)
            nl = nlive_ref[0]
            live = flat < nl
            pre = T[PRE_]
            vis = T[VIS_]
            total = S + dsum
            inside = (pre < pos1) & (pre + vis > pos1)
            land = live & (
                (pre > pos1)
                | ((pre == pos1) & (~skip)
                   & ((vis > 0) | (oseq > T[IS_])))
            )
            j0 = first_idx(inside | land)
            preX = at(PRE_, j0)
            visX = at(VIS_, j0)
            ancX = at(A_, j0)
            bufX = at(B_, j0)
            has_split = (j0 < W) & (preX < pos1) & (preX + visX > pos1)
            land_dead = j0 >= nl
            j_l = jnp.minimum(j0, nl)
            span_s = bufX >= SETTLED_BASE
            off = pos1 - preX
            A_nosplit = jnp.where(
                land_dead, jnp.minimum(pos1 - dsum, S),
                ancX - (preX - pos1),
            )
            Aval = jnp.where(
                has_split, ancX + jnp.where(span_s, off, 0), A_nosplit
            )
            t1 = jnp.where(has_split, j0 + 1, j_l)
            n_new = jnp.where(has_split, 2, 1)
            err_ref[0] = err_ref[0] | jnp.where(
                (~has_split) & land_dead & (total < pos1),
                ERR_BAD_POS, 0,
            ) | jnp.where(nl + n_new > W, ERR_CAPACITY, 0)
            roll_from(t1)

            @pl.when(has_split)
            def _():
                roll_from(t1)
                oh_h = flat == (t1 - 1)  # head (row j_s)
                set1(L_, oh_h, off)
                set1(VIS_, oh_h, off)
                oh_t = flat == (t1 + 1)  # tail (raw copy of j_s)
                set1(B_, oh_t, T[B_] + off)
                set1(L_, oh_t, T[L_] - off)

                @pl.when(span_s)
                def _():
                    set1(A_, oh_t, T[A_] + off)

                set1(PRE_, oh_t, pos1)
                set1(VIS_, oh_t, T[VIS_] - off)

            ohn = flat == t1
            set1(A_, ohn, Aval)
            set1(B_, ohn, obuf)
            set1(L_, ohn, oilen)
            set1(IS_, ohn, oseq)
            set1(IC_, ohn, oclient)
            set1(RS_, ohn, NOT_REMOVED)
            clear_new_row(ohn)
            for p in range(PK):
                pkey = pkey_ref[p * B + i]
                pval = pval_ref[p * B + i]

                @pl.when(pkey != NO_KEY)
                def _(pkey=pkey, pval=pval):
                    v = jnp.where(
                        pval == PROP_DELETE, PROP_ABSENT, pval
                    )
                    for k in range(KK):
                        @pl.when(pkey == k)
                        def _(k=k, v=v):
                            set1(PP0 + k, ohn, v)
            set1(PRE_, ohn, pos1)
            set1(VIS_, ohn, oilen)
            nlive_ref[0] = nl + n_new

        @pl.when(is_range)
        def _():
            # Both boundary splits resolve in pre-split coordinates
            # from one perspective pass, then compose as two
            # dest-based rolls (ensureIntervalBoundary,
            # mergeTree.ts:1706).
            skip, dsum = vis_pass(orefseq, oclient)
            nl = nlive_ref[0]
            live = flat < nl
            pre = T[PRE_]
            vis = T[VIS_]
            total = S + dsum
            err_ref[0] = err_ref[0] | jnp.where(
                total < pos2, ERR_BAD_POS, 0
            )
            inside1 = (pre < pos1) & (pre + vis > pos1)
            inside2 = (pre < pos2) & (pre + vis > pos2)
            j1 = first_idx(inside1)
            j2 = first_idx(inside2)
            has1 = j1 < W
            has2 = j2 < W
            pre1 = at(PRE_, j1)
            anc1 = at(A_, j1)
            buf1 = at(B_, j1)
            pre2 = at(PRE_, j2)
            anc2 = at(A_, j2)
            buf2 = at(B_, j2)
            off1 = pos1 - pre1
            off2 = pos2 - pre2
            span1 = buf1 >= SETTLED_BASE
            span2 = buf2 >= SETTLED_BASE
            # Settled coordinates of the range ends, resolved from the
            # PRE-split state: a split's tail has pre == pos exactly,
            # so c = tail anchor; otherwise the first live row with
            # pre >= pos (unchanged by the splits) anchors the
            # coordinate, falling back past the live rows.
            jc1 = first_idx(live & (pre >= pos1))
            jc2 = first_idx(live & (pre >= pos2))
            c1_nos = jnp.where(
                jc1 < W, at(A_, jc1) - (at(PRE_, jc1) - pos1),
                pos1 - dsum,
            )
            c2_nos = jnp.where(
                jc2 < W, at(A_, jc2) - (at(PRE_, jc2) - pos2),
                pos2 - dsum,
            )
            c1 = jnp.where(
                has1, anc1 + jnp.where(span1, off1, 0), c1_nos
            )
            c2 = jnp.where(
                has2, anc2 + jnp.where(span2, off2, 0), c2_nos
            )
            r1 = jnp.where(
                has1, j1 + 1, jnp.where(has2, j2 + 1, W)
            )
            err_ref[0] = err_ref[0] | jnp.where(
                nl + has1.astype(jnp.int32) + has2.astype(jnp.int32)
                > W,
                ERR_CAPACITY, 0,
            )

            @pl.when(has1 | has2)
            def _():
                roll_from(r1)

            @pl.when(has1 & has2)
            def _():
                roll_from(j2 + 2)

            @pl.when(has1)
            def _():
                oh_h = flat == j1
                set1(L_, oh_h, off1)
                set1(VIS_, oh_h, off1)
                oh_t = flat == (j1 + 1)
                set1(B_, oh_t, T[B_] + off1)
                set1(L_, oh_t, T[L_] - off1)

                @pl.when(span1)
                def _():
                    set1(A_, oh_t, T[A_] + off1)

                set1(PRE_, oh_t, pos1)
                set1(VIS_, oh_t, T[VIS_] - off1)

            @pl.when(has2)
            def _():
                d2 = j2 + has1.astype(jnp.int32)
                base = jnp.where(has1 & (j1 == j2), off1, 0)
                oh_d = flat == d2
                set1(L_, oh_d, off2 - base)
                set1(VIS_, oh_d, off2 - base)
                # tail2 is ALWAYS a raw copy of the ORIGINAL row j2
                # (untouched by split1 fixups), so adjust by off2
                # against the original even when j1 == j2.
                oh_t = flat == (d2 + 1)
                set1(B_, oh_t, T[B_] + off2)
                set1(L_, oh_t, T[L_] - off2)

                @pl.when(span2)
                def _():
                    set1(A_, oh_t, T[A_] + off2)

                set1(PRE_, oh_t, pos2)
                set1(VIS_, oh_t, T[VIS_] - off2)

            nlive_ref[0] = (
                nl + has1.astype(jnp.int32) + has2.astype(jnp.int32)
            )

            # ---- gap materialization (overlay_ref "gap
            # materialization"): lazily create span rows for settled
            # coords the range covers. Per-gap bounds stage through
            # the G scratch so the loop's scalars are cheap row loads.
            def gaps():
                nl = nlive_ref[0]
                live = flat < nl
                is_span = T[B_] >= SETTLED_BASE
                consume = jnp.where(live & is_span, T[L_], 0)
                end = T[A_] + consume
                glo = jnp.where(flat == 0, 0, _roll1_flat(end))
                ghi = jnp.where(live, T[A_], S)
                prev_live = (flat == 0) | (
                    _roll1_flat(live.astype(jnp.int32)) > 0
                )
                gapvalid = live | prev_live
                lo = jnp.maximum(glo, c1)
                hi = jnp.minimum(ghi, c2)
                G[0] = lo
                G[1] = hi
                G[2] = ghi
                return gapvalid & (lo < hi)

            n_mat = jnp.sum(gaps().astype(jnp.int32))

            def gap_body(_, carry):
                mat = gaps()
                nl = nlive_ref[0]
                j = first_idx(mat)
                loJ = at_g(G, 0, j)
                hiJ = at_g(G, 1, j)
                ghiJ = at_g(G, 2, j)
                # Visible prefix of the new span row: the displaced
                # row's prefix minus the settled run [loJ, ghiJ) that
                # still sits between them (gap after the live rows:
                # against the grand total).
                preJ = at(PRE_, j)
                pre_new = jnp.where(
                    j < nl, preJ, S + dsum
                ) - (ghiJ - loJ)
                err_ref[0] = err_ref[0] | jnp.where(
                    nl + 1 > W, ERR_CAPACITY, 0
                )
                roll_from(j)
                oh = flat == j
                set1(A_, oh, loJ)
                set1(B_, oh, SETTLED_BASE + loJ)
                set1(L_, oh, hiJ - loJ)
                set1(IS_, oh, 0)
                set1(IC_, oh, NO_CLIENT)
                set1(RS_, oh, NOT_REMOVED)
                clear_new_row(oh)
                set1(PRE_, oh, pre_new)
                set1(VIS_, oh, hiJ - loJ)
                nlive_ref[0] = nl + 1
                return carry

            lax.fori_loop(0, n_mat, gap_body, 0)

            # ---- covered-range updates (markRangeRemoved
            # mergeTree.ts:1960 / annotateRange :1895) straight off
            # the maintained columns — no rescan (vis > 0 already
            # implies the row is live, unskipped and visible).
            pre = T[PRE_]
            vis = T[VIS_]
            covered = (
                (vis > 0) & (pre >= pos1) & (pre + vis <= pos2)
                & (flat < nlive_ref[0])
            )

            @pl.when(is_rem)
            def _():
                rcl = T[RC0:PP0]
                already = T[RS_] != NOT_REMOVED
                set1(RS_, covered & ~already, oseq)
                iota_k = jax.lax.broadcasted_iota(
                    jnp.int32, rcl.shape, 0
                )
                first_free = jnp.min(
                    jnp.where(rcl == NO_CLIENT, iota_k, KR), axis=0
                )
                no_free = first_free == KR
                slot = jnp.where(already, first_free, 0)
                write = covered & ~(already & no_free)
                T[RC0:PP0] = jnp.where(
                    write[None] & (iota_k == slot[None]), oclient, rcl
                )
                err_ref[0] = err_ref[0] | jnp.where(
                    jnp.any(covered & already & no_free),
                    ERR_REMOVERS, 0,
                )

            @pl.when(is_ann)
            def _():
                # Last writer wins; a delete tombstones on span rows
                # (it must fold as a delete of the settled prop) but
                # clears on text rows (they are authoritative).
                is_span = T[B_] >= SETTLED_BASE
                for p in range(PK):
                    pkey = pkey_ref[p * B + i]
                    pval = pval_ref[p * B + i]
                    newv = jnp.where(
                        pval == PROP_DELETE,
                        jnp.where(is_span, PROP_DELETE, PROP_ABSENT),
                        jnp.broadcast_to(pval, shape),
                    )
                    for k in range(KK):
                        @pl.when(pkey == k)
                        def _(k=k, newv=newv):
                            set1(PP0 + k, covered, newv)

        return 0

    lax.fori_loop(0, nops_ref[0], body, 0)

    t_anchor[...] = T[A_]
    t_buf[...] = T[B_]
    t_len[...] = T[L_]
    t_iseq[...] = T[IS_]
    t_iclient[...] = T[IC_]
    t_rseq[...] = T[RS_]
    for k in range(KR):
        t_rcl[k] = T[RC0 + k]
    for k in range(KK):
        t_props[k] = T[PP0 + k]
    nrows_out_ref[0] = nlive_ref[0]
    err_out_ref[0] = err_ref[0]


def _to_tiles(v):
    return v.reshape(-1, LANES)


# The XLA module name of the fused replay (``jit_replay_fused``; the
# streaming path's per-segment program extends it) and the name of its
# Pallas chunk kernel: profiler readers find device time by these.
OVERLAY_PROGRAM = "replay_fused"
OVERLAY_KERNEL = "overlay_apply_chunk"


@functools.partial(jax.jit, static_argnums=(2,))
def overlay_apply_chunk(table: OverlayTable, ops: OpBatch,
                        interpret: bool = False) -> OverlayTable:
    """Apply a chunk of sequenced ops (ascending seq order) to the
    overlay in ONE pallas kernel invocation. Bit-identical to
    `overlay_ref.OverlayDoc.apply` run op-by-op (differentially gated
    by tests/test_overlay_pallas.py)."""
    window = table.length.shape[0]
    KR = table.rem_clients.shape[1]
    KK = table.props.shape[1]
    B = ops.pos1.shape[0]
    PK = ops.prop_keys.shape[1]
    assert window % (8 * LANES) == 0, "window must be a multiple of 1024"

    tile_in = [
        _to_tiles(table.anchor), _to_tiles(table.buf_start),
        _to_tiles(table.length), _to_tiles(table.ins_seq),
        _to_tiles(table.ins_client), _to_tiles(table.rem_seq),
        jnp.moveaxis(table.rem_clients, 1, 0).reshape(KR, -1, LANES),
        jnp.moveaxis(table.props, 1, 0).reshape(KK, -1, LANES),
    ]
    op_in = [
        ops.op_type, ops.pos1, ops.pos2, ops.seq, ops.client,
        ops.buf_start, ops.ins_len,
        jnp.moveaxis(ops.prop_keys, 1, 0).reshape(PK * B),
        jnp.moveaxis(ops.prop_vals, 1, 0).reshape(PK * B),
        ops.ref_seq,
    ]

    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    W8 = window // LANES
    out_shapes = (
        jax.ShapeDtypeStruct((W8, LANES), jnp.int32),  # anchor
        jax.ShapeDtypeStruct((W8, LANES), jnp.int32),  # buf
        jax.ShapeDtypeStruct((W8, LANES), jnp.int32),  # len
        jax.ShapeDtypeStruct((W8, LANES), jnp.int32),  # ins_seq
        jax.ShapeDtypeStruct((W8, LANES), jnp.int32),  # ins_client
        jax.ShapeDtypeStruct((W8, LANES), jnp.int32),  # rem_seq
        jax.ShapeDtypeStruct((KR, W8, LANES), jnp.int32),
        jax.ShapeDtypeStruct((KK, W8, LANES), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),  # n_rows
        jax.ShapeDtypeStruct((1,), jnp.int32),  # error
    )
    C = 8 + KR + KK  # 6 scalar cols + rcl + props + pre/vis
    outs = pl.pallas_call(
        _overlay_chunk_kernel,
        out_shape=out_shapes,
        name=OVERLAY_KERNEL,
        in_specs=[smem()] * 14 + [vmem()] * 8,
        out_specs=tuple([vmem()] * 8 + [smem(), smem()]),
        scratch_shapes=[
            pltpu.VMEM((C, W8, LANES), jnp.int32),  # stacked table
            pltpu.VMEM((3, W8, LANES), jnp.int32),  # gap lo/hi/ghi
            pltpu.SMEM((1,), jnp.int32),  # n_live
            pltpu.SMEM((1,), jnp.int32),  # error flags
        ],
        interpret=interpret,
    )(
        jnp.reshape(table.n_rows, (1,)), jnp.reshape(table.error, (1,)),
        jnp.asarray([B], jnp.int32),
        jnp.reshape(table.settled_len, (1,)),
        *op_in, *tile_in,
    )
    (anchor, buf, length, iseq, iclient, rseq, rcl, props, nrows,
     err) = outs
    return OverlayTable(
        n_rows=nrows[0],
        anchor=anchor.reshape(-1),
        buf_start=buf.reshape(-1),
        length=length.reshape(-1),
        ins_seq=iseq.reshape(-1),
        ins_client=iclient.reshape(-1),
        rem_seq=rseq.reshape(-1),
        rem_clients=jnp.moveaxis(rcl.reshape(KR, -1), 0, 1),
        props=jnp.moveaxis(props.reshape(KK, -1), 0, 1),
        settled_len=table.settled_len,
        error=err[0],
    )


@jax.jit
def fold_device(table: OverlayTable, msn: jnp.ndarray):
    """Settle-merge under applied MSN `msn` (overlay_ref.fold; the
    zamboni role, zamboni.ts:19) as one XLA dispatch.

    Returns ``(table', records, n_rec)``: ONE stable binary partition
    (log-shift compaction, `_pack_partition` — no sort network, no
    gathers) packs surviving rows to the front (re-anchored) and the
    folding rows to the back; because the partition is stable, the
    back IS the fold-record block in storage (== coordinate) order.
    Records are ``(W, 5+KK)`` columns ``[anchor, code, buf, len,
    ins_seq, props...]`` with pre-fold anchors (ins_seq carries the
    per-position insert-attribution key into the settled state — the
    attributionCollection.ts role); ``code == REC_NONE`` rows
    (dropped text) reconstruct to nothing but stay in the block so
    one partition serves both outputs.
    """
    W = table.length.shape[0]
    KR = table.rem_clients.shape[1]
    KK = table.props.shape[1]
    idx = jnp.arange(W, dtype=jnp.int32)
    live = idx < table.n_rows
    is_span = live & (table.buf_start >= SETTLED_BASE)
    removed = live & (table.rem_seq != NOT_REMOVED)
    drop = removed & (table.rem_seq <= msn)
    settle_text = live & ~removed & ~is_span & (table.ins_seq <= msn)
    settle_span = live & ~removed & is_span
    folding = drop | settle_text | settle_span

    exc = jnp.where(drop & is_span, table.length, 0)
    ins = jnp.where(settle_text, table.length, 0)
    exc_b = jnp.cumsum(exc) - exc
    ins_b = jnp.cumsum(ins) - ins
    new_anchor = (table.anchor - exc_b + ins_b).astype(jnp.int32)
    new_s = table.settled_len + jnp.sum(ins) - jnp.sum(exc)

    keep = live & ~folding
    n_new = jnp.sum(keep.astype(jnp.int32))
    n_rec = jnp.sum(folding.astype(jnp.int32))
    new_buf = jnp.where(is_span, SETTLED_BASE + new_anchor,
                        table.buf_start)
    code = jnp.where(
        settle_text, REC_SETTLE_TEXT,
        jnp.where(drop & is_span, REC_DROP_SPAN,
                  jnp.where(settle_span, REC_SETTLE_SPAN, 0)),
    ).astype(jnp.int32)
    cols = (
        new_anchor, new_buf, table.length, table.ins_seq,
        table.ins_client, table.rem_seq,
        *(table.rem_clients[:, k] for k in range(KR)),
        *(table.props[:, k] for k in range(KK)),
        table.anchor, code,
    )
    packed = _pack_partition(~keep, cols)
    valid = idx < n_new

    def fill(a, f):
        return jnp.where(valid, a, f)

    out = OverlayTable(
        n_rows=n_new,
        anchor=fill(packed[0], 0),
        buf_start=fill(packed[1], 0),
        length=fill(packed[2], 0),
        ins_seq=fill(packed[3], 0),
        ins_client=fill(packed[4], NO_CLIENT),
        rem_seq=fill(packed[5], NOT_REMOVED),
        rem_clients=jnp.where(
            valid[:, None], jnp.stack(packed[6:6 + KR], axis=1), NO_CLIENT
        ),
        props=jnp.where(
            valid[:, None], jnp.stack(packed[6 + KR:6 + KR + KK], axis=1),
            PROP_ABSENT,
        ),
        settled_len=new_s.astype(jnp.int32),
        error=table.error,
    )

    # The back of the partition holds the folding rows in storage
    # order (stable), directly followed by dead rows; rotate them to
    # the front of the record block for the log append.
    old_anchor_p = packed[6 + KR + KK]
    code_p = packed[6 + KR + KK + 1]
    rec_cols = (old_anchor_p, code_p, packed[1], packed[2], packed[3],
                *packed[6 + KR:6 + KR + KK])
    records = jnp.roll(jnp.stack(rec_cols, axis=1), -n_new, axis=0)
    return out, records, n_rec


@functools.partial(
    jax.jit, static_argnums=(5, 6), donate_argnums=(0, 2, 3)
)
@named_program(OVERLAY_PROGRAM)
def replay_fused(
    table: OverlayTable, stream_ops: OpBatch, log, counts, msn_by_chunk,
    chunk: int, interpret: bool = False, epoch0=0,
):
    """The WHOLE replay as one dispatch: `lax.fori_loop` over chunks,
    each iteration = pallas apply + XLA fold + log append, all
    device-resident (stream, msn schedule, log, table ride the loop
    carry; XLA keeps the donated log in place). One host->device
    dispatch replaces ~n/chunk of them — the host loop and its
    per-chunk scalar uploads are the dominant cost once the kernel is
    O(window).

    `msn_by_chunk[ci]` is the applied MSN at chunk ci's end (the fold
    perspective). Returns ``(table, log, counts, cursor)``.

    `epoch0` (streaming ingress): this call replays a SEGMENT of a
    larger stream whose global chunk numbering starts at `epoch0`;
    counts index globally and the log cursor carries in/out through
    `counts`'s prior entries (the caller threads table/log/counts
    across segment calls while the next segment's host->device
    transfer overlaps this one's compute)."""
    n_chunks = msn_by_chunk.shape[0]
    epoch0 = jnp.asarray(epoch0, jnp.int32)
    # Resume the log cursor where earlier segments left it (the mask
    # is all-false at epoch0 == 0, so a fresh replay starts at 0).
    cursor0 = jnp.sum(
        counts * (jnp.arange(counts.shape[0]) < epoch0)
    ).astype(jnp.int32)

    def step(ci, carry):
        table, log, counts, cursor = carry
        table, log, counts, cursor = _chunk_step_body(
            table, stream_ops, ci * chunk, chunk, msn_by_chunk[ci],
            log, counts, cursor, epoch0 + ci, interpret,
        )
        return (table, log, counts, cursor)

    return lax.fori_loop(
        0, n_chunks, step, (table, log, counts, cursor0)
    )


def _chunk_step_body(
    table, stream_ops, lo, chunk, msn, log, counts, cursor, epoch,
    interpret,
):
    """One steady-state replay step, fully device-side: slice ops
    [lo, lo+chunk) from the device-resident stream, run the pallas
    chunk kernel, fold at the chunk boundary, and append the fold
    records to the HBM log (donated: XLA updates in place).

    Returns ``(table', log', counts', cursor')``; ``counts[epoch]``
    records this epoch's record count so the host can reconstruct the
    settled document epoch-by-epoch after the run."""
    sl = lambda a: lax.dynamic_slice_in_dim(a, lo, chunk, axis=0)
    batch = OpBatch(
        op_type=sl(stream_ops.op_type), pos1=sl(stream_ops.pos1),
        pos2=sl(stream_ops.pos2), seq=sl(stream_ops.seq),
        ref_seq=sl(stream_ops.ref_seq), client=sl(stream_ops.client),
        buf_start=sl(stream_ops.buf_start),
        ins_len=sl(stream_ops.ins_len),
        prop_keys=sl(stream_ops.prop_keys),
        prop_vals=sl(stream_ops.prop_vals),
    )
    table = overlay_apply_chunk(table, batch, interpret)
    table, records, n_rec = fold_device(table, msn)
    log = lax.dynamic_update_slice(
        log, records, (cursor, jnp.int32(0))
    )
    counts = counts.at[epoch].set(n_rec)
    return table, log, counts, cursor + n_rec


@functools.partial(
    jax.jit, static_argnums=(3, 9), donate_argnums=(0, 5, 6)
)
def replay_chunk_step(
    table: OverlayTable, stream_ops: OpBatch, lo, chunk: int,
    msn, log, counts, cursor, epoch, interpret: bool = False,
):
    """One replay step as its own dispatch (the incremental form:
    warm-up with `limit_chunks`, message-driven replicas, tests).
    `replay_fused` runs the same body for the whole stream in one
    dispatch."""
    return _chunk_step_body(
        table, stream_ops, lo, chunk, msn, log, counts, cursor, epoch,
        interpret,
    )
