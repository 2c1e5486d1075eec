"""Batched multi-document sequencer kernel (the deli hot loop on TPU).

The reference sequencer (server/routerlicious/packages/lambdas/src/deli/
lambda.ts:818 `ticket`) is per-document serial scalar code: stamp
sequence numbers, track per-client reference sequence numbers in a heap
(clientSeqManager.ts:22), maintain MSN = min over connected clients'
refSeqs, and nack invalid submissions (stale refSeq lambda.ts:967,
out-of-order clientSeq, unknown client).

TPU-native re-expression (BASELINE.md config 5 — 10k docs x 64
clients): documents are the data-parallel axis (`vmap`), the op batch
is a `lax.scan`, and each scan step does the per-document work as
O(max_clients) vector ops — so one step processes *every* document's
next op in lockstep with D*C lanes of VPU work. The per-client "heap"
becomes a dense refSeq row per document; MSN is a masked min-reduce
(the reduction the reference maintains incrementally with a heap).

Scalar oracle: fluidframework_tpu/server/sequencer.py
(DocumentSequencer). Differential gate: tests/test_sequencer_kernel.py
drives both with identical random traffic and asserts identical stamps,
nack codes, and MSNs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..protocol.constants import INT32_MAX
from ..utils.metrics import named_program

# Submission kinds (SeqBatch.kind).
SUB_OP = 0  # ordinary client message (op/noop/...): validate + stamp
SUB_JOIN = 1  # client join: admit into the MSN set, stamp a join message
SUB_LEAVE = 2  # client leave: evict, stamp a leave message
SUB_PAD = 3  # padding: no effect, no stamp
SUB_SYSTEM = 4  # server-originated control: stamp unconditionally,
#                 bypassing client validation (deli's system-message
#                 path — summary ack/nack from scribe)

# Boxcar group sentinel (the `groups` batch column): submissions with
# group >= 0 belong to an atomic boxcar; -1 means standalone.
NO_GROUP = -1

# Nack codes (0 = accepted). Values match server/sequencer.py.
ACCEPT = 0
NACK_STALE_REFSEQ = 400
NACK_UNKNOWN_CLIENT = 403
NACK_FUTURE_REFSEQ = 416
NACK_OUT_OF_ORDER = 422


class SequencerState(NamedTuple):
    """Per-document sequencer state, documents on the leading axis.

    The dense [D, C] client table replaces the reference's per-doc heap
    (clientSeqManager.ts:22); slot index = client id within the doc.
    """

    seq: jnp.ndarray  # int32[D] last assigned sequence number
    min_seq: jnp.ndarray  # int32[D] minimum sequence number (MSN)
    connected: jnp.ndarray  # bool[D, C]
    ref_seq: jnp.ndarray  # int32[D, C] last seen refSeq per client
    client_seq: jnp.ndarray  # int32[D, C] last accepted clientSeq per client


class SeqBatch(NamedTuple):
    """A batch of submissions: one column per scan step, [D, B]."""

    kind: jnp.ndarray  # int32[D, B] SUB_*
    client: jnp.ndarray  # int32[D, B] client slot in [0, C)
    client_seq: jnp.ndarray  # int32[D, B]
    ref_seq: jnp.ndarray  # int32[D, B]


class SeqResult(NamedTuple):
    """Per-submission verdicts, [D, B]."""

    seq: jnp.ndarray  # int32: assigned sequence number (0 if not stamped)
    min_seq: jnp.ndarray  # int32: MSN as of this submission
    nack: jnp.ndarray  # int32: ACCEPT or NACK_* code
    # bool: submission was masked out with no stamp AND no nack — the
    # tail of an aborted boxcar (scalar `_handle` breaks out of the
    # batch after a nack) or a deduped resubmission (DeliRole's
    # at-least-once ingress dedup drops it silently).
    skipped: jnp.ndarray


def make_state(n_docs: int, max_clients: int) -> SequencerState:
    return SequencerState(
        seq=jnp.zeros(n_docs, jnp.int32),
        min_seq=jnp.zeros(n_docs, jnp.int32),
        connected=jnp.zeros((n_docs, max_clients), jnp.bool_),
        ref_seq=jnp.zeros((n_docs, max_clients), jnp.int32),
        client_seq=jnp.zeros((n_docs, max_clients), jnp.int32),
    )


def grow_state(state: SequencerState, n_docs: int = None,
               n_clients: int = None) -> SequencerState:
    """Zero-pad the packed state to [n_docs, n_clients] (dynamic
    doc-slot / client-slot growth; new rows are empty documents)."""
    d, c = state.connected.shape
    nd = d if n_docs is None else max(d, n_docs)
    nc = c if n_clients is None else max(c, n_clients)
    if (nd, nc) == (d, c):
        return state
    pad1 = ((0, nd - d),)
    pad2 = ((0, nd - d), (0, nc - c))
    return SequencerState(
        seq=jnp.pad(state.seq, pad1),
        min_seq=jnp.pad(state.min_seq, pad1),
        connected=jnp.pad(state.connected, pad2),
        ref_seq=jnp.pad(state.ref_seq, pad2),
        client_seq=jnp.pad(state.client_seq, pad2),
    )


def _step_one_doc(state: SequencerState, aborted, kind, client, client_seq,
                  ref_seq, group, *, dedup: bool = False):
    """Process one submission for one document (vmapped over docs).

    All fields here are per-document scalars / [C] rows; straight-line
    masked code (no control flow) mirroring DocumentSequencer.sequence
    and deli ticket() (lambda.ts:818). `aborted` is the batch-local
    boxcar-abort tracker: the group id whose remaining submissions are
    masked out (a nack aborts the REST of its boxcar, the `_handle`
    break semantics).
    """
    n_clients = state.connected.shape[0]
    slot = jnp.clip(client, 0, n_clients - 1)
    onehot = jnp.arange(n_clients, dtype=jnp.int32) == slot

    is_join = kind == SUB_JOIN
    is_leave = kind == SUB_LEAVE
    is_sys = kind == SUB_SYSTEM

    known = state.connected[slot]
    in_box = group >= 0
    box_dead = in_box & (group == aborted)
    if dedup:
        # Resubmission dedup (DeliRole's idempotent-producer role): a
        # clientSeq at or below the last accepted one is dropped
        # silently — checked BEFORE the nack ladder, so a stale
        # resubmission never pollutes the stream with spurious nacks.
        dup = (kind == SUB_OP) & known & (client_seq <= state.client_seq[slot])
    else:
        dup = jnp.zeros((), jnp.bool_)
    skipped = box_dead | dup
    is_op = (kind == SUB_OP) & ~skipped

    # Validation ladder (first failing rule wins), reference order in
    # DocumentSequencer.sequence: unknown -> stale -> future -> gap.
    nack = jnp.where(
        is_op & ~known,
        NACK_UNKNOWN_CLIENT,
        jnp.where(
            is_op & (ref_seq < state.min_seq),
            NACK_STALE_REFSEQ,
            jnp.where(
                is_op & (ref_seq > state.seq),
                NACK_FUTURE_REFSEQ,
                jnp.where(
                    is_op & (client_seq != state.client_seq[slot] + 1),
                    NACK_OUT_OF_ORDER,
                    ACCEPT,
                ),
            ),
        ),
    ).astype(jnp.int32)

    ok_op = is_op & (nack == ACCEPT)
    live = ~box_dead
    do_join = is_join & live
    # leave of an unknown client stamps nothing (oracle returns None).
    ok_leave = is_leave & known & live
    do_sys = is_sys & live
    stamped = ok_op | do_join | ok_leave | do_sys

    new_seq = state.seq + stamped.astype(jnp.int32)

    # Client-table updates (system stamps bypass the table entirely).
    connected = jnp.where(
        onehot & do_join, True, jnp.where(onehot & ok_leave, False, state.connected)
    )
    # join admits at ref_seq = head seq *before* its own stamp
    # (oracle join(): ref_seq=self.seq then _stamp increments).
    new_ref = jnp.where(do_join, state.seq, ref_seq)
    ref_row = jnp.where(onehot & (ok_op | do_join), new_ref, state.ref_seq)
    cseq_row = jnp.where(
        onehot & do_join,
        0,
        jnp.where(onehot & ok_op, client_seq, state.client_seq),
    )

    # MSN: min over connected clients' refSeqs; empty set trails the
    # head; monotone (oracle _update_msn). Recomputed only when a
    # message is stamped, matching the oracle's call sites.
    masked = jnp.where(connected, ref_row, INT32_MAX)
    any_conn = jnp.any(connected)
    candidate = jnp.where(any_conn, jnp.min(masked), new_seq)
    new_min = jnp.where(stamped, jnp.maximum(state.min_seq, candidate), state.min_seq)

    # A nack aborts the rest of its boxcar (nack is only ever nonzero
    # for live ops, so this can't retrigger inside a dead group).
    new_aborted = jnp.where(in_box & (nack != ACCEPT), group, aborted)

    out = SeqResult(
        seq=jnp.where(stamped, new_seq, 0).astype(jnp.int32),
        min_seq=new_min.astype(jnp.int32),
        nack=nack,
        skipped=skipped,
    )
    return (
        SequencerState(
            seq=new_seq.astype(jnp.int32),
            min_seq=new_min.astype(jnp.int32),
            connected=connected,
            ref_seq=ref_row.astype(jnp.int32),
            client_seq=cseq_row.astype(jnp.int32),
        ),
        new_aborted.astype(jnp.int32),
        out,
    )


def _sequence_batch_impl(state: SequencerState, aborted, batch: SeqBatch,
                         groups, dedup: bool):
    step = jax.vmap(functools.partial(_step_one_doc, dedup=dedup))

    def body(carry, col):
        st, ab = carry
        kind, client, client_seq, ref_seq, group = col
        st2, ab2, out = step(st, ab, kind, client, client_seq,
                             ref_seq, group)
        return (st2, ab2), out

    cols = (
        jnp.swapaxes(batch.kind, 0, 1),
        jnp.swapaxes(batch.client, 0, 1),
        jnp.swapaxes(batch.client_seq, 0, 1),
        jnp.swapaxes(batch.ref_seq, 0, 1),
        jnp.swapaxes(groups, 0, 1),
    )
    (new_state, new_aborted), out = lax.scan(body, (state, aborted), cols)
    # out fields are [B, D] -> [D, B]
    return new_state, new_aborted, SeqResult(
        *(jnp.swapaxes(a, 0, 1) for a in out)
    )


def pack_submissions(slot, kind, client, client_seq, ref_seq, groups,
                     n_docs: int, max_cols: int):
    """Pack PRE-COLUMNIZED 1-D submission arrays into dense ``[D, B]``
    kernel chunks (host-side, vectorized numpy).

    Inputs are six equal-length 1-D arrays — one entry per submission,
    in stream order — exactly the shape the columnar op-log codec
    (`protocol.record_batch`) hands over, so the live pipeline feeds
    the kernel without ever materializing per-record Python tuples.
    Per-doc column index = the submission's rank within its document
    (stable argsort + cumulative count keeps per-doc order == record
    order); documents whose rank exceeds `max_cols` spill into further
    chunks (the boxcar-abort tracker threads across them).

    Yields ``(sel, sl, ic, kind2, client2, cseq2, ref2, grp2)`` per
    chunk: `sel` indexes the original arrays (slice or bool mask),
    ``[sl, ic]`` gathers that chunk's verdicts out of the kernel's
    ``[D, B]`` result, and the five dense int32 arrays are the
    `SeqBatch` + groups input."""
    slot = np.asarray(slot, np.int64)
    n = slot.shape[0]
    if n == 0:
        return
    kind = np.asarray(kind)
    client = np.asarray(client)
    client_seq = np.asarray(client_seq)
    ref_seq = np.asarray(ref_seq)
    groups = np.asarray(groups)
    ar = np.arange(n)
    order = np.argsort(slot, kind="stable")
    ss = slot[order]
    first = np.empty(n, bool)
    first[0] = True
    first[1:] = ss[1:] != ss[:-1]
    col_sorted = ar - np.maximum.accumulate(np.where(first, ar, 0))
    col = np.empty(n, np.int64)
    col[order] = col_sorted
    n_chunks = int(col.max()) // max_cols + 1
    for k in range(n_chunks):
        if n_chunks == 1:
            sel = slice(None)
            sl, ic = slot, col
        else:
            sel = (col // max_cols) == k
            sl, ic = slot[sel], col[sel] - k * max_cols
        b = 8
        top = int(ic.max()) + 1
        while b < top:
            b <<= 1
        kind2 = np.full((n_docs, b), SUB_PAD, np.int32)
        client2 = np.zeros((n_docs, b), np.int32)
        cseq2 = np.zeros((n_docs, b), np.int32)
        ref2 = np.zeros((n_docs, b), np.int32)
        grp2 = np.full((n_docs, b), NO_GROUP, np.int32)
        kind2[sl, ic] = kind[sel]
        client2[sl, ic] = client[sel]
        cseq2[sl, ic] = client_seq[sel]
        ref2[sl, ic] = ref_seq[sel]
        grp2[sl, ic] = groups[sel]
        yield sel, sl, ic, kind2, client2, cseq2, ref2, grp2


# The span decomposition every columnar ingest/emit path shares: a
# homogeneous run vectorizes (one `add_columns` call, one verdict
# slice, one blob-heap memcpy), category boundaries fall back to
# per-record handling without losing stream order. Defined next to the
# codec (it is pure numpy over codec columns, and jax-free consumers —
# the fused durable+broadcast hop — use it too); re-exported here
# beside `pack_submissions` because kernel callers treat it as part of
# the packing toolkit.
from ..protocol.record_batch import mask_runs  # noqa: E402,F401


def no_aborts(n_docs: int):
    """A fresh boxcar-abort tracker ([D], no group aborted)."""
    return jnp.full((n_docs,), -2, jnp.int32)


def sequence_batch(state: SequencerState, batch: SeqBatch, groups=None,
                   dedup: bool = False):
    """Sequence a [D, B] submission batch: scan over B, vmap over D.

    `groups` (int32[D, B], optional) assigns submissions to atomic
    boxcars: a nack masks out the rest of that group (NO_GROUP = -1 =
    standalone). `dedup` enables silent resubmission dedup (the
    at-least-once-ingress DeliRole semantics).

    Returns (new_state, SeqResult[D, B])."""
    if groups is None:
        groups = jnp.full(batch.kind.shape, NO_GROUP, jnp.int32)
    new_state, _, out = _sequence_batch_impl(
        state, no_aborts(state.seq.shape[0]), batch, groups, dedup
    )
    return new_state, out


@functools.partial(jax.jit, donate_argnums=0)
def sequence_batch_jit(state: SequencerState, batch: SeqBatch):
    return sequence_batch(state, batch)


# The XLA module name of the live deli's sequencer program, on one
# device and sharded alike (``jit_sequence_batch_grouped``): profiler
# readers find its device time by this name.
SEQUENCER_PROGRAM = "sequence_batch_grouped"


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=4)
@named_program(SEQUENCER_PROGRAM)
def _sequence_batch_grouped_jit(state, aborted, batch, groups, dedup):
    return _sequence_batch_impl(state, aborted, batch, groups, dedup)


_SHARDED_FN_CACHE: dict = {}


def sharded_sequence_fn(mesh, dedup: bool = False, axis: str = "docs"):
    """Compile the grouped sequencer scan data-parallel over `mesh`.

    Documents are embarrassingly parallel here — verdicts, boxcar
    aborts, and resubmission dedup are all per-doc state — so the
    whole `[D, C]` pool shards over a 1-D device mesh with
    ``PartitionSpec(axis)`` on every per-doc array (state rows, the
    `[D, B]` batch columns, the groups plane, the abort tracker) and
    ZERO cross-device collectives inside the scan: each device runs
    the identical vmap-over-local-docs / scan-over-B body on its slice
    of the doc axis. `D` must be a multiple of ``mesh.size`` (the pool
    keeps it so). Returns a jitted
    ``fn(state, aborted, batch, groups) -> (state', aborted', SeqResult)``
    with the same donation contract as `sequence_batch_grouped`; the
    caller threads `aborted'` across a pump's chunks exactly as in the
    single-device path, so boxcar groups may still span chunks.

    Compiled callables cache process-wide per (mesh, dedup, axis) —
    paired with `parallel.mesh.shared_docs_mesh`, every pool/bench in
    a process shares one jit cache instead of re-tracing per instance.
    """
    key = (mesh, bool(dedup), axis)
    cached = _SHARDED_FN_CACHE.get(key)
    if cached is not None:
        return cached
    docs = jax.sharding.PartitionSpec(axis)
    state_specs = SequencerState(
        seq=docs, min_seq=docs, connected=docs, ref_seq=docs,
        client_seq=docs,
    )
    batch_specs = SeqBatch(
        kind=docs, client=docs, client_seq=docs, ref_seq=docs,
    )
    res_specs = SeqResult(seq=docs, min_seq=docs, nack=docs, skipped=docs)

    @named_program(SEQUENCER_PROGRAM)
    def local(state, aborted, batch, groups):
        return _sequence_batch_impl(state, aborted, batch, groups, dedup)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(state_specs, docs, batch_specs, docs),
        out_specs=(state_specs, docs, res_specs),
        check_vma=False,
    )
    jitted = jax.jit(fn, donate_argnums=(0, 1))
    _SHARDED_FN_CACHE[key] = jitted
    return jitted


def sequence_batch_grouped(state: SequencerState, batch: SeqBatch, groups,
                           dedup: bool = False, aborted=None):
    """Jitted entry for the live deli pipeline: boxcar groups + optional
    resubmission dedup. `aborted` (from `no_aborts` or a previous
    chunk's return) threads the abort tracker across the chunks of one
    pump, so boxcars MAY span chunk boundaries (group ids must be
    unique per doc per pump). Donates (consumes) the input state and
    tracker; returns (new_state, new_aborted, SeqResult)."""
    if aborted is None:
        aborted = no_aborts(state.seq.shape[0])
    return _sequence_batch_grouped_jit(state, aborted, batch, groups,
                                       bool(dedup))
