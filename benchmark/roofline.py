"""Peaks by device kind, and the bytes each measured kernel's algorithm
must move, computed from the shapes of its work.

Both kernels are integer code with a few operations per byte, so the
least time the chip could take is the bytes over the memory bandwidth;
a kernel's roofline share is that least time over its measured time.
"""

from __future__ import annotations

import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")
I32 = 4


def peak(device_kind: str) -> dict:
    """The peaks of `device_kind`; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS} (have {sorted(table)})")
    return table[device_kind]


def roofline_pct(n_bytes: float, seconds: float, device_kind: str) -> float:
    return 100.0 * n_bytes / peak(device_kind)["hbm_bytes_per_s"] / seconds


def prop_runs(props: np.ndarray) -> int:
    """Number of maximal runs of equal properties over a document."""
    if len(props) == 0:
        return 0
    return 1 + int(np.count_nonzero(np.any(props[1:] != props[:-1], axis=1)))


def overlay_bytes(n_ops: int, arena_chars: int, final_chars: int,
                  runs: int, n_keys: int) -> int:
    """One log's replay: the op columns in (ten int32 per op: type, two
    positions, seq, refSeq, client, text offset and length, key,
    value) and the inserted text in; the final document's characters
    and its annotation runs (length plus a value per key) out."""
    return I32 * (10 * n_ops + arena_chars + final_chars
                  + runs * (1 + n_keys))


def sequencer_bytes(records: int, touched_docs: int, clients: int) -> int:
    """Sequencing: per record, the submission (kind, client, clientSeq,
    refSeq) in and the verdict (seq, MSN, nack code) out; per document
    a pump touches, its state read and written: head seq and MSN, and
    one connected flag, refSeq and clientSeq per client."""
    per_doc = 2 * (2 * I32 + clients * (1 + 2 * I32))
    return 7 * I32 * records + per_doc * touched_docs
