"""Bytes the sequencer pool's row reload must move
(`SEQ_POOL_LOAD_PROGRAM`), computed from the rows it loads.

The reload is integer code with no arithmetic to speak of, so its least
time is these bytes at the chip's memory bandwidth
(`roofline.roofline_pct`)."""

from __future__ import annotations

I32 = 4
BOOL = 1


def pool_load_bytes(rows: int, clients: int) -> int:
    """Per row loaded: its index in, and the row written: head seq and
    MSN, and per client column a connected flag, refSeq and
    clientSeq."""
    return rows * (I32 + 2 * I32 + clients * (BOOL + 2 * I32))
