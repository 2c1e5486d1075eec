"""Canonical document-state digests for cross-implementation identity.

Different replay engines segment the same document differently (the
scalar oracle keeps per-op segments, the kernel coalesces settled
runs), so raw segment lists are not comparable. `normalize_spans`
reduces any (content, props) span list to its canonical form —
maximal runs of identical props — which is a pure function of the
visible document state; `state_digest` hashes it. Used by the
full-stream bit-identity gate (`chip_smoke.py`'s replay phase vs
GOLDEN.json — the north star's "bit-identical final state" contract,
BASELINE.json).
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, List, Optional, Tuple


def normalize_spans(
    spans: List[Tuple[Any, Optional[dict]]]
) -> List[Tuple[str, Optional[dict]]]:
    """Merge adjacent spans with identical props; empty props == None.

    Content may be str or a list of items; everything is rendered to
    its text form (items joined) so engines that store codepoints and
    engines that store strings normalize identically.
    """
    out: List[Tuple[str, Optional[dict]]] = []
    for content, props in spans:
        if not isinstance(content, str):
            content = "".join(
                c if isinstance(c, str) else chr(c) for c in content
            )
        if not content:
            continue
        p = props or None
        if out and out[-1][1] == p:
            out[-1] = (out[-1][0] + content, p)
        else:
            out.append((content, p))
    return out


def state_digest(spans: List[Tuple[Any, Optional[dict]]]) -> str:
    """SHA-256 over the canonical span form."""
    norm = normalize_spans(spans)
    payload = json.dumps(
        [[t, p] for t, p in norm], sort_keys=True, ensure_ascii=True
    )
    return hashlib.sha256(payload.encode()).hexdigest()
