"""Deli role checkpoints: share of the window spent in fenced checkpoint
writes, from the role's own `checkpoint_ms` histogram (its sum over
the window)."""


def read(run):
    ms = run.facts.get("checkpoint_ms")
    lo, hi = run.facts.get("window", (0.0, 0.0))
    if ms is None or hi <= lo:
        return None
    return 100.0 * ms / 1000.0 / (hi - lo)
