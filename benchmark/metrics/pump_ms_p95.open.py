"""Deli role: 95th percentile of the wall time of `_Role.step` calls
that moved records, over the window (host clock)."""

from benchmark.harness import quantile


def read(run):
    pumps = run.facts.get("pumps")
    if not pumps:
        return None
    ms = [1000.0 * dt for _, dt, moved, _, _ in pumps if moved]
    return quantile(ms, 0.95) if ms else None
