"""How late the load generator ran: 95th percentile over the window's
frames of (append finished - frame due), host clock of the generator."""

from benchmark.harness import quantile


def read(run):
    late = run.facts.get("late_s")
    if late is None or len(late) == 0:
        return None
    return 1000.0 * quantile(late, 0.95)
