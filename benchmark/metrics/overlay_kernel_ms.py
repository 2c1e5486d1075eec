"""Device time per log of the fused overlay replay program."""


def read(run):
    ts, n = run.trace_summary, run.facts.get("logs_traced")
    if ts is None or not n:
        return None
    s = ts.program_seconds("replay_fused")
    return 1000.0 * s / n if s > 0 else None
