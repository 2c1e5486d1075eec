"""Shared by the readers of the program's own spans: the process span
log (`fluidframework_tpu.utils.metrics.get_span_log`) filtered to the
run's traced interval, or to its window where nothing was traced.

Every reader returns None, never a partial number, where the program
keeps no span log or the log overwrote spans of the interval."""

from benchmark.harness import quantile


def interval(run):
    return run.trace_interval or run.facts.get("window")


def spans(run):
    """Records ``(name, t0, t1, parent_id, span_id, n)`` that started
    inside the interval, or None."""
    iv = interval(run)
    if not iv:
        return None
    try:
        from fluidframework_tpu.utils.metrics import get_span_log
    except ImportError:  # a program without spans
        return None
    log = get_span_log()
    if log.lost_since(iv[0]):
        return None
    return [s for s in log.snapshot() if iv[0] <= s[1] < iv[1]]


def named(recs, name):
    return [s for s in recs if s[0] == name]


def p95_ms(run, name, moved_only=False):
    """95th percentile (ms) of the spans called `name`; `moved_only`
    keeps those that handled records."""
    recs = spans(run)
    if recs is None:
        return None
    ms = [1000.0 * (s[2] - s[1]) for s in named(recs, name)
          if not moved_only or s[5]]
    return quantile(ms, 0.95) if ms else None


def records(recs, root="deli.pump"):
    """Records the interval's steps moved (the pump spans' counts)."""
    return sum(s[5] or 0 for s in named(recs, root))


def per_checkpoint_ms(run, phase, parent="deli.checkpoint"):
    """Sum (ms) of the `phase` spans directly under a `parent` span, per
    `parent` span in the interval."""
    recs = spans(run)
    if recs is None:
        return None
    roots = {s[4] for s in named(recs, parent)}
    if not roots:
        return None
    total = sum(s[2] - s[1] for s in named(recs, phase) if s[3] in roots)
    return 1000.0 * total / len(roots)
