"""Share of the traced window in which no operation ran on the device."""

from benchmark.trace import idle_pct


def read(run):
    return idle_pct(run.trace_summary)
