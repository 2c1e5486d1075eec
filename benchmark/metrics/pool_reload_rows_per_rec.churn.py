"""Sequencer pool residency: state rows the pool reloaded over the
window (the role's `deli_pool_loads_total` counter) per raw record the
role consumed in it."""


def read(run):
    loads = run.facts.get("pool_loads_window")
    n = run.facts.get("records_window")
    if loads is None or not n:
        return None
    return loads / n
