"""The reader of the deli's checkpoint flushes, on a synthetic span log:
the mean flush in the interval, and None where the program flushes no
checkpoint off its pump or the log overwrote spans of the interval."""

from types import SimpleNamespace

import pytest

from benchmark import harness
from fluidframework_tpu.utils import metrics as M

INTERVAL = (10.0, 20.0)

# (name, t0, t1, parent, span id, n): a flush before the interval, a
# cadence checkpoint in it whose flush runs on the writer thread (a
# root), and a second flush.
FLUSHES = [
    ("deli.checkpoint.flush", 9.0, 9.5, None, 1, None),
    ("deli.checkpoint.snapshot", 11.0, 11.04, 2, 3, None),
    ("checkpoint.encode", 11.04, 11.041, 2, 4, None),
    ("deli.checkpoint", 11.0, 11.042, None, 2, None),
    ("checkpoint.fence", 11.042, 11.043, 5, 6, None),
    ("checkpoint.write", 11.043, 11.082, 5, 7, None),
    ("deli.checkpoint.flush", 11.042, 11.082, None, 5, None),
    ("deli.checkpoint.flush", 12.0, 12.06, None, 8, None),
]


def _run():
    return SimpleNamespace(trace_interval=INTERVAL,
                           facts={"window": INTERVAL})


@pytest.fixture
def span_log():
    def install(spans, capacity=None):
        log = M.SpanLog(capacity=capacity or len(spans))
        for rec in spans:
            log.append(rec)
        M.set_span_log(log)
        return log

    old = M.get_span_log()
    yield install
    M.set_span_log(old)


def read(run):
    return harness.metric_reader("checkpoint_flush_ms.open")(run)


def test_mean_flush_in_the_interval(span_log):
    span_log(FLUSHES)
    assert read(_run()) == pytest.approx(50.0)


def test_none_where_no_flush_ran_off_the_pump(span_log):
    # The inline checkpoint: the store's phases under `deli.checkpoint`.
    span_log([s for s in FLUSHES if s[0] != "deli.checkpoint.flush"])
    assert read(_run()) is None


def test_none_when_interval_spans_were_overwritten(span_log):
    log = span_log(FLUSHES, capacity=len(FLUSHES) - 1)
    assert log.overwritten == 1  # the flush before the interval
    assert read(_run()) == pytest.approx(50.0)
    log = span_log(FLUSHES, capacity=len(FLUSHES) - 2)
    assert read(_run()) is None


def test_listed_for_the_open_cell_only():
    spec = harness.bench_spec()
    (m,) = [m for m in spec["per_layer"]
            if m["name"] == "checkpoint_flush_ms.open"]
    assert m["workloads"] == ["sequence-open"]
    assert m["moves"] == "seq_latency_p95_ms"
    assert m["layer"] == "deli role checkpoints"
