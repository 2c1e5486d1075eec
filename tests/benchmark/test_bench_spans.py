"""The readers of the program's own spans, on a synthetic span log: the
value each reads, and None where the log overwrote spans of the traced
interval."""

from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness
from fluidframework_tpu.utils import metrics as M

INTERVAL = (10.0, 20.0)

# (name, t0, t1, parent, span id, n): one step before the interval,
# then two steps inside it, the second with a checkpoint.
SPANS = [
    ("deli.poll", 5.0, 9.0, None, 90, 7),
    ("deli.poll", 11.0, 11.02, 1, 2, 100),
    ("deli.kernel", 11.03, 11.06, 3, 4, 100),
    ("deli.emit", 11.06, 11.08, 3, 5, None),
    ("deli.flush", 11.02, 11.08, 1, 3, None),
    ("deli.append", 11.08, 11.09, 1, 6, None),
    ("deli.pump", 11.0, 11.1, None, 1, 100),
    ("deli.poll", 12.0, 12.01, 10, 11, 50),
    ("deli.kernel", 12.01, 12.02, 12, 13, 50),
    ("deli.emit", 12.02, 12.035, 12, 14, None),
    ("deli.flush", 12.01, 12.035, 10, 12, None),
    ("deli.append", 12.035, 12.045, 10, 15, None),
    ("deli.checkpoint.snapshot", 12.045, 12.5, 16, 17, None),
    ("checkpoint.fence", 12.5, 12.6, 16, 18, None),
    ("checkpoint.encode", 12.6, 13.5, 16, 19, None),
    ("checkpoint.write", 13.5, 13.99, 16, 20, None),
    ("deli.checkpoint", 12.045, 13.99, 10, 16, None),
    ("deli.pump", 12.0, 14.0, None, 10, 50),
    ("overlay.alloc", 11.0, 11.001, None, 30, None),
    ("overlay.pad", 11.001, 11.031, None, 31, None),
    ("overlay.upload", 11.031, 11.05, None, 32, None),
]

EXPECTED = {
    "poll_ms_p95.open": 20.0,
    "append_ms_p95.open": 10.0,
    # 100 records due at 10.9 read at 11.0, 50 due at 11.5 read at 12.0.
    "queue_wait_ms_p95.open": 500.0,
    "kernel_wait_us_per_rec.open": 1e6 * 0.04 / 150,
    "deli_host_us_per_rec.open": 1e6 * (0.085 - 0.04) / 150,
    "checkpoint_snapshot_ms.open": 455.0,
    "checkpoint_fence_ms.open": 100.0,
    "checkpoint_encode_ms.open": 900.0,
    "checkpoint_write_ms.open": 490.0,
    "replay_pad_ms": 30.0,
}


def _run():
    due = np.concatenate([np.full(100, 0.9), np.full(50, 1.5)])
    return SimpleNamespace(
        trace_interval=INTERVAL,
        facts={"window": INTERVAL, "n_joins": 1000, "logs_traced": 1,
               "sched": SimpleNamespace(due=due),
               "pumps": [(10.99, 0.2, 100, 1000, 1100),
                         (11.99, 2.1, 50, 1100, 1150)]})


@pytest.fixture
def span_log():
    def install(capacity):
        log = M.SpanLog(capacity=capacity)
        for rec in SPANS:
            log.append(rec)
        M.set_span_log(log)
        return log

    old = M.get_span_log()
    yield install
    M.set_span_log(old)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value(span_log, name):
    span_log(len(SPANS))
    assert harness.metric_reader(name)(_run()) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_none_when_interval_spans_were_overwritten(span_log, name):
    # Dropping the step before the interval loses nothing it reads ...
    log = span_log(len(SPANS) - 1)
    assert log.overwritten == 1
    assert harness.metric_reader(name)(_run()) == pytest.approx(
        EXPECTED[name])
    # ... dropping one inside it makes every reader refuse.
    log = span_log(len(SPANS) - 2)
    assert log.overwritten == 2
    assert harness.metric_reader(name)(_run()) is None


def test_span_readers_need_an_interval(span_log):
    span_log(len(SPANS))
    run = SimpleNamespace(trace_interval=None, facts={})
    assert harness.metric_reader("poll_ms_p95.open")(run) is None
