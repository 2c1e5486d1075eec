"""Trace reduction, on a small trace recorded on a TPU v5e chip: two
jitted programs (`probe_step`, `probe_other`) run three times, each
round after a 10 ms host wait annotated `probe.host_wait`."""

import os

import pytest

from benchmark import harness, trace

SMALL = os.path.join(harness.BENCH_DIR, "testdata", "small_trace.xplane.pb")
WINDOW_S = 0.0372


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(SMALL, WINDOW_S)


def test_programs_are_grouped_by_jitted_name(summary):
    assert set(summary.programs) == {"jit_probe_step", "jit_probe_other"}
    assert summary.program_seconds("probe_step") > 0
    assert summary.program_seconds("no_such_program") == 0.0
    assert summary.n_devices == 1


def test_busy_is_the_union_of_op_intervals(summary):
    # The ops of both programs, never more than the programs' own time.
    assert 0 < summary.busy_s <= sum(summary.programs.values()) + 1e-9
    assert summary.busy_s < summary.window_s
    assert 99.0 < trace.idle_pct(summary) < 100.0


def test_breakdown_names_ops_and_gaps(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert all(" = " not in name for name, _ in b["device_ops"])
    gaps = b["idle_gaps"]
    assert 0 < len(gaps) <= 10
    # The two 10 ms waits between rounds are the longest gaps.
    assert [n for n, _ in gaps[:2]] == ["probe.host_wait"] * 2
    assert all(s > 0.009 for _, s in gaps[:2])


@pytest.mark.parametrize("intervals,merged", [
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(0, 2), (1, 3), (5, 6)], [(0, 3), (5, 6)]),
    ([(4, 5), (0, 10)], [(0, 10)]),
    ([], []),
])
def test_union(intervals, merged):
    assert trace.union(intervals) == merged


def test_idle_pct_without_a_busy_device():
    assert trace.idle_pct(None) is None
    assert trace.idle_pct(trace.TraceSummary(busy_s=0.0, window_s=1.0)) \
        is None
