"""Peaks table and the byte counts of the two roofline metrics."""

import numpy as np
import pytest

from benchmark import roofline


def test_known_device_kind():
    p = roofline.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flop_per_s"] == 197e12


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError):
        roofline.peak(kind)


def test_overlay_bytes_follow_the_shapes():
    base = roofline.overlay_bytes(1000, 5000, 3000, 10, 8)
    assert roofline.overlay_bytes(2000, 5000, 3000, 10, 8) - base == 40000
    assert roofline.overlay_bytes(1000, 5001, 3000, 10, 8) - base == 4
    assert roofline.overlay_bytes(1000, 5000, 3001, 10, 8) - base == 4
    assert roofline.overlay_bytes(1000, 5000, 3000, 11, 8) - base == 36


def test_sequencer_bytes_follow_the_shapes():
    base = roofline.sequencer_bytes(100, 10, 64)
    assert roofline.sequencer_bytes(101, 10, 64) - base == 28
    assert roofline.sequencer_bytes(100, 11, 64) - base == 2 * (8 + 64 * 9)
    assert roofline.sequencer_bytes(0, 0, 64) == 0


def test_roofline_pct():
    # 819 MB in 1 ms at 819 GB/s is the whole roofline.
    assert roofline.roofline_pct(819e6, 1.0, "TPU v5 lite") == \
        pytest.approx(0.1)
    assert roofline.roofline_pct(819e6, 1e-3, "TPU v5 lite") == \
        pytest.approx(100.0)


def test_prop_runs():
    p = np.full((5, 8), -1, np.int32)
    assert roofline.prop_runs(p) == 1
    p[2:4, 1] = 3
    assert roofline.prop_runs(p) == 3
    assert roofline.prop_runs(p[:0]) == 0
