"""The string reference against the program's scalar oracle, and its
control (annotations resolved at the head) against the reference."""

import numpy as np
import pytest

from benchmark import traffic as gen
from benchmark.reference import string_ref

from test_bench_traffic import small_string_config


def _digest(text, props):
    from fluidframework_tpu.testing.digest import state_digest

    return state_digest([
        (chr(int(c)), {f"k{k}": int(v) for k, v in enumerate(row)
                       if v != string_ref.ABSENT} or None)
        for c, row in zip(text, props)])


@pytest.mark.parametrize("seed", [4, 2**31 + 9])
def test_string_reference_digest_equals_the_scalar_oracle(seed):
    from fluidframework_tpu.core.mergetree import replay_passive

    cfg, trf = small_string_config(1200)
    log = gen.lagged_log(cfg, trf, seed)
    text, props = string_ref.replay(log, 64)
    oracle = replay_passive(
        _messages(log), initial="".join(map(chr, log.text[:64])))
    assert _digest(text, props) == _state(oracle)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_string_control_differs_from_the_reference(seed):
    from benchmark.drivers import replay

    cfg, trf = small_string_config(1500)
    log = gen.lagged_log(cfg, trf, seed)
    text, props = string_ref.replay(log, 64)
    ctext, cprops = string_ref.replay(log, 64, control=True)
    assert replay.mismatched_chars(ctext, cprops, text, props) > 0
    assert replay.mismatched_chars(text, props, text, props) == 0


def _messages(log):
    from fluidframework_tpu.testing.synthetic import ColumnarStream

    return ColumnarStream(**vars(log)).as_messages()


def _state(oracle):
    from fluidframework_tpu.testing.digest import state_digest

    return state_digest(oracle.annotated_spans())


def test_mismatch_counts_length_and_properties():
    from benchmark.drivers import replay

    t = np.array([97, 98, 99], np.int32)
    p = np.full((3, 8), -1, np.int32)
    q = p.copy()
    q[1, 3] = 5
    assert replay.mismatched_chars(t, q, t, p) == 1
    assert replay.mismatched_chars(t[:2], p[:2], t, p) == 1
