"""A run of each cell at a small size on the CPU, past the harness's look
for a chip: sound, it comes out correct; with the timed path broken
underneath, `correct` comes out false."""

import numpy as np
import pytest

from benchmark import harness

REPLAY = ("replay-lagged",
          {"ops_per_log": 600, "clients": 16, "collab_window": 64,
           "engine": {"chunk": 64, "window": 1024, "removers": 8}}, {})
DELI = ("sequence-open", {"docs": 48, "clients": 6, "batch": 1024},
        {"rate_per_s": 1500.0})


def run_cell(cell_name, cfg_up, trf_up, seconds=0.6, seed=2**31 + 21):
    spec = harness.bench_spec()
    cell, cfg, trf = harness.find_cell(spec, cell_name)
    cfg.update(cfg_up)
    trf.update(trf_up)
    run = harness.Run(cell, cfg, trf, seed, seconds, False,
                      require_chip=False, spec=spec)
    try:
        harness.driver_for(cfg).run(run)
        return run.result()
    finally:
        run.cleanup()


def checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


@pytest.mark.parametrize("cell", [REPLAY, DELI], ids=lambda c: c[0])
def test_sound_run_is_correct(cell):
    res = run_cell(*cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


def test_replay_second_run_of_a_seed_reads_the_cache():
    import os

    first = run_cell(*REPLAY)
    kept = sorted(os.listdir(harness.CACHE_DIR))
    second = run_cell(*REPLAY)
    assert [f.split("-")[:2] for f in kept] == [["lagged", "log"],
                                                ["lagged", "ref"]]
    assert sorted(os.listdir(harness.CACHE_DIR)) == kept
    assert first["correct"] and second["correct"], second["checks"]


def test_replay_op_left_unapplied_is_caught(monkeypatch):
    from fluidframework_tpu.core.overlay_replay import OverlayDeviceReplica
    from fluidframework_tpu.ops.mergetree_kernel import OP_INSERT, OP_NOOP

    orig = OverlayDeviceReplica.prepare_host

    def skip_last_insert(self):
        orig(self)
        k = int(np.flatnonzero(self._host.op_type == OP_INSERT)[-1])
        self._host.op_type[k] = OP_NOOP

    monkeypatch.setattr(OverlayDeviceReplica, "prepare_host",
                        skip_last_insert)
    res = run_cell(*REPLAY)
    assert not res["correct"]
    assert checks(res)["chars_mismatched"] > 0


def test_replay_one_log_altered_is_caught(monkeypatch):
    import jax

    from fluidframework_tpu.core.overlay_replay import OverlayDeviceReplica

    orig = OverlayDeviceReplica.replay
    calls = [0]

    def alter_third(self, limit_chunks=None):
        orig(self, limit_chunks)
        calls[0] += 1
        if calls[0] == 3:  # the warm-up is the first call
            counts = np.array(self.counts)
            counts[0] += 1
            self.counts = jax.device_put(counts)

    monkeypatch.setattr(OverlayDeviceReplica, "replay", alter_third)
    res = run_cell(*REPLAY)
    assert not res["correct"]
    assert checks(res)["logs_differing"] > 0


def _patch_verdicts(monkeypatch, alter):
    from fluidframework_tpu.server.deli_kernel import PackedDeliCore

    orig = PackedDeliCore.run

    def run(self, as_arrays=False):
        res = orig(self, as_arrays)
        if len(res.seq):
            if as_arrays:
                res.seq, res.skipped = res.seq.copy(), res.skipped.copy()
            alter(res)
        return res

    monkeypatch.setattr(PackedDeliCore, "run", run)


def test_deli_stamp_altered_is_caught(monkeypatch):
    def bump(res):
        res.seq[0] += 1

    _patch_verdicts(monkeypatch, bump)
    res = run_cell(*DELI)
    assert not res["correct"]
    c = checks(res)
    assert c["stamps_mismatched"] + c["joins_mismatched"] > 0


def test_deli_half_a_pump_left_out_is_caught(monkeypatch):
    def drop_half(res):
        n = len(res.seq)
        for i in range(n // 2, n):
            res.skipped[i] = True

    _patch_verdicts(monkeypatch, drop_half)
    res = run_cell(*DELI)
    assert not res["correct"]
    c = checks(res)
    assert c["records_missing"] + c["joins_mismatched"] > 0
