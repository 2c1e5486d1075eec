"""Deli role checkpoints: time per checkpoint building the snapshot
(`deli.checkpoint.snapshot`: pool compaction and the per-document
state dict)."""

from benchmark.metrics._spans import per_checkpoint_ms


def read(run):
    return per_checkpoint_ms(run, "deli.checkpoint.snapshot")
