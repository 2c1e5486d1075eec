"""Deli role checkpoints: time per checkpoint in the store's
`checkpoint.fence` phase (program spans directly under
`deli.checkpoint`)."""

from benchmark.metrics._spans import per_checkpoint_ms


def read(run):
    return per_checkpoint_ms(run, "checkpoint.fence")
