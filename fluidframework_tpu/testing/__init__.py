"""Test harnesses: seeded multi-client op farms and mock plumbing.

Mirrors the roles of the reference's test-runtime-utils mocks
(packages/runtime/test-runtime-utils/src/mocks.ts) and the merge-tree
farm runner (packages/dds/merge-tree/src/test/mergeTreeOperationRunner.ts):
drive N collaborating clients with a seeded random op mix through an
in-proc sequencer, interleaving delivery, and assert all replicas
converge to identical state.
"""

from .farm import FarmConfig, run_sharedstring_farm, random_op_for
from .chaos import (
    ChaosConfig,
    ChaosResult,
    run_chaos,
    stream_digest,
)

__all__ = [
    "ChaosConfig",
    "ChaosResult",
    "FarmConfig",
    "random_op_for",
    "run_chaos",
    "run_sharedstring_farm",
    "stream_digest",
]
