"""Compile the main path's kernels for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`): what it
refuses — a slice not aligned to the tiling, too much fast memory, a
program that does not fit — would fail on the chip. These tests
compile the serving path's kernels at the widths `chip_smoke.py` runs
them (the 1M-op replay geometry and BASELINE config 5's 10,000 docs x
64 clients), so every PR is checked against the chip's compiler at no
chip time. Nothing runs: a compile that passes is not a chip run.

Only one process may load the TPU library, and it keeps it until it
exits. So the topology is described inside a module-scoped fixture
(never at import, never autouse), and every such test lives in this
one file: pytest-xdist's `--dist loadfile` then hands them all to one
worker. The persistent compilation cache is off around them (a
described-device entry cannot be read back without a chip).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

WINDOW, REMOVERS, PROP_KEYS, CHUNK = 2048, 24, 8, 256
DOCS, CLIENTS, STEPS = 10_000, 64, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(build, sharding):
    """Shapes of what `build()` returns, placed on `sharding`."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding),
        jax.eval_shape(build),
    )


def _op_batch(n_ops: int):
    from fluidframework_tpu.ops.mergetree_kernel import OpBatch

    def build():
        col = jnp.zeros(n_ops, jnp.int32)
        pairs = jnp.zeros((n_ops, 1), jnp.int32)
        return OpBatch(op_type=col, pos1=col, pos2=col, seq=col,
                       ref_seq=col, client=col, buf_start=col,
                       ins_len=col, prop_keys=pairs, prop_vals=pairs)

    return build


def _seq_inputs():
    from fluidframework_tpu.ops.sequencer_kernel import SeqBatch, make_state

    def state():
        return make_state(DOCS, CLIENTS)

    def batch():
        col = jnp.zeros((DOCS, STEPS), jnp.int32)
        return SeqBatch(kind=col, client=col, client_seq=col, ref_seq=col)

    return state, batch


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_overlay_apply_chunk_compiles_for_v5e(one_chip):
    from fluidframework_tpu.ops.overlay_pallas import (
        OVERLAY_KERNEL,
        make_overlay_table,
        overlay_apply_chunk,
    )

    table = _specs(lambda: make_overlay_table(
        WINDOW, REMOVERS, PROP_KEYS, settled_len=64), one_chip)
    ops = _specs(_op_batch(CHUNK), one_chip)
    compiled = overlay_apply_chunk.lower(table, ops).compile()
    assert _is_kernel(compiled)
    # The kernel's instruction carries its pinned name (trace readers).
    assert f"%{OVERLAY_KERNEL}." in compiled.as_text()


def test_sequence_batch_compiles_for_v5e(one_chip):
    from fluidframework_tpu.ops.sequencer_kernel import sequence_batch_jit

    state, batch = _seq_inputs()
    compiled = sequence_batch_jit.lower(
        _specs(state, one_chip), _specs(batch, one_chip)
    ).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("rows", [8, 16384])
def test_pool_load_compiles_for_v5e(one_chip, rows):
    """The pool's row reload at its smallest and largest bucket over the
    churning partition's 16,384-row, 16-column pool."""
    from fluidframework_tpu.ops.sequencer_kernel import make_state
    from fluidframework_tpu.server.deli_kernel import (
        SEQ_POOL_LOAD_PROGRAM,
        _load_rows,
    )

    state = _specs(lambda: make_state(16384, 16), one_chip)
    idx = _specs(lambda: jnp.zeros(rows, jnp.int32), one_chip)
    vals = [_specs(lambda a=a: jnp.zeros((rows,) + a.shape[1:], a.dtype),
                   one_chip) for a in jax.eval_shape(
                       lambda: make_state(16384, 16))]
    compiled = _load_rows.lower(state, idx, *vals).compile()
    assert SEQ_POOL_LOAD_PROGRAM in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_sharded_sequence_fn_compiles_for_v5e_2x2(topo):
    from fluidframework_tpu.ops.sequencer_kernel import (
        no_aborts,
        sharded_sequence_fn,
    )

    mesh = Mesh(np.asarray(topo.devices[:4]), ("docs",))
    docs = NamedSharding(mesh, PartitionSpec("docs"))
    state, batch = _seq_inputs()
    fn = sharded_sequence_fn(mesh)
    compiled = fn.lower(
        _specs(state, docs), _specs(lambda: no_aborts(DOCS), docs),
        _specs(batch, docs),
        _specs(lambda: jnp.zeros((DOCS, STEPS), jnp.int32), docs),
    ).compile()
    # Docs are embarrassingly parallel: no collective in the scan.
    assert "all-reduce" not in compiled.as_text()


def test_mergetree_pallas_chunk_compiles_for_v5e(one_chip):
    from fluidframework_tpu.ops.mergetree_kernel import make_table
    from fluidframework_tpu.ops.mergetree_pallas import apply_chunk

    table = _specs(lambda: make_table(16384, REMOVERS, PROP_KEYS),
                   one_chip)
    ops = _specs(_op_batch(CHUNK), one_chip)
    compiled = apply_chunk.lower(table, ops).compile()
    assert _is_kernel(compiled)
