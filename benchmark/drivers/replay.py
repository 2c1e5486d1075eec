"""Driver of the string-replay cells (configuration "system": "replay").

The window replays one whole sequenced op log after another through
the program's overlay engine, `core.overlay_replay.OverlayDeviceReplica`
and its fused `replay()`, until `--seconds` have passed. Each log is
timed from its host columns, through replica build and upload and the
fused replay, to the final state complete on the device. The rate is
all ops over the time from the window's start to the end of its last
log.

Correctness, after the window: every log's final device state must be
bit-identical to one log drawn from the seed, and that log's document
(text and annotations, read through the replica's public surface) must
equal the benchmark's reference replay of the same log.

A seed's log and its reference replay are kept in ``.bench_cache/``
inside the checkout, so only a seed's first run there pays for them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The code that makes a log and its reference replay from the seed.
GEN_SOURCES = [os.path.join(_BENCH, p) for p in (
    "traffic.py", "reference/string_ref.py", "reference/mergetree_ref.cpp")]


def _not_made():
    raise RuntimeError("the log maker left no log in the cache")


def _document_arrays(spans, n_keys: int):
    """(text int32[L], props int32[L, n_keys]) of `annotated_spans()`."""
    text = np.fromiter((ord(c) for c, _ in spans), np.int32, len(spans))
    props = np.full((len(spans), n_keys), -1, np.int32)
    for i, (_, p) in enumerate(spans):
        if p:
            for key, val in p.items():
                props[i, int(key[1:])] = int(val)
    return text, props


def mismatched_chars(text, props, ref_text, ref_props) -> int:
    """Positions whose character or any property differs, plus the
    difference in length."""
    n = min(len(text), len(ref_text))
    bad = (text[:n] != ref_text[:n]) | np.any(props[:n] != ref_props[:n],
                                              axis=1)
    return int(np.count_nonzero(bad)) + abs(len(text) - len(ref_text))


def _warmup_columns(cfg: dict) -> SimpleNamespace:
    """A log of the cell's shapes that is cheap to make: every op a
    caught-up one-character insert at position 0."""
    n, initial = int(cfg["ops_per_log"]), int(cfg["initial_chars"])
    seq = np.arange(1, n + 1, dtype=np.int32)
    zeros = np.zeros(n, np.int32)
    return SimpleNamespace(
        op_type=zeros, pos1=zeros, pos2=zeros, seq=seq, ref_seq=seq - 1,
        client=(np.arange(n, dtype=np.int32) % int(cfg["clients"])) + 1,
        buf_start=initial + np.arange(n, dtype=np.int32),
        ins_len=np.ones(n, np.int32), prop_key=np.full(n, -1, np.int32),
        prop_val=zeros,
        min_seq=np.maximum(0, seq - int(cfg["collab_window"])).astype(
            np.int32),
        text=np.full(initial + n, ord("a"), np.int32))


def run(run) -> None:
    from benchmark import harness, roofline
    from benchmark.harness import log
    from benchmark.reference import string_ref

    cfg, trf = run.config, run.traffic
    # The log and its reference replay follow from the seed alone: both
    # are kept in the checkout's cache, so a seed's later runs skip
    # them. A process of its own makes the log (procs/log_proc.py) while
    # this one warms up, and this one always reads the log from the
    # cache: the window then starts from the same state on every run.
    key = {"seed": run.seed, "config": cfg, "traffic": trf}
    spec_path = os.path.join(run.work, "log.json")
    with open(spec_path, "w") as f:
        json.dump({"config": cfg, "traffic": trf, "seed": run.seed,
                   "key": key, "sources": GEN_SOURCES,
                   "cache_dir": harness.CACHE_DIR}, f)
    maker = subprocess.Popen(
        [sys.executable, os.path.join(_BENCH, "procs", "log_proc.py"),
         spec_path], env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        jax = run.start_jax()
        with run.part("import"):
            from fluidframework_tpu.core.overlay_replay import (
                OverlayDeviceReplica,
            )
            from fluidframework_tpu.testing.synthetic import ColumnarStream
        eng = cfg["engine"]
        initial = int(cfg["initial_chars"])
        n_keys = int(cfg["prop_keys"])
        interpret = run.device["platform"] == "cpu"  # CPU tests only

        def one_log(stream):
            with run.span("replay.build"):
                rep = OverlayDeviceReplica(
                    stream, initial_len=initial, chunk_size=eng["chunk"],
                    window=eng["window"], n_removers=eng["removers"],
                    n_prop_keys=n_keys, interpret=interpret)
            with run.span("replay.upload"):
                rep.prepare()
            with run.span("replay.fused"):
                rep.replay()
                jax.block_until_ready((rep.table, rep.log, rep.counts,
                                       rep.cursor))
            return rep

        with run.part("warmup"):  # a log of the cell's shapes
            one_log(ColumnarStream(**vars(_warmup_columns(cfg)))) \
                .check_errors()
        with run.part("generate"):
            if maker.wait() != 0:
                raise RuntimeError("the log maker failed")
            cols = SimpleNamespace(**harness.cached_arrays(
                "lagged-log", key, GEN_SOURCES, _not_made))
    finally:
        if maker.poll() is None:
            maker.kill()
            maker.wait()
    stream = ColumnarStream(**vars(cols))

    # A traced run traces the window's first log: the profiler's
    # device buffer holds one fused replay's events, not two.
    t0 = run.open_window()
    run.trace_start()
    logs = []
    while True:
        logs.append(one_log(stream))
        run.trace_stop()
        now = time.perf_counter()
        if now - t0 >= run.seconds:
            break
    t_end = run.close_window(now)
    traced = 1 if run.trace else 0
    run.e2e["replay_ops_per_s"] = len(logs) * len(stream) / (t_end - t0)
    run.memory_peak()
    run.attempted = len(logs)

    # --- correctness: one drawn log against the reference, the rest
    # bit-identical to it on the device.
    t_post = time.perf_counter()
    k = int(np.random.default_rng(run.seed).integers(len(logs)))
    chosen = logs[k]
    leaves = lambda r: jax.tree_util.tree_leaves(  # noqa: E731
        (r.table, r.log, r.counts, r.cursor))
    base = leaves(chosen)
    differing = sum(
        1 for i, r in enumerate(logs) if i != k and not all(
            bool(jax.numpy.array_equal(a, b))
            for a, b in zip(base, leaves(r))))
    try:
        chosen.check_errors()
        text, props = _document_arrays(chosen.annotated_spans(), n_keys)
    except Exception as exc:  # a kernel error flag: nothing to compare
        log(f"replay error: {exc!r}")
        text, props = np.zeros(0, np.int32), np.zeros((0, n_keys), np.int32)
    del logs, chosen, base
    t_read = time.perf_counter() - t_post
    # The reference runs in the main thread: a native loop that
    # allocates this much runs several times slower in a second thread
    # on the chip's host.
    ref = harness.cached_arrays(
        "lagged-ref", key, GEN_SOURCES,
        lambda: dict(zip(("text", "props"),
                         string_ref.replay(cols, initial))))
    ref_text, ref_props = ref["text"], ref["props"]
    log(f"post: read back {t_read:.3f} s, reference "
        f"{time.perf_counter() - t_post - t_read:.3f} s")
    bad = mismatched_chars(text, props, ref_text, ref_props)
    run.check("chars_mismatched", bad, 0)
    run.check("logs_differing", differing, 0)
    run.failed = differing + (1 if bad else 0)

    run.facts["logs_traced"] = traced
    run.facts["overlay_bytes"] = roofline.overlay_bytes(
        len(stream), len(cols.text), len(ref_text),
        roofline.prop_runs(ref_props), n_keys)
    run.reduce_trace()
