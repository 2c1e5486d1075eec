"""What every cell's run shares: finding the cell's files by name, the
device check, the set-up clock, spans, the compile count, the profiler
trace, the per-layer metric readers and the result line.

A cell is found by name in ``BENCHMARK.json``. Its configuration is the
JSON file named there, its traffic mix is ``traffic/<traffic>.json``,
the driver that runs it is ``drivers/<config "system">.py``, and each
per-layer metric is read by ``metrics/<metric name>.py`` (or by a reader
that several cells' metrics share, see `metric_reader`). Adding a
cell, a mix or a metric is adding files and entries, never editing one.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a bad cell)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def bench_spec() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(spec: dict, name: str) -> Tuple[dict, dict, dict]:
    """(cell, configuration, traffic mix) of the cell called `name`."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    config["name"] = cell["config"]
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     f"{cell['traffic']}.json"))
    traffic["name"] = cell["traffic"]
    return cell, config, traffic


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"missing benchmark file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_for(config: dict):
    return load_module(os.path.join(BENCH_DIR, "drivers",
                                    f"{config['system']}.py"),
                       f"bench_driver_{config['system']}")


def metric_reader(name: str) -> Callable:
    """The reader of metric `name`: ``metrics/<name>.py``, or else the
    reader its cells share, ``metrics/<name up to the first '.'>.py``
    (``device_idle_pct.replay`` and ``device_idle_pct.open`` are both
    read by ``device_idle_pct.py``)."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, "metrics",
                            f"{name.split('.', 1)[0]}.py")
    mod = load_module(path, "bench_metric_" + name.replace(".", "_")
                      .replace("-", "_"))
    return mod.read


CACHE_DIR = os.path.join(ROOT, ".bench_cache")


def cached_arrays(tag: str, key: Any, sources: List[str],
                  make: Callable[[], Dict[str, Any]]) -> Dict[str, Any]:
    """The arrays `make()` returns, kept in ``.bench_cache/`` inside the
    checkout under `tag` and a hash of `key` (JSON: the seed and every
    parameter `make` reads) and of the files in `sources` (the code that
    makes them), so a changed parameter or source never reads an old
    entry. Only what follows from the seed alone belongs here."""
    import hashlib

    import numpy as np

    h = hashlib.sha256(json.dumps(key, sort_keys=True).encode())
    for src in sources:
        with open(src, "rb") as f:
            h.update(hashlib.sha256(f.read()).digest())
    path = os.path.join(CACHE_DIR, f"{tag}-{h.hexdigest()[:24]}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    arrays = make()
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return arrays


def cell_metrics(spec: dict, cell: str) -> Tuple[List[dict], List[dict]]:
    """(end-to-end, per-layer) metric entries this cell reports."""
    def mine(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (mine(m) if "workloads" in m else m["moves"] in names)]
    return e2e, layer


def quantile(values, q: float) -> float:
    """The exact q-quantile (nearest rank, no interpolation)."""
    import numpy as np

    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("nan")
    k = min(v.size - 1, max(0, int(np.ceil(q * v.size)) - 1))
    return float(v[k])


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """One run of one cell. Drivers call `part` around set-up steps,
    `open_window`/`close_window` around the measured window, `span`
    around calls into the system's layers, and `check` for every number
    compared; `finish` prints the result line."""

    def __init__(self, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool,
                 t_start: Optional[float] = None,
                 require_chip: bool = True, spec: Optional[dict] = None):
        self.spec = spec if spec is not None else bench_spec()
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.require_chip = require_chip
        self.spans: List[Tuple[str, float, float]] = []
        self.facts: Dict[str, Any] = {}
        self.e2e: Dict[str, float] = {}
        self.checks: List[Tuple[str, float, float]] = []
        self.attempted = 0
        self.failed = 0
        self.device: Dict[str, Any] = {}
        self.trace_summary = None
        self._compiles = [0, 0.0]
        self._window: Optional[Tuple[float, float]] = None
        self._trace_dir: Optional[str] = None
        self._trace_t: Optional[Tuple[float, float]] = None
        self.work = tempfile.mkdtemp(prefix="bench-")

    # ------------------------------------------------------------ device

    def start_jax(self):
        """Import JAX with the persistent compile cache inside the
        checkout, check the device, and start counting compiles."""
        if self.require_chip:
            # Every program, however quick to compile, so that a run
            # after the first in a checkout compiles nothing.
            os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
                ROOT, ".jax_cache")
        import jax

        if self.require_chip:
            jax.config.update("jax_compilation_cache_dir",
                              os.environ["JAX_COMPILATION_CACHE_DIR"])
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              0)
            # No eviction: the cache holds one cell's programs, and the
            # evicting cache's per-entry access files race its writes.
            jax.config.update("jax_compilation_cache_max_size", -1)
        devs = jax.devices()
        chips = int(self.cell.get("chips", 1))
        if self.require_chip and (devs[0].platform != "tpu"
                                  or len(devs) < chips):
            raise BenchError(
                f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}

        # JAX reports a backend compile, or a program loaded from the
        # persistent cache, with this event: any inside the window fails.
        def on_event(event: str, duration: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self._compiles[0] += 1
                self._compiles[1] += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)
        return jax

    def compiles(self) -> Tuple[int, float]:
        return self._compiles[0], self._compiles[1]

    def memory_peak(self) -> None:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        self.device["memory_peak_bytes"] = int(
            stats.get("peak_bytes_in_use", 0))

    # ------------------------------------------------------------ clocks

    @contextlib.contextmanager
    def part(self, name: str):
        """A timed step of set-up, printed on its own line."""
        c0 = self.compiles()
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        n, s = self.compiles()
        log(f"setup.{name}: {dt:.3f} s (compiles {n - c0[0]}, "
            f"{s - c0[1]:.3f} s)")

    @contextlib.contextmanager
    def span(self, name: str):
        """A call into one layer: a host-clock interval, and a profiler
        annotation that names idle gaps in a traced run."""
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def open_window(self) -> float:
        t = time.perf_counter()
        self._window = (t, t)
        self._window_compiles = self.compiles()[0]
        self.setup_s = t - self.t_start
        self.e2e["setup_s"] = self.setup_s
        log(f"setup.total: {self.setup_s:.3f} s")
        return t

    def close_window(self, t_end: Optional[float] = None) -> float:
        t = time.perf_counter() if t_end is None else t_end
        self._window = (self._window[0], t)
        n = self.compiles()[0] - self._window_compiles
        log(f"window: {t - self._window[0]:.3f} s, compiles inside: {n}")
        if n:
            raise BenchError(f"{n} program(s) compiled or loaded inside the "
                             "measured window: warm-up missed a shape")
        return t

    def window_spans(self, name: str, lo: Optional[float] = None,
                     hi: Optional[float] = None) -> List[float]:
        """Durations (s) of spans called `name` that start in [lo, hi)
        (default: the measured window)."""
        lo = self._window[0] if lo is None else lo
        hi = self._window[1] if hi is None else hi
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and lo <= t0 < hi]

    # ------------------------------------------------------------- trace

    def trace_start(self) -> None:
        if not self.trace or self._trace_dir is not None:
            return
        import jax

        self._trace_dir = tempfile.mkdtemp(prefix="trace-", dir=self.work)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._trace_t = (time.perf_counter(), 0.0)

    def trace_stop(self) -> None:
        if self._trace_dir is None or self._trace_t[1]:
            return
        import jax

        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self._trace_t = (self._trace_t[0], t1)

    @property
    def trace_interval(self) -> Optional[Tuple[float, float]]:
        return self._trace_t

    def reduce_trace(self) -> None:
        if self._trace_dir is None:
            return
        from benchmark import trace as _trace

        path = _trace.find_xplane(self._trace_dir)
        self.trace_summary = _trace.summarize(
            path, window_s=self._trace_t[1] - self._trace_t[0])
        self.device["busy_s"] = self.trace_summary.busy_s
        self.device["window_s"] = self.trace_summary.window_s

    # ------------------------------------------------------------ result

    def check(self, name: str, value: float, limit: float) -> None:
        """One number compared against its limit (value <= limit)."""
        self.checks.append((name, value, limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim
                                         in self.checks)

    def result(self) -> dict:
        e2e, layer = cell_metrics(self.spec, self.cell["name"])
        metrics: Dict[str, dict] = {}
        if self.trace:
            for m in layer:
                v = metric_reader(m["name"])(self)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
        else:
            for m in e2e:
                if m["name"] in self.e2e:
                    metrics[m["name"]] = {"value": float(self.e2e[m["name"]]),
                                          "unit": m["unit"]}
        out: Dict[str, Any] = {
            "correct": self.correct, "attempted": int(self.attempted),
            "failed": int(self.failed), "metrics": metrics,
            "device": self.device,
        }
        if self.trace and self.trace_summary is not None:
            out["breakdown"] = self.trace_summary.breakdown()
        out["checks"] = {n: {"value": v, "limit": lim}
                         for n, v, lim in self.checks}
        return out

    def finish(self) -> dict:
        res = self.result()
        for n, v, lim in self.checks:
            log(f"check {n}: {v} (limit {lim})")
        print(json.dumps(res), flush=True)
        return res

    def cleanup(self) -> None:
        shutil.rmtree(self.work, ignore_errors=True)
