"""Reduction of a JAX profiler trace to device metrics.

- busy: the union of the intervals in which an operation ran on a
  device ("XLA Ops" line of each device plane), averaged over devices;
- program time: device time of each XLA program ("XLA Modules" line),
  grouped by name with the run id stripped — the name follows the
  jitted function's name (``jit_<fn>``);
- idle gaps: the holes in the busy union, each named by the innermost
  host annotation (`jax.profiler.TraceAnnotation`) that covers its
  middle, or ``host:unannotated``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_RUN_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def program_name(name: str) -> str:
    return _RUN_ID.sub("", name.strip())


def op_name(name: str) -> str:
    """An XLA op event's instruction name, without its HLO text."""
    return name.split(" = ", 1)[0].strip()


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    programs: Dict[str, float] = field(default_factory=dict)
    ops: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    n_devices: int = 0

    def program_seconds(self, pattern: str) -> float:
        """Device seconds of every program whose name contains
        `pattern` (0.0 when none ran)."""
        return sum(s for n, s in self.programs.items() if pattern in n)

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _device_planes(pd) -> list:
    """Planes of devices that ran XLA ops (a TPU host also records
    empty device planes, such as the Megascale one)."""
    return [p for p in pd.planes if p.name.startswith("/device:")
            and any(line.name == OPS_LINE for line in p.lines)]


def summarize(path: str, window_s: float) -> TraceSummary:
    """Reduce the trace at `path`; `window_s` is the traced interval as
    the host clock measured it (start to stop of the profiler)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return summarize_planes(pd, window_s)


def summarize_planes(pd, window_s: float) -> TraceSummary:
    devices = _device_planes(pd)
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    busy_total = 0.0
    first_busy: List[Tuple[float, float]] = []
    for plane in devices:
        intervals: List[Tuple[float, float]] = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    a = float(ev.start_ns)
                    intervals.append((a, a + float(ev.duration_ns)))
                    n = op_name(ev.name)
                    ops[n] = ops.get(n, 0.0) + ev.duration_ns * 1e-9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    n = program_name(ev.name)
                    programs[n] = programs.get(n, 0.0) \
                        + ev.duration_ns * 1e-9
        merged = union(intervals)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        if not first_busy:
            first_busy = merged
    n_dev = max(1, len(devices))
    summary = TraceSummary(busy_s=busy_total / n_dev, window_s=window_s,
                           programs={k: v / n_dev for k, v in
                                     programs.items()},
                           ops={k: v / n_dev for k, v in ops.items()},
                           n_devices=len(devices))
    summary.gaps = _named_gaps(pd, first_busy)
    return summary


def _host_annotations(pd) -> List[Tuple[float, float, str]]:
    """(start_ns, end_ns, name) of the benchmark's host annotations: the
    events of host thread lines whose names are dotted layer names."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if "." in name and " " not in name and not \
                        name.startswith("$"):
                    a = float(ev.start_ns)
                    out.append((a, a + float(ev.duration_ns), name))
    return out


def _named_gaps(pd, busy: List[Tuple[float, float]], keep: int = 10
                ) -> List[Tuple[str, float]]:
    """The `keep` longest holes between busy intervals, named."""
    holes = sorted(((b - a, a, b) for (_, a), (b, _) in zip(busy, busy[1:])
                    if b > a), reverse=True)[:keep]
    if not holes:
        return []
    anns = _host_annotations(pd)
    gaps = []
    for _, a, b in holes:
        mid = 0.5 * (a + b)
        best = None
        for s, e, n in anns:
            if s <= mid <= e and (best is None or e - s < best[0]):
                best = (e - s, n)
        gaps.append((best[1] if best else "host:unannotated",
                     (b - a) * 1e-9))
    return gaps


def idle_pct(summary: "TraceSummary"):
    """100 x (1 - busy / window), or None without a busy device."""
    if summary is None or summary.window_s <= 0 or summary.busy_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
