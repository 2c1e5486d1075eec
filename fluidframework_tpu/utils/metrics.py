"""Process-wide metrics: counters, gauges, fixed-bucket histograms.

The reference service emits structured per-operation metrics through
Lumberjack (server/routerlicious/packages/services-telemetry) and the
client stamps `ISequencedDocumentMessage.traces` for per-stage
latency. `utils.telemetry` mirrors the *event* side of that; this
module is the *aggregation* side: a lock-safe `MetricsRegistry` of
counters, gauges, and fixed-bucket histograms, labeled by
(role, doc, stage, ...), with Prometheus-text and JSON snapshot
encoders.

Design constraints (the observability contract of ISSUE 3):

- **Cheap** — instruments are plain attribute bumps under one lock;
  hot paths cache instrument objects at construction and record
  per-pump aggregates, never per-record work on the kernel path. A
  `set_enabled(False)` switch swaps the default registry for a no-op
  `NullRegistry`.
- **Deterministic-safe** — metrics are observational only: nothing
  here feeds back into sequencing, so stamped output and chaos golden
  digests are unchanged with instrumentation on.
- **Per-process with explicit merge** — registries do NOT share state
  across processes; supervised children snapshot their registry into
  their heartbeat file and the supervisor folds the snapshots with
  `MetricsRegistry.merge` (counters/histograms add, gauges last-write).

`span` times one layer boundary three ways at once: a
`jax.profiler.TraceAnnotation` (so a profiler trace names the host
work between device events), the `span_ms{span=...}` histogram family,
and a bounded process-wide `SpanLog` of (name, start, end, parent id,
span id, records) tuples.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from bisect import bisect_left
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "DEFAULT_LATENCY_BUCKETS_MS",
    "SPAN_BUCKETS_MS",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "Span",
    "SpanLog",
    "dump_snapshot_line",
    "format_report",
    "get_flight_recorder",
    "get_registry",
    "get_span_log",
    "histogram_quantile",
    "histogram_stats",
    "merge_snapshots",
    "named_program",
    "set_enabled",
    "set_flight_recorder",
    "set_registry",
    "set_span_log",
    "slo_summary",
    "span",
]

# Fixed latency buckets (ms): sub-millisecond ticks through 10s tails.
DEFAULT_LATENCY_BUCKETS_MS = (
    0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0,
)


def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class Counter:
    """Monotone counter."""

    __slots__ = ("name", "labels", "value", "_lock")

    def __init__(self, name: str, labels: Dict[str, str], lock):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self._lock = lock

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """Point-in-time value."""

    __slots__ = ("name", "labels", "value", "_lock")

    def __init__(self, name: str, labels: Dict[str, str], lock):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self._lock = lock

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)


class Histogram:
    """Fixed-bucket histogram (Prometheus `le`-inclusive upper bounds
    plus an implicit +Inf overflow bucket)."""

    __slots__ = ("name", "labels", "bounds", "counts", "sum", "count",
                 "_lock")

    def __init__(self, name: str, labels: Dict[str, str],
                 bounds: Tuple[float, ...], lock):
        self.name = name
        self.labels = labels
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(set(self.bounds)):
            raise ValueError(f"histogram bounds must be sorted unique: {bounds}")
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        self._lock = lock

    def observe(self, v: float) -> None:
        # bisect_left: v == bound lands IN that bucket (le-inclusive).
        i = bisect_left(self.bounds, v)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1


class _NullInstrument:
    """Shared no-op instrument (disabled-registry mode)."""

    __slots__ = ()
    value = 0.0
    sum = 0.0
    count = 0

    def inc(self, n: float = 1.0) -> None:
        pass

    def dec(self, n: float = 1.0) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass


_NULL_INSTRUMENT = _NullInstrument()


class NullRegistry:
    """Registry whose instruments do nothing — `set_enabled(False)`
    makes `get_registry()` return one, so instrumented components pay a
    single no-op call per record/pump."""

    namespace = "fluid"

    def counter(self, name: str, **labels) -> _NullInstrument:
        return _NULL_INSTRUMENT

    def gauge(self, name: str, **labels) -> _NullInstrument:
        return _NULL_INSTRUMENT

    def histogram(self, name: str, buckets=None, **labels) -> _NullInstrument:
        return _NULL_INSTRUMENT

    def snapshot(self) -> dict:
        return {"counters": [], "gauges": [], "histograms": []}

    def merge(self, snap: dict) -> None:
        pass

    def to_prometheus(self) -> str:
        return ""

    def reset(self) -> None:
        pass


class MetricsRegistry:
    """Lock-safe instrument registry with deterministic snapshots.

    One instance per process; instruments are create-or-return by
    (kind, name, labels) so call sites can either cache the instrument
    (hot paths) or re-look it up (cold paths)."""

    def __init__(self, namespace: str = "fluid"):
        self.namespace = namespace
        self._lock = threading.Lock()
        self._instruments: Dict[tuple, Any] = {}

    # ------------------------------------------------------ instruments

    def _get(self, kind: str, cls, name: str, labels: Dict[str, Any],
             *args):
        key = (kind, name, _label_key(labels))
        with self._lock:
            inst = self._instruments.get(key)
            if inst is None:
                for other_kind in ("counter", "gauge", "histogram"):
                    if other_kind != kind and any(
                        k[0] == other_kind and k[1] == name
                        for k in self._instruments
                    ):
                        raise ValueError(
                            f"metric {name!r} already registered as "
                            f"{other_kind}, not {kind}"
                        )
                inst = cls(name, dict(key[2]), *args, self._lock)
                self._instruments[key] = inst
            return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get("counter", Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get("gauge", Gauge, name, labels)

    def histogram(self, name: str,
                  buckets: Optional[Iterable[float]] = None,
                  **labels) -> Histogram:
        bounds = tuple(buckets) if buckets else DEFAULT_LATENCY_BUCKETS_MS
        h = self._get("histogram", Histogram, name, labels, bounds)
        if h.bounds != tuple(float(b) for b in bounds):
            raise ValueError(
                f"histogram {name!r} re-registered with different buckets"
            )
        return h

    def reset(self) -> None:
        with self._lock:
            self._instruments.clear()

    # --------------------------------------------------------- snapshot

    def snapshot(self) -> dict:
        """JSON-able, deterministic (sorted) state of every instrument.

        Histogram fields are copied UNDER the instruments' shared lock:
        counts, sum and count must come from one instant, or a
        concurrent `observe` between the field reads yields a torn
        snapshot whose explicit sum/count disagree with its buckets —
        and every downstream consumer (merge across process snapshots,
        quantile estimation, the mean column) silently inherits the
        skew."""
        counters, gauges, histograms = [], [], []
        with self._lock:
            items = sorted(self._instruments.items())
            for (kind, name, labels), inst in items:
                entry = {"name": name, "labels": dict(labels)}
                if kind == "counter":
                    counters.append({**entry, "value": inst.value})
                elif kind == "gauge":
                    gauges.append({**entry, "value": inst.value})
                else:
                    h = {
                        **entry, "buckets": list(inst.bounds),
                        "counts": list(inst.counts), "sum": inst.sum,
                        "count": inst.count,
                    }
                    histograms.append(h)
        for h in histograms:
            if h["count"] > 0:
                # Quantiles ride the snapshot (the /slo surface), but
                # they are DERIVED — merge() folds buckets/sum/count
                # and recomputes; None marks an estimate beyond the
                # last finite bucket (JSON has no Infinity).
                h["quantiles"] = {
                    q: (None if v == float("inf") else round(v, 4))
                    for q, v in (
                        ("p50", histogram_quantile(h, 0.5)),
                        ("p95", histogram_quantile(h, 0.95)),
                        ("p99", histogram_quantile(h, 0.99)),
                    )
                }
                h["mean"] = round(h["sum"] / h["count"], 4)
        return {"counters": counters, "gauges": gauges,
                "histograms": histograms}

    def merge(self, snap: dict) -> None:
        """Fold a child's `snapshot()` in: counters and histogram
        buckets ADD, gauges take the snapshot's value (children report
        disjoint label sets — e.g. role=... — so last-write is safe)."""
        for c in snap.get("counters", ()):
            self.counter(c["name"], **c["labels"]).inc(c["value"])
        for g in snap.get("gauges", ()):
            self.gauge(g["name"], **g["labels"]).set(g["value"])
        for h in snap.get("histograms", ()):
            inst = self.histogram(h["name"], buckets=h["buckets"],
                                  **h["labels"])
            with inst._lock:
                for i, n in enumerate(h["counts"]):
                    inst.counts[i] += n
                inst.sum += h["sum"]
                inst.count += h["count"]

    # ------------------------------------------------------- exposition

    @staticmethod
    def _fmt_labels(labels: Dict[str, str], extra: str = "") -> str:
        parts = []
        for k, v in sorted(labels.items()):
            esc = str(v).replace("\\", "\\\\").replace('"', '\\"')
            parts.append('%s="%s"' % (k, esc))
        if extra:
            parts.append(extra)
        return "{" + ",".join(parts) + "}" if parts else ""

    @staticmethod
    def _fmt_num(v: float) -> str:
        return str(int(v)) if float(v).is_integer() else repr(float(v))

    def to_prometheus(self) -> str:
        """Prometheus text exposition format (one scrape body)."""
        snap = self.snapshot()
        out: List[str] = []
        seen_type: set = set()

        def full(name: str) -> str:
            return f"{self.namespace}_{name}" if self.namespace else name

        for kind in ("counters", "gauges"):
            ptype = "counter" if kind == "counters" else "gauge"
            for m in snap[kind]:
                fname = full(m["name"])
                if fname not in seen_type:
                    out.append(f"# TYPE {fname} {ptype}")
                    seen_type.add(fname)
                out.append(
                    f"{fname}{self._fmt_labels(m['labels'])} "
                    f"{self._fmt_num(m['value'])}"
                )
        qlines: List[str] = []
        for m in snap["histograms"]:
            fname = full(m["name"])
            if fname not in seen_type:
                out.append(f"# TYPE {fname} histogram")
                seen_type.add(fname)
            cum = 0
            for bound, n in zip(
                list(m["buckets"]) + ["+Inf"], m["counts"]
            ):
                cum += n
                le = bound if bound == "+Inf" else self._fmt_num(bound)
                le_label = 'le="%s"' % le
                out.append(
                    f"{fname}_bucket"
                    f"{self._fmt_labels(m['labels'], le_label)} {cum}"
                )
            out.append(
                f"{fname}_sum{self._fmt_labels(m['labels'])} "
                f"{self._fmt_num(m['sum'])}"
            )
            out.append(
                f"{fname}_count{self._fmt_labels(m['labels'])} {m['count']}"
            )
            # Bucket-interpolated quantile estimates as a sibling gauge
            # family (`<name>_q{quantile=...}`) — NOT extra `<name>`
            # series, which a strict parser would reject under TYPE
            # histogram. Buffered and appended AFTER the histogram
            # loop: a metric family's samples must stay one contiguous
            # group, and a histogram name with several label sets
            # would otherwise interleave `<name>` and `<name>_q`.
            # Estimates beyond the last finite bucket are omitted
            # rather than faked.
            if m["count"] > 0:
                qname = f"{fname}_q"
                for q in (0.5, 0.95, 0.99):
                    v = histogram_quantile(m, q)
                    if v == float("inf"):
                        continue
                    if qname not in seen_type:
                        qlines.append(f"# TYPE {qname} gauge")
                        seen_type.add(qname)
                    qlabel = 'quantile="%s"' % q
                    qlines.append(
                        f"{qname}"
                        f"{self._fmt_labels(m['labels'], qlabel)}"
                        f" {self._fmt_num(round(v, 4))}"
                    )
        out.extend(qlines)
        return "\n".join(out) + ("\n" if out else "")


# ---------------------------------------------------------------------------
# default registry + enable switch
# ---------------------------------------------------------------------------

_default_registry: Any = MetricsRegistry()
_null_registry = NullRegistry()
_enabled = True


def get_registry():
    """The process's default registry (a `NullRegistry` while
    `set_enabled(False)` is in effect)."""
    return _default_registry if _enabled else _null_registry


def set_registry(registry) -> Any:
    """Swap the default registry; returns the previous one (bench
    isolation: fresh registry per measured run)."""
    global _default_registry
    old = _default_registry
    _default_registry = registry
    return old


def set_enabled(flag: bool) -> bool:
    """Toggle instrumentation process-wide. Components that cached
    instruments keep them; components constructed while disabled get
    no-ops. Returns the previous setting."""
    global _enabled
    old = _enabled
    _enabled = bool(flag)
    return old


# ---------------------------------------------------------------------------
# snapshot files + reporting (tools/metrics_report.py backend)
# ---------------------------------------------------------------------------


def dump_snapshot_line(path: str, snapshot: dict, **meta) -> None:
    """Append one JSONL line `{"t": ..., **meta, "snapshot": ...}` —
    the run-artifact form `tools/metrics_report.py` renders."""
    with open(path, "a") as f:
        f.write(json.dumps({"t": time.time(), **meta,
                            "snapshot": snapshot}) + "\n")


def merge_snapshots(snapshots: Iterable[dict]) -> MetricsRegistry:
    """Fold snapshots (or metrics.jsonl line dicts) into one registry."""
    reg = MetricsRegistry()
    for snap in snapshots:
        reg.merge(snap.get("snapshot", snap))
    return reg


def histogram_quantile(h: dict, q: float) -> float:
    """Estimate quantile `q` from a snapshot histogram entry by linear
    interpolation within its bucket; `inf` if it lands in overflow."""
    total = h["count"]
    if total <= 0:
        return 0.0
    target = q * total
    bounds = h["buckets"]
    cum = 0
    for i, n in enumerate(h["counts"]):
        if n == 0:
            continue
        if cum + n >= target:
            if i >= len(bounds):
                return float("inf")
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i]
            return lo + (hi - lo) * max(0.0, target - cum) / n
        cum += n
    return float("inf")


def histogram_stats(h: dict) -> dict:
    """The SLO-facing summary of one snapshot histogram entry:
    count, mean (exact, from the explicit sum), and bucket-interpolated
    p50/p95/p99. Quantiles landing beyond the last finite bucket come
    back as ``float("inf")`` — the caller decides how to render that
    (the JSON surfaces map it to None)."""
    count = int(h.get("count", 0))
    if count <= 0:
        return {"count": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0,
                "p99": 0.0}
    return {
        "count": count,
        "mean": h["sum"] / count,
        "p50": histogram_quantile(h, 0.5),
        "p95": histogram_quantile(h, 0.95),
        "p99": histogram_quantile(h, 0.99),
    }


def slo_summary(snap: dict) -> dict:
    """The `/slo` endpoint body: every histogram with observations,
    reduced to its quantile summary (JSON-safe — beyond-last-bucket
    estimates become None). Counters/gauges are generally omitted
    (they live on `/metrics.json`) with one exception: admission
    FEEDBACK counters (``ingress_*`` — nacks by reason, throttles,
    admits) ride along under ``"counters"``, because an operator
    reading tail quantiles needs to see load the front door REFUSED
    next to the latency of the load it admitted — a clean p99 over a
    throttled stream is not a clean p99."""
    out = []
    for h in snap.get("histograms", ()):
        if not h.get("count"):
            continue
        stats = histogram_stats(h)
        out.append({
            "name": h["name"], "labels": dict(h.get("labels") or {}),
            "count": stats["count"],
            "mean": round(stats["mean"], 4),
            **{q: (None if stats[q] == float("inf")
                   else round(stats[q], 4))
               for q in ("p50", "p95", "p99")},
        })
    body: Dict[str, Any] = {"histograms": out}
    ingress = [
        {"name": c["name"], "labels": dict(c.get("labels") or {}),
         "value": c["value"]}
        for c in snap.get("counters", ())
        if str(c.get("name", "")).startswith("ingress_")
        and c.get("value")
    ]
    if ingress:
        body["counters"] = ingress
    return body


def _fmt_ms(v: float) -> str:
    if v == float("inf"):
        return ">max"
    if v >= 100:
        return f"{v:.0f}"
    return f"{v:.2f}"


def format_report(snapshots: Iterable[dict]) -> str:
    """Human table over merged snapshots: per-stage latency histograms
    (count/mean/p50/p90/p99), then counters and gauges."""
    snap = merge_snapshots(snapshots).snapshot()
    lines: List[str] = []

    def label_str(labels: dict) -> str:
        return ",".join(f"{k}={v}" for k, v in sorted(labels.items()))

    hists = [h for h in snap["histograms"] if h["count"] > 0]
    if hists:
        lines.append(
            f"{'histogram':<26} {'labels':<34} {'count':>9} "
            f"{'mean':>9} {'p50':>9} {'p95':>9} {'p99':>9}"
        )
        for h in hists:
            stats = histogram_stats(h)
            lines.append(
                f"{h['name']:<26} {label_str(h['labels']):<34} "
                f"{stats['count']:>9} {_fmt_ms(stats['mean']):>9} "
                f"{_fmt_ms(stats['p50']):>9} "
                f"{_fmt_ms(stats['p95']):>9} "
                f"{_fmt_ms(stats['p99']):>9}"
            )
    rows = [("counter", c) for c in snap["counters"] if c["value"]]
    rows += [("gauge", g) for g in snap["gauges"]]
    if rows:
        if hists:
            lines.append("")
        lines.append(f"{'kind':<8} {'metric':<30} {'labels':<34} {'value':>12}")
        for kind, m in rows:
            lines.append(
                f"{kind:<8} {m['name']:<30} {label_str(m['labels']):<34} "
                f"{MetricsRegistry._fmt_num(m['value']):>12}"
            )
    return "\n".join(lines) if lines else "(no metrics recorded)"


# ---------------------------------------------------------------------------
# slow-op flight recorder (the /traces surface)
# ---------------------------------------------------------------------------


class FlightRecorder:
    """Bounded ring buffer of SLOW-op span records.

    Histograms answer "what is p99"; they cannot answer "which ops
    were the p99" — a tail-latency regression report needs the exact
    slow ops attached (doc/client/seq plus every stage timestamp).
    This keeps the last `capacity` spans whose end-to-end latency
    either exceeds a fixed `threshold_ms` or, when none is set, the
    ROLLING p99 of the last `window` observations — so the buffer
    always holds the current tail, never a firehose.

    Two-phase API so the hot path never builds a span dict it is about
    to drop:

        if recorder.note(e2e_ms):          # updates the rolling window
            recorder.add(e2e_ms, {...})    # admit the full span

    Observational only and lock-safe; `snapshot()` returns the spans
    oldest-first, each as ``{"e2e_ms": ..., **span}``.
    """

    RECALC_EVERY = 32  # rolling-p99 refresh cadence (observations)

    def __init__(self, capacity: int = 128,
                 threshold_ms: Optional[float] = None,
                 window: int = 512, min_samples: int = 32):
        from collections import deque

        self.capacity = int(capacity)
        self.threshold_ms = threshold_ms
        self.min_samples = int(min_samples)
        self._spans = deque(maxlen=self.capacity)
        self._recent = deque(maxlen=int(window))
        self._rolling_p99 = float("inf")
        self._since_recalc = 0
        self.seen = 0
        self.recorded = 0
        self._lock = threading.Lock()

    def _refresh_p99(self) -> None:
        n = len(self._recent)
        if n < self.min_samples:
            self._rolling_p99 = float("inf")
            return
        ordered = sorted(self._recent)
        self._rolling_p99 = ordered[min(n - 1, int(0.99 * (n - 1)))]

    def note(self, e2e_ms: float) -> bool:
        """Fold one end-to-end latency into the rolling window; True
        iff the op qualifies for the buffer (the caller then builds
        the span and calls `add`)."""
        with self._lock:
            self.seen += 1
            self._recent.append(float(e2e_ms))
            self._since_recalc += 1
            if self._since_recalc >= self.RECALC_EVERY:
                self._since_recalc = 0
                self._refresh_p99()
            if self.threshold_ms is not None:
                return e2e_ms >= self.threshold_ms
            return e2e_ms >= self._rolling_p99

    def add(self, e2e_ms: float, span: Dict[str, Any]) -> None:
        with self._lock:
            self.recorded += 1
            self._spans.append({"e2e_ms": round(float(e2e_ms), 4),
                                **span})

    def observe(self, e2e_ms: float, span: Dict[str, Any]) -> bool:
        """One-shot form for cold paths: note + add when admitted."""
        if self.note(e2e_ms):
            self.add(e2e_ms, span)
            return True
        return False

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._recent.clear()
            self._rolling_p99 = float("inf")
            self._since_recalc = 0
            self.seen = 0
            self.recorded = 0


def _env_slow_threshold() -> Optional[float]:
    """`FLUID_TRACE_SLOW_MS`: a FIXED slow-op threshold (ms) for the
    process's default flight recorder — spans at/above it are kept
    instead of the rolling-p99 gate. The scenario/chaos harnesses set
    it ("0" = keep every span, ring-bounded) so a short run's /traces
    evidence does not depend on the rolling window having armed;
    unset (the default) keeps the adaptive production behavior."""
    v = os.environ.get("FLUID_TRACE_SLOW_MS", "")
    if not v:
        return None
    try:
        return float(v)
    except ValueError:
        return None


_default_recorder = FlightRecorder(threshold_ms=_env_slow_threshold())


def get_flight_recorder() -> FlightRecorder:
    """The process's default slow-op recorder (fed by the runtime's
    apply-side trace fold and, in wire-trace mode, the farm's
    broadcaster role; served by `monitor.MetricsServer` `/traces`)."""
    return _default_recorder


def set_flight_recorder(recorder: FlightRecorder) -> FlightRecorder:
    """Swap the default recorder; returns the previous one (bench/test
    isolation, like `set_registry`)."""
    global _default_recorder
    old = _default_recorder
    _default_recorder = recorder
    return old


# ---------------------------------------------------------------------------
# spans: the program's own layer timings
# ---------------------------------------------------------------------------

# Span durations run from tens of microseconds (an emit, a heartbeat)
# to seconds (a checkpoint): finer first buckets than the op latencies.
SPAN_BUCKETS_MS = (0.05, 0.1, 0.25) + DEFAULT_LATENCY_BUCKETS_MS


class SpanLog:
    """Bounded ring of finished spans, oldest first, each a tuple
    ``(name, t0, t1, parent_id, span_id, n)`` on the `time.perf_counter`
    clock (`n`: records the span handled, or None). A full ring drops
    its oldest span per append and counts it: `overwritten`, and
    `overwritten_until`, the latest end among dropped spans, so a
    reader can tell whether its interval lost any (`lost_since`)."""

    def __init__(self, capacity: int = 16384):
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self.overwritten = 0
        self.overwritten_until = float("-inf")
        self._lock = threading.Lock()

    def append(self, rec: tuple) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self.overwritten += 1
                end = self._ring[0][2]
                if end > self.overwritten_until:
                    self.overwritten_until = end
            self._ring.append(rec)

    def lost_since(self, t: float) -> bool:
        """Whether a span that ended at or after `t` was overwritten."""
        return self.overwritten_until >= t

    def snapshot(self) -> List[tuple]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.overwritten = 0
            self.overwritten_until = float("-inf")


_span_log = SpanLog()
_span_ids = itertools.count(1)
_span_tls = threading.local()
_trace_annotation: Any = None


def get_span_log() -> SpanLog:
    """The process's span ring (fed by every `span`)."""
    return _span_log


def set_span_log(log: SpanLog) -> SpanLog:
    """Swap the span ring; returns the previous one (test isolation,
    like `set_flight_recorder`)."""
    global _span_log
    old = _span_log
    _span_log = log
    return old


def _annotation_cls():
    """`jax.profiler.TraceAnnotation` once the process has imported
    JAX, else None: tracing never imports JAX into a process that does
    not use it (the CPU roles of a farm)."""
    global _trace_annotation
    if _trace_annotation is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        _trace_annotation = getattr(prof, "TraceAnnotation", None)
    return _trace_annotation


class _Frame:
    """One open span. The caller may set `n` (records handled)."""

    __slots__ = ("span", "t0", "sid", "parent", "ann", "n")


class _NullFrame:
    __slots__ = ()

    n = None

    def __setattr__(self, name, value) -> None:
        pass


_NULL_FRAME = _NullFrame()


class Span:
    """A reusable span of one name: build it once (its histogram binds
    to the registry current at construction, as every instrument
    does), then ``with sp as f: ...; f.n = records``. Parent links come
    from a per-thread stack of open spans, so nesting `with` blocks is
    all a caller does. Off (a no-op) while `set_enabled(False)`."""

    __slots__ = ("name", "_hist")

    def __init__(self, name: str, labels: Dict[str, Any]):
        self.name = name
        self._hist = get_registry().histogram(
            "span_ms", buckets=SPAN_BUCKETS_MS, span=name, **labels
        )

    def __enter__(self):
        if not _enabled:
            return _NULL_FRAME
        stack = getattr(_span_tls, "stack", None)
        if stack is None:
            stack = _span_tls.stack = []
        f = _Frame()
        f.span = self
        f.sid = next(_span_ids)
        f.parent = stack[-1].sid if stack else None
        f.n = None
        cls = _trace_annotation or _annotation_cls()
        f.ann = cls(self.name) if cls is not None else None
        if f.ann is not None:
            f.ann.__enter__()
        stack.append(f)
        f.t0 = time.perf_counter()
        return f

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        stack = getattr(_span_tls, "stack", None)
        if not stack or stack[-1].span is not self:
            return  # entered while disabled
        f = stack.pop()
        if f.ann is not None:
            f.ann.__exit__(None, None, None)
        if _enabled:
            self._hist.observe((t1 - f.t0) * 1000.0)
            _span_log.append((self.name, f.t0, t1, f.parent, f.sid, f.n))


def span(name: str, **labels) -> Span:
    """A `Span` called `name` (a dotted layer name, ``<role>.<phase>``)
    whose durations land in ``span_ms{span=name, **labels}``. Build it
    once, at the caller's construction, and enter it per call."""
    return Span(name, labels)


def named_program(name: str) -> Callable:
    """Decorator naming the function that `jax.jit` wraps next: the XLA
    module (and the profiler's program events) become ``jit_<name>``
    whatever the Python function is called, so a trace reader that
    finds a program by name survives a refactor."""
    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn

    return rename
