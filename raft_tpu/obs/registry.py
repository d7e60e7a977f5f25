"""Process-wide metrics registry: counters, wall-clock timers, histograms.

The telemetry analog of the reference's NVTX-range + spdlog infrastructure
(cpp/include/raft/core/nvtx.hpp, logger.hpp) — except measured, not just
annotated: every :func:`record_span` feeds BOTH the profiler timeline
(``jax.profiler.TraceAnnotation``, the NVTX-range analog core/trace.py already
provides) and this registry, so hot-path timings survive the process even when
no profiler capture is active.

Zero-dep and thread-safe (one ``threading.Lock`` around the maps; jax.profiler
is imported lazily and only when a span actually opens). Telemetry is OFF by
default: the gate is the ``RAFT_TPU_OBS`` env var (or :func:`enable` /
:func:`disable` at runtime), and every instrumented hot path guards its
emission with ``if obs.enabled():`` so the disabled cost is a single branch.
When disabled, :func:`record_span` returns one shared no-op context manager
(``NOOP_SPAN`` — identity-testable, which is how the overhead contract is
asserted in tests) and never touches the registry.

Span timings are host wall-clock around the instrumented region. JAX dispatch
is asynchronous, so a span around a jitted call measures its enqueue (plus
any trace/compile), not device execution. Device time comes from a profiler
trace: every span is also a ``TraceAnnotation``, so it lands on the
profiler's host plane on the same clock as the device's ops, and a trace
reader can put each device gap down to the span the host was in.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from typing import Optional

from raft_tpu.obs import tracing as _tracing

__all__ = [
    "EXEMPLAR_CAP",
    "MetricsRegistry",
    "NOOP_SPAN",
    "add",
    "disable",
    "enable",
    "enabled",
    "export_jsonl",
    "inc_gauge",
    "observe",
    "record_launch",
    "record_span",
    "record_timing",
    "registry",
    "reset",
    "set_gauge",
    "snapshot",
]

#: exemplars kept per histogram (newest win) — enough to link each
#: percentile bucket of a live latency histogram to a recent trace id
#: without growing the snapshot unboundedly
EXEMPLAR_CAP = 8

_enabled = os.environ.get("RAFT_TPU_OBS", "").strip().lower() in (
    "1", "true", "on", "yes",
)


def enabled() -> bool:
    """The single-branch hot-path gate: instrumented code runs its emission
    only under ``if obs.enabled():``."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


class _TimerStat:
    """count / total / min / max of one named wall-clock timer."""

    __slots__ = ("count", "total_s", "min_s", "max_s")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.min_s = math.inf
        self.max_s = 0.0

    def add(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        if seconds < self.min_s:
            self.min_s = seconds
        if seconds > self.max_s:
            self.max_s = seconds

    def as_dict(self) -> dict:
        return {
            "count": self.count,
            "total_s": self.total_s,
            "min_s": self.min_s,
            "max_s": self.max_s,
            "mean_s": self.total_s / self.count if self.count else 0.0,
        }


class _HistStat:
    """Power-of-two-bucketed histogram (+ count/sum/min/max exact).

    Carries a small bounded **exemplar ring**: when an observation lands
    while a trace is open (or the caller passes ``trace_id`` explicitly),
    the ``(bucket, trace_id, value)`` triple is kept so a percentile bucket
    in a snapshot links back to a concrete recent trace — "p99 is 80 ms,
    and HERE is a request that paid it". The ring is ``EXEMPLAR_CAP`` deep
    (newest win) and dies with ``reset()``, so trace ids never leak across
    tests or runs."""

    __slots__ = ("count", "sum", "min", "max", "buckets", "exemplars")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.buckets: dict = {}
        self.exemplars: deque = deque(maxlen=EXEMPLAR_CAP)

    def add(self, value: float, trace_id: Optional[str] = None) -> None:
        self.count += 1
        self.sum += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value
        # bucket upper bound = smallest power of two >= value (0 for v <= 0).
        # repr, not %g: 6-sig-digit rounding would print 2**21 as
        # 'le_2.09715e+06', and the percentile parser reading that back
        # would report an "upper bound" BELOW the observed max
        bound = 0.0 if value <= 0 else 2.0 ** math.ceil(math.log2(value))
        key = f"le_{bound!r}"
        self.buckets[key] = self.buckets.get(key, 0) + 1
        if trace_id is not None:
            self.exemplars.append(
                {"bucket": key, "trace_id": trace_id, "value": value})

    def as_dict(self) -> dict:
        out = {
            "count": self.count,
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "buckets": dict(self.buckets),
        }
        if self.exemplars:
            out["exemplars"] = list(self.exemplars)
        # p50/p90/p99 UPPER bounds derived from the power-of-two buckets:
        # over-estimates the true quantile by ≤2× (the bucket resolution);
        # shared with the fleet merge so per-process and merged views agree.
        # Lazy import: preloading obs.aggregate at package-import time would
        # shadow the `python -m raft_tpu.obs.aggregate` runpy execution.
        from raft_tpu.obs.aggregate import percentile_bounds

        out.update(percentile_bounds(self.buckets, self.count))
        return out


class _GaugeStat:
    """Last-value gauge with exact min/max/count of everything set."""

    __slots__ = ("value", "min", "max", "count")

    def __init__(self):
        self.value = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.count = 0

    def set(self, value: float) -> None:
        self.value = value
        self.count += 1
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def inc(self, delta: float) -> None:
        self.set(self.value + delta)

    def as_dict(self, process_key: str) -> dict:
        # "last" keys the final value by process so the fleet merge can
        # preserve per-process last values exactly (obs/aggregate merges
        # min-of-min / max-of-max and unions these maps)
        return {"value": self.value, "min": self.min, "max": self.max,
                "count": self.count, "last": {process_key: self.value}}


class MetricsRegistry:
    """Thread-safe named counters + timers + histograms with dict snapshots
    and JSONL export. One process-wide default instance lives in this module
    (:func:`registry`); algorithms never construct their own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict = {}  # guarded-by: _lock
        self._timers: dict = {}    # guarded-by: _lock
        self._hists: dict = {}     # guarded-by: _lock
        self._gauges: dict = {}    # guarded-by: _lock

    # -- writes -------------------------------------------------------------
    def add(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def record_timing(self, name: str, seconds: float) -> None:
        with self._lock:
            stat = self._timers.get(name)
            if stat is None:
                stat = self._timers[name] = _TimerStat()
            stat.add(seconds)

    def observe(self, name: str, value: float,
                trace_id: Optional[str] = None) -> None:
        """Record one histogram observation. ``trace_id`` (or, when None,
        the innermost open span's trace) lands in the histogram's exemplar
        ring so percentile buckets link to concrete recent traces."""
        if trace_id is None:
            cur = _tracing.current_span()
            if cur is not None:
                trace_id = cur[0]
        with self._lock:
            stat = self._hists.get(name)
            if stat is None:
                stat = self._hists[name] = _HistStat()
            stat.add(value, trace_id)

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            stat = self._gauges.get(name)
            if stat is None:
                stat = self._gauges[name] = _GaugeStat()
            stat.set(float(value))

    def inc_gauge(self, name: str, delta: float = 1) -> None:
        with self._lock:
            stat = self._gauges.get(name)
            if stat is None:
                stat = self._gauges[name] = _GaugeStat()
            stat.inc(float(delta))

    # -- reads --------------------------------------------------------------
    def snapshot(self) -> dict:
        """Plain-dict copy: {"counters": .., "timers": .., "histograms": ..,
        "gauges": ..}. Empty sections are included so consumers need no key
        checks."""
        pk = f"p{_tracing.process_info()[0]}"
        with self._lock:
            return {
                "counters": dict(self._counters),
                "timers": {k: v.as_dict() for k, v in self._timers.items()},
                "histograms": {k: v.as_dict() for k, v in self._hists.items()},
                "gauges": {k: v.as_dict(pk) for k, v in self._gauges.items()},
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._timers.clear()
            self._hists.clear()
            self._gauges.clear()

    def export_jsonl(self, path, extra: Optional[dict] = None) -> dict:
        """Append one timestamped snapshot line to ``path``; returns the
        record written. ``extra`` keys ride at the top level (run ids, phase
        tags). Every record is stamped with ``process_index`` /
        ``process_count`` (obs/tracing.process_info) so per-process files
        merge into a fleet view via ``python -m raft_tpu.obs.aggregate``."""
        pi, pc = _tracing.process_info()
        rec = {"t": round(time.time(), 3), "process_index": pi,
               "process_count": pc, **(extra or {}), **self.snapshot()}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
        return rec


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

_ANN_UNRESOLVED = object()
_ann_cls = _ANN_UNRESOLVED


def _trace_annotation():
    """jax.profiler.TraceAnnotation, resolved lazily (the registry must stay
    importable in jax-free parent processes like bench.py's orchestrator);
    None when jax is unavailable."""
    global _ann_cls
    if _ann_cls is _ANN_UNRESOLVED:
        try:
            import jax.profiler

            _ann_cls = jax.profiler.TraceAnnotation
        # jax-free parents are a supported state — nothing to classify
        except Exception:  # pragma: no cover  # graftlint: ignore[unclassified-except]
            _ann_cls = None
    return _ann_cls


def _classify_error(exc) -> str:
    """Failure kind for a span that raised, via resilience.classify (lazy:
    resilience imports obs, so the import must not run at module load).
    Falls back to the bare class name if the resilience layer is absent."""
    try:
        from raft_tpu.resilience.errors import classify

        return classify(exc)
    # this IS the classify call site; its own fallback (a partially
    # imported resilience package) has only the type name to offer
    except Exception:  # graftlint: ignore[unclassified-except]
        return type(exc).__name__.lower()


class _Span:
    """Context manager: profiler trace annotation + registry wall-clock +
    one node of the span tree (obs/tracing.py).

    Exception-safe by contract: a body that raises still records its
    duration, and the span (plus a ``span.errors.{kind}`` counter) is tagged
    with the ``resilience.classify()`` kind of the failure."""

    __slots__ = ("_name", "_reg", "_t0", "_t0_epoch", "_ann", "_attrs",
                 "_ids", "_token")

    def __init__(self, name: str, reg: MetricsRegistry,
                 attrs: Optional[dict] = None):
        self._name = name
        self._reg = reg
        self._attrs = attrs

    def set_attr(self, key: str, value):
        """Attach one typed attribute (rows/probes/tiles/shard …) to the
        span record; chainable. Values discovered mid-body land here."""
        if self._attrs is None:
            self._attrs = {}
        self._attrs[key] = value
        return self

    def __enter__(self):
        ann_cls = _trace_annotation()
        self._ann = ann_cls(self._name) if ann_cls is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self._ids, self._token = _tracing.enter_span()
        self._t0_epoch = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        error = None
        if exc is not None:
            error = _classify_error(exc)
            self._reg.add(f"span.errors.{error}")
        self._reg.record_timing(self._name, dt)
        _tracing.exit_span(self._ids, self._token, name=self._name,
                           t0=self._t0_epoch, dur_s=dt, attrs=self._attrs,
                           error=error)
        return False


class _NoopSpan:
    """Shared do-nothing span handed out whenever telemetry is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set_attr(self, key, value):
        return self


NOOP_SPAN = _NoopSpan()

_default = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _default


def record_span(name: str, reg: Optional[MetricsRegistry] = None,
                attrs: Optional[dict] = None):
    """``with obs.record_span("ivf_pq::search"): ...`` — times the block into
    the registry, marks it on the profiler timeline, AND records one node of
    the span tree (parented on the enclosing span via contextvar —
    obs/tracing.py). ``attrs`` attaches typed attributes (rows/probes/tiles/
    shard); hot paths should build the dict inside their existing
    ``if obs.enabled():`` block so the off path allocates nothing. When
    telemetry is disabled this returns the shared :data:`NOOP_SPAN` (no
    allocation, no registry touch)."""
    if not _enabled:
        return NOOP_SPAN
    return _Span(name, reg if reg is not None else _default, attrs)


def record_launch(span: str, counter: str):
    """``with obs.record_launch("ivf_flat::launch",
    "ivf_flat.search.launches"): program(...)`` — a :func:`record_span`
    around ONE jitted program call, plus 1 on ``counter``. The span ends
    when the call returns, so it times the enqueue, not the device; the
    counter counts programs launched. Off: :data:`NOOP_SPAN`, no count."""
    if not _enabled:
        return NOOP_SPAN
    _default.add(counter, 1)
    return _Span(span, _default, None)


def add(name: str, value: float = 1) -> None:
    if _enabled:
        _default.add(name, value)


def record_timing(name: str, seconds: float) -> None:
    if _enabled:
        _default.record_timing(name, seconds)


def observe(name: str, value: float, trace_id: Optional[str] = None) -> None:
    if _enabled:
        _default.observe(name, value, trace_id)


def set_gauge(name: str, value: float) -> None:
    """Set a last-value gauge (queue depth, memory watermark, recall
    estimate). Snapshots carry last value + exact min/max/count; the fleet
    merge keeps per-process last values (obs/aggregate)."""
    if _enabled:
        _default.set_gauge(name, value)


def inc_gauge(name: str, delta: float = 1) -> None:
    """Adjust a gauge relative to its current value (inc semantics)."""
    if _enabled:
        _default.inc_gauge(name, delta)


def snapshot() -> dict:
    return _default.snapshot()


def reset() -> None:
    _default.reset()


def export_jsonl(path, extra: Optional[dict] = None) -> dict:
    return _default.export_jsonl(path, extra)
