"""Runtime telemetry: metrics registry, span trees, fleet merge, health probe.

The observability layer the reference ships as NVTX ranges + an spdlog logger
(core/nvtx.hpp, core/logger.hpp), grown into something measurable: a
process-wide registry (obs/registry.py) that hot paths feed counters and
wall-clock spans into behind a single-branch ``obs.enabled()`` gate; a
hierarchical tracing layer (obs/tracing.py) that parents nested spans into
trace trees exportable as Perfetto-loadable Chrome trace JSON; an exact
fleet-wide merge of per-process snapshots (obs/aggregate.py, also
``python -m raft_tpu.obs.aggregate``); and a subprocess-isolated
device-health probe (obs/health.py) that answers "is this backend alive?" in
bounded time — the check bench.py runs before committing its TPU window (the
round-5 wedge ate the whole window with no record; ISSUE 1).

Usage::

    from raft_tpu import obs

    obs.enable()                      # or RAFT_TPU_OBS=1 in the env
    with obs.record_span("my::phase", attrs={"rows": n}):
        with obs.record_span("my::tile"):   # parented under my::phase
            ...
    obs.add("my.rows", n)             # counter
    obs.observe("my.batch_s", dt)     # pow2 histogram (p50/p90/p99 bounds)
    obs.snapshot()                    # {"counters": .., "timers": .., ..}
    obs.export_jsonl("results/obs.jsonl", {"run": "r06"})  # process-stamped
    obs.export_chrome_trace("results/trace_dev.json")      # open in Perfetto

Instrumented code gates every emission::

    if obs.enabled():
        obs.add("ivf_pq.search.queries", q)

so the telemetry-off cost of a hot path is one function call and one branch.
A span around a jitted call times its enqueue; device time is read from a
profiler trace, where every span also lands as a host event
(``jax.profiler.TraceAnnotation``) on the device's clock.
"""

# NOTE: obs.aggregate and obs.report are deliberately NOT imported here —
# preloading either would shadow its `python -m raft_tpu.obs.<mod>` runpy
# execution; reach them as `from raft_tpu.obs import aggregate, report`.
# The SLO plane (obs.slo / obs.shadow / obs.memory / obs.report) is also
# kept off the package import path because it reaches into resilience,
# which imports obs back — import those modules directly when needed.
from raft_tpu.obs import tracing
from raft_tpu.obs.registry import (
    NOOP_SPAN,
    MetricsRegistry,
    add,
    disable,
    enable,
    enabled,
    export_jsonl,
    inc_gauge,
    observe,
    record_launch,
    record_span,
    record_timing,
    registry,
    reset,
    set_gauge,
    snapshot,
)
from raft_tpu.obs.tracing import (
    chrome_trace,
    clear_spans,
    export_chrome_trace,
    process_info,
    spans,
)
from raft_tpu.obs.health import MAX_TIMEOUT, HealthReport, probe

__all__ = [
    "MAX_TIMEOUT",
    "HealthReport",
    "MetricsRegistry",
    "NOOP_SPAN",
    "add",
    "chrome_trace",
    "clear_spans",
    "disable",
    "enable",
    "enabled",
    "export_chrome_trace",
    "export_jsonl",
    "inc_gauge",
    "observe",
    "probe",
    "process_info",
    "record_launch",
    "record_span",
    "record_timing",
    "registry",
    "reset",
    "set_gauge",
    "snapshot",
    "spans",
    "tracing",
]
