"""Device-memory accounting: HBM watermarks as gauges and span attrs.

The IVF-BQ capacity rung deliberately fills a chip (15.6M resident rows —
ROADMAP item 3), and "Memory Safe Computations with XLA" (PAPERS.md) argues
memory pressure should be *visible* before it is fatal — yet until now the
only memory signal in the repo was the OOM exception itself. This module
turns residency into telemetry:

* :func:`device_stats` — per-device ``bytes_in_use`` / ``peak_bytes_in_use``
  via ``Device.memory_stats()`` (populated on TPU; the CPU backend returns
  nothing);
* :func:`live_bytes` — the CPU fallback: total ``nbytes`` over
  ``jax.live_arrays()`` (every committed array the process still holds);
* :func:`sample` — one watermark snapshot for a named scope, recorded as
  ``memory.<tag>.*`` gauges (obs/registry) and returned as a plain dict the
  caller can attach to its span (``span.set_attr``) or metric line;
* :func:`index_bytes` / :func:`record_index` — per-index residency: the sum
  of array-leaf ``nbytes`` across an index/store's fields, as a
  ``memory.index.<name>.bytes`` gauge.

Never triggers backend init: like ``tracing.process_info``, every jax touch
is gated on an ALREADY-initialized backend (the round-5 wedge class — a
telemetry read must not pay first-touch init), so this module is safe to
call from the report CLI or a jax-free parent; it just answers zeros there.
"""

from __future__ import annotations

import sys

from raft_tpu import obs

__all__ = [
    "device_stats",
    "index_bytes",
    "live_bytes",
    "record_index",
    "sample",
]


def _live_jax():
    """The jax module ONLY when a backend is already initialized (the
    tracing.process_info contract: never trigger init from telemetry)."""
    jax = sys.modules.get("jax")
    xb = sys.modules.get("jax._src.xla_bridge")
    if jax is None or xb is None or not getattr(xb, "_backends", None):
        return None
    return jax


def device_stats() -> list:
    """Per-device memory stats: ``[{"device", "platform", "bytes_in_use",
    "peak_bytes_in_use"}, ...]`` for every local device that reports them.
    Empty on CPU (the backend has no allocator stats) and when no backend
    is live."""
    jax = _live_jax()
    if jax is None:
        return []
    out = []
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        # a backend without allocator stats is a supported state, not a
        # failure to classify
        except Exception:  # graftlint: ignore[unclassified-except]
            stats = None
        if not stats:
            continue
        row = {
            "device": str(dev.id),
            "platform": dev.platform,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get(
                "peak_bytes_in_use", stats.get("bytes_in_use", 0))),
        }
        # allocator capacity, where the backend reports one — the HBM
        # budget denominator obs/costmodel.hbm_budget projects against
        if stats.get("bytes_limit"):
            row["bytes_limit"] = int(stats["bytes_limit"])
        out.append(row)
    return out


def live_bytes() -> int:
    """Total bytes of every live committed array in the process — the CPU
    fallback watermark (the CPU allocator exposes no per-device stats).

    Deduplicated by buffer identity (round-11 audit): ``jax.live_arrays()``
    can hand back several Array objects over the SAME device buffer
    (no-copy ``device_put``, donated-buffer aliasing), and summing their
    ``nbytes`` naively double-counts the buffer. Arrays are keyed by
    ``unsafe_buffer_pointer()`` where the runtime provides it (single-shard
    arrays), falling back to object identity — distinct buffers never share
    a pointer, so the dedup can only remove true aliases."""
    jax = _live_jax()
    if jax is None:
        return 0
    total = 0
    seen = set()
    for arr in jax.live_arrays():
        try:
            try:
                key = ("buf", int(arr.unsafe_buffer_pointer()))
            # sharded/committed-elsewhere arrays expose no single buffer
            # pointer — object identity is the conservative fallback
            # (never merges distinct buffers)
            except Exception:  # graftlint: ignore[unclassified-except]
                key = ("obj", id(arr))
            if key in seen:
                continue
            seen.add(key)
            total += int(arr.nbytes)
        # a deleted-buffer race during iteration must not fail a
        # watermark read
        except Exception:  # graftlint: ignore[unclassified-except,swallowed-exception]
            pass
    return total


def sample(tag: str) -> dict:
    """One memory watermark for scope ``tag`` (a bench section, an index
    name, "serving"): ``{"source", "bytes_in_use", "peak_bytes_in_use",
    "per_device"?}``. Source is ``"device_stats"`` when the backend reports
    allocator stats (TPU) and ``"live_arrays"`` otherwise (CPU). Recorded
    as ``memory.<tag>.bytes_in_use`` / ``.peak_bytes`` gauges; the returned
    dict is what callers attach as span attrs."""
    with obs.record_span("obs.memory::sample", attrs={"tag": tag}):
        per_dev = device_stats()
        if per_dev:
            out = {
                "source": "device_stats",
                "bytes_in_use": sum(d["bytes_in_use"] for d in per_dev),
                "peak_bytes_in_use": sum(
                    d["peak_bytes_in_use"] for d in per_dev),
                "per_device": per_dev,
            }
        else:
            b = live_bytes()
            out = {"source": "live_arrays", "bytes_in_use": b,
                   "peak_bytes_in_use": b}
        if obs.enabled():
            obs.set_gauge(f"memory.{tag}.bytes_in_use", out["bytes_in_use"])
            obs.set_gauge(f"memory.{tag}.peak_bytes",
                          out["peak_bytes_in_use"])
        return out


def index_bytes(index) -> int:
    """Resident bytes of one index/store: the sum of ``nbytes`` over its
    array-valued fields (dataclass fields, __dict__ and __slots__ entries,
    one level deep — the layout every index in this repo uses)."""
    total = 0
    seen = set()
    fields = {}
    for src in (getattr(index, "__dict__", None),):
        if src:
            fields.update(src)
    for name in getattr(type(index), "__dataclass_fields__", ()) or ():
        fields.setdefault(name, getattr(index, name, None))
    for slot in getattr(type(index), "__slots__", ()) or ():
        fields.setdefault(slot, getattr(index, slot, None))
    for val in fields.values():
        nbytes = getattr(val, "nbytes", None)
        if isinstance(nbytes, int) and id(val) not in seen:
            seen.add(id(val))
            total += nbytes
    return total


def record_index(name: str, index) -> int:
    """Record ``index``'s residency as the ``memory.index.<name>.bytes``
    gauge; returns the byte count (0 for array-free objects)."""
    b = index_bytes(index)
    if obs.enabled():
        obs.set_gauge(f"memory.index.{name}.bytes", b)
    return b
