"""Hierarchical span trees: contextvar parenting, bounded ring, Perfetto export.

PR 1's ``record_span`` produced *flat* timers — enough to say "ivf_pq::search
ran 40 times for 12 s" but not where inside a search the time went. This
module upgrades every enabled span into a node of a trace tree:

* **Parenting** is a :mod:`contextvars` variable, so nesting follows the call
  stack for free (threads and ``contextvars.copy_context`` tasks each get
  their own lineage; a span opened on a fresh thread starts a new trace).
* **Identity** is ``(trace_id, span_id, parent_id)`` — ids come from a
  process-local counter (deterministic, no clock/RNG reads), prefixed with
  the pid so traces from different processes never collide when merged.
* **Storage** is a bounded ring (``RAFT_TPU_OBS_TRACE_CAP``, default 4096
  spans) guarded by one lock; completed spans append one small dict each.
  The ring, not an unbounded list, is what makes leaving telemetry on for a
  whole bench window safe.
* **Export** is Chrome trace-event JSON (:func:`chrome_trace` /
  :func:`export_chrome_trace`) — one ``"X"`` (complete) event per span with
  its attributes under ``args``, plus ``"i"`` (instant) events for the
  resilience recovery ring — loadable directly in Perfetto
  (https://ui.perfetto.dev) or ``chrome://tracing``.

Everything here is stdlib-only at import time (jax and resilience are
reached lazily), so the module stays importable in jax-free parents.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

__all__ = [
    "alloc_id",
    "chrome_trace",
    "clear_spans",
    "clock_handshake",
    "current_span",
    "enter_span",
    "exit_span",
    "export_chrome_trace",
    "fleet_trace_id",
    "manual_span",
    "process_info",
    "push_span",
    "reset_fleet_ids",
    "set_ring_cap",
    "spans",
]

# ---------------------------------------------------------------------------
# process identity (fleet aggregation stamps)
# ---------------------------------------------------------------------------


def _jax_process_info():
    """(process_index, process_count) from jax, ONLY when a backend already
    exists. jax.process_index() initializes the backend on first touch —
    exactly the operation that wedged round 5 — so this never triggers init:
    it requires jax AND an initialized xla_bridge backend to already be in
    sys.modules, else answers None. Multi-host launchers that want stamps
    without a live backend set RAFT_TPU_PROCESS_INDEX/COUNT instead."""
    try:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is None or not getattr(xb, "_backends", None):
            return None
        return int(jax.process_index()), int(jax.process_count())
    # a stamp is best-effort decoration: jax-internals drift degrades to
    # "no live backend", never to a telemetry failure worth classifying
    except Exception:  # graftlint: ignore[unclassified-except]
        return None


def process_info() -> tuple:
    """(process_index, process_count) for stamping telemetry records.

    Resolution order: ``RAFT_TPU_PROCESS_INDEX``/``RAFT_TPU_PROCESS_COUNT``
    env override (tests, launchers), then an already-initialized jax backend,
    then ``(0, 1)``. Never initializes a backend (see :func:`_jax_process_info`).
    """
    pi = os.environ.get("RAFT_TPU_PROCESS_INDEX", "").strip()
    pc = os.environ.get("RAFT_TPU_PROCESS_COUNT", "").strip()
    if pi.lstrip("-").isdigit():
        return int(pi), int(pc) if pc.lstrip("-").isdigit() else 1
    live = _jax_process_info()
    if live is not None:
        return live
    return 0, 1


# ---------------------------------------------------------------------------
# span ring + contextvar lineage
# ---------------------------------------------------------------------------

def _ring_cap() -> int:
    raw = os.environ.get("RAFT_TPU_OBS_TRACE_CAP", "").strip()
    if raw.isdigit() and int(raw) > 0:
        return int(raw)
    return 4096


_SPANS: deque = deque(maxlen=_ring_cap())  # guarded-by: _LOCK
_LOCK = threading.Lock()


def set_ring_cap(cap: int) -> None:
    """Resize the span ring at runtime (newest spans kept). The
    ``RAFT_TPU_OBS_TRACE_CAP`` env var is read once at import — a process
    that decides on a long attribution run AFTER importing raft_tpu uses
    this instead (the runtime twin of the env var)."""
    global _SPANS
    with _LOCK:
        _SPANS = deque(_SPANS, maxlen=max(1, int(cap)))
_ids = itertools.count(1)
_ID_PREFIX = f"{os.getpid():x}"

#: (trace_id, span_id) of the innermost open span in this context
_current: contextvars.ContextVar = contextvars.ContextVar(
    "raft_tpu_obs_span", default=None)


def current_span() -> Optional[tuple]:
    """(trace_id, span_id) of the innermost open span, or None."""
    return _current.get()


def _next_id() -> str:
    return f"{_ID_PREFIX}-{next(_ids)}"


def enter_span():
    """Open a span in the current context: allocate ids, inherit the trace
    from the enclosing span (or start a new trace at the root), and make
    this span the parent of anything opened inside it.

    Returns ``((trace_id, span_id, parent_id), token)``; the token MUST be
    passed back to :func:`exit_span`."""
    parent = _current.get()
    sid = _next_id()
    if parent is None:
        ids = (_next_id(), sid, None)
    else:
        ids = (parent[0], sid, parent[1])
    token = _current.set((ids[0], ids[1]))
    return ids, token


def exit_span(ids, token, *, name: str, t0: float, dur_s: float,
              attrs: Optional[dict] = None,
              error: Optional[str] = None) -> dict:
    """Close a span opened by :func:`enter_span`: restore the parent context
    and append the completed record to the ring. Returns the record."""
    _current.reset(token)
    trace_id, span_id, parent_id = ids
    rec = {
        "name": name,
        "trace_id": trace_id,
        "span_id": span_id,
        "parent_id": parent_id,
        "t0": t0,
        "dur_s": dur_s,
        "tid": threading.get_ident(),
    }
    if attrs:
        rec["attrs"] = dict(attrs)
    if error is not None:
        rec["error"] = error
    push_span(rec)
    return rec


#: per-site fleet dispatch counters (``fleet_trace_id``)
_fleet_ids: dict = {}  # guarded-by: _LOCK


def fleet_trace_id(site: str) -> str:
    """Deterministic FLEET-scoped id for one dispatch of ``site``:
    ``fleet:<site>:<n>`` where n counts this process's dispatches of that
    site. Deliberately NOT pid-prefixed — under SPMD every host runs the
    identical dispatch sequence, so host i and host j stamp the SAME id on
    the same logical dispatch, which is exactly what lets the trace
    stitcher (obs/aggregate.stitch_traces) line per-host tracks up into
    one fleet trace. Span/trace ids stay host-local (:func:`alloc_id`);
    this rides spans as an ``attrs`` entry."""
    with _LOCK:
        n = _fleet_ids.get(site, 0) + 1
        _fleet_ids[site] = n
    return f"fleet:{site}:{n}"


def reset_fleet_ids() -> None:
    """Reset the per-site dispatch counters (tests simulating two hosts
    from one process re-zero between 'hosts' to mirror SPMD determinism)."""
    with _LOCK:
        _fleet_ids.clear()


def clock_handshake(reference_epoch: Optional[float] = None) -> dict:
    """The per-process clock-offset handshake record that opens a flight
    recording: this host's epoch and monotonic readings, plus ``offset_s``
    relative to a fleet-agreed reference epoch (``reference_epoch`` or the
    ``RAFT_TPU_FLEET_EPOCH`` env var a multi-host launcher distributes).
    With no reference the offset is 0.0 — single-host recordings stitch
    unshifted. The stitcher subtracts ``offset_s`` from a host's event
    timestamps so skewed wall clocks align on one timeline."""
    pi, pc = process_info()
    t_epoch = time.time()
    t_mono = time.monotonic()
    if reference_epoch is None:
        raw = os.environ.get("RAFT_TPU_FLEET_EPOCH", "").strip()
        try:
            reference_epoch = float(raw) if raw else None
        except ValueError:
            reference_epoch = None
    return {
        "type": "clock_offset",
        "process_index": pi,
        "process_count": pc,
        "t_epoch": round(t_epoch, 6),
        "t_mono": round(t_mono, 6),
        "offset_s": (round(t_epoch - reference_epoch, 6)
                     if reference_epoch is not None else 0.0),
    }


def alloc_id() -> str:
    """One fresh span/trace id from the process-local counter. Callers
    building EXPLICIT-lineage spans (:func:`manual_span`) allocate ids up
    front so children can reference a parent that completes later — the
    serving request lifecycle, whose root span closes after its dispatch
    children were already recorded on another thread."""
    return _next_id()


def manual_span(name: str, *, t0: float, dur_s: float,
                trace_id: Optional[str] = None,
                span_id: Optional[str] = None,
                parent_id: Optional[str] = None,
                attrs: Optional[dict] = None,
                error: Optional[str] = None) -> dict:
    """Record one COMPLETED span with explicit lineage, bypassing the
    contextvar stack. This is the cross-thread escape hatch: a serving
    request's submit → admit → dispatch → complete lifecycle spans the
    caller thread and the batcher's worker, so contextvar parenting cannot
    link them — the queue allocates the request's ids at submit
    (:func:`alloc_id`) and files each lifecycle phase under them as it
    happens. ``t0`` is epoch seconds (the ring/export convention). Returns
    the record pushed to the ring."""
    rec = {
        "name": name,
        "trace_id": trace_id if trace_id is not None else _next_id(),
        "span_id": span_id if span_id is not None else _next_id(),
        "parent_id": parent_id,
        "t0": t0,
        "dur_s": dur_s,
        "tid": threading.get_ident(),
    }
    if attrs:
        rec["attrs"] = dict(attrs)
    if error is not None:
        rec["error"] = error
    push_span(rec)
    return rec


def push_span(rec: dict) -> None:
    with _LOCK:
        _SPANS.append(rec)


def spans() -> list:
    """Snapshot of the completed-span ring, oldest first."""
    with _LOCK:
        return list(_SPANS)


def clear_spans() -> None:
    with _LOCK:
        _SPANS.clear()


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------


def chrome_trace(span_records: Optional[list] = None,
                 events: Optional[list] = None,
                 extra: Optional[dict] = None) -> dict:
    """Assemble a Chrome trace-event JSON dict from span records (default:
    the ring) and instant events (default: the resilience recovery ring).

    Spans become ``"X"`` complete events (ts/dur in microseconds, pid =
    ``process_index`` so multi-host traces interleave cleanly in one
    Perfetto view); recovery events become ``"i"`` instants. Span attributes
    and ids ride under ``args`` and round-trip through the file."""
    if span_records is None:
        span_records = spans()
    if events is None:
        events = _resilience_events()
    pi, pc = process_info()
    out = []
    for rec in span_records:
        args = {
            "trace_id": rec.get("trace_id"),
            "span_id": rec.get("span_id"),
            "parent_id": rec.get("parent_id"),
        }
        args.update(rec.get("attrs") or {})
        if "error" in rec:
            args["error"] = rec["error"]
        out.append({
            "name": rec.get("name", "?"),
            "cat": "span",
            "ph": "X",
            "ts": round(float(rec.get("t0", 0.0)) * 1e6, 1),
            "dur": round(float(rec.get("dur_s", 0.0)) * 1e6, 1),
            "pid": pi,
            "tid": rec.get("tid", 0),
            "args": args,
        })
    for ev in events:
        ev = dict(ev)
        out.append({
            "name": ev.pop("event", "event"),
            "cat": "resilience",
            "ph": "i",
            "s": "p",
            "ts": round(float(ev.pop("t", 0.0)) * 1e6, 1),
            "pid": pi,
            "tid": 0,
            "args": ev,
        })
    meta = {"process_index": pi, "process_count": pc}
    if extra:
        meta.update(extra)
    return {"traceEvents": out, "displayTimeUnit": "ms", "otherData": meta}


def _resilience_events() -> list:
    """The resilience recovery ring, reached lazily (resilience imports obs,
    so a module-level import here would be a cycle); empty when the package
    is only partially imported."""
    try:
        from raft_tpu.resilience.retry import recent_events

        return recent_events()
    # a partially imported resilience package (bootstrap orderings) means
    # "no instant events for this export" — nothing to classify
    except Exception:  # graftlint: ignore[unclassified-except]
        return []


def export_chrome_trace(path, extra: Optional[dict] = None) -> dict:
    """Serialize :func:`chrome_trace` to ``path`` crash-safely (tmp file +
    flush + fsync + atomic rename — the bench/progress.py durability
    contract: a kill mid-write leaves the old file or the complete new one)
    and return the dict. Bench code must route through
    ``bench/progress.write_artifact`` instead (graftlint ``span-name``
    enforces it); this is the library entry."""
    doc = chrome_trace(extra=extra)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    return doc
