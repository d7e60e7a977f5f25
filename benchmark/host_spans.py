"""The program's own host spans in a traced slice, on the profiler's clock.

The search entries and every jitted program call inside them are spans of
the program's telemetry (``obs.record_span`` / ``obs.record_launch``),
each also a ``jax.profiler.TraceAnnotation``, so they are host events of
the same trace as the device's ops. Read by ``entry_ms.batch``,
``launch_ms.batch``, ``launches.batch`` and ``idle_in_entry.batch``.
Times are in ns, as in :class:`trace_reduce.Reduction`.
"""

from __future__ import annotations

ENTRY_SPANS = ("ivf_flat::search", "ivf_pq::search", "refine::refine")
LAUNCH_SPANS = ("ivf_flat::launch", "ivf_pq::launch", "refine::launch")
LAUNCH_COUNTERS = ("ivf_flat.search.launches", "ivf_pq.search.launches",
                   "refine.launches")


def union(intervals) -> list:
    """Sorted, disjoint [start, end] pairs covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def clipped(trace, intervals) -> list:
    """The union of ``intervals`` clipped to the traced slice."""
    return union((max(s, trace.t0), min(e, trace.t1)) for s, e in intervals)


def spans(trace, names) -> list:
    """The union of the host events named in ``names``, in the slice."""
    return clipped(trace, ((s, e) for n, s, e in trace.host if n in names))


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Length of the intersection of two unions."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        out += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle(trace) -> list:
    """The slice's intervals in which the first device traced runs no op
    (the device ``Reduction.idle_gaps`` reads)."""
    ops = next(iter(trace.devices.values()))
    busy = clipped(trace, ((s, e) for _, s, e in ops))
    edges = [trace.t0] + [x for iv in busy for x in iv] + [trace.t1]
    return [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def batches(marks) -> int:
    """Batches run between the slice's start and stop marks."""
    if "stop" not in marks:
        return 0
    return marks["stop"]["n_done"] - marks["start"]["n_done"]


def entry(ctx):
    """(entry-span union, batches) of the slice, or None where the trace
    holds no entry span or no whole batch."""
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    n = batches(ctx.marks)
    iv = spans(t, ENTRY_SPANS)
    if n <= 0 or not iv:
        return None
    return iv, n
