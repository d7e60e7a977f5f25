"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (the device plane's ``XLA Ops`` line), clipped to the traced window and
  averaged over the devices traced;
* kernel time: the summed device time of the ops whose names match a
  pattern;
* the ops that took most time, by short name (:func:`op_name`);
* the idle gaps, each labelled with the innermost host span that covers
  its midpoint (the harness's ``bench::`` spans and the program's own).

The traced window is the host span ``WINDOW_SPAN`` where the trace has it,
else the extent of the device ops.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench::traced"
OP_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(name: str) -> str:
    """An op's short name: a TPU trace names ops by their whole HLO text
    (``%_strip_class_call.7 = (f32[...]) custom-call(...)``); keep what
    precedes `` = `` and drop the ``%`` and the ``.<n>`` suffix."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduction:
    """Device ops and host spans of one trace, in nanoseconds."""

    def __init__(self, profile):
        self.devices = {}   # plane name -> [(name, start, end)]
        self.host = []      # (name, start, end)
        for plane in profile.planes:
            if plane.name.startswith("/device:") and "TPU" in plane.name \
                    and "NON_CORE" not in plane.name.upper():
                ops = [(op_name(e.name), e.start_ns, e.end_ns)
                       for line in plane.lines if line.name == OP_LINE
                       for e in line.events]
                if ops:
                    self.devices[plane.name] = ops
            elif plane.name.startswith("/host:"):
                self.host.extend((e.name, e.start_ns, e.end_ns)
                                 for line in plane.lines
                                 for e in line.events
                                 if not e.name.startswith("$"))
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if spans:
            self.t0, self.t1 = min(s for s, _ in spans), max(e for _, e in spans)
        elif self.devices:
            self.t0, self.t1 = self.extent()
        else:
            self.t0 = self.t1 = 0.0

    @classmethod
    def from_dir(cls, log_dir: str) -> "Reduction":
        from jax.profiler import ProfileData

        return cls(ProfileData.from_file(find_xplane(log_dir)))

    def extent(self):
        """(first start, last end) of the device ops, in ns."""
        ops = [o for v in self.devices.values() for o in v]
        if not ops:
            return None
        return min(s for _, s, _ in ops), max(e for _, _, e in ops)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, s, e):
        return max(s, self.t0), min(e, self.t1)

    def _busy(self, ops):
        iv = [self._clip(s, e) for _, s, e in ops]
        return _merge([(s, e) for s, e in iv if e > s])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices traced."""
        if not self.devices:
            return 0.0
        total = sum(e - s for ops in self.devices.values()
                    for s, e in self._busy(ops))
        return total * 1e-9 / len(self.devices)

    def op_time_s(self, patterns) -> float:
        """Summed device seconds of the ops matching any of ``patterns``
        (regular expressions), averaged over the devices traced."""
        rx = [re.compile(p) for p in patterns]
        total = 0.0
        for ops in self.devices.values():
            for name, s, e in ops:
                if any(r.search(name) for r in rx):
                    s, e = self._clip(s, e)
                    total += max(0.0, e - s)
        return total * 1e-9 / max(1, len(self.devices))

    def top_ops(self, n: int = 10) -> list:
        acc = {}
        for ops in self.devices.values():
            for name, s, e in ops:
                s, e = self._clip(s, e)
                if e > s:
                    acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
        k = max(1, len(self.devices))
        return sorted(([name, t / k] for name, t in acc.items()),
                      key=lambda x: -x[1])[:n]

    def _label(self, t: float) -> str:
        best = None
        for name, s, e in self.host:
            if s <= t <= e and name != WINDOW_SPAN and (
                    best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "no host span"

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps of the first device traced, as
        [label of the host span covering the gap, seconds]."""
        if not self.devices:
            return []
        busy = self._busy(next(iter(self.devices.values())))
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._label((s + e) / 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]
