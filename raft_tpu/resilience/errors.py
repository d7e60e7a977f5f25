"""Failure classification: every broad handler routes through one table.

The repo's two worst incidents were both *unclassified* failures: round 5
lost the whole bench window to a wedged TPU backend (``BENCH_r05.json``
rc=124 — a DEADLINE-class hang) and round 4 lost the DEEP-10M section to
``RESOURCE_EXHAUSTED`` near HBM capacity (an OOM-class failure that a
halved tile size would have survived). Both were stamped ``repr(e)[:300]``
and thrown away. "Memory Safe Computations with XLA" (PAPERS.md) argues the
memory-pressure class should be handled structurally; the prerequisite is
telling the classes apart.

:func:`classify` maps a raw exception to one of four kinds:

* ``OOM``       — device/host allocation failure (``RESOURCE_EXHAUSTED``,
  ``MemoryError``): retryable at a REDUCED size (retry.degrade_on_oom).
* ``TRANSIENT`` — connection resets, ``UNAVAILABLE``/``ABORTED`` runtime
  states, interrupted syscalls: retryable as-is with backoff.
* ``DEADLINE``  — budget expiry (``subprocess.TimeoutExpired``, the
  resilience ``Deadline``, cooperative interrupts): NOT retryable inside
  the expired scope; callers surface partial/degraded results.
* ``FATAL``     — everything else (shape errors, bad params, real bugs):
  never retried, always re-raised.

Classification is type-first, then message-pattern (XLA errors cross the
jaxlib boundary as ``XlaRuntimeError`` with a gRPC-style status prefix, so
string matching is the stable contract), then the ``__cause__`` chain —
wrapped errors keep their class.
"""

from __future__ import annotations

import subprocess

from raft_tpu.core.interruptible import InterruptedException

#: the four failure kinds (values are the spelling used in obs counter
#: names: ``resilience.retries.oom``, ``bench.section_error.transient``, …)
OOM = "oom"
TRANSIENT = "transient"
DEADLINE = "deadline"
FATAL = "fatal"

KINDS = (OOM, TRANSIENT, DEADLINE, FATAL)

#: kinds that with_retries may retry as-is (OOM retries only through the
#: size-reducing degradation executor, never verbatim)
RETRYABLE = (TRANSIENT,)

# message patterns, matched case-insensitively against str(exc). Order
# matters: OOM outranks DEADLINE outranks TRANSIENT (an OOM inside a timed
# scope is still an OOM — shrinking the work is the right response).
_OOM_PATTERNS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
    "out_of_memory",
    "allocation failure",
    "failed to allocate",
    "hbm limit",
)
_DEADLINE_PATTERNS = (
    "deadline_exceeded",
    "deadline exceeded",
    "timed out",
    "timeout",
)
_TRANSIENT_PATTERNS = (
    "unavailable",
    "aborted",
    "connection reset",
    "connection refused",
    "connection closed",
    "broken pipe",
    "socket closed",
    "temporarily unavailable",
    "try again",
    "transient",
)

# exception type NAMES matched without importing their defining modules
# (jaxlib's XlaRuntimeError moves between modules across jax versions; the
# name is the stable part)
_DEADLINE_TYPE_NAMES = {"DeadlineExceeded", "TimeoutExpired", "TimeoutError"}


def _classify_one(exc: BaseException) -> str:
    """Classify one exception, ignoring its cause chain."""
    if isinstance(exc, MemoryError):
        return OOM
    if isinstance(exc, (subprocess.TimeoutExpired, TimeoutError)):
        return DEADLINE
    if isinstance(exc, InterruptedException):
        # a cooperative cancel is a budget decision by another thread —
        # handled like an expired deadline (stop, surface partials), never
        # retried
        return DEADLINE
    if isinstance(exc, ConnectionError):  # reset / refused / broken pipe
        return TRANSIENT
    if isinstance(exc, InterruptedError):  # EINTR
        return TRANSIENT
    if type(exc).__name__ in _DEADLINE_TYPE_NAMES:
        return DEADLINE
    msg = str(exc).lower()
    if any(p in msg for p in _OOM_PATTERNS):
        return OOM
    if any(p in msg for p in _DEADLINE_PATTERNS):
        return DEADLINE
    if any(p in msg for p in _TRANSIENT_PATTERNS):
        return TRANSIENT
    return FATAL


def classify(exc: BaseException) -> str:
    """Map ``exc`` to ``OOM | TRANSIENT | DEADLINE | FATAL``.

    Walks a bounded ``__cause__`` chain so an EXPLICITLY wrapped
    ``RESOURCE_EXHAUSTED`` (``raise X from oom``) still classifies as OOM
    instead of FATAL. The implicit ``__context__`` chain is deliberately
    NOT walked: a genuine bug raised while *handling* a retryable error
    must stay FATAL, not inherit the retryable class and get re-run.
    """
    seen = 0
    cur: BaseException | None = exc
    while cur is not None and seen < 5:
        kind = _classify_one(cur)
        if kind != FATAL:
            return kind
        cur = cur.__cause__
        seen += 1
    return FATAL


def is_retryable(kind: str) -> bool:
    """True for kinds :func:`~raft_tpu.resilience.retry.with_retries` may
    re-invoke verbatim (OOM is recoverable too, but only through the
    size-reducing degradation executor)."""
    return kind in RETRYABLE
