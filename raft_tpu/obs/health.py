"""Device-health probe: "can JAX init + run one tiny op on this platform?"

Round 5 lost an entire bench round to a wedged backend: ``jax.devices()``
hung at 0% CPU inside the measurement child and the attempt burned its full
2,500 s window (``BENCH_r05.json`` rc=124). The failure mode is backend
*initialization* hanging — unkillable from inside the process, invisible
until the watchdog fires. So the probe is subprocess-isolated and
hard-bounded: a fresh child imports jax, runs one tiny matmul, and prints a
sentinel; the parent waits at most ``timeout`` seconds (clamped to
:data:`MAX_TIMEOUT`) and kills the child on overrun. A dead backend costs
~20 s instead of a round of evidence.

A chip belongs to one process at a time, so the subprocess probe is for a
parent that has NOT touched JAX (bench.py's orchestrator, which never does).
A process that already holds the chip asks :func:`in_process` instead: a
child could not open the chip, or would quietly start on the CPU.

Import-light on purpose (no jax at module level): bench.py's orchestrator
calls this before it ever touches a backend.

Standalone: ``python -m raft_tpu.obs.health [--platform cpu] [--timeout 20]``
prints the report as JSON and exits 0 (healthy) / 1 (unhealthy).
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from dataclasses import asdict, dataclass
from typing import Optional

# Hard ceiling on any single probe, whatever the caller asks for: the whole
# point is bounding time-to-verdict.
MAX_TIMEOUT = 30.0

_SENTINEL = "RAFT_TPU_HEALTH_OK"

_CHILD_CODE = (
    "import jax, jax.numpy as jnp\n"
    "x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)\n"
    "v = float(jnp.sum(x @ x.T))\n"
    "print('" + _SENTINEL + "', jax.devices()[0].platform, v, flush=True)\n"
)


@dataclass
class HealthReport:
    healthy: bool
    platform: str  # platform requested ("default" = ambient)
    backend: str  # platform the child actually initialized ("" if unknown)
    elapsed_s: float
    reason: str  # "" when healthy

    def as_dict(self) -> dict:
        return asdict(self)


def probe(
    platform: str = "default",
    timeout: float = 20.0,
    child_code: Optional[str] = None,
) -> HealthReport:
    """Run the health check in a fresh bounded subprocess.

    ``platform``: "default" probes whatever backend the ambient environment
    selects (the TPU when one is attached); "cpu" probes the CPU backend
    (``JAX_PLATFORMS=cpu`` in the child). ``child_code`` overrides the
    child program (tests use it to simulate a hanging backend).
    """
    import os

    timeout = min(float(timeout), MAX_TIMEOUT)
    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    code = child_code if child_code is not None else _CHILD_CODE

    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return HealthReport(
            False, platform, "", round(time.monotonic() - t0, 2),
            f"probe timed out after {timeout:g}s "
            "(backend init or first op hang)",
        )
    elapsed = round(time.monotonic() - t0, 2)
    for line in (proc.stdout or "").splitlines():
        if line.startswith(_SENTINEL):
            parts = line.split()
            backend = parts[1] if len(parts) > 1 else ""
            return HealthReport(True, platform, backend, elapsed, "")
    return HealthReport(
        False, platform, "", elapsed,
        f"probe child rc={proc.returncode}; "
        f"stderr: {(proc.stderr or '')[-500:]}",
    )


def in_process() -> HealthReport:
    """The verdict for the backend THIS process already holds: one tiny
    matmul on its default device, timed. For callers that own the chip,
    where a subprocess :func:`probe` cannot open it."""
    import jax
    import jax.numpy as jnp

    t0 = time.monotonic()
    try:
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        jax.block_until_ready(x @ x.T)
        backend = jax.devices()[0].platform
    except Exception as e:
        from raft_tpu import resilience

        return HealthReport(False, "default", "",
                            round(time.monotonic() - t0, 2),
                            f"{resilience.classify(e)}: {e!r}"[:500])
    return HealthReport(True, "default", backend,
                        round(time.monotonic() - t0, 2), "")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--platform", default="default",
                    help='"default" (ambient backend) or "cpu"')
    ap.add_argument("--timeout", type=float, default=20.0,
                    help=f"seconds before the probe is killed "
                         f"(clamped to {MAX_TIMEOUT:g})")
    args = ap.parse_args(argv)
    report = probe(args.platform, args.timeout)
    print(json.dumps(report.as_dict()))
    return 0 if report.healthy else 1


if __name__ == "__main__":
    sys.exit(main())
