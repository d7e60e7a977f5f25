"""Look for process-wide stalls on the chip's host: a Python thread asks to
sleep 1 ms at a time and records by how much each sleep overran.

    python3 benchmark/tools/stall_probe.py --seconds 60 [--jax]

Without ``--jax`` the process never touches JAX: what stalls then is the
machine. With ``--jax`` the TPU backend is initialised first and a second
thread keeps one small program running back to back, as a served queue's
worker does. Prints the overruns above 20 ms with their times.
"""

from __future__ import annotations

import argparse
import json
import threading
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--jax", action="store_true")
    args = ap.parse_args()
    stop = threading.Event()
    worker = None
    if args.jax:
        import jax
        import jax.numpy as jnp
        import numpy as np

        f = jax.jit(lambda x: (x @ x.T).sum(axis=1))
        x = jnp.ones((64, 128), jnp.float32)
        np.asarray(f(x))

        def work():
            while not stop.is_set():
                np.asarray(f(x))

        worker = threading.Thread(target=work)
        worker.start()
    t0 = time.perf_counter()
    over = []
    while time.perf_counter() - t0 < args.seconds:
        t = time.perf_counter()
        time.sleep(0.001)
        late = time.perf_counter() - t - 0.001
        if late > 0.02:
            over.append([round(t - t0, 3), round(late * 1e3, 1)])
    stop.set()
    if worker is not None:
        worker.join()
    print(json.dumps({"jax": args.jax, "seconds": args.seconds,
                      "overruns_over_20ms": over}), flush=True)


if __name__ == "__main__":
    main()
