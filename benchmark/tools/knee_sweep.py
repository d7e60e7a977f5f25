"""Find a served configuration's knee: one set-up, then the open loop at
several fixed rates, each for a few seconds, telemetry off.

    python3 benchmark/tools/knee_sweep.py --config sift1m-ivf_flat \
        --seed 5 --seconds 10 --rates 500,1000,2000,4000

Per rate, one JSON line: p50/p99 latency from due time, requests, failures,
the queue's depth over the window (a backlog that grows shows as a rising
depth and a p99 that tracks the window's length) and the generator's
lateness. The knee is the highest rate whose p99 stays within the queue's
``slo_s`` with no growing backlog.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main() -> None:
    import jax
    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    run.require_devices(args.chips)
    run.enable_compile_cache()
    cfg = run.load_json(os.path.join(run.BENCH, "configs",
                                     args.config + ".json"))
    data = run.load_module(f"data/{cfg['data']}.py")
    system = run.load_module(f"systems/{cfg['family']}.py")
    loop = run.load_module("loops/open_poisson.py")
    corpus, queries = jax.block_until_ready(
        data.make(args.seed, cfg["n_rows"], cfg["dim"], cfg["n_queries"]))
    queries_np = np.asarray(queries)
    index = system.build(corpus, cfg, args.seed)
    queue = system.serve(index, queries_np, cfg)
    tracer = run.Tracer(False)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        due = loop.schedule(rate, args.seconds, args.seed + i)
        qidx = np.resize(np.random.default_rng(i).permutation(
            queries_np.shape[0]), due.shape[0])
        state = {"queue": queue, "due": due, "qidx": qidx,
                 "rows": queries_np.astype(np.float32)[qidx],
                 "wait_after_s": 30.0}
        b0 = queue.batches
        queue.start()
        rec = loop.window(state, args.seconds, tracer)
        e2e = loop.end_to_end(rec)
        depth = [d for _, d in rec["depth"]]
        third = max(1, len(depth) // 3)
        print(json.dumps({
            "rate_per_s": rate, **e2e,
            "batches": queue.batches - b0,
            "depth_first_third_mean": float(np.mean(depth[:third])),
            "depth_last_third_mean": float(np.mean(depth[-third:])),
            "depth_max": max(depth),
            "late_p99_ms": 1e3 * float(np.percentile(rec["late_s"], 99))}),
            flush=True)


if __name__ == "__main__":
    main()
