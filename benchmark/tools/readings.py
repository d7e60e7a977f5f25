"""Readings for the limits of ``correct``, in one process on the chip.

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 3 [--control]

For each seed: one whole run of the cell (set-up, a short window at the
cell's own load, the comparison) and its compared numbers. With
``--control``, also the control: the plain reference itself, computed on
float8 (e4m3) inputs, put in the program's place over every query of the
set, and held to the same comparison. One JSON line per reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def control_numbers(cell: dict, seed: int) -> dict:
    """{way: compared numbers and recall} of the control on ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = cell["config"]
    k = cfg["k"]
    data = run.load_module(f"data/{cfg['data']}.py")
    ref = run.load_module(f"reference/{cfg['reference']}.py")
    corpus, queries = jax.block_until_ready(
        data.make(seed, cfg["n_rows"], cfg["dim"], cfg["n_queries"]))
    qidx = np.arange(cfg["n_queries"], dtype=np.int32)

    def exact(qi, ids):
        return ref.pair_dist(corpus, queries, qi, ids)

    ref_d, _ = ref.knn(corpus, queries, k)
    fetch = max(k, int(cfg.get("k_fetch") or k))
    d8, i8 = ref.knn(corpus, queries, fetch, input_dtype=jnp.float8_e4m3fn)
    ways = {"fp8": (d8[:, :k], i8[:, :k]),
            "fp8_choice": (exact(qidx, i8[:, :k]), i8[:, :k])}
    if fetch > k:
        ex = exact(qidx, i8)
        order = np.argsort(ex, axis=1, kind="stable")[:, :k]
        ways["fp8_refined"] = (np.take_along_axis(ex, order, axis=1),
                               np.take_along_axis(i8, order, axis=1))
    out = {}
    for way, (d, ids) in ways.items():
        numbers, recall = run.check.compare(qidx, d, ids, 0, ref_d, exact,
                                            cell["limits"])
        out[way] = {**numbers, "recall_at_10": recall}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--no-program", action="store_true")
    args = ap.parse_args()
    cell = run.cell_spec(args.workload)
    devs = run.require_devices(cell["chips"])
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        if not args.no_program:
            res = run.run_cell(cell, seed, args.seconds, False, devs,
                               t_start=time.perf_counter())
            print(json.dumps({"reading": "program", "seed": seed,
                              "check": res["check"],
                              "metrics": res["metrics"]}), flush=True)
        if args.control:
            print(json.dumps({"reading": "control", "seed": seed,
                              "check": control_numbers(cell, seed)}),
                  flush=True)


if __name__ == "__main__":
    main()
