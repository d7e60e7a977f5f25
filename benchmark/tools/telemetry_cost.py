"""What the program's telemetry costs a batch cell, in one process on the
chip: the same index and batches run in windows with telemetry off, with
telemetry on and the profiler off, and with both on (the traced run).

    python3 benchmark/tools/telemetry_cost.py --workload <cell> \
        --seed <n> --seconds 8 [--rounds 2] [--out <file.jsonl>]

Each round runs the three settings, the second round in reverse order,
so a drift of the host is not read as a cost. Per window it prints one
JSON line: ``qps`` and the mean host call of a batch (``host_ms``: the
call into the search entry to its return), over the whole window, and for
the traced setting over the traced slice alone (``slice_qps``,
``slice_host_ms``) with the cell's per-layer metrics read from that
slice. Then the host cost of one empty span with telemetry on
(``span_us``), and, from the first traced slice, the stats of a few device
ops (which stat carries a ``jax.named_scope``) under ``op_stats``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402
import trace_reduce  # noqa: E402


def _emit(rec: dict, out) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def _host_ms(calls) -> float:
    return 1e3 * sum(r - c for c, r, _ in calls) / max(1, len(calls))


def _op_stats(log_dir: str, n: int = 8) -> list:
    """Name and stats of the first ``n`` distinct device ops of the first
    TPU plane traced."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_reduce.find_xplane(log_dir))
    seen, out = set(), []
    for plane in pd.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OP_LINE:
                continue
            for e in line.events:
                name = trace_reduce.op_name(e.name)
                if name in seen:
                    continue
                seen.add(name)
                out.append({"op": name, "line": line.name,
                            "stats": {k: str(v)[:200] for k, v in e.stats}})
                if len(out) >= n:
                    return out
        break
    return out


def _span_us(n: int = 20000) -> float:
    """Host microseconds of one empty span with telemetry on."""
    from raft_tpu import obs

    obs.enable()
    t = time.perf_counter()
    for _ in range(n):
        with obs.record_span("bench::probe"):
            pass
    return 1e6 * (time.perf_counter() - t) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    from raft_tpu import obs

    cell = run.cell_spec(args.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    try:
        devs = run.require_devices(cell["chips"])
    except run.NoChip as e:
        print(f"telemetry_cost.py: {e}", file=sys.stderr)
        return 3
    run.enable_compile_cache()
    data = run.load_module(f"data/{cfg['data']}.py")
    system_mod = run.load_module(f"systems/{cfg['family']}.py")
    loop = run.load_module(f"loops/{traffic['loop']}.py")
    corpus, queries = jax.block_until_ready(
        data.make(args.seed, cfg["n_rows"], cfg["dim"], cfg["n_queries"]))
    import numpy as np

    index = system_mod.build(corpus, cfg, args.seed)
    system = {"batch_search": system_mod.batch_search(index, corpus, cfg)}
    obs.enable()
    state = loop.prepare(system, np.asarray(queries), traffic, args.seed,
                         args.seconds)
    peaks = run.roofline.peaks_for(devs[0].device_kind)
    order = ["off", "on", "traced"]
    first_trace = True
    for r in range(args.rounds):
        for setting in (order if r % 2 == 0 else order[::-1]):
            if setting == "off":
                obs.disable()
            else:
                obs.enable()
            tracer = run.Tracer(setting == "traced", on_mark=run._counters)
            record = loop.window(state, args.seconds, tracer)
            calls = record["calls"]
            rec = {"workload": args.workload, "round": r,
                   "setting": setting,
                   "qps": loop.end_to_end(record)["qps"],
                   "host_ms": _host_ms(calls), "batches": len(calls)}
            if setting == "traced" and tracer.state == "done":
                m0, m1 = tracer.marks["start"], tracer.marks["stop"]
                rec["slice_qps"] = (m1["n_done"] - m0["n_done"]) \
                    * record["batch"] / (m1["t"] - m0["t"])
                # the slice's first call also holds the profiler's start
                rec["slice_host_ms"] = _host_ms(
                    calls[m0["n_done"] + 1:m1["n_done"]])
                if first_trace:
                    rec["op_stats"] = _op_stats(tracer.dir)
                    first_trace = False
                reduction = tracer.reduce()
                ctx = types.SimpleNamespace(
                    trace=reduction, config=cfg, traffic=traffic,
                    record=record, marks=tracer.marks, counters={},
                    build_s=0.0, peaks=peaks, roofline=run.roofline,
                    log=lambda m: None)
                rec["metrics"] = {
                    m["name"]: run.load_module(
                        f"metrics/{m['name']}.py").read(ctx)
                    for m in cell["per_layer"]}
            elif tracer.dir:
                shutil.rmtree(tracer.dir, ignore_errors=True)
            _emit(rec, args.out)
    _emit({"workload": args.workload, "span_us": _span_us()}, args.out)
    obs.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
