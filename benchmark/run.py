"""Run one benchmark cell on the chip and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``configs/<config>.json``: the corpus, the index and the search), its
traffic mix (``traffic/<mix>.json``, whose ``loop`` names the generator in
``loops/``) and its limits (``limits/<cell>.json``). The configuration
names its data generator (``data/``), its system adapter (``systems/``) and
its plain reference (``reference/``); each per-layer metric is read by
``metrics/<metric>.py``. Adding a cell, a configuration, a mix or a metric
adds files and entries; nothing here changes.

A run: device check (a TPU with as many chips as the cell asks, or exit 3
with no result), corpus and queries made on the device from the seed, index
build, warm-up of exactly the shapes the window uses (``setup_s``), the
measured window, the device's peak memory, then the exact reference over
every answer the window returned (``check.py``). With ``--trace 1`` the
program's telemetry is on and a slice of the window is traced; the result
then carries the cell's per-layer metrics instead of its end-to-end ones.

The last line of stdout is one JSON object; the numbers that decided
``correct`` are also the last lines of stderr.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_START_S, TRACE_SECONDS = 1.0, 3.0

for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import check  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(rel: str):
    """A module under the benchmark's directory, by relative path (its
    name may hold dots, as metric names do)."""
    path = os.path.join(BENCH, rel)
    name = "bench_" + rel.replace("/", "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, spec: dict = None) -> dict:
    """Everything one cell needs, found by the names in ``BENCHMARK.json``."""
    spec = spec or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    return assemble(workload, cell["config"], cell["traffic"],
                    int(cell["chips"]), spec)


def assemble(name: str, config: str, traffic: str, chips: int, spec: dict,
             limits: str = None) -> dict:
    """A cell from its parts' names: configuration, traffic mix and limits
    (``limits/<name>.json`` unless another is named); the metrics are those
    ``spec`` gives ``name``."""
    entry = {c["name"]: c for c in spec["configs"]}[config]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a list of cells goes to every cell that
    # reports the end-to-end metric it moves
    per_layer = [m for m in spec["per_layer"] if name in m.get(
        "workloads", [name] if m["moves"] in e2e_names else [])]
    return {"name": name, "chips": chips,
            "config": load_json(os.path.join(ROOT, entry["file"])),
            "traffic": load_json(os.path.join(BENCH, "traffic",
                                              traffic + ".json")),
            "limits": load_json(os.path.join(BENCH, "limits",
                                             (limits or name) + ".json")),
            "end_to_end": e2e, "per_layer": per_layer}


def require_devices(chips: int):
    """The cell's TPU devices; anything else raises :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> None:
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs compiled or loaded from the cache while armed."""

    def __init__(self):
        import jax.monitoring

        self.armed, self.events = False, {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and ("backend_compile" in event
                           or "cache_retrieval" in event):
            self.events[event] = self.events.get(event, 0) + 1


class Tracer:
    """Traces ``TRACE_SECONDS`` of the window, from ``TRACE_START_S``
    after its start; the loops call :meth:`tick` between units of work."""

    def __init__(self, enabled: bool, on_mark=None):
        self.enabled, self.on_mark = enabled, on_mark
        self.state, self.marks, self.dir, self._ann = "idle", {}, None, None

    def _mark(self, name, n_done):
        self.marks[name] = {"t": time.perf_counter(), "n_done": n_done,
                            "epoch": time.time(),
                            "counters": self.on_mark() if self.on_mark
                            else {}}

    def tick(self, elapsed: float, n_done) -> None:
        import jax

        if not self.enabled:
            return
        if self.state == "idle" and elapsed >= TRACE_START_S \
                and elapsed != float("inf"):
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self._ann.__enter__()
            self._mark("start", n_done)
            self.state = "on"
        elif self.state == "on" and elapsed >= TRACE_START_S + TRACE_SECONDS:
            self._mark("stop", n_done)
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self):
        if self.state != "done":
            return None
        try:
            return trace_reduce.Reduction.from_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _counters() -> dict:
    from raft_tpu import obs

    return dict(obs.snapshot()["counters"])


def _delta(after: dict, before: dict, prefixes=("",)) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if any(k.startswith(p) for p in prefixes)
            and v - before.get(k, 0)}


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devs)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devs,
             t_start: float = T_PROCESS, adapt_system=None) -> dict:
    """One run of ``cell``; returns the result object. ``adapt_system``
    (tests only) may wrap the system's entry points."""
    import jax
    import numpy as np

    from raft_tpu import obs

    cfg, traffic = cell["config"], cell["traffic"]
    data = load_module(f"data/{cfg['data']}.py")
    system_mod = load_module(f"systems/{cfg['family']}.py")
    loop = load_module(f"loops/{traffic['loop']}.py")
    ref = load_module(f"reference/{cfg['reference']}.py")
    peaks = roofline.peaks_for(devs[0].device_kind) if trace else None
    compiles = CompileCounter()

    obs.enable()  # the warm-up reads which engines ran
    corpus, queries = jax.block_until_ready(
        data.make(seed, cfg["n_rows"], cfg["dim"], cfg["n_queries"]))
    queries_np = np.asarray(queries)
    t = time.perf_counter()
    index = system_mod.build(corpus, cfg, seed)
    build_s = time.perf_counter() - t
    system = {"batch_search": system_mod.batch_search(index, corpus, cfg)}
    if hasattr(system_mod, "serve"):
        system["serve"] = lambda: system_mod.serve(index, queries_np, cfg)
    if adapt_system is not None:
        system = adapt_system(system)
    c0 = _counters()
    state = loop.prepare(system, queries_np, traffic, seed, seconds)
    engines = _delta(_counters(), c0, system_mod.ENGINE_COUNTERS)
    if not trace:
        obs.disable()
    from raft_tpu.obs import compile as obs_compile

    traces0 = obs_compile.trace_count()
    tracer = Tracer(trace, on_mark=_counters)
    compiles.armed = True
    setup_s = time.perf_counter() - t_start
    c_window = _counters()
    # the set-up's objects (traced programs, the index, the data) leave the
    # collector's scans, as a server freezes them once after its warm-up;
    # what the window allocates is collected as usual and its pauses count
    gc.collect()
    gc.freeze()
    try:
        record = loop.window(state, seconds, tracer)
    finally:
        gc.unfreeze()
    counters = _delta(_counters(), c_window)
    compiles.armed = False
    e2e = loop.end_to_end(record)
    log(f"setup: build_s={build_s} setup_s={setup_s} engines={engines}")
    log(f"window: programs_compiled_or_loaded={compiles.events} "
        f"program_retraces={obs_compile.trace_count() - traces0}")
    if "late_s" in record:
        late = record["late_s"]
        stalls = [round(float(t), 3) for t in record["due_s"][late > 0.05]]
        log(f"generator lateness: p50_ms={1e3 * float(np.median(late))} "
            f"p99_ms={1e3 * float(np.percentile(late, 99))} "
            f"max_ms={1e3 * float(late.max())} depth_max="
            f"{max((d for _, d in record['depth']), default=0)} "
            f"late_over_50ms_at_s={stalls[:20]}")
    if record.get("calls"):
        ms = 1e3 * np.array([done - call for call, _, done in record["calls"]])
        med = float(np.median(ms))
        log(f"batches: n={ms.size} ms_p50={med} ms_p90="
            f"{float(np.percentile(ms, 90))} ms_max={float(ms.max())} "
            f"over_1.5x_median={int((ms > 1.5 * med).sum())}")
    memory_peak = _peak_bytes(devs)
    reduction = tracer.reduce()
    obs.disable()

    qidx, d, ids, missing = loop.answers(state, record)
    del index, system, state
    gc.collect()
    t = time.perf_counter()
    ref_d, _ = ref.knn(corpus, queries, cfg["k"])
    numbers, recall = check.compare(
        qidx, d, ids, missing, ref_d,
        lambda qi, ii: ref.pair_dist(corpus, queries, qi, ii),
        cell["limits"])
    log(f"check: answers={qidx.shape[0]} missing={missing} "
        f"reference_s={time.perf_counter() - t}")
    e2e["recall_at_10"] = recall
    e2e["setup_s"] = setup_s
    log("end_to_end: " + json.dumps(
        {k: v for k, v in e2e.items() if k not in ("attempted", "failed")}))

    result = {"correct": check.passed(numbers),
              "attempted": int(e2e["attempted"]),
              "failed": int(e2e["failed"]), "metrics": {},
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs),
                         "memory_peak_bytes": memory_peak}}
    if not trace:
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        # what a per-layer metric reader may read
        ctx = types.SimpleNamespace(
            trace=reduction, config=cfg, traffic=traffic, record=record,
            marks=tracer.marks, counters=counters, build_s=build_s,
            peaks=peaks, roofline=roofline, log=log)
        for m in cell["per_layer"]:
            value = load_module(f"metrics/{m['name']}.py").read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduction is not None:
            log(f"trace: devices={ {k: len(v) for k, v in reduction.devices.items()} } "
                f"host_events={len(reduction.host)} window_ns="
                f"{(reduction.t0, reduction.t1)} device_ops_ns="
                f"{reduction.extent()}")
            result["device"]["busy_s"] = reduction.busy_s
            result["device"]["window_s"] = reduction.window_s
            result["breakdown"] = {"device_ops": reduction.top_ops(10),
                                   "idle_gaps": reduction.idle_gaps(10)}
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell_spec(args.workload)
    try:
        devs = require_devices(cell["chips"])
    except NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr, flush=True)
        return 3
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    for name, v in result["check"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
