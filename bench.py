"""Headline benchmark — prints ONE JSON line for the driver.

Round-4 metric set (BASELINE.md targets, QPS@recall methodology of
docs/source/raft_ann_benchmarks.md:420-438):

  * IVF-PQ  build+search, SIFT-1M-shaped (1M x 128 fp32, clustered), k=10,
    nlist=1024, nprobe escalated 16..256 until recall@10 >= 0.95 (with
    exact-distance refine re-rank, as the reference harness configures).
    This is the HEADLINE metric; vs_baseline = QPS / 1e6 (the north-star
    1M-QPS-on-v5e-64 target, on ONE chip).
  * IVF-Flat build+search at the same shape, nlist=1024, same nprobe
    escalation and recall gate.
  * brute-force exact kNN QPS (the correctness anchor + round-1 metric).
  * CAGRA build+search at the SAME 1M shape (round-4; was a 100k subset):
    IVF-candidate graph build, graph_degree=64, itopk/width escalated to
    the recall gate.
  * deep10m: 10M x 96 ANN-crossover section — exact chunked-scan baseline
    (the score matrix no longer fits HBM) vs IVF-PQ+refine, plus the
    extrapolated per-chip SIFT-1B share (BASELINE.md:35-37).
  * Real SIFT is used automatically when present under RAFT_TPU_DATA_DIR
    (bench/io.py TEXMEX/big-ann/hdf5 ingestion); the cached synthetic
    ``siftlike`` otherwise, named honestly in the metric.

Recall is measured with stats.neighborhood_recall (device-side, the
stats/neighborhood_recall.cuh analog) against exact brute-force ground truth.

Timing: every timed window ends in ``jax.block_until_ready``, which waits
for the device on a directly attached TPU; R calls are dispatched
back-to-back and the window closes on the last one.

Failure hardening (round-2; round-6, ISSUE 1): the parent stays off JAX —
the measuring child is the one process that holds the chip — and:

  * runs a subprocess-isolated ~20 s DEVICE-HEALTH PROBE (obs/health.py)
    before the child starts, so a backend that cannot initialize fails
    the run in seconds instead of burning the whole window (round 5:
    BENCH_r05.json came back rc=124 with no output at all);
  * runs the measurement in a SUBPROCESS with a hard timeout derived from
    the remaining watchdog budget. A failed chip attempt exits non-zero
    with the chip error: there is no CPU fallback, and a CPU number is
    never printed in place of a chip one;
  * has the child CHECKPOINT every completed suite section (plus periodic
    heartbeats) to ``results/bench_progress.jsonl`` (bench/progress.py), so
    when the attempt fails the parent — and the belt-and-braces watchdog —
    still print the chip's completed sections (``"salvaged": true``, with
    the error) before exiting non-zero. ``scripts/bench_salvage.py`` does
    the same offline for a run the driver killed outright.

Flags: ``--heartbeat PATH`` (default results/bench_progress.jsonl),
``--no-heartbeat``, ``--skip-health``. Child knobs for tests:
``RAFT_TPU_BENCH_TINY=1`` shrinks every section to smoke-test scale;
``RAFT_TPU_BENCH_SECTIONS=brute_force,ivf_flat`` runs a subset (brute force
always runs — it is the ground-truth anchor);
``RAFT_TPU_BENCH_INDEX_CACHE=1`` (or a directory path) persists each built
index through the v2 crash-safe snapshot path between the build and search
sections, so a wedged search window no longer costs the build.

Telemetry (round 8): children run with obs enabled — search sections record
per-batch latency histograms (p50/p90/p99 upper bounds ride the metric
line) and each child writes a process-stamped metrics snapshot + Perfetto
trace to ``RAFT_TPU_BENCH_METRICS_DIR`` / ``RAFT_TPU_BENCH_TRACE_DIR``
(parent default: results/metrics, results) through bench/progress.py's
fsync'd channel; the parent folds the per-process metric files into
``results/metrics_fleet.json`` (obs/aggregate). Diff rounds with
``scripts/bench_compare.py``.
"""

import json
import os
import subprocess
import sys
import threading
import time
import traceback

WATCHDOG_SECONDS = float(os.environ.get("RAFT_TPU_BENCH_TIMEOUT", "2900"))
TPU_ATTEMPT_SECONDS = float(os.environ.get("RAFT_TPU_BENCH_TPU_TIMEOUT", "2500"))
# parent-side reserve: health probe (<=30 s) + the subprocess spawn +
# salvage/emit tail — kept OUT of the attempt window so the watchdog never
# fires before the parent can report the attempt's outcome
ORCH_OVERHEAD_SECONDS = 45.0
MIN_ATTEMPT_SECONDS = 120.0
# full probe bound (obs/health.MAX_TIMEOUT): a cold backend can spend
# >20 s on jax init, and a false "unhealthy" would fail a good round
HEALTH_PROBE_SECONDS = 30.0
_REPO = os.path.dirname(os.path.abspath(__file__))

_HB_PATH = None  # set by main(); _fail salvages from it before surrendering
_PROGRESS = None  # progress module, file-path-loaded by main() pre-watchdog


def _load_by_path(modname: str, *relpath: str):
    """Load a repo module by FILE PATH without executing raft_tpu/__init__:
    the parent — and especially the watchdog thread's _fail — must never
    block on the import lock of a partially-initialized raft_tpu/jax
    package (the exact wedge class this orchestration guards against)."""
    import importlib.util

    path = os.path.join(_REPO, *relpath)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    # registered BEFORE exec: dataclasses (health.HealthReport) resolve
    # their defining module through sys.modules at class-creation time
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def _emit(payload: dict) -> None:
    sys.stdout.write(json.dumps(payload) + "\n")
    sys.stdout.flush()


def _fail(reason: str, code: int = 1) -> None:
    # last resort before bench_error: a salvaged line from the checkpoint
    # side-channel still carries a real number of record (_PROGRESS was
    # loaded before the watchdog started — no imports happen here)
    if _HB_PATH and _PROGRESS is not None:
        try:
            line = _PROGRESS.salvage(
                _PROGRESS.read_progress(_HB_PATH), source=_HB_PATH)
            if line is not None:
                line["error"] = reason[-1000:]
                _emit(line)
                os._exit(code)
        except Exception:
            pass
    _emit(
        {
            "metric": "bench_error",
            "value": 0.0,
            "unit": "QPS",
            "vs_baseline": 0.0,
            "error": reason[-2000:],
        }
    )
    # os._exit: safe from any thread, skips atexit/backends that may be wedged.
    os._exit(code)


# ---------------------------------------------------------------------------
# Child mode: the actual measurement
# ---------------------------------------------------------------------------

def _force(x):
    """Wait for every dispatched computation behind ``x``."""
    import jax

    return jax.block_until_ready(x)


def _observe_batch_latency(run, queries, reps: int, hist: str) -> None:
    """Per-batch latency pass: time each rep INDIVIDUALLY (dispatch +
    forced completion — back-to-back amortization cannot see per-batch
    latency) into histogram ``hist``, so metric lines carry p50/p90/p99
    upper bounds, not just means. The ONE timing protocol shared by every
    section (a second copy could silently drift its percentiles)."""
    from raft_tpu import obs

    for _ in range(reps):
        t1 = time.perf_counter()
        v, _ = run(queries)
        _force(v)
        obs.observe(hist, time.perf_counter() - t1)


def _time_qps(run, queries, reps: int, hist: str = "") -> float:
    """Amortized wall-clock QPS of `run(queries)` over `reps` dispatches.

    When telemetry is on and ``hist`` names a histogram, a SECOND pass
    (:func:`_observe_batch_latency`) records per-batch latency; the QPS
    number still comes from the amortized loop, unchanged."""
    from raft_tpu import obs

    v, _ = run(queries)
    _force(v)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        v, _ = run(queries)
    _force(v)  # drains the dispatch queue
    dt = (time.perf_counter() - t0) / reps
    if hist and obs.enabled():
        _observe_batch_latency(run, queries, reps, hist)
    return queries.shape[0] / dt


def section_error(e):
    """Classified section-failure stamp (ISSUE 3): every section guard
    routes through resilience.classify so the failure CLASS survives into
    the metric line and the obs counters, not just repr(e). Lazy imports:
    bench.py's parent mode must stay off the raft_tpu/jax import lock, so
    this only runs inside the measuring child."""
    from raft_tpu import obs, resilience

    kind = resilience.classify(e)
    obs.add(f"bench.section_error.{kind}")
    return {"error": repr(e)[:300], "kind": kind}


def _sections_filter():
    """RAFT_TPU_BENCH_SECTIONS="ivf_flat,cagra" → the enabled subset; None
    means everything. brute_force ignores this (it is the gt anchor)."""
    raw = os.environ.get("RAFT_TPU_BENCH_SECTIONS", "").replace(" ", "")
    only = {s for s in raw.split(",") if s}
    return only or None


def run_suite():
    import jax
    import jax.numpy as jnp
    import numpy as np

    t_suite0 = time.perf_counter()

    def elapsed():
        return time.perf_counter() - t_suite0

    from raft_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # round-3: cold XLA compiles dominated builds

    from raft_tpu import obs
    from raft_tpu import resilience
    from raft_tpu import stats
    from raft_tpu.bench import progress as prog
    from raft_tpu.bench.datasets import sift_like
    from raft_tpu.neighbors import (brute_force, cagra, ivf_bq, ivf_flat,
                                    ivf_pq, refine)
    from raft_tpu.obs import costmodel as obs_costmodel
    from raft_tpu.obs import memory as obs_memory
    from raft_tpu.obs import roofline as obs_roofline

    # telemetry ON for the whole measured child (round-8): the bench window
    # exists to answer where the time went, so spans/counters/latency
    # histograms must populate — the per-call overhead is host-side
    # microseconds against ms-scale batches, and the per-phase completion
    # barriers it enables (cagra _sync) are exactly what build_phases_s
    # needs to be comparable across rounds
    obs.enable()

    def latency_percentiles(hist_name):
        """p50/p90/p99 upper bounds of one batch-latency histogram, for the
        section's metric line (≤2× bucket-bound error, obs/aggregate)."""
        h = obs.snapshot()["histograms"].get(hist_name) or {}
        return {k: h[k] for k in ("p50_ub", "p90_ub", "p99_ub") if k in h}


    on_cpu = jax.devices()[0].platform == "cpu"
    tiny = bool(os.environ.get("RAFT_TPU_BENCH_TINY"))
    if tiny:
        # smoke-test sizing (tests/test_obs.py): every section in seconds
        N, DIM, Q, K, REPS, NLIST = 2_000, 32, 200, 10, 1, 64
        NPROBE0, CAGRA_N = 8, 1_000
    elif on_cpu:
        # CPU sizing (RAFT_TPU_BENCH_CHILD=cpu, the tests): same pipeline,
        # small enough to finish on host cores
        N, DIM, Q, K, REPS, NLIST = 100_000, 64, 1_000, 10, 2, 256
        NPROBE0, CAGRA_N = 16, 20_000
    else:
        N, DIM, Q, K, REPS, NLIST = 1_000_000, 128, 10_000, 10, 5, 1024
        # escalation starts at 16 (round-4: recall 0.96 ≥ the 0.95 gate at
        # half the probe mass — 149K/138K QPS for Flat/PQ, both above the
        # 129K brute-force anchor); ×2 steps cover the old 32..256 range
        NPROBE0, CAGRA_N = 16, 100_000

    only = _sections_filter()

    def section_on(name):
        return only is None or name in only

    extras = {"n": N, "dim": DIM, "q": Q, "k": K, "n_lists": NLIST,
              "dataset": f"siftlike-{N // 1000}k-{DIM}"}

    # --- real SIFT when present, else cached synthetic (honest naming) -----
    # (bench/io.py resolves TEXMEX / big-ann / hdf5 layouts under
    # RAFT_TPU_DATA_DIR; no egress on this machine, so presence is up to
    # the operator — the fallback is the siftlike generator)
    from raft_tpu.bench.datasets import data_dir
    from raft_tpu.bench.io import load_real_dataset

    real = None
    if not on_cpu:
        try:
            real = load_real_dataset(data_dir(), "sift", max_rows=N)
        except Exception as e:
            # classified fallback-to-synthetic (the kind disambiguates a
            # transient read from a genuinely absent dataset)
            extras["real_dataset_error"] = section_error(e)
            real = None
    if real is not None:
        base, qs, _ = real
        dataset = jnp.asarray(np.asarray(base, np.float32))
        queries = jnp.asarray(np.asarray(qs[:Q], np.float32))
        N, DIM = int(dataset.shape[0]), int(dataset.shape[1])
        Q = int(queries.shape[0])
        extras.update(n=N, dim=DIM, q=Q, dataset="sift-real")
    else:
        data_u8, queries_u8 = sift_like(N, DIM, Q)
        dataset = jnp.asarray(data_u8, jnp.float32)
        queries = jnp.asarray(queries_u8, jnp.float32)

    # --- v2 index-snapshot cache (ISSUE 7): persist each built index the
    # moment its build lands, so a wedged SEARCH window costs the searches,
    # not the build — the remaining round-5 exposure class. Opt-in:
    # RAFT_TPU_BENCH_INDEX_CACHE=1 (default dir results/index_cache) or a
    # directory path. Saves ride the v2 container (atomic, CRC'd), so a
    # kill mid-save leaves the previous cache entry; a corrupt/stale entry
    # fails its integrity check at load and the section rebuilds.
    cache_env = os.environ.get("RAFT_TPU_BENCH_INDEX_CACHE", "").strip()
    cache_dir = ""
    if cache_env and cache_env.lower() not in ("0", "false", "off", "no"):
        cache_dir = (os.path.join("results", "index_cache")
                     if cache_env.lower() in ("1", "true", "on", "yes")
                     else cache_env)
        os.makedirs(cache_dir, exist_ok=True)

    def cache_path(name):
        # the key carries the build CONFIG, not just the dataset shape: a
        # stale-config entry silently benchmarked as the current config
        # would corrupt the round's numbers worse than a rebuild costs
        return (os.path.join(cache_dir, f"{name}_{extras['dataset']}.raft")
                if cache_dir else "")

    def cache_load(name, loader):
        """Cached index or None. Classified: a corrupt cache entry (torn
        pre-v2 file, stale shape) is reported and rebuilt, never fatal."""
        path = cache_path(name)
        if not (path and os.path.exists(path)):
            return None
        try:
            idx = loader(path)
            obs.add("bench.index_cache.hit")
            return idx
        except Exception as e:
            extras.setdefault("index_cache_errors", {})[name] = \
                section_error(e)
            return None

    def cache_store(name, index):
        """Persist a freshly built index; returns the extras stamp."""
        path = cache_path(name)
        if not path:
            return ""
        try:
            index.save(path)
            obs.add("bench.index_cache.store")
            return "stored"
        except Exception as e:
            extras.setdefault("index_cache_errors", {})[name] = \
                section_error(e)
            return "store_error"

    # --- checkpoint side-channel (bench/progress.py): one JSONL record the
    # moment each section lands, so a mid-suite wedge preserves everything
    # finished so far
    hb = prog.from_env(platform=jax.devices()[0].platform)
    hb.start({"n": N, "dim": DIM, "q": Q, "k": K, "n_lists": NLIST,
              "dataset": extras["dataset"], "tiny": tiny})

    # --- ground truth + brute-force QPS anchor ------------------------------
    hb.set_section("brute_force")
    bf_index = brute_force.build(dataset, metric="sqeuclidean")
    gt_vals, gt_ids = brute_force.search(bf_index, queries, K, select_algo="exact")
    _force(gt_vals)

    def bf_run(qs):
        return brute_force.search(bf_index, qs, K, select_algo="approx")

    bf_qps = _time_qps(bf_run, queries, REPS,
                       hist="bench.brute_force.batch_latency_s")
    bf_recall = float(stats.neighborhood_recall(bf_run(queries)[1], gt_ids))
    extras["brute_force"] = {"qps": round(bf_qps, 1), "recall": round(bf_recall, 4),
                             **latency_percentiles(
                                 "bench.brute_force.batch_latency_s")}
    hb.section("brute_force", extras["brute_force"])

    # --- static-HBM predictor baseline (round 11): the watermark with the
    # shared residents (dataset, queries, gt, brute-force anchor) in place
    # but no section index yet. Each section's predicted_hbm_bytes is this
    # baseline + the static index prediction (+ the dispatch transients
    # where the backend's allocator sees them) — the admission projection
    # (in_use + predicted), validated per section against the measured
    # watermark with bench_compare direction rules (ratio toward 1.0).
    extras["hbm_baseline_bytes"] = int(
        obs_memory.sample("bench.baseline")["bytes_in_use"])

    def hbm_section_start(name):
        """Watermark at section start — the ``in_use`` half of the
        admission projection the section's prediction adds onto."""
        return int(obs_memory.sample(f"bench.{name}.start")["bytes_in_use"])

    def stamp_cost(row, name, index, n_probes, mem0):
        """Predicted-vs-measured HBM accounting for one section:
        ``predicted_index_bytes`` must equal the ``index_bytes`` gauge
        EXACTLY (the static layout model vs the built artifact), and
        ``predicted_hbm_bytes / measured_watermark_bytes`` should sit near
        1.0. Both sides are RESIDENT-state numbers — ``mem0 + static
        index prediction`` vs ``bytes_in_use`` after the section's
        searches — on every backend: dispatch transients are freed by
        sample time (and TPU ``peak_bytes_in_use`` is process-monotonic,
        so it would fold earlier sections' peaks in). The transient
        estimate ships separately (``predicted_dispatch_transient_bytes``,
        what ``check_admission`` projects per dispatch)."""
        row["predicted_index_bytes"] = obs_costmodel.predict_index_bytes(
            **obs_costmodel.index_layout(index))
        est = obs_costmodel.estimate_search(index, q=Q, k=K,
                                            n_probes=n_probes)
        row["predicted_dispatch_transient_bytes"] = est["transient_bytes"]
        mem = obs_memory.sample(f"bench.{name}")
        pred = mem0 + row["predicted_index_bytes"]
        row["predicted_hbm_bytes"] = int(pred)
        row["measured_watermark_bytes"] = int(mem["bytes_in_use"])
        if row["measured_watermark_bytes"]:
            row["hbm_predicted_to_measured"] = round(
                pred / row["measured_watermark_bytes"], 3)
        stamp_roofline(row, name, index, row.get("k_fetch", K), n_probes)

    def stamp_roofline(row, name, index, k_fetch, n_probes):
        """Roofline stamp for one section (ISSUE 12 acceptance: every
        section that stamps ``predicted_index_bytes`` also stamps
        ``mxu_utilization`` / ``bound`` / ``padded_fraction`` /
        ``achieved_gflops``). ``measured_s`` is the MIN of the section's
        per-batch latency histogram — the cleanest forced-completion
        batch; it includes refine + dispatch overhead, so the stamped
        utilization is END-TO-END (a floor on kernel utilization, which
        is the honest per-config efficiency record). ``bound`` is the
        static roofline verdict; on platforms off the peak table it
        reads ``unknown`` and ``peaks_source`` says why."""
        try:
            h = obs.snapshot()["histograms"].get(
                f"bench.{name}.batch_latency_s") or {}
            measured = h.get("min") if h.get("count") else None
            # occupancy rides the dispatch note the search itself made
            # (telemetry is on suite-wide); storage padding from the
            # host-cached lens is the fallback when no kernel planning ran
            rec = (obs_roofline.entries().get(f"{name}.search") or {})
            occ = rec.get("occupancy")
            util = obs_roofline.utilization_search(
                index, q=Q, k=int(k_fetch), n_probes=n_probes,
                measured_s=measured, occupancy=occ)
            row["flops_per_batch"] = util["flops"]
            row["bytes_per_batch"] = util["bytes"]
            row["bound"] = util["bound"]
            row["peaks_source"] = util["peaks_source"]
            for key in ("achieved_gflops", "mxu_utilization",
                        "hbm_bw_utilization", "model_to_measured"):
                if util.get(key) is not None:
                    row[key] = util[key]
            # ONE meaning for padded_fraction across backends and rounds
            # (bench_compare diffs it directionally — a semantics flip
            # between kernel-relative and storage-relative numbers would
            # fake a regression): always the STORAGE padding of the
            # capacity-padded lists. The kernel planner's scan-relative
            # fraction (pow2 fetch blocks, only where kernel planning
            # ran) rides separately as scan_padded_fraction.
            import numpy as _np

            lens = getattr(index, "_lens_np_cache", None)
            if lens is None:
                lens = _np.asarray(index.list_sizes())
            cap = index.n_lists * index.max_list_size
            row["padded_fraction"] = round(
                max(0.0, 1.0 - float(lens.sum()) / cap), 4) if cap else 0.0
            if occ and "padded_row_fraction" in occ:
                row["scan_padded_fraction"] = occ["padded_row_fraction"]
        except Exception as e:
            # a broken stamp must not cost the section's numbers
            row["roofline_error"] = section_error(e)

    def timed_build(build):
        """(index, cold_s, warm_s): cold includes XLA compiles (cached on
        disk across runs); warm rebuilds with the programs hot — the
        steady-state build throughput the reference's numbers describe."""
        t0 = time.perf_counter()
        index = build()
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        index = build()
        return index, round(cold, 1), round(time.perf_counter() - t0, 1)

    def stamp_build(row, entry, cold_s, warm_s, **model_kwargs):
        """Build-phase trajectory stamp (round 17 — previously only search
        was stamped): ``build_s``/``build_warm_s``/``build_rows_per_s``
        plus the static build roofline fields (``build_flops`` /
        ``build_bytes`` / ``build_bound`` + utilizations where the warm
        build time and platform peaks exist). ``build_rows_per_s`` comes
        from the WARM rebuild — the steady-state number XLA compile noise
        can't pollute; a cache-hit section (0.0/0.0) stamps the times but
        no throughput (a load is not a build)."""
        row["build_s"] = cold_s
        row["build_warm_s"] = warm_s
        n_rows = model_kwargs["n"]
        t = warm_s or cold_s
        if t:
            row["build_rows_per_s"] = round(n_rows / t, 1)
        try:
            util = obs_roofline.utilization(entry,
                                            measured_s=(warm_s or None),
                                            **model_kwargs)
            row["build_flops"] = util["flops"]
            row["build_bytes"] = util["bytes"]
            row["build_bound"] = util["bound"]
            for key in ("achieved_gflops", "mxu_utilization",
                        "hbm_bw_utilization"):
                if util.get(key) is not None:
                    row[f"build_{key}"] = util[key]
        except Exception as e:
            row["build_roofline_error"] = section_error(e)

    # --- IVF-Flat at BASELINE config (nlist=1024, nprobe=32, escalating) ----
    # Section guards (ISSUE 3): a failed IVF section must not sink the
    # suite — the headline falls back down flat -> brute force, and the
    # failure ships classified in extras instead of killing the child.
    flat = None
    serving_src_index = None  # kept alive for the serving section's store
    if section_on("ivf_flat"):
        hb.set_section("ivf_flat")
        try:
            mem0 = hbm_section_start("ivf_flat")

            def build_flat():
                idx = ivf_flat.build(dataset, ivf_flat.IvfFlatParams(
                    n_lists=NLIST, kmeans_trainset_fraction=0.2))
                _force(idx.list_norms)
                return idx

            flat_index = cache_load(f"ivf_flat_nl{NLIST}",
                                    ivf_flat.IvfFlatIndex.load)
            flat_cache = "hit"
            if flat_index is None:
                flat_index, cold_s, warm_s = timed_build(build_flat)
                flat_cache = cache_store(f"ivf_flat_nl{NLIST}", flat_index)
            else:
                cold_s = warm_s = 0.0
            for nprobe in (NPROBE0, NPROBE0 * 2, NPROBE0 * 4, NPROBE0 * 8,
                           NPROBE0 * 16):
                vals, ids = ivf_flat.search(flat_index, queries, K, n_probes=nprobe)
                recall = float(stats.neighborhood_recall(ids, gt_ids, vals, gt_vals))
                if flat is None or recall > flat["recall"]:
                    flat = {"nprobe": nprobe, "recall": round(recall, 4)}
                if recall >= 0.95:
                    break
            flat["qps"] = round(_time_qps(
                lambda qs: ivf_flat.search(flat_index, qs, K, n_probes=flat["nprobe"]),
                queries, REPS, hist="bench.ivf_flat.batch_latency_s"), 1)
            flat.update(latency_percentiles("bench.ivf_flat.batch_latency_s"))
            stamp_build(flat, "ivf_flat.build", cold_s, warm_s,
                        n=N, dim=DIM, n_lists=NLIST,
                        train_rows=int(N * 0.2))
            # per-index residency watermark (ISSUE 10): gauge + metric line
            flat["index_bytes"] = obs_memory.record_index(
                "ivf_flat", flat_index)
            # static-HBM predictor validation (ISSUE 11): exact index
            # prediction + admission-projection vs measured watermark
            stamp_cost(flat, "ivf_flat", flat_index, flat["nprobe"], mem0)
            if flat_cache:
                flat["index_cache"] = flat_cache
            extras["ivf_flat"] = flat
            if section_on("serving"):
                serving_src_index = flat_index  # reused, freed there
            else:
                del flat_index
        except Exception as e:
            flat = None
            extras["ivf_flat"] = section_error(e)
        hb.section("ivf_flat", extras["ivf_flat"])

    # --- IVF-PQ at BASELINE config + refine re-rank (the headline) ----------
    pq = None
    if section_on("ivf_pq"):
        hb.set_section("ivf_pq")
        try:
            mem0 = hbm_section_start("ivf_pq")

            def build_pq():
                idx = ivf_pq.build(dataset, ivf_pq.IvfPqParams(
                    n_lists=NLIST, pq_dim=DIM // 2, pq_bits=8,
                    kmeans_trainset_fraction=0.2))
                _force(idx.b_sum)
                return idx

            pq_name = f"ivf_pq_nl{NLIST}_pq{DIM // 2}x8"
            pq_index = cache_load(pq_name, ivf_pq.IvfPqIndex.load)
            pq_cache = "hit"
            if pq_index is None:
                pq_index, cold_s, warm_s = timed_build(build_pq)
                pq_cache = cache_store(pq_name, pq_index)
            else:
                cold_s = warm_s = 0.0
            # over-fetch then exact re-rank (refine-inl.cuh:70 style): escalate
            # nprobe at 4x over-fetch until the recall gate holds, then shrink the
            # over-fetch while the gate still holds — the fetch width sets the
            # in-kernel top-kf cost and the merge width, so the smallest passing
            # K_FETCH is the fastest configuration
            for nprobe in (NPROBE0, NPROBE0 * 2, NPROBE0 * 4, NPROBE0 * 8,
                           NPROBE0 * 16):
                _, cand = ivf_pq.search(pq_index, queries, 4 * K, n_probes=nprobe)
                vals, ids = refine.refine(dataset, queries, cand, K)
                recall = float(stats.neighborhood_recall(ids, gt_ids, vals, gt_vals))
                if pq is None or recall > pq["recall"]:
                    pq = {"nprobe": nprobe, "recall": round(recall, 4), "k_fetch": 4 * K}
                if recall >= 0.95:
                    break
            if pq["recall"] >= 0.95:
                for kf in (2 * K, K):
                    _, cand = ivf_pq.search(pq_index, queries, kf, n_probes=pq["nprobe"])
                    vals, ids = refine.refine(dataset, queries, cand, K)
                    recall = float(stats.neighborhood_recall(ids, gt_ids, vals, gt_vals))
                    if recall < 0.95:
                        break
                    pq.update(recall=round(recall, 4), k_fetch=kf)

            def pq_timed(qs):
                _, cand = ivf_pq.search(pq_index, qs, pq["k_fetch"],
                                        n_probes=pq["nprobe"])
                return refine.refine(dataset, qs, cand, K)

            pq["qps"] = round(_time_qps(
                pq_timed, queries, REPS,
                hist="bench.ivf_pq.batch_latency_s"), 1)
            pq.update(latency_percentiles("bench.ivf_pq.batch_latency_s"))
            stamp_build(pq, "ivf_pq.build", cold_s, warm_s,
                        n=N, dim=DIM, n_lists=NLIST, pq_dim=DIM // 2,
                        train_rows=int(N * 0.2))
            pq["index_bytes"] = obs_memory.record_index("ivf_pq", pq_index)
            stamp_cost(pq, "ivf_pq", pq_index, pq["nprobe"], mem0)
            if pq_cache:
                pq["index_cache"] = pq_cache
            extras["ivf_pq"] = pq
            del pq_index
        except Exception as e:
            pq = None
            extras["ivf_pq"] = section_error(e)
        hb.section("ivf_pq", extras["ivf_pq"])

    # --- IVF-BQ: RaBitQ-style 1-bit codes + exact refine (ROADMAP item 3) --
    # The scan reads rot_dim/8 bytes per probed entry (32× under fp32, 4×
    # under the r04 IVF-PQ configuration's 64 B codes); the recall gate is
    # held by nprobe THEN k_fetch escalation through the exact re-rank.
    # The per-chip capacity rung (after deep10m, where the 1M arrays are
    # freed) replaces the r04 extrapolated SIFT-1B-class number with a
    # MEASURED per_chip_capacity_rows / per_chip_qps pair.
    bq = None
    if section_on("ivf_bq"):
        hb.set_section("ivf_bq")
        try:
            mem0 = hbm_section_start("ivf_bq")

            def build_bq():
                idx = ivf_bq.build(dataset, ivf_bq.IvfBqParams(
                    n_lists=NLIST, kmeans_trainset_fraction=0.2))
                _force(idx.list_scale)
                return idx

            bq_name = f"ivf_bq_nl{NLIST}"
            bq_index = cache_load(bq_name, ivf_bq.IvfBqIndex.load)
            bq_cache = "hit"
            if bq_index is None:
                bq_index, cold_s, warm_s = timed_build(build_bq)
                bq_cache = cache_store(bq_name, bq_index)
            else:
                cold_s = warm_s = 0.0
            def bq_pair(nprobe, kf):
                _, cand = ivf_bq.search(bq_index, queries, kf,
                                        n_probes=nprobe)
                return refine.refine(dataset, queries, cand, K)

            bq = _bq_gate_escalate(
                bq_pair,
                lambda vals, ids: float(stats.neighborhood_recall(
                    ids, gt_ids, vals, gt_vals)),
                K, (NPROBE0, NPROBE0 * 2, NPROBE0 * 4, NPROBE0 * 8,
                    NPROBE0 * 16))

            def bq_timed(qs):
                _, cand = ivf_bq.search(bq_index, qs, bq["k_fetch"],
                                        n_probes=bq["nprobe"])
                return refine.refine(dataset, qs, cand, K)

            bq_timed(queries)  # warm: the one legal trace
            traces0 = ivf_bq.scan_trace_count()
            bq["qps"] = round(_time_qps(
                bq_timed, queries, REPS,
                hist="bench.ivf_bq.batch_latency_s"), 1)
            # steady-state contract: the timed repeats re-dispatch ONE
            # compiled program (check.sh smoke gates this at zero)
            bq["recompiles_during_search"] = \
                ivf_bq.scan_trace_count() - traces0
            bq.update(latency_percentiles("bench.ivf_bq.batch_latency_s"))
            stamp_build(bq, "ivf_bq.build", cold_s, warm_s,
                        n=N, dim=DIM, n_lists=NLIST,
                        train_rows=int(N * 0.2),
                        rot_dim=bq_index.rot_dim, bits=bq_index.bits,
                        rotation_kind=bq_index.rotation_kind)
            bq["index_bytes"] = obs_memory.record_index("ivf_bq", bq_index)
            stamp_cost(bq, "ivf_bq", bq_index, bq["nprobe"], mem0)
            if bq_cache:
                bq["index_cache"] = bq_cache
            # resident-bytes accounting: code bytes are the headline (the
            # aux scalars ride along at 8 B/row, reported separately)
            nb = bq_index.code_bytes_per_row
            bq["code_bytes_per_row"] = nb
            bq["aux_bytes_per_row"] = 8
            bq["pq_code_bytes_per_row"] = DIM // 2  # r04 config: pq_dim=D/2 ×8b
            bq["code_compression_x"] = round((DIM // 2) / nb, 2)
            # CPU preview seeds the per-chip pair from this section; the
            # TPU capacity rung below overwrites it with the large-scale
            # measurement (round-6 CPU-preview precedent)
            bq["per_chip_capacity_rows"] = N
            bq["per_chip_qps"] = bq["qps"]
            bq["per_chip_recall"] = bq["recall"]
            bq["per_chip_measured"] = True
            extras["ivf_bq"] = bq
            del bq_index
        except Exception as e:
            bq = None
            extras["ivf_bq"] = section_error(e)
        hb.section("ivf_bq", extras["ivf_bq"])

    # --- Filtered & hybrid search (round 20) -------------------------------
    # The selectivity ladder (unfiltered / 10% / 1%) on flat + bq, plus the
    # fused dense+sparse rung. Three contracts measured per family:
    # filtered_recall against brute force OVER THE SURVIVORS (what a
    # filtered query means), filtered_to_unfiltered_qps_ratio (push-down
    # means a filter costs VMEM masking + plan widening, never a second
    # scan — the ratio is a standing zero-tolerance gate), and
    # recompiles_during_filtered_search across mask-content mutations at
    # fixed popcount (the zero-recompile contract; pass-rate CHANGES may
    # legitimately retrace through the widened plan, so the ladder mutates
    # permutations of one mask).
    if section_on("filtered"):
        hb.set_section("filtered")
        try:
            from raft_tpu.core.bitset import Bitset
            from raft_tpu.neighbors import hybrid as hybrid_mod
            from raft_tpu.obs import compile as fl_compile

            hbm_section_start("filtered")
            FN = int(min(N, 30_000 if on_cpu else 200_000))
            f_nlist = int(min(NLIST, 64 if tiny else 256))
            fdata = dataset[:FN]
            f_rng = np.random.default_rng(13)
            filt = {"n": FN, "n_lists": f_nlist, "nprobe": NPROBE0}

            def _id_recall(ids, gt_global):
                ids = np.asarray(ids)
                return float(np.mean([
                    len(set(ids[r]) & set(gt_global[r])) / K
                    for r in range(ids.shape[0])]))

            def _survivor_gt(surv):
                bf = brute_force.build(fdata[jnp.asarray(surv)])
                _, gi = brute_force.search(bf, queries, K,
                                           select_algo="exact")
                return surv[np.asarray(gi)]

            fl_index = ivf_flat.build(fdata, ivf_flat.IvfFlatParams(
                n_lists=f_nlist, kmeans_trainset_fraction=0.2))
            fbq_index = ivf_bq.build(fdata, ivf_bq.IvfBqParams(
                n_lists=f_nlist, kmeans_trainset_fraction=0.2))

            def flat_run(f):
                return lambda qs: ivf_flat.search(
                    fl_index, qs, K, n_probes=NPROBE0, filter=f)

            def bq_run(f):
                kf = min(K * 4, 512)

                def run(qs):
                    _, cand = ivf_bq.search(fbq_index, qs, kf,
                                            n_probes=NPROBE0, filter=f)
                    return refine.refine(fdata, qs, cand, K)
                return run

            def flat_traces():
                return (fl_compile.trace_count("ivf_flat.search")
                        + fl_compile.trace_count("ivf_flat.search_ragged"))

            for fam, mk_run, traces in (
                    ("ivf_flat", flat_run, flat_traces),
                    ("ivf_bq", bq_run, ivf_bq.scan_trace_count)):
                row = {}
                base_qps = None
                base_mask = None
                for sel, tag in ((None, "unfiltered"), (0.10, "sel10"),
                                 (0.01, "sel01")):
                    if sel is None:
                        f, surv = None, np.arange(FN)
                    else:
                        mask = f_rng.random(FN) < sel
                        mask[:K] = True  # >= K survivors at any FN
                        f = Bitset.from_mask(mask)
                        surv = np.flatnonzero(mask)
                        if sel == 0.01:
                            base_mask = mask
                    run = mk_run(f)
                    gt_glob = _survivor_gt(surv)
                    _, ids = run(queries)
                    rung = {"qps": round(_time_qps(
                        run, queries, REPS,
                        hist=f"bench.filtered.{fam}.{tag}_latency_s"), 1)}
                    if sel is None:
                        base_qps = rung["qps"]
                        rung["recall"] = round(_id_recall(ids, gt_glob), 4)
                    else:
                        rung["filtered_recall"] = round(
                            _id_recall(ids, gt_glob), 4)
                        rung["selectivity"] = sel
                        if base_qps:
                            rung["filtered_to_unfiltered_qps_ratio"] = \
                                round(rung["qps"] / base_qps, 3)
                    row[tag] = rung
                # zero-recompile: permute the 1% mask (same popcount ->
                # same widened plan) and re-dispatch; any retrace is a
                # contract violation
                t0 = traces()
                for _ in range(3):
                    perm = f_rng.permutation(base_mask)
                    perm[:K] = True
                    vv, _ = mk_run(Bitset.from_mask(perm))(queries)
                    _force(vv)
                row["recompiles_during_filtered_search"] = traces() - t0
                filt[fam] = row

            # hybrid rung: fused dense+sparse vs exact fused ground truth
            vocab, sdim = 1000, 128
            sp_rows = ((f_rng.random((FN, vocab)) < 0.02)
                       * f_rng.random((FN, vocab))).astype(np.float32)
            hyb = hybrid_mod.build(
                np.asarray(fdata), sp_rows,
                ivf_bq.IvfBqParams(n_lists=f_nlist,
                                   metric="inner_product",
                                   kmeans_trainset_fraction=0.2),
                sparse_dim=sdim)
            FQ = int(min(Q, 256))
            qd = np.asarray(queries[:FQ])
            qs_sp = sp_rows[:FQ]
            fused_q = hybrid_mod.fuse_queries(hyb, qd, qs_sp)
            fused_rows = jnp.concatenate(
                [fdata, hyb.beta * hybrid_mod.project_sparse(
                    sp_rows, sdim)], axis=1)
            exact = fused_q @ fused_rows.T
            gt_h = np.asarray(
                jax.lax.top_k(exact, K)[1])
            hv, hi = hybrid_mod.search(hyb, qd, qs_sp, K,
                                       n_probes=NPROBE0 * 2)
            hrow = {"sparse_dim": sdim, "vocab": vocab,
                    "hybrid_recall": round(_id_recall(hi, gt_h), 4),
                    "qps": round(_time_qps(
                        lambda q_: hybrid_mod.search(
                            hyb, q_, qs_sp[: q_.shape[0]], K,
                            n_probes=NPROBE0 * 2),
                        jnp.asarray(qd), REPS,
                        hist="bench.filtered.hybrid_latency_s"), 1)}
            filt["hybrid"] = hrow
            extras["filtered"] = filt
            del fl_index, fbq_index, hyb
        except Exception as e:
            extras["filtered"] = section_error(e)
        hb.section("filtered", extras["filtered"])

    # --- IVF-BQ build fast path (ROADMAP item 5, round 17) -----------------
    # Three rungs of the billion-scale build story: (a) the dense-vs-SRHT
    # rotation apply timing pair at d >= 512 (the O(d²)→O(d·log d) claim,
    # measured); (b) the STREAMED Hadamard build at bench scale — rows/s +
    # the closed-form peak-residency prediction, restated at the SIFT-1B
    # 15.6M-row per-chip share (the number that must fit one chip); (c)
    # the multi-bit no-refine rung: 4-bit extended codes ranked by the
    # estimate alone (refine_ratio=1), the high-recall regime with no
    # exact re-rank and no caller-held dataset.
    if section_on("bq_build"):
        hb.set_section("bq_build")
        try:
            from raft_tpu.ops import linalg as linalg_mod

            bqb = {}
            # (a) rotation apply pair at d >= 512
            rot_d = max(512, linalg_mod.hadamard_rot_dim(DIM))
            rot_rows = 4096 if not tiny else 512
            kr = jax.random.key(7)
            rmat = linalg_mod.make_rotation_matrix(kr, rot_d)
            signs = linalg_mod.make_srht_signs(kr, rot_d)
            xr = jax.random.normal(jax.random.key(8), (rot_rows, rot_d))
            dense_fn = jax.jit(lambda x: linalg_mod.rotate_rows(x, rmat, "dense"))
            had_fn = jax.jit(lambda x: linalg_mod.rotate_rows(x, signs, "hadamard"))

            def _rot_time(fn):
                _force(fn(xr))  # warm/compile
                reps = 10
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = fn(xr)
                _force(out)
                return (time.perf_counter() - t0) / reps

            td, th = _rot_time(dense_fn), _rot_time(had_fn)
            bqb["rotation_dim"] = rot_d
            bqb["rotation_rows"] = rot_rows
            bqb["rotation_dense_s"] = round(td, 6)
            bqb["rotation_hadamard_s"] = round(th, 6)
            bqb["rotation_speedup_x"] = round(td / th, 2) if th else None

            # (b) streamed Hadamard build at bench scale
            np_ds = np.asarray(dataset, np.float32)
            bparams = ivf_bq.IvfBqParams(
                n_lists=NLIST, rotation_kind="hadamard",
                kmeans_trainset_fraction=0.2)
            chunk_used = min(N, max(N // 8, 65536)) if not tiny else N
            t0 = time.perf_counter()
            sidx = ivf_bq.build_streaming(
                lambda s, e: np_ds[s:e], N, DIM, bparams,
                chunk_rows=chunk_used)
            _force(sidx.list_scale)
            sb_s = time.perf_counter() - t0
            bqb["build_s"] = round(sb_s, 1)
            bqb["build_rows_per_s"] = round(N / sb_s, 1)
            bqb["build_chunk_rows"] = chunk_used
            bqb["streamed_dropped"] = int(sidx._streaming_dropped)
            pb = obs_costmodel.predict_build_streaming_bytes(
                n=N, dim=DIM, n_lists=NLIST,
                max_list_size=sidx.max_list_size, chunk_rows=chunk_used,
                train_rows=int(N * 0.2), rot_dim=sidx.rot_dim,
                rotation_kind="hadamard")
            bqb["build_peak_predicted_bytes"] = pb["peak_bytes"]
            bqb["build_index_predicted_bytes"] = pb["index_bytes"]
            # the SIFT-1B per-chip share restated with the same formula
            # (15,625,000 rows — the r09 capacity rung's resident share):
            # mls at the auto list cap, 512-pow2 rounded
            from raft_tpu.neighbors import _packing as packing_mod

            share = 15_625_000
            share_lists = max(NLIST, 4096)
            share_cap = packing_mod.round_list_size(
                packing_mod.auto_list_cap(share, share_lists, 512), 512,
                pow2_chunks=True)
            pb16 = obs_costmodel.predict_build_streaming_bytes(
                n=share, dim=DIM, n_lists=share_lists,
                max_list_size=share_cap, chunk_rows=262_144,
                train_rows=2_000_000,
                rot_dim=linalg_mod.hadamard_rot_dim(DIM),
                rotation_kind="hadamard")
            bqb["sift1b_share_peak_predicted_bytes"] = pb16["peak_bytes"]
            stamp_build(bqb, "ivf_bq.build", round(sb_s, 1), 0.0,
                        n=N, dim=DIM, n_lists=NLIST,
                        train_rows=int(N * 0.2), rot_dim=sidx.rot_dim,
                        rotation_kind="hadamard")
            del sidx

            # (c) multi-bit no-refine rung (recall from the estimate alone)
            mb_bits = int(os.environ.get("RAFT_TPU_BQ_BITS", "4"))
            midx = ivf_bq.build(dataset, ivf_bq.IvfBqParams(
                n_lists=NLIST, bits=mb_bits, rotation_kind="hadamard",
                kmeans_trainset_fraction=0.2))
            _force(midx.list_scale)
            mb = None
            for nprobe in (NPROBE0, NPROBE0 * 2, NPROBE0 * 4, NPROBE0 * 8,
                           NPROBE0 * 16):
                vals, ids = ivf_bq.search(midx, queries, K,
                                          n_probes=nprobe)
                rec = float(stats.neighborhood_recall(ids, gt_ids, vals,
                                                      gt_vals))
                if mb is None or rec > mb["no_refine_recall"]:
                    mb = {"no_refine_nprobe": nprobe,
                          "no_refine_recall": round(rec, 4)}
                if rec >= 0.95:
                    break
            bqb.update(mb)
            bqb["no_refine_bits"] = mb_bits
            bqb["no_refine_code_bytes_per_row"] = midx.code_bytes_per_row
            bqb["no_refine_qps"] = round(_time_qps(
                lambda qs: ivf_bq.search(
                    midx, qs, K, n_probes=bqb["no_refine_nprobe"]),
                queries, REPS, hist="bench.bq_build.batch_latency_s"), 1)
            bqb.update(latency_percentiles("bench.bq_build.batch_latency_s"))
            del midx
            extras["bq_build"] = bqb
        except Exception as e:
            extras["bq_build"] = section_error(e)
        hb.section("bq_build", extras["bq_build"])

    # --- Serving: streaming traffic against the paged mutable store --------
    # (ISSUE 8): Poisson arrivals into the SLO-aware QueryQueue over a
    # PagedListStore, with upserts interleaved mid-traffic. Reports QPS +
    # p50/p90/p99 vs offered load, the batch-size-1 dispatch baseline, and
    # asserts the zero-recompile upsert contract via the paged-scan trace
    # counter. The index cache learns the store's compact() output, so the
    # next run pages the cached snapshot back in instead of rebuilding.
    if section_on("serving"):
        if on_cpu or elapsed() < 1000:
            hb.set_section("serving")
            try:
                srv_name = f"serving_ivf_flat_nl{NLIST}"
                srv_idx = cache_load(srv_name, ivf_flat.IvfFlatIndex.load)
                srv_cache = "hit"
                if srv_idx is None:
                    srv_idx = serving_src_index
                    srv_cache = ""
                    if srv_idx is None:
                        srv_idx = ivf_flat.build(dataset, ivf_flat.IvfFlatParams(
                            n_lists=NLIST, kmeans_trainset_fraction=0.2))
                        _force(srv_idx.list_norms)
                # consume the tuner's emitted operating point when one is
                # present AND was tuned for THIS configuration (the
                # context knobs in its fingerprint must match) — else fall
                # back to the sweep defaults. The provenance is stamped
                # either way; the hand-written sweep_r*_config.json flow
                # is retired (scripts/archive/README.md).
                from raft_tpu.tuning import autotune as _autotune
                srv_nprobe = (flat or {}).get("nprobe", NPROBE0)
                srv_tuned = None
                op = _autotune.load_operating_point()
                op_knobs = (op or {}).get("knobs") or {}
                if op is not None and op.get("meets_slo") \
                        and op_knobs.get("algo") == "ivf_flat" \
                        and op_knobs.get("n_lists") == NLIST \
                        and op_knobs.get("k") == K \
                        and isinstance(op_knobs.get("n_probes"), int):
                    srv_nprobe = int(op_knobs["n_probes"])
                    srv_tuned = {"tuned_by": op.get("tuned_by"),
                                 "tuned_fp": op.get("fp")}
                out = _serving_streaming(
                    srv_idx, queries, K, nprobe=srv_nprobe, tiny=tiny,
                    rng_seed=7)
                # tuned_by: None = no compatible operating point on disk,
                # serving ran the defaults — explicit, not silent
                out.update(srv_tuned or {"tuned_by": None})
                # the cache learns the post-traffic compact() snapshot:
                # upserted rows survive into the next run's store
                if srv_cache != "hit":
                    srv_cache = cache_store(srv_name, out.pop("_store").compact())
                else:
                    out.pop("_store", None)
                if srv_cache:
                    out["index_cache"] = srv_cache
                extras["serving"] = out
                del srv_idx
            except Exception as e:
                extras["serving"] = section_error(e)
        else:
            extras["serving"] = {"error": "skipped: time budget"}
        hb.section("serving", extras["serving"])
    serving_src_index = None  # release for the large sections below

    # --- Capacity: multi-tenant chaos rung (ISSUE 15 / ROADMAP item 4) ----
    # N tenants at ~4× HBM oversubscription under skewed Poisson traffic
    # through the acting admission controller: zero OOM verdicts, every
    # demotion/promotion/rejection classified, per-tenant SLO rows
    # exported, and the snapshot-restore hot swap a MEASURED latency row.
    if section_on("capacity"):
        if on_cpu or elapsed() < 1100:
            hb.set_section("capacity")
            try:
                extras["capacity"] = _capacity_chaos(tiny=tiny)
            except Exception as e:
                extras["capacity"] = section_error(e)
        else:
            extras["capacity"] = {"error": "skipped: time budget"}
        hb.section("capacity", extras["capacity"])

    # --- Maintenance: always-live index rung (ISSUE 18 / ROADMAP item 2) --
    # A paged store under a distribution-shifting upsert stream with the
    # drift-driven incremental re-clustering manager pumping in the idle
    # gaps, vs an identical no-maintenance control: the maintained store
    # must hold the control's starting Wilson band with ZERO scan
    # recompiles across the cycles and zero unclassified failures.
    if section_on("maintenance"):
        if on_cpu or elapsed() < 1150:
            hb.set_section("maintenance")
            try:
                extras["maintenance"] = _maintenance_rung(tiny=tiny)
            except Exception as e:
                extras["maintenance"] = section_error(e)
        else:
            extras["maintenance"] = {"error": "skipped: time budget"}
        hb.section("maintenance", extras["maintenance"])

    # --- Tuning: the closed autotuning loop (ISSUE 20 / ROADMAP item 2) ---
    # Offline: the diagnosis-driven tuner converges onto a calibrated
    # synthetic SLO with no hand-written sweep config and emits
    # results/operating_point.json (which the serving section above reads
    # back on the NEXT run — the same learn-across-runs shape as the index
    # cache). Online: an induced load spike at the tuned point that the
    # burn-rate controller must absorb — zero recompiles, zero
    # unclassified verdicts, burn states back in budget after recovery.
    if section_on("tuning"):
        if on_cpu or elapsed() < 1200:
            hb.set_section("tuning")
            try:
                extras["tuning"] = _autotune_rung(tiny=tiny)
            except Exception as e:
                extras["tuning"] = section_error(e)
        else:
            extras["tuning"] = {"error": "skipped: time budget"}
        hb.section("tuning", extras["tuning"])

    # --- CAGRA at the FULL bench scale and the FULL query batch (VERDICT
    # r4 weak #3: q=2000 vs the IVF rows' q=10000 needed a footnote).
    # Build = IVF candidate scan (+ compressed-traversal payload, round 5);
    # search races the compressed and exact traversals over an (itopk,
    # width) ladder and reports the fastest config meeting the 0.95 gate.
    if section_on("cagra"):
        hb.set_section("cagra")
        try:
            if not on_cpu and elapsed() > 800:
                raise RuntimeError("skipped: time budget (cagra build ~8 min)")
            if on_cpu:
                cn = CAGRA_N
                cq = queries[:min(Q, 2000)]
                csub = dataset[:cn]
                _, cgt = brute_force.search(brute_force.build(csub), cq, K,
                                            select_algo="exact")
                cgt_v = None
                calgo = "brute"
            else:
                cn, csub, cq = N, dataset, queries
                cgt, cgt_v = gt_ids, gt_vals
                calgo = "auto"
            t0 = time.perf_counter()
            # graph_degree=64 (the reference default): measured the difference
            # between 0.87 and 0.98 recall at 1M — degree-32 graphs lose
            # navigability at this scale
            # telemetry is already on suite-wide (run_suite's obs.enable()),
            # so cagra's obs-gated per-phase _sync barriers measure
            # completion times, which is what build_phases_s must record
            #
            # round 6: synthetic data is uint8 — building from it keeps the
            # stored dataset (the fused traversal's exit-re-rank gather
            # source) at 1 byte/dim in HBM; u8→f32 is exact so recall vs
            # the f32 ground truth is unchanged. tiny mode forces the
            # compression payload so the fused-kernel smoke rung exists.
            cdata = jnp.asarray(data_u8[:cn]) if real is None else csub
            cparams = cagra.CagraParams(
                intermediate_graph_degree=128 if not on_cpu else 64,
                graph_degree=64 if not on_cpu else 32,
                build_algo=calgo,
                compress="on" if tiny else "auto")
            cname = (f"cagra{cn // 1000}k_igd{cparams.intermediate_graph_degree}"
                     f"_gd{cparams.graph_degree}_{calgo}_{cparams.compress}")
            cidx = cache_load(cname, cagra.CagraIndex.load)
            ccache = "hit"
            if cidx is None:
                cidx = cagra.build(cdata, cparams)
                _force(cidx.graph)
                if cidx.nbr_codes is not None:
                    _force(cidx.nbr_codes)  # compression is part of build_s
                ccache = cache_store(cname, cidx)
            # on a cache hit build_s reports 0.0 (the ivf sections'
            # convention) — the load time is not a build time, and
            # bench_compare must not read it as one
            cbuild = 0.0 if ccache == "hit" else time.perf_counter() - t0

            def c_rec(ci, cv):
                return float(stats.neighborhood_recall(ci, cgt, cv, cgt_v)
                             if cgt_v is not None
                             else stats.neighborhood_recall(ci, cgt))

            # fused rungs lead (round-6 tentpole, the expected winners on
            # TPU); unfused compressed/exact rungs stay as the comparison
            # and the fallback when the kernel path loses or errors
            ladder = [("fused", 64, 4), ("fused", 96, 8),
                      ("compressed", 64, 4), ("exact", 64, 4),
                      ("compressed", 96, 8), ("exact", 96, 4)]
            if tiny:
                # smoke: one rung, through the fused kernel (check.sh
                # asserts the reported traversal is "fused")
                ladder = [("fused", 32, 2)]
            elif on_cpu:
                # interpret-mode kernels are debug-speed; the CPU ladder
                # races the jnp loops only
                ladder = [c for c in ladder if c[0] != "fused"]
            if cidx.nbr_codes is None:
                ladder = [c for c in ladder if c[0] == "exact"]
            best = None
            last_err = None
            for trav, itopk, w in ladder:
                # compile-cold runs pay ~1 min per rung: stop laddering before
                # the 10M section's time gate (elapsed<1600) is starved, as
                # long as at least one rung has landed
                if best is not None and elapsed() > 1250:
                    break
                if obs.enabled():
                    obs.add("bench.cagra.ladder_rungs", 1)
                sp = cagra.CagraSearchParams(itopk_size=itopk, search_width=w,
                                             traversal=trav)
                try:
                    cv, ci = cagra.search(cidx, cq, K, sp)
                    crec = c_rec(ci, cv)
                except Exception as e:
                    obs.add("bench.cagra.rung_error."
                            + resilience.classify(e))
                    last_err = e
                    continue
                # a sub-gate rung cannot beat an at-gate best: skip its timing
                if best is not None and best["recall"] >= 0.95 > crec:
                    continue
                # no hist here: the per-batch latency pass would run for
                # EVERY rung and burn window budget on configs that lose
                # the ladder — the winner gets one dedicated pass below
                cqps = round(_time_qps(
                    lambda qs: cagra.search(cidx, qs, K, sp),
                    cq, max(1, REPS // 2)), 1)
                cand = {"traversal": trav, "itopk": itopk, "width": w,
                        "recall": round(crec, 4), "qps": cqps}
                better = (best is None
                          or (crec >= 0.95 > best["recall"])
                          or (crec >= 0.95 and best["recall"] >= 0.95
                              and cqps > best["qps"])
                          or (crec > best["recall"] and best["recall"] < 0.95))
                if better:
                    best = cand
            if best is None:
                raise RuntimeError(
                    f"every cagra ladder rung failed; last: {last_err!r}")
            best["build_s"] = round(cbuild, 1)
            # ONE per-batch latency pass, for the selected config only
            # (percentiles must describe a single config, and losing rungs
            # must not pay the individually-forced dispatches)
            best_sp = cagra.CagraSearchParams(
                itopk_size=best["itopk"], search_width=best["width"],
                traversal=best["traversal"])
            c0 = obs.snapshot()["counters"]
            h0 = c0.get("cagra.search.hops", 0)
            reps_lat = max(1, REPS // 2)
            # hop counting forces a per-call device fetch, so it rides only
            # the latency pass (whose protocol forces every call anyway) —
            # the amortized QPS loops above stay pipelined
            prev_ch = os.environ.get("RAFT_TPU_CAGRA_COUNT_HOPS")
            os.environ["RAFT_TPU_CAGRA_COUNT_HOPS"] = "1"
            try:
                _observe_batch_latency(
                    lambda qs: cagra.search(cidx, qs, K, best_sp),
                    cq, reps_lat, "bench.cagra.batch_latency_s")
            finally:
                if prev_ch is None:
                    os.environ.pop("RAFT_TPU_CAGRA_COUNT_HOPS", None)
                else:
                    os.environ["RAFT_TPU_CAGRA_COUNT_HOPS"] = prev_ch
            best.update(latency_percentiles("bench.cagra.batch_latency_s"))
            # per-hop counts (fused traversal only — the device-resident
            # unfused while_loop never surfaces its trip count): total hops
            # the latency pass executed, and the per-batch average
            counters = obs.snapshot()["counters"]
            hops = counters.get("cagra.search.hops", 0) - h0
            if hops:
                obs.add("bench.cagra.hops", hops)
                best["hops_per_batch"] = round(hops / reps_lat, 1)
            # a silent fused→compressed fallback keeps the rung LABEL
            # "fused" while the measured numbers came from the unfused
            # loop — stamp the row degraded (deep10m precedent) so the
            # committed extras never claim kernel QPS it didn't measure.
            # Delta'd against c0 like hops: a fallback in an earlier,
            # LOSING rung must not taint the winner's clean pass
            if best["traversal"] == "fused":
                fb = {k2.rsplit(".", 1)[-1]: v - c0.get(k2, 0)
                      for k2, v in counters.items()
                      if k2.startswith("cagra.search.fused_fallback.")
                      and v > c0.get(k2, 0)}
                if fb or not hops:
                    best["degraded"] = "fused_fallback"
                    best["fused_fallbacks"] = fb
            best["build_phases_s"] = getattr(cidx, "_build_timings_s", {})
            if ccache:
                best["index_cache"] = ccache
            best["n"] = cn
            best["q"] = int(cq.shape[0])
            extras["cagra"] = best
            del cidx
        except Exception as e:  # a cagra failure must not sink the headline
            extras["cagra"] = section_error(e)
        hb.section("cagra", extras["cagra"])

    # --- DEEP-10M-shaped ANN crossover (VERDICT r3 #3): at 10M rows the
    # (q, n) brute-force score matrix no longer fits HBM — exact search
    # drops to a chunked streaming scan and IVF-PQ+refine must win. Also
    # reports the naive per-chip SIFT-1B share extrapolation
    # (BASELINE.md:35-37: 1B rows / 64 chips = 15.6M rows/chip).
    if not on_cpu and section_on("deep10m"):
        if elapsed() < 1600:
            hb.set_section("deep10m")
            try:
                # free every 1M-section device array first: the 10M section
                # peaks near HBM capacity (round-4: RESOURCE_EXHAUSTED with the
                # 1M fp32 dataset + ground truth still resident)
                del bf_index, dataset, queries, gt_vals, gt_ids
                try:
                    del csub, cq, cgt, cgt_v, cv, ci
                except NameError:
                    pass
                extras["deep10m"] = _deep10m_crossover(REPS)
            except Exception as e:
                err = section_error(e)
                if err["kind"] == resilience.OOM:
                    # round-4 incident class (RESOURCE_EXHAUSTED near HBM
                    # capacity): one degraded-scale retry — half the rows
                    # is a worse headline but infinitely better than none,
                    # and it ships marked degraded
                    try:
                        out = _deep10m_crossover(REPS, scale=0.5)
                        out["degraded"] = True
                        out["first_attempt_error"] = err
                        extras["deep10m"] = out
                    except Exception as e2:
                        extras["deep10m"] = section_error(e2)
                else:
                    extras["deep10m"] = err
        else:
            extras["deep10m"] = {"error": "skipped: time budget"}
        hb.section("deep10m", extras["deep10m"])

    # --- IVF-BQ per-chip capacity rung (ROADMAP item 3's headline): hold
    # the SIFT-1B per-chip row share (1B / 64 chips = 15.6M rows) RESIDENT
    # as 1-bit codes and MEASURE recall-gated QPS at that scale — the
    # number that replaces r04's sift1b_per_chip_qps_extrapolated. Runs
    # after deep10m so the 1M-section arrays are already freed; OOM
    # retries once at half scale, stamped degraded (ISSUE 3 precedent).
    if not on_cpu and section_on("ivf_bq") and isinstance(bq, dict):
        if elapsed() < 1800:
            hb.set_section("ivf_bq_capacity")
            try:
                rung = _ivf_bq_capacity(REPS, 15_625_000, DIM, K)
            except Exception as e:
                err = section_error(e)
                rung = None
                if err["kind"] == resilience.OOM:
                    try:
                        rung = _ivf_bq_capacity(REPS, 15_625_000 // 2, DIM, K)
                        rung["degraded"] = True
                        rung["first_attempt_error"] = err
                    except Exception as e2:
                        # keep the first attempt's OOM stamp: it is WHY the
                        # rung degraded, and the retry's failure rides along
                        err = {**section_error(e2),
                               "first_attempt_error": err}
                if rung is None:
                    bq["capacity_rung_error"] = err
            if rung is not None:
                bq["scale_sweep"] = [
                    {"n": N, "recall": bq["recall"], "qps": bq["qps"]}, rung]
                if rung.get("recall", 0.0) >= 0.95:
                    bq["per_chip_capacity_rows"] = rung["n"]
                    bq["per_chip_qps"] = rung["qps"]
                    bq["per_chip_recall"] = rung["recall"]
        else:
            # heartbeat the skip too (deep10m convention): a watcher must
            # be able to tell "skipped" from "crashed before the section"
            hb.set_section("ivf_bq_capacity")
            bq["capacity_rung_error"] = {"error": "skipped: time budget"}
        hb.section("ivf_bq_capacity", bq)

    # --- DEEP-100M (BASELINE row): measured offline by scripts/deep100m.py
    # (streamed build + truncated-cache search takes ~20+ min — too long
    # for the driver's bench run). When its committed artifact exists it is
    # embedded verbatim, labeled with its provenance.
    d100 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "results", "DEEP100M_r05.json")
    if os.path.exists(d100):
        try:
            with open(d100) as f:
                extras["deep100m"] = {
                    "measured_offline_by": "scripts/deep100m.py",
                    **json.load(f)}
        except Exception as e:
            extras["deep100m"] = section_error(e)

    # --- headline: ivf_pq, falling back down the same order salvage uses
    # when a sections filter excluded it
    ds_name = "sift" if extras["dataset"] == "sift-real" else "siftlike"
    shape_tag = f"{ds_name}{N // 1000}k_{DIM}d_k{K}"
    if pq is not None:
        headline, gate = pq["qps"], pq["recall"]
        metric = f"ivf_pq_qps_{shape_tag}_recall{pq['recall']}"
    elif flat is not None:
        headline, gate = flat["qps"], flat["recall"]
        metric = f"ivf_flat_qps_{shape_tag}_recall{flat['recall']}"
    else:
        headline, gate = bf_qps, bf_recall
        metric = f"brute_force_qps_{shape_tag}"
    result = {
        "metric": metric,
        "value": round(headline, 1),
        "unit": "QPS",
        "vs_baseline": round(headline / prog.NORTH_STAR_QPS, 4),
        "platform": jax.devices()[0].platform,
        "recall_gate_met": bool(gate >= 0.95),
        "extras": extras,
    }

    # --- per-host telemetry artifacts (round-8 fleet aggregation): one
    # process-stamped metrics snapshot + one Perfetto trace per process,
    # both through bench/progress.py's fsync'd channel (graftlint span-name
    # flags direct export calls here). The parent merges the metric files
    # into results/metrics_fleet.json via obs/aggregate.
    # the run is COMPLETE: checkpoint the headline FIRST, so even a hung
    # (not raising) telemetry write below — fsync on a wedged mount — leaves
    # a salvageable run_end record rather than eating the finished round
    hb.finish({"metric": metric, "value": result["value"]})

    # best-effort by contract: telemetry artifacts are a nice-to-have, and
    # their write failing (read-only fs, disk full) must never downgrade a
    # COMPLETED measured round to heartbeat salvage
    try:
        pi, _pc = prog.process_info()
        mdir = os.environ.get("RAFT_TPU_BENCH_METRICS_DIR", "").strip()
        if mdir:
            mpath = os.path.join(mdir, f"bench_p{pi}.jsonl")
            prog.export_metrics(mpath, obs.snapshot(),
                                extra={"run": "bench", "metric": metric,
                                       "platform": result["platform"]})
            result["metrics_file"] = mpath
        tdir = os.environ.get("RAFT_TPU_BENCH_TRACE_DIR", "").strip()
        if tdir:
            tpath = os.path.join(tdir, f"trace_bench_p{pi}.json")
            prog.write_artifact(tpath, obs.chrome_trace(
                extra={"run": "bench", "metric": metric}))
            result["trace_file"] = tpath
    except Exception as e:
        extras["telemetry_export_error"] = section_error(e)
    return result


def _serving_streaming(index, queries, k: int, nprobe: int, tiny: bool,
                       rng_seed: int = 7) -> dict:
    """Streaming-traffic section (ISSUE 8): Poisson arrivals into the
    SLO-aware QueryQueue over a paged mutable store built from ``index``.

    Measures (a) the batch-size-1 dispatch baseline (sequential single
    queries, each forced — the no-batching serving strawman), then (b) the
    dynamic batcher at several offered loads with mixed per-request
    deadlines and upsert batches interleaved mid-traffic. Reports achieved
    QPS + p50/p90/p99 per offered load, the best speedup at
    no-worse-than-baseline p99, and the paged-scan retrace count across
    the serving window (the zero-recompile upsert contract).

    Round 10 (ISSUE 10): the section exercises the WHOLE observability
    plane — per-request traces through the queue, a seeded shadow sampler
    maintaining the live recall estimate (pumped between windows, off the
    measured clock: the worker-thread mode would steal CPU from the window
    it is measuring), the three-class SLO engine's burn rates, and memory
    watermarks — and streams ``obs.report`` snapshots to
    ``results/obs_report.jsonl`` through the crash-safe progress channel.
    """
    import numpy as np

    from raft_tpu import obs, serving
    from raft_tpu.bench import progress as prog
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import costmodel as obs_costmodel
    from raft_tpu.obs import flight as obs_flight
    from raft_tpu.obs import health as obs_health
    from raft_tpu.obs import memory as obs_memory
    from raft_tpu.obs import report as obs_report
    from raft_tpu.obs import roofline as obs_roofline
    from raft_tpu.obs import shadow as obs_shadow
    from raft_tpu.obs import slo as obs_slo

    rng = np.random.default_rng(rng_seed)
    q_pool = np.asarray(queries, np.float32)
    dim = q_pool.shape[1]
    if tiny:
        n_req, max_batch, mults = 64, 32, (2.0, 5.0)
        upsert_every, upsert_rows = 16, 8
    else:
        n_req, max_batch, mults = 256, 64, (2.0, 5.0, 10.0)
        upsert_every, upsert_rows = 32, 32

    store = serving.PagedListStore.from_index(index)
    # growth (the one legal recompile source) is paid up front: the
    # serving window itself must re-dispatch compiled programs only
    store.reserve(2 * len(mults) * (n_req // max(1, upsert_every) + 1)
                  * upsert_rows)
    out = {"store": store.stats(), "nprobe": int(nprobe), "k": int(k)}

    # --- batch-1 baseline ---------------------------------------------------
    def one(i):
        v, _ = serving.search(store, q_pool[i % len(q_pool)][None], k,
                              n_probes=nprobe)
        _force(v)

    one(0)  # warm/compile the bucket-1 program
    n1 = 32 if tiny else 64
    lats1 = []
    for i in range(n1):
        t1 = time.perf_counter()
        one(i)
        lats1.append(time.perf_counter() - t1)
    lat1 = float(np.median(lats1))
    p99_1 = float(np.percentile(lats1, 99))
    out["batch1"] = {"qps": round(1.0 / lat1, 1),
                     "p50_ms": round(np.percentile(lats1, 50) * 1e3, 3),
                     "p99_ms": round(p99_1 * 1e3, 3)}

    # warm the remaining batch buckets (compiles out of the measured window)
    b = 1
    while b < max_batch:
        b = min(b * 2, max_batch)
        v, _ = serving.search(store, np.repeat(q_pool[:1], b, axis=0), k,
                              n_probes=nprobe)
        _force(v)
    t2 = time.perf_counter()
    v, _ = serving.search(store, np.repeat(q_pool[:1], max_batch, axis=0),
                          k, n_probes=nprobe)
    _force(v)
    lat_full = time.perf_counter() - t2
    slo_s = max(4.0 * lat_full, 2.0 * lat1)

    # --- observability plane (ISSUE 10) -------------------------------------
    # shadow sampler: a seeded fraction of served queries re-checked
    # against the store's own exact scan (n_probes = n_lists — exact over
    # the LIVE corpus, so mid-traffic upserts are scored fairly)
    # default_rate() carries the env knob's garbage-tolerance + [0,1]
    # clamp; the bench only supplies its own default when the knob is unset
    raw_rate = os.environ.get(obs_shadow.RATE_ENV, "").strip()
    shadow_rate = obs_shadow.default_rate() if raw_rate else \
        (0.5 if tiny else 0.25)
    sampler = obs_shadow.ShadowSampler(
        lambda qq: serving.search(store, qq, k, n_probes=store.n_lists),
        k=k, rate=shadow_rate, seed=rng_seed, max_pending=512)
    engine = obs_slo.SloEngine(
        obs_slo.default_serving_slos(slo_s, sampler=sampler))
    report_path = os.path.join("results", "obs_report.jsonl")
    prog.truncate(report_path)  # fresh report stream per run
    out["shadow_rate"] = shadow_rate
    # warm the shadow's exact-scan program (n_probes = n_lists is its own
    # compiled shape) off the clock, so the serving window's zero-recompile
    # counter measures the mutation contract, not shadow warmup
    v, _ = serving.search(store, q_pool[:1], k, n_probes=store.n_lists)
    _force(v)

    # upsert id range fixed per run: re-runs replace, the store stays bounded
    next_upsert = [10_000_000]
    pending_deletes = []   # oldest outstanding upsert batches, FIFO

    def upsert_some():
        vecs = rng.standard_normal((upsert_rows, dim)).astype(np.float32)
        ids = np.arange(next_upsert[0], next_upsert[0] + upsert_rows)
        next_upsert[0] += upsert_rows
        store.upsert(vecs, ids)
        pending_deletes.append(ids)

    def delete_some():
        # tombstone the oldest outstanding upsert batch (round 16: the
        # mixed-traffic source that feeds the compaction trigger)
        if pending_deletes:
            store.delete(pending_deletes.pop(0))

    upsert_some()  # warm the assign/encode/scatter programs off the clock

    # warm the compaction fold/swap programs off the measured clock, and
    # hand the windows a ratio-triggered background manager (round 16):
    # worker-threaded, so cycles run beside the single-threaded pump loop.
    # The trigger ratio is sized to the window's planned delete flow so a
    # cycle actually fires mid-traffic at any corpus size.
    delete_some()
    serving.CompactionManager(store, ratio=0.0).pump()
    expected_deletes = len(mults) * (n_req // max(1, upsert_every)) \
        * upsert_rows
    compact_ratio = max(1e-4, 0.5 * expected_deletes / max(1, store.size))
    compact_mgr = serving.CompactionManager(
        store, ratio=compact_ratio, min_tombstones=upsert_rows,
        interval_s=0.02)

    last_queue = [None]  # most recent window's queue (report depth source)

    # --- flight recorder (ISSUE 16): continuous operating-point windows
    # over the serving traffic, streamed crash-safe; the knob vector is a
    # CALLABLE so each load window's live queue (its batch cap in
    # particular) keys its own fingerprint group on the frontier
    flight_path = os.path.join("results", "flight_streaming.jsonl")
    prog.truncate(flight_path)  # fresh recording per run
    st0 = store.stats()

    def _flight_knobs():
        knobs = {"algo": store.kind, "scan": "paged",
                 "nprobe": int(nprobe), "k": int(k),
                 "page_rows": st0.get("page_rows"),
                 "n_lists": st0.get("n_lists")}
        if last_queue[0] is not None:
            knobs.update(last_queue[0].knobs())
        return knobs

    raw_iv = os.environ.get(obs_flight.INTERVAL_ENV, "").strip()
    # the opening device-health verdict comes from THIS process's backend:
    # it holds the chip, so a subprocess probe could not open it
    flight = obs_flight.FlightRecorder(
        flight_path, knobs=_flight_knobs, engine=engine, sampler=sampler,
        queue=lambda: last_queue[0], health=obs_health.in_process(),
        interval_s=None if raw_iv else (0.2 if tiny else 0.5))
    flight.sample()

    def run_load(rate: float, batch_cap: int, with_upserts: bool,
                 shadow=None) -> dict:
        """One Poisson window: submit at ``rate`` req/s with mixed
        per-request deadlines, pump the queue in the gaps (the bench loop
        IS the serving worker — single-threaded, deterministic)."""
        queue = serving.QueryQueue(
            serving.searcher(store, k, n_probes=nprobe),
            slo_s=slo_s, max_batch=batch_cap,
            # waiting longer than one full-batch dispatch to fill a batch
            # never pays: the next batch would have absorbed the arrivals
            fill_wait_s=lat_full, shadow=shadow,
            # pre-dispatch admission gauges (ISSUE 11): every batch's
            # predicted footprint is checked against the live watermark
            # and the verdict recorded — observability only this round
            cost_model=obs_costmodel.paged_scan_estimator(
                store, k, n_probes=nprobe))
        last_queue[0] = queue
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
        # mixed deadlines: most requests roomy, every 5th tight
        timeouts = [slo_s * (2.0 if i % 5 == 0 else 8.0)
                    for i in range(n_req)]
        handles = []
        i = 0
        t0 = time.perf_counter()
        while i < n_req:
            flight.maybe_sample()  # one branch + clock read off-interval
            now = time.perf_counter() - t0
            if now >= arrivals[i]:
                handles.append(queue.submit(q_pool[i % len(q_pool)],
                                            timeout_s=timeouts[i]))
                i += 1
                if with_upserts and i % upsert_every == 0:
                    upsert_some()  # mutation mid-traffic, zero recompiles
                    delete_some()  # tombstones feed the compactor
                continue
            if not queue.pump():
                time.sleep(min(arrivals[i] - now, 2e-4))
        queue.drain(timeout=120.0)
        # close the window on THIS load's fingerprint while its queue is
        # still the live knob source (≥ one window per offered load)
        flight.sample()
        wall = time.perf_counter() - t0
        ok_lats = [h.latency_s for h in handles if h.verdict == "ok"]
        n_ok = len(ok_lats)
        misses = sum(1 for h in handles if h.verdict == "deadline")
        other = n_req - n_ok - misses
        row = {
            "offered_qps": round(rate, 1),
            "qps": round(n_ok / wall, 1) if wall > 0 else 0.0,
            "served": n_ok, "deadline_misses": misses,
            "unclassified": 0 if other == 0 else other,
            "batches": queue.batches, "multi_batches": queue.multi_batches,
            "mean_batch": round(n_ok / max(1, queue.batches), 2),
        }
        if ok_lats:
            row["p50_ms"] = round(np.percentile(ok_lats, 50) * 1e3, 3)
            row["p90_ms"] = round(np.percentile(ok_lats, 90) * 1e3, 3)
            row["p99_ms"] = round(np.percentile(ok_lats, 99) * 1e3, 3)
        return row

    # --- batch-size-1 SERVING reference: the no-batching strawman at its
    # own sustainable load (0.7 × its capacity — beyond that its queue
    # diverges). Its p99 is the "equal p99" bar the dynamic rows answer to.
    # serving-window recompile + HBM prediction baselines (ISSUE 11): the
    # reserve() above pre-paid growth, so the window's prediction is "the
    # watermark holds flat" — validated against the post-traffic sample;
    # every retrace inside the window must land shape-attributed in the
    # compile ledger with ZERO unexplained residue
    traces0 = serving.scan_trace_count()
    unexplained0 = obs_compile.unexplained_retraces()
    out["predicted_index_bytes"] = obs_costmodel.predict_index_bytes(
        **obs_costmodel.index_layout(store))
    out["index_bytes"] = obs_memory.record_index("serving_store", store)
    mem_before = obs_memory.sample("serving.window_start")
    scan_est = obs_costmodel.estimate_search(store, q=max_batch, k=k,
                                             n_probes=nprobe)
    base_rate = 0.7 / lat1
    base = run_load(base_rate, batch_cap=1, with_upserts=False)
    out["batch1_serving"] = base

    # --- dynamic batching at multiples of the strawman's load, upserts
    # interleaved mid-traffic; after each window (off the measured clock)
    # the shadow queue drains, the SLO engine samples, and one obs.report
    # snapshot is streamed to the crash-safe report file
    loads = []
    compact_mgr.start()
    try:
        for mult in mults:
            row = run_load(mult * base_rate, batch_cap=max_batch,
                           with_upserts=True, shadow=sampler)
            row["offered_x_batch1"] = mult
            sampler.drain(timeout_s=60.0)
            obs_report.export(report_path, obs_report.collect(
                engine=engine, sampler=sampler, queue=last_queue[0],
                extra={"offered_x_batch1": mult}))
            loads.append(row)
    finally:
        compact_mgr.stop()
        # a worker cycle that raced the final window's mutations lands
        # classified `stale`; with traffic stopped, finish the reclaim
        # deterministically — the cycle count is a compared metric
        for _ in range(4):
            cyc = compact_mgr.pump()
            if cyc is None or cyc.get("status") == "ok":
                break
    # background compaction over the window (round 16): cycles must have
    # run without retracing the scans — bench_compare gates the pair
    mstats = compact_mgr.stats()
    out["compaction_cycles"] = compact_mgr.cycles
    out["tombstone_ratio_peak"] = mstats["tombstone_ratio_peak"]
    out["compaction"] = mstats
    out["recompiles_during_serving"] = serving.scan_trace_count() - traces0
    # zero-tolerance residue (bench_compare gates it): a retrace without a
    # shape-diff has no attribution and is a contract violation; attributed
    # retraces ship with their diffs in the obs_report compile section
    out["unexplained_retraces"] = \
        obs_compile.unexplained_retraces() - unexplained0
    # roofline stamp (ISSUE 12): the paged gather scan at the full batch
    # bucket against platform peaks — measured by the forced full-batch
    # dispatch (lat_full), end-to-end like the section stamps. The
    # padded fraction is the capacity-padded chain waste every probe
    # pays (table_width × page_rows slots vs live rows) — the number
    # ROADMAP item 2's paged-Pallas merge would shrink.
    try:
        st = store.stats()
        chain_slots = store.n_lists * st["table_width"] * st["page_rows"]
        occ = {"padded_row_fraction": round(
            max(0.0, 1.0 - st["rows"] / chain_slots), 4)
            if chain_slots else 0.0,
            "fill_fraction": round(st["fill_fraction"], 4)}
        # paged-planner occupancy (round 16): page-fill / tombstone-waste
        # fractions from the SAME planning code the Pallas engine uses
        from raft_tpu.ops.strip_scan import paged_occupancy_stats
        pocc = paged_occupancy_stats(
            st["table_width"], st["page_rows"], store._list_pages,
            st["rows"], st["tombstones"], max_batch, nprobe, k,
            int(store.pages.shape[-1]) * store.pages.dtype.itemsize)
        for key in ("page_fill", "tombstone_fraction", "chain_fill",
                    "pages_per_fetch", "n_sub"):
            occ[key] = pocc[key]
        util = obs_roofline.utilization_search(
            store, q=max_batch, k=k, n_probes=nprobe,
            measured_s=lat_full, occupancy=occ)
        out["flops_per_batch"] = util["flops"]
        out["bytes_per_batch"] = util["bytes"]
        out["bound"] = util["bound"]
        out["peaks_source"] = util["peaks_source"]
        out["padded_fraction"] = occ["padded_row_fraction"]
        for key in ("achieved_gflops", "mxu_utilization",
                    "hbm_bw_utilization", "model_to_measured"):
            if util.get(key) is not None:
                out[key] = util[key]
    except Exception as e:
        # same classified stamp + counter every section guard uses
        out["roofline_error"] = section_error(e)

    # --- packed-vs-paged same-corpus QPS pair (round 16): the ≤10 %-gap
    # criterion as a measured row, not a claim. The store's own compact()
    # output is the identical corpus; both engines run the full batch
    # shape, forced. On CPU this is a preview (both sides run their CPU
    # engines); the TPU run — paged Pallas vs the packed strip kernel —
    # is the number of record.
    out["paged_engine"] = serving.paged_engine(store, k)
    try:
        from raft_tpu.neighbors import ivf_bq as bench_ivf_bq
        from raft_tpu.neighbors import ivf_flat as bench_ivf_flat
        from raft_tpu.neighbors import ivf_pq as bench_ivf_pq

        fam = {"ivf_flat": bench_ivf_flat, "ivf_pq": bench_ivf_pq,
               "ivf_bq": bench_ivf_bq}[store.kind]
        comp = store.compact()
        reps = 3 if tiny else 5
        tiles = -(-max_batch // len(q_pool))
        qb = np.tile(q_pool, (tiles, 1))[:max_batch]

        def packed_once():
            v, _ = fam.search(comp, qb, k, n_probes=nprobe)
            _force(v)

        def paged_once():
            v, _ = serving.search(store, qb, k, n_probes=nprobe)
            _force(v)

        packed_once()
        paged_once()  # both engines warmed off the clock
        tp = time.perf_counter()
        for _ in range(reps):
            packed_once()
        packed_s = (time.perf_counter() - tp) / reps
        tp = time.perf_counter()
        for _ in range(reps):
            paged_once()
        paged_s = (time.perf_counter() - tp) / reps
        out["packed_qps"] = round(max_batch / packed_s, 1)
        out["paged_qps"] = round(max_batch / paged_s, 1)
        # direction: up; 1.0 = parity, >= 0.9 is the acceptance target
        out["paged_to_packed_qps_ratio"] = round(packed_s / paged_s, 4)
        # the packed snapshot is measurement-only: release it before the
        # window's memory watermark is sampled (it is NOT serving state)
        del comp
    except Exception as e:
        out["paged_vs_packed_error"] = section_error(e)
    out["loads"] = loads
    out["slo_ms"] = round(slo_s * 1e3, 3)
    # headline comparison: best dynamic throughput among loads whose p99
    # stayed at (or under) the batch-1 server's — "beats batch-size-1
    # dispatch at equal p99"
    base_p99 = base.get("p99_ms")
    if loads and base["qps"] > 0:
        out["best_qps_x_batch1"] = round(
            max(r["qps"] for r in loads) / base["qps"], 2)
    eligible = [r for r in loads
                if base_p99 and r.get("p99_ms", 1e9) <= base_p99 * 1.1]
    if eligible and base["qps"] > 0:
        best = max(eligible, key=lambda r: r["qps"])
        out["speedup_vs_batch1_equal_p99"] = round(
            best["qps"] / base["qps"], 2)
    if obs.enabled():
        obs.add("bench.serving.requests", (1 + len(mults)) * n_req)

    # --- final operating-point record (ISSUE 10 / ROADMAP item 5): SLO
    # states + burn rates, live recall ± CI, memory watermark — the row
    # shape the closed-loop autotuner will consume
    mem = obs_memory.sample("serving")
    states = engine.evaluate()
    out["slo"] = {
        name: {"state": row.get("state"),
               "burn_fast": round(row["burn_fast"], 4),
               "burn_slow": round(row["burn_slow"], 4)}
        if "burn_fast" in row else {"state": row.get("state")}
        for name, row in states.items()}
    lat = states.get("serving_p99") or {}
    avail = states.get("serving_availability") or {}
    # a failed signal source (state=unknown, no burn keys) must surface as
    # ABSENT, not as a perfect 0.0 burn — bench_compare renders the missing
    # key as "gone", which is the honest row for a broken monitor
    out["slo_p99_burn_rate"] = (round(lat["burn_rate"], 4)
                                if "burn_rate" in lat else None)
    out["availability"] = avail.get("value")
    out["availability_burn_rate"] = (round(avail["burn_rate"], 4)
                                     if "burn_rate" in avail else None)
    est = sampler.estimate()
    out["recall_estimate"] = est["recall"]
    out["recall_ci_low"] = round(est["ci_low"], 4)
    out["recall_ci_high"] = round(est["ci_high"], 4)
    out["shadow_samples"] = est["samples"]
    out["shadow_dropped"] = est["dropped"]
    out["recall_stale"] = est["stale"]
    out["memory_watermark_bytes"] = mem["bytes_in_use"]
    out["memory_source"] = mem["source"]
    # predicted-vs-measured for the serving window (ISSUE 11): reserve()
    # pre-paid all growth, so the static resident prediction is simply
    # "the window-start watermark holds" — compared resident-to-resident
    # (dispatch transients are freed by sample time; their estimate ships
    # separately as the per-dispatch admission projection)
    pred = int(mem_before["bytes_in_use"])
    out["predicted_hbm_bytes"] = pred
    out["predicted_dispatch_transient_bytes"] = scan_est["transient_bytes"]
    out["measured_watermark_bytes"] = int(mem["bytes_in_use"])
    if out["measured_watermark_bytes"]:
        out["hbm_predicted_to_measured"] = round(
            pred / out["measured_watermark_bytes"], 3)
    # pre-dispatch admission verdict counts over the whole window (the
    # item-4 controller's input; a healthy CPU window is all-admit with
    # budget_source=unknown, a TPU window projects against bytes_limit)
    out["admission"] = obs_costmodel.admission_counts(
        obs.snapshot()["counters"])
    out["obs_report_file"] = report_path
    obs_report.export(report_path, obs_report.collect(
        engine=engine, sampler=sampler, queue=last_queue[0],
        extra={"final": True}))

    # flight recording headline + frontier artifact (ISSUE 16): the
    # fingerprint-grouped Pareto set ROADMAP item 2's autotuner consumes
    out["flight_file"] = flight_path
    out["flight_windows"] = flight.windows_recorded
    out["straggler_events"] = flight.straggler_events
    try:
        frontier = obs_flight.extract_frontier(
            obs_flight.read_recording(flight_path))
        prog.write_artifact(os.path.join("results", "frontier.json"),
                            frontier)
        out["frontier_points"] = frontier["pareto_points"]
        out["frontier_file"] = os.path.join("results", "frontier.json")
    except Exception as e:
        out["flight_error"] = section_error(e)

    out["store_after"] = store.stats()
    out["_store"] = store  # the section owner compacts + caches this
    return out


def _capacity_chaos(tiny: bool, rng_seed: int = 11) -> dict:
    """Multi-tenant capacity chaos rung (ISSUE 15 acceptance): N tenants
    with skewed (Zipf) popularity served as Poisson streaming traffic,
    ~4× oversubscribed against a SYNTHETIC HBM budget, through the acting
    :class:`raft_tpu.serving.CapacityController`. Gates:

    * ZERO OOM verdicts — oversubscription lands as classified
      demotions / degraded warm serves / rejections, never an allocator
      failure;
    * every demotion, promotion and rejection classified (no
      unclassified residue in the per-tenant report);
    * per-tenant SLO rows exported through the crash-safe progress
      channel (``results/obs_report_capacity.jsonl``);
    * the snapshot-restore hot-swap (promote) latency is a MEASURED row
      (``promote_p50_s``), not a claim.
    """
    import tempfile

    import numpy as np

    from raft_tpu import obs, resilience, serving
    from raft_tpu.bench import progress as prog
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import costmodel as obs_costmodel
    from raft_tpu.obs import flight as obs_flight
    from raft_tpu.obs import report as obs_report

    rng = np.random.default_rng(rng_seed)
    if tiny:
        n_tenants, n_req, rows, dim = 8, 160, 900, 16
    else:
        n_tenants, n_req, rows, dim = 12, 480, 3000, 32
    snap_dir = tempfile.mkdtemp(prefix="raft_tpu_capacity_")

    # build the tenants (off the serving clock — registration is the
    # expensive moment by design) and size the synthetic budget at ~4×
    # oversubscription of their FULL residency
    registry = serving.TenantRegistry()
    sizing = serving.CapacityController(registry=registry,
                                        budget_bytes=1 << 50)
    datasets = {}
    for i in range(n_tenants):
        name = f"tenant{i:02d}"
        X = rng.standard_normal((rows, dim)).astype(np.float32)
        datasets[name] = X
        idx = ivf_flat.build(X, ivf_flat.IvfFlatParams(
            n_lists=8, list_size_cap=0))
        sizing.register(name, idx, snap_dir)
    total_full = registry.resident_bytes()
    biggest = max(t.resident_bytes() for t in registry.tenants())
    one_probe = obs_costmodel.estimate_search(
        registry.tenants()[0].hot_obj, q=1, k=5,
        n_probes=4)["transient_bytes"]
    # ~4× oversubscribed, but with room for at least one hot tenant plus
    # a dispatch transient (otherwise the rung measures nothing but
    # rejections)
    budget = int(max(total_full / 4.0,
                     (biggest + 2 * one_probe) / 0.8))
    ctrl = serving.CapacityController(
        registry=registry, budget_bytes=budget, window_s=0.2)
    # re-place every tenant under the REAL budget (registration-time
    # admission ran against the sizing sentinel); the demotion window
    # bounds each pass, so wait it out until the ledger converges
    t_rebudget = time.perf_counter() + 30
    rec = ctrl.admit(0, entry="capacity.rebudget")
    while rec["verdict"] != "admit" and time.perf_counter() < t_rebudget:
        if not ctrl.make_room(rec.get("shortfall_bytes", 0)):
            time.sleep(ctrl.window_s + 0.02)
        rec = ctrl.admit(0, entry="capacity.rebudget")
    out = {
        "tenants": n_tenants,
        "budget_bytes": budget,
        "oversubscription_x": round(total_full / budget, 2),
        "rows_per_tenant": rows,
    }

    # skewed popularity (Zipf-ish) + Poisson arrivals
    ranks = np.arange(1, n_tenants + 1, dtype=np.float64)
    popularity = (1.0 / ranks ** 1.1)
    popularity /= popularity.sum()
    names = sorted(datasets)
    choices = rng.choice(n_tenants, size=n_req, p=popularity)
    think = rng.exponential(0.002, size=n_req)  # offered-load shaping
    outcomes = {"ok": 0, "degraded": 0, "rejected": 0, "deadline": 0,
                "oom": 0, "other": 0}
    k = 5

    # flight recorder over the chaos window (ISSUE 16): the tier census
    # rides the fingerprint, so residency reshuffles land as NEW frontier
    # groups — the capacity plane's operating points over time
    def _flight_knobs():
        census = {tier: sum(1 for t in registry.tenants()
                            if t.tier == tier)
                  for tier in ("hot", "warm", "cold")}
        return {"algo": "ivf_flat", "scan": "capacity", "k": k,
                "tenants": n_tenants, "tier_census": census}

    flight_path = os.path.join("results", "flight_capacity.jsonl")
    prog.truncate(flight_path)
    raw_iv = os.environ.get(obs_flight.INTERVAL_ENV, "").strip()
    flight = obs_flight.FlightRecorder(
        flight_path, knobs=_flight_knobs, capacity=ctrl,
        interval_s=None if raw_iv else (0.05 if tiny else 0.2))
    flight.sample()  # window 0 opens the recording before traffic

    t0 = time.perf_counter()
    for i in range(n_req):
        flight.maybe_sample()
        name = names[int(choices[i])]
        q = datasets[name][rng.integers(0, rows)][None].astype(np.float32)
        try:
            with resilience.Deadline(2.0, label="capacity.request"):
                res = ctrl.search(name, q, k, n_probes=4)
            outcomes["degraded" if res.degraded else "ok"] += 1
        except Exception as e:
            kind = resilience.classify(e)
            if isinstance(e, serving.CapacityRejected):
                outcomes["rejected"] += 1
            elif kind in outcomes:
                outcomes[kind] += 1
            else:
                outcomes["other"] += 1
        if i % 12 == 0:
            # the reverse path, off the request: popular demoted tenants
            # get their measured hot swap when the budget allows
            ctrl.autopromote(1)
        if think[i] > 0.004:
            time.sleep(min(think[i], 0.01))
    wall = time.perf_counter() - t0
    flight.sample()  # close the chaos window's recording

    # force ≥1 measured promote even if the window stayed all-admit: the
    # hot-swap latency row must exist (acceptance: measured, not claimed)
    if ctrl.promote_latency()["count"] == 0:
        victim = names[-1]
        ctrl.demote(victim)
        ctrl.registry.get(victim).last_demoted = 0.0
        ctrl.promote(victim)

    report = obs_report.collect(capacity=ctrl)
    cap_sec = report["capacity"]
    out["qps"] = round(n_req / wall, 1) if wall > 0 else 0.0
    out.update({
        "served_ok": outcomes["ok"],
        "degraded_serves": outcomes["degraded"],
        "rejections": outcomes["rejected"],
        "deadline_misses": outcomes["deadline"],
        # the headline gate: the allocator never saw an over-budget
        # dispatch, so the only acceptable count is zero
        "oom_verdicts": outcomes["oom"],
        "unclassified": outcomes["other"],
        "demotions": cap_sec["demotions"],
        "promotions": cap_sec["promotions"],
        "tenants_resident_hot": cap_sec["tenants_resident_hot"],
        "tenants_resident_warm": cap_sec["tenants_resident_warm"],
        "resident_bytes": cap_sec["resident_bytes"],
        "resident_fraction": cap_sec["resident_fraction"],
    })
    plat = cap_sec["promote"]
    out["promote_count"] = plat.get("count", 0)
    if plat.get("p50_s") is not None:
        out["promote_p50_s"] = plat["p50_s"]
        out["promote_p99_s"] = plat.get("p99_s")

    # degraded recall attribution: one demoted tenant's warm answers vs
    # its own exact search — the number the WARM tier costs. Pick a
    # tenant whose codes are ALREADY resident (a cold victim would need
    # an admission-checked reload the packed ledger may refuse).
    try:
        warm_now = [t.name for t in ctrl.registry.tenants()
                    if t.warm_index is not None]
        victim = warm_now[0] if warm_now else names[0]
        t = ctrl.registry.get(victim)
        if t.tier == "hot":
            ctrl.demote(victim)
        X = datasets[victim]
        qs = X[:32] + 0.01 * rng.standard_normal((32, dim)).astype(
            np.float32)
        res = ctrl.search(victim, qs, k, n_probes=64)
        d2 = ((X[None, :, :] - qs[:, None, :]) ** 2).sum(-1)
        exact_topk = np.argsort(d2, axis=1)[:, :k]
        got = np.asarray(res.indices)
        hits = sum(len(set(got[i]) & set(exact_topk[i]))
                   for i in range(len(qs)))
        out["degraded_recall"] = round(hits / (len(qs) * k), 4)
    except Exception as e:
        out["degraded_recall_error"] = section_error(e)

    # per-tenant SLO rows through the crash-safe channel (acceptance);
    # fresh stream per run, like the serving section's report file
    report_path = os.path.join("results", "obs_report_capacity.jsonl")
    prog.truncate(report_path)
    obs_report.export(report_path, report)
    out["obs_report_file"] = report_path
    out["per_tenant_rows"] = len(cap_sec["tenants"])
    out["flight_file"] = flight_path
    out["flight_windows"] = flight.windows_recorded
    if obs.enabled():
        obs.add("bench.capacity.requests", n_req)
    return out


def _maintenance_rung(tiny: bool, rng_seed: int = 13) -> dict:
    """Always-live index rung (ISSUE 18 acceptance): a paged store under a
    distribution-shifting upsert stream, MAINTAINED by the drift-driven
    incremental re-clustering manager in the serving idle gaps, against an
    identical NO-maintenance control. Rows reported:

    * ``recall_maintained`` / ``recall_control`` vs exact ground truth at
      fixed (k, n_probes), measured per batch with queries chasing the
      drifting distribution (``recall_curve_*``) — plus the HEALTHY
      pre-drift Wilson band and ``maintained_in_band`` (the maintained
      recall must still hold that band after the whole stream, while the
      unmaintained control may decay out of it);
    * ``recompiles_during_serving`` — paged scan (re)trace delta across
      every maintenance cycle (capacity-shaped swap operands ⇒ 0);
    * ``maintenance_cycles`` / ``stale_aborts`` / ``drift_score`` /
      ``list_skew`` straight from the manager's report;
    * ``unclassified`` — maintenance failures outside the known kinds
      (the only acceptable count is zero).
    """
    import numpy as np

    from raft_tpu import serving
    from raft_tpu.neighbors import ivf_pq
    from raft_tpu.obs.shadow import wilson_interval

    rng = np.random.default_rng(rng_seed)
    if tiny:
        n0, dim, n_lists, batches, b_rows, n_q = 1200, 16, 8, 3, 300, 64
    else:
        n0, dim, n_lists, batches, b_rows, n_q = 6000, 32, 16, 6, 600, 256
    k, n_probes = 10, max(2, n_lists // 2)

    # ivf_pq, deliberately: a drifted row encodes against its STALE
    # center, so the control's quantization error (and recall) degrades
    # with the drift — exactly the decay re-clustering repairs by
    # re-encoding the affected rows against fresh split centers (an
    # ivf_flat control hides the story: its list scans are exact)
    base = rng.standard_normal((n0, dim)).astype(np.float32)
    idx = ivf_pq.build(base, ivf_pq.IvfPqParams(
        n_lists=n_lists, pq_dim=max(8, dim // 2), pq_bits=8,
        list_size_cap=0))
    maintained = serving.PagedListStore.from_index(idx, page_rows=64)
    control = serving.PagedListStore.from_index(idx, page_rows=64)
    # the bench owns every raw row it streamed, so the manager re-encodes
    # from EXACT vectors (the row_source contract); without it the
    # re-encode quantizes a reconstruction — a second lossy hop
    ledger = {}
    mgr = serving.MaintenanceManager(
        maintained, compaction=None, drift_threshold=0.5, split_skew=1.5,
        min_split_rows=8,
        row_source=lambda ids: ledger["rows"][np.asarray(ids)])

    # pre-grow both stores to the stream's final footprint, OFF the
    # recompile window: pool growth is a legitimate, caller-visible
    # retrace (a deployment sizes its pools), and excluding it lets the
    # window below isolate maintenance-induced retraces specifically
    rows_total = n0 + batches * b_rows
    pages_fin = 2 * (-(-rows_total // 64)) + n_lists
    chain_max = -(-(batches * b_rows + n0) // 64)
    width_fin = 1
    while width_fin < chain_max:
        width_fin *= 2
    maintained.restore_shape(pages_fin, width_fin)
    control.restore_shape(pages_fin, width_fin)

    def _recall(store, queries, exact_ids) -> tuple:
        _vals, got = serving.search(store, queries, k, n_probes=n_probes)
        got = np.asarray(got)
        nq = queries.shape[0]
        hits = sum(len(set(got[i].tolist()) & set(exact_ids[i].tolist()))
                   for i in range(nq))
        return hits, nq * k

    all_rows = [base]
    next_id = n0
    dead_ids: set = set()

    def _gt(queries) -> "np.ndarray":
        # exact ground truth over the surviving rows from the host
        # ledger (pq codes are lossy; the bench owns the raw rows)
        rows_all = np.concatenate(all_rows)
        ledger["rows"] = rows_all  # ids are positional in this rung
        ids_all = np.arange(next_id, dtype=np.int64)
        live = (np.ones(next_id, bool) if not dead_ids
                else ~np.isin(ids_all, np.fromiter(dead_ids, np.int64)))
        rows_live, ids_np = rows_all[live], ids_all[live]
        d2 = ((queries[:, None, :] - rows_live[None, :, :]) ** 2).sum(-1)
        return ids_np[np.argsort(d2, axis=1)[:, :k]]

    def _queries_at(center: float) -> "np.ndarray":
        return (rng.standard_normal((n_q, dim)).astype(np.float32) * 0.3
                + center)

    # warm both scan programs, then open the recompile window: from here
    # on, upserts stay within the pre-grown capacity and maintenance
    # swaps keep shapes, so ANY retrace below is a contract violation
    q0 = _queries_at(0.0)
    gt0 = _gt(q0)
    h0_m, tot = _recall(maintained, q0, gt0)
    h0_c, _ = _recall(control, q0, gt0)
    tc0 = serving.scan_trace_count()
    # the gate band: the control's HEALTHY (pre-drift) Wilson interval —
    # the maintained store must still answer inside it after the whole
    # stream, while the unmaintained control may decay out of it
    ci_low, ci_high = wilson_interval(h0_c, tot)

    # distribution-shifting stream, maintenance pumped in the serving
    # idle gaps BETWEEN batches: each batch drifts further from the
    # build-time data and tightens, piling rows onto ever-fewer stale
    # lists — the skew/drift signal the detector must catch. Live
    # traffic chases the drift: every batch is measured with queries
    # from ITS OWN distribution against exact ground truth.
    cycles = 0
    unclassified = 0
    known = {"oom", "transient", "fatal", "deadline", "delay", "hang"}
    curve_m, curve_c = [], []
    r_m = r_c = h0_m / tot
    for b in range(batches):
        shift = (b + 1) * 2.0
        rows = (rng.standard_normal((b_rows, dim)).astype(np.float32)
                * 0.3 + shift)
        ids = np.arange(next_id, next_id + b_rows, dtype=np.int64)
        next_id += b_rows
        all_rows.append(rows)
        # refresh the exact-row ledger BEFORE the pump below: the
        # manager's row_source reads it for any id the store holds
        ledger["rows"] = np.concatenate(all_rows)
        maintained.upsert(rows, ids)
        control.upsert(rows, ids)
        # a few deletes of old rows: the tombstone component feeds the
        # same drift score
        dead = np.unique(rng.integers(0, n0, size=max(4, b_rows // 32)))
        dead_ids.update(dead.tolist())
        maintained.delete(dead)
        control.delete(dead)
        # one maintenance step per idle gap (the deterministic driver)
        rec = mgr.pump()
        status = (rec or {}).get("status")
        if status == "ok":
            cycles += 1
        elif (status not in (None, "idle", "noop", "denied", "stale")
              and status not in known):
            unclassified += 1
        qb = _queries_at(shift)
        gtb = _gt(qb)
        hm, totb = _recall(maintained, qb, gtb)
        hc, _ = _recall(control, qb, gtb)
        r_m, r_c = hm / totb, hc / totb
        curve_m.append(round(r_m, 4))
        curve_c.append(round(r_c, 4))
    # drain: let the detector go quiet (bounded), serving in between
    for _ in range(4):
        if not mgr.detect()["drifted"]:
            break
        rec = mgr.pump()
        status = (rec or {}).get("status")
        if status == "ok":
            cycles += 1
        elif (status not in (None, "idle", "noop", "denied", "stale")
              and status not in known):
            unclassified += 1
        qb = _queries_at(batches * 2.0)
        gtb = _gt(qb)
        hm, totb = _recall(maintained, qb, gtb)
        r_m = hm / totb
    tc1 = serving.scan_trace_count()
    r0_c = h0_c / tot
    rep = mgr.report()
    out = {
        "rows_final": int(maintained.size),
        "stream_batches": batches,
        "recall_maintained": round(r_m, 4),
        "recall_control": round(r_c, 4),
        "recall_maintained_start": round(h0_m / tot, 4),
        "recall_control_start": round(r0_c, 4),
        "recall_curve_maintained": curve_m,
        "recall_curve_control": curve_c,
        "recall_band_low": round(ci_low, 4),
        "recall_band_high": round(ci_high, 4),
        "maintained_in_band": bool(r_m >= ci_low),
        "recall_decay": round(max(0.0, h0_m / tot - r_m), 4),
        "control_decay": round(max(0.0, r0_c - r_c), 4),
        "maintenance_cycles": cycles,
        "stale_aborts": int(rep["stale_aborts"]),
        "drift_score": round(float(rep["drift_score"]), 4),
        "list_skew": round(float(rep["list_skew"]), 4),
        "rows_moved": int(rep["rows_moved"]),
        "recompiles_during_serving": int(tc1 - tc0),
        "unclassified": int(unclassified + rep["failures"]),
    }
    return out


def _autotune_rung(tiny: bool, rng_seed: int = 17) -> dict:
    """Closed-loop autotuning rung (ISSUE 20 acceptance): the offline
    diagnosis-driven tuner converges to an operating point meeting a
    synthetic SLO with NO hand-written sweep config, then the online
    burn-rate controller absorbs an induced load spike at that point.

    Phase A — offline: an :class:`raft_tpu.tuning.autotune.Autotuner`
    serves propose → window → explain iterations over a live
    QueryQueue/store (every window a flight fingerprint, every proposal
    justified by a ranked diagnosis from ``obs.explain``), accumulates
    the Pareto frontier and emits ``results/operating_point.json``. The
    recall floor is CALIBRATED, not hand-written: the widest recall gap
    on the measured probe ladder places the target between two rungs, so
    the loop must actually move to meet it.

    Phase B — online: serving restarts AT the emitted point (read back
    from disk — the same consumption path the serving section uses), a
    saturating load spike drives the p99 SLO into burn, and the
    :class:`raft_tpu.serving.BurnRateController` nudges knobs down
    (recall-guardrailed), then reverts toward the tuned point over cool
    windows. Every action lands as a ``tuning.action`` event on the
    flight timeline, and the episode must close with zero scan
    recompiles, zero unexplained retraces, zero unclassified request
    verdicts, and the final burn states back inside the error budget
    (``spike_budget_burn == 0``).
    """
    import numpy as np

    from raft_tpu import obs, serving
    from raft_tpu.bench import progress as prog
    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.obs import compile as obs_compile
    from raft_tpu.obs import explain as obs_explain
    from raft_tpu.obs import flight as obs_flight
    from raft_tpu.obs import report as obs_report
    from raft_tpu.obs import shadow as obs_shadow
    from raft_tpu.obs import slo as obs_slo
    from raft_tpu.tuning import autotune

    rng = np.random.default_rng(rng_seed)
    if tiny:
        n0, dim, n_lists, n_req = 1500, 16, 16, 40
        probe_ladder, cap_ladder = [2, 4, 8], [4, 8, 16]
    else:
        n0, dim, n_lists, n_req = 6000, 32, 32, 96
        probe_ladder, cap_ladder = [4, 8, 16], [8, 16, 32]
    k = 10
    cap_max = cap_ladder[-1]

    data = rng.standard_normal((n0, dim)).astype(np.float32)
    q_pool = rng.standard_normal((max(64, 2 * n_req), dim)) \
        .astype(np.float32)
    idx = ivf_flat.build(data, ivf_flat.IvfFlatParams(
        n_lists=n_lists, kmeans_trainset_fraction=0.5))
    store = serving.PagedListStore.from_index(idx)

    # warm EVERY (probe rung ∪ exact-scan) × pow2-bucket program off every
    # measured clock: the whole closed loop below — tuner windows, the
    # controller's live n_probes / batch-cap moves, the shadow sampler's
    # exact scans — must re-dispatch compiled programs only
    for np_ in list(probe_ladder) + [n_lists]:
        b = 1
        while True:
            v, _ = serving.search(store, np.repeat(q_pool[:1], b, axis=0),
                                  k, n_probes=np_)
            _force(v)
            if b >= cap_max:
                break
            b = min(2 * b, cap_max)

    t0 = time.perf_counter()
    for _ in range(3):
        v, _ = serving.search(store, q_pool[:1], k,
                              n_probes=probe_ladder[-1])
        _force(v)
    lat1 = max(1e-6, (time.perf_counter() - t0) / 3)
    t0 = time.perf_counter()
    v, _ = serving.search(store, np.repeat(q_pool[:1], cap_max, axis=0),
                          k, n_probes=probe_ladder[-1])
    _force(v)
    lat_full = max(1e-6, time.perf_counter() - t0)
    slo_s = max(4.0 * lat_full, 2.0 * lat1)

    # calibrate the synthetic recall SLO off the MEASURED ladder: the
    # floor sits in the widest recall gap between adjacent rungs, so it
    # is meetable (some rung clears it with margin) and binding (the
    # start rung misses it with margin) at any corpus/seed
    q_cal = q_pool[:cap_max]
    _, exact_cal = serving.search(store, q_cal, k, n_probes=n_lists)
    exact_cal = np.asarray(exact_cal)

    def _recall_at(nprobe: int) -> float:
        _, got = serving.search(store, q_cal, k, n_probes=nprobe)
        got = np.asarray(got)
        hits = sum(
            len(set(got[i].tolist()) & set(exact_cal[i].tolist()))
            for i in range(q_cal.shape[0]))
        return hits / (q_cal.shape[0] * k)

    ladder_recall = [_recall_at(p) for p in probe_ladder]
    gaps = [ladder_recall[i + 1] - ladder_recall[i]
            for i in range(len(ladder_recall) - 1)]
    if gaps and max(gaps) > 0.08:
        gi = gaps.index(max(gaps))
        floor = (ladder_recall[gi] + ladder_recall[gi + 1]) / 2.0
    else:  # degenerate ladder (all rungs alike): aim just under the top
        floor = ladder_recall[-1] - 0.03
    floor = round(min(0.95, max(0.2, floor)), 3)
    # the deployment's HARD recall SLO sits a band below the preferred
    # point: the controller may spend recall down to it under pressure,
    # never through it (the Wilson-CI guardrail enforces exactly this)
    floor_hard = round(max(0.05, floor - 0.1), 3)
    slo = {"p99_s": 5.0 * slo_s, "recall_floor": floor}
    out = {"n": n0, "dim": dim, "n_lists": n_lists, "k": k,
           "probe_ladder": probe_ladder, "cap_ladder": cap_ladder,
           "ladder_recall": [round(r, 4) for r in ladder_recall],
           "recall_floor": floor, "recall_floor_hard": floor_hard,
           "slo_p99_ms": round(slo["p99_s"] * 1e3, 3)}

    base_rate = 0.5 / lat1

    def _window_traffic(queue, rate, n, timeout_mult=50.0, flight=None,
                        ctrl=None, ctrl_every=0):
        """One Poisson traffic slice: submit at ``rate`` req/s, pump the
        queue in the gaps (the bench loop IS the serving worker)."""
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
        handles = []
        i = 0
        t_start = time.perf_counter()
        while i < n:
            if flight is not None:
                flight.maybe_sample()
            now = time.perf_counter() - t_start
            if now >= arrivals[i]:
                handles.append(queue.submit(
                    q_pool[i % len(q_pool)],
                    timeout_s=timeout_mult * slo_s))
                i += 1
                if ctrl is not None and ctrl_every and i % ctrl_every == 0:
                    ctrl.pump()
                continue
            if not queue.pump():
                time.sleep(min(arrivals[i] - now, 2e-4))
        queue.drain(timeout=120.0)
        return handles, time.perf_counter() - t_start

    # --- Phase A: offline tuner ------------------------------------------
    windows_path = os.path.join("results", "autotune_windows.jsonl")
    prog.truncate(windows_path)

    def serve_window(values):
        """Serve ONE window under the proposed knob vector with a FRESH
        sampler + SLO engine (windowed Wilson CI — a cumulative estimate
        would lag the knob moves it is supposed to judge)."""
        nprobe = int(values["n_probes"])
        cap = int(values["batch_cap"])
        sampler = obs_shadow.ShadowSampler(
            lambda qq: serving.search(store, qq, k, n_probes=n_lists),
            k=k, rate=1.0, seed=rng_seed, max_pending=n_req + 8)
        engine = obs_slo.SloEngine(
            obs_slo.default_serving_slos(slo_s, sampler=sampler,
                                         recall_floor=floor),
            fast_window_s=30.0, slow_window_s=120.0)
        queue = serving.QueryQueue(
            serving.searcher(store, k, n_probes=nprobe),
            slo_s=slo_s, max_batch=cap, fill_wait_s=lat_full,
            shadow=sampler)
        handles, wall = _window_traffic(queue, base_rate, n_req)
        sampler.drain(timeout_s=60.0)
        ok = [h.latency_s for h in handles if h.verdict == "ok"]
        return {
            "ops": {"qps": round(len(ok) / wall, 1) if wall > 0 else 0.0,
                    "p99_ub_s": (float(np.percentile(ok, 99))
                                 if ok else None),
                    "requests_ok": len(ok)},
            "report": obs_report.collect(engine=engine, sampler=sampler,
                                         queue=queue),
        }

    # single-value context knobs (algo / n_lists / k) ride along so the
    # emitted operating point names the configuration it was tuned FOR —
    # the serving section's compatibility gate keys off them
    tuner = autotune.Autotuner(
        serve_window,
        [autotune.Knob("n_probes", probe_ladder),
         autotune.Knob("batch_cap", cap_ladder, start=cap_ladder[1]),
         autotune.Knob("algo", ["ivf_flat"]),
         autotune.Knob("n_lists", [n_lists]),
         autotune.Knob("k", [k])],
        slo=slo, settle=3, max_windows=10, deadline_s=60.0,
        path=windows_path)
    tuner_stats = tuner.run()
    op_emitted = tuner.emit_operating_point()
    frontier = tuner.frontier()
    prog.write_artifact(os.path.join("results", "autotune_frontier.json"),
                        frontier)

    windows = tuner.windows()
    primaries = {}
    explain_invalid = 0
    proposals_undiagnosed = 0
    unexplained = 0
    for rec in windows:
        diag = rec.get("explain") or {}
        key = diag.get("primary") or "healthy"
        primaries[key] = primaries.get(key, 0) + 1
        explain_invalid += len(obs_explain.validate(diag))
        prop = rec.get("proposal")
        if not isinstance(prop, dict) or "diagnosis" not in prop:
            proposals_undiagnosed += 1
        # zero-tolerance gate counts CONSEQUENTIAL unknowns only: a
        # window that FAILED its tuner bound with no diagnosis. At tiny
        # CPU scale a burn-rate row can blip warn/breach on scheduler
        # jitter in a window whose measurement still meets the bound by
        # miles — explain honestly says unknown (it is in
        # diagnosis_counts), but that blip is not an unexplained
        # slowdown the gate should fail on
        elif key == "unknown" and not prop.get("meets_slo", True):
            unexplained += 1
    out["tuner"] = tuner_stats
    out["diagnosis_counts"] = primaries
    out["unexplained_diagnoses"] = unexplained
    out["explain_invalid"] = explain_invalid
    out["proposals_undiagnosed"] = proposals_undiagnosed
    out["frontier_points"] = frontier.get("pareto_points", 0)
    out["frontier_file"] = os.path.join("results", "autotune_frontier.json")
    out["windows_file"] = windows_path
    if op_emitted is None:
        out["operating_point_error"] = "no frontier point emitted"
        return out
    out["operating_point_file"] = autotune.default_operating_point_path()
    out["meets_slo"] = bool(op_emitted.get("meets_slo"))
    out["tuned_qps"] = op_emitted.get("qps")
    out["tuned_recall"] = op_emitted.get("recall")
    p99 = op_emitted.get("p99_ub_s")
    out["tuned_p99_ms"] = round(p99 * 1e3, 3) if p99 else None

    # --- Phase B: online control at the tuned point ----------------------
    # the operating point is read BACK FROM DISK — the same
    # load_operating_point consumption path bench sections use; the
    # hand-written sweep config is dead code from here on
    op = autotune.load_operating_point()
    op_knobs = (op or {}).get("knobs") or {}
    nprobe_tuned = op_knobs.get("n_probes")
    cap_tuned = op_knobs.get("batch_cap")
    if nprobe_tuned not in probe_ladder:
        nprobe_tuned = probe_ladder[-1]
    if cap_tuned not in cap_ladder:
        cap_tuned = cap_ladder[1]
    out["tuned_by"] = (op or {}).get("tuned_by")
    out["tuned_fp"] = (op or {}).get("fp")
    out["tuned_knobs"] = {"n_probes": nprobe_tuned, "batch_cap": cap_tuned}

    live = {"n_probes": int(nprobe_tuned)}

    def live_search(qq):
        return serving.search(store, qq, k, n_probes=live["n_probes"])

    sampler2 = obs_shadow.ShadowSampler(
        lambda qq: serving.search(store, qq, k, n_probes=n_lists),
        k=k, rate=1.0, seed=rng_seed + 1, max_pending=8 * n_req)
    # burn windows scaled to the rung's wall clock (the production 60 s /
    # 600 s pair would never see this spike end): fast ≈ one calm slice,
    # slow ≈ the spike; threshold 5 on the 1% latency budget means ≥5%
    # of a fast window slow ⇒ hot. The latency target is 2× the serving
    # bound: the engine's pow2-bucket bad-counting is ≤2× conservative
    # (every request in the bucket CONTAINING the target counts bad), so
    # a target inside the healthy tail's own bucket burns budget on
    # ordinary calm traffic — the controller gate needs the whole
    # healthy bucket under the target, while spike queue waits (≈6×
    # slo_s by construction) still land far above it
    engine2 = obs_slo.SloEngine(
        obs_slo.default_serving_slos(2.0 * slo_s, sampler=sampler2,
                                     recall_floor=floor_hard),
        fast_window_s=0.6, slow_window_s=2.5, threshold=5.0)
    queue2 = serving.QueryQueue(
        live_search, slo_s=slo_s, max_batch=cap_max,
        fill_wait_s=lat_full, shadow=sampler2)
    queue2.set_batch_cap(int(cap_tuned))
    actuators = [
        serving.KnobActuator(
            "n_probes", probe_ladder,
            lambda: live["n_probes"],
            lambda vv: live.__setitem__("n_probes", int(vv)),
            costs_recall=True),
        serving.KnobActuator(
            "batch_cap", cap_ladder,
            lambda: queue2.batch_cap, queue2.set_batch_cap),
    ]
    ctrl = serving.BurnRateController(
        engine2, actuators, sampler=sampler2, recall_floor=floor_hard,
        max_actions=1, cool_windows=2, deadline_s=60.0)

    flight_path = os.path.join("results", "flight_autotune.jsonl")
    prog.truncate(flight_path)

    def _spike_knobs():
        knobs = {"algo": store.kind, "n_lists": n_lists, "k": k,
                 "n_probes": live["n_probes"]}
        knobs.update(queue2.knobs())
        return knobs

    flight2 = obs_flight.FlightRecorder(
        flight_path, knobs=_spike_knobs, engine=engine2, sampler=sampler2,
        queue=queue2, interval_s=0.1)
    flight2.sample()  # window 0, off every measured clock

    traces0 = serving.scan_trace_count()
    unexplained0 = obs_compile.unexplained_retraces()

    # calm phase at the tuned point: the controller must HOLD (any action
    # here is a livelock bug, not control)
    calm_handles, calm_wall = _window_traffic(
        queue2, base_rate, n_req, flight=flight2, ctrl=ctrl, ctrl_every=8)
    sampler2.drain(timeout_s=60.0)
    calm_ok = [h.latency_s for h in calm_handles if h.verdict == "ok"]
    out["calm_qps"] = round(len(calm_ok) / calm_wall, 1) \
        if calm_wall > 0 else 0.0
    out["calm_actions"] = (ctrl.report() or {}).get("actions", 0)

    # induced load spike: each burst is DUMPED at once (arrival rate far
    # above any service rate), so the backlog's tail queue wait is
    # burst/service_rate by construction — sized to ≈6× slo_s off the
    # MEASURED per-dispatch cost at the tuned batch (lat_full, the
    # one-shot batch-cap_max timing, overestimates the steady-state
    # dispatch by whatever first-call slack it caught, and a Poisson
    # spike sized off it can fail to outrun the real service rate). The
    # controller is pumped BETWEEN bursts: latencies only exist once a
    # burst's backlog drains
    t0_disp = time.perf_counter()
    _force(live_search(q_pool[:int(cap_tuned)])[0])
    t_disp = max(time.perf_counter() - t0_disp, 1e-5)
    burst = int(cap_tuned) * min(96, max(3, int(6.0 * slo_s / t_disp) + 1))
    spike_rate = 1e9
    spike_handles = []
    for _ in range(4):
        hs, _ = _window_traffic(queue2, spike_rate, burst,
                                timeout_mult=400.0, flight=flight2)
        spike_handles.extend(hs)
        ctrl.pump()
        flight2.maybe_sample()

    # recovery: calm slices until the controller walks every knob back to
    # its tuned rung (cool-streak hysteresis pays one revert per
    # cool_windows quiet ticks — bounded, asserted below)
    restored = False
    recovery_handles = []
    for _ in range(40):
        hs, _ = _window_traffic(queue2, base_rate, 8, flight=flight2)
        recovery_handles.extend(hs)
        tick = ctrl.pump() or {}
        flight2.maybe_sample()
        restored = all(a.idx == a.tuned_idx for a in actuators)
        if restored and tick.get("status") == "cool" \
                and not tick.get("actions"):
            break
        time.sleep(0.05)
    # the error-budget verdict of record: burn states the moment the
    # controller declares the episode over (cool tick, knobs restored).
    # Scored HERE — the shadow drain below takes real wall time with no
    # fresh traffic, so a later evaluate would re-anchor the 0.6 s fast
    # window onto a sparse mid-recovery sample and re-count spike bads.
    final_rows = engine2.evaluate()
    sampler2.drain(timeout_s=60.0)
    flight2.sample()

    out["recompiles_during_spike"] = serving.scan_trace_count() - traces0
    out["unexplained_retraces"] = \
        obs_compile.unexplained_retraces() - unexplained0
    all_handles = calm_handles + spike_handles + recovery_handles
    misses = sum(1 for h in all_handles if h.verdict == "deadline")
    n_ok = sum(1 for h in all_handles if h.verdict == "ok")
    out["spike_requests"] = len(all_handles)
    out["spike_deadline_misses"] = misses
    out["unclassified"] = len(all_handles) - n_ok - misses
    out["knobs_restored"] = bool(restored)

    crep = ctrl.report() or {}
    out["controller_actions"] = crep.get("actions", 0)
    out["controller_nudges"] = crep.get("nudges", 0)
    out["controller_reverts"] = crep.get("reverts", 0)
    out["guardrail_holds"] = crep.get("guardrail_holds", 0)
    out["controller_failures"] = crep.get("failures", 0)
    out["slo_breach_windows"] = crep.get("breach_ticks", 0)

    # a spike the loop absorbed leaves no SLO in breach once the fast
    # window clears (zero-tolerance in bench_compare)
    out["spike_budget_burn"] = sum(
        1 for r in final_rows.values()
        if isinstance(r, dict) and r.get("state") == "breach")
    out["final_slo"] = {
        name: {"state": row.get("state"),
               "burn_fast": round(row["burn_fast"], 4)}
        if "burn_fast" in row else {"state": row.get("state")}
        for name, row in final_rows.items()}

    # the reconstructible-episode check: every controller action must be
    # a validating tuning.action event on the flight timeline
    out["flight_file"] = flight_path
    out["flight_windows"] = flight2.windows_recorded
    try:
        recording = obs_flight.read_recording(flight_path)
        actions_seen = [
            e for rec in recording if rec.get("type") == "flight_window"
            for e in (rec.get("events") or [])
            if e.get("event") == "tuning.action"]
        bad = sum(1 for e in actions_seen
                  if not all(f in e for f in ("knob", "frm", "to",
                                              "action")))
        out["tuning_action_events"] = len(actions_seen)
        out["tuning_action_events_invalid"] = bad
    except Exception as e:
        out["flight_error"] = section_error(e)

    # the v6 report with the controller's tuning section must validate
    final_report = obs_report.collect(
        engine=engine2, sampler=sampler2, queue=queue2, controller=ctrl)
    prog.write_artifact(os.path.join("results", "autotune_report.json"),
                        final_report)
    out["report_tuning_problems"] = [
        p for p in obs_report.validate(final_report)
        if "tuning" in p]
    if obs.enabled():
        obs.add("bench.tuning.requests",
                len(all_handles) + len(windows) * n_req)
    return out


def _bq_gate_escalate(run_pair, recall_of, k: int, probe_ladder) -> dict:
    """The ONE copy of the IVF-BQ recall-gate protocol (1M section and
    capacity rung both ride it — two copies would silently drift into
    measuring different configurations): escalate nprobe at a 4·k
    over-fetch first, then widen the over-fetch at the best nprobe until
    the exact re-rank holds the 0.95 gate, capped at the strip engine's
    k=512. ``run_pair(nprobe, k_fetch) -> (vals, ids)`` runs scan+refine;
    ``recall_of(vals, ids) -> float`` scores against ground truth."""
    best = None
    for nprobe in probe_ladder:
        kf = min(4 * k, 512)
        rec = recall_of(*run_pair(nprobe, kf))
        if best is None or rec > best["recall"]:
            best = {"nprobe": int(nprobe), "recall": round(rec, 4),
                    "k_fetch": kf}
        if rec >= 0.95:
            break
    if best["recall"] < 0.95:
        for kf in (8 * k, 16 * k, 32 * k):
            kf = min(kf, 512)
            rec = recall_of(*run_pair(best["nprobe"], kf))
            if rec > best["recall"]:
                best.update(recall=round(rec, 4), k_fetch=kf)
            if rec >= 0.95:
                break
    return best


def _ivf_bq_capacity(reps: int, n_rows: int, dim: int, k: int) -> dict:
    """One memory-resident IVF-BQ rung at ``n_rows``: build (1-bit codes +
    correction scalars resident, dataset uint8-resident for the exact
    re-rank), chunked-scan exact ground truth, nprobe/k_fetch escalation to
    the 0.95 gate, then measured QPS. The per-chip capacity MEASUREMENT —
    scan work and residency both real at this row count, no extrapolation."""
    import jax.numpy as jnp

    from raft_tpu import stats
    from raft_tpu.bench.datasets import sift_like
    from raft_tpu.neighbors import batch_knn, ivf_bq, refine

    Q = 10_000
    # n_lists scales with rows (√n-ish, the deep10m regime note: pairs per
    # probed list ≈ the strip width keeps the engine in its design regime)
    nlist = 4096 if n_rows >= 4_000_000 else 1024
    data_u8, queries_u8 = sift_like(n_rows, dim, Q, seed=2)
    dataset = jnp.asarray(data_u8)               # uint8-resident rerank source
    queries = jnp.asarray(queries_u8, jnp.float32)
    out = {"n": n_rows, "dim": dim, "q": Q, "n_lists": nlist,
           "dataset": f"siftlike-{n_rows // 1_000_000}m-{dim}-uint8"}

    gt_vals, gt_ids = batch_knn.search_device_chunked(
        dataset, queries, k, chunk_rows=32768)
    _force(gt_vals)

    t0 = time.perf_counter()
    idx = ivf_bq.build(dataset, ivf_bq.IvfBqParams(
        n_lists=nlist, kmeans_trainset_fraction=0.1, list_size_cap=4096))
    _force(idx.list_scale)
    out["build_s"] = round(time.perf_counter() - t0, 1)
    out["code_bytes_per_row"] = idx.code_bytes_per_row

    def run_pair(nprobe, kf):
        _, cand = ivf_bq.search(idx, queries, kf, n_probes=nprobe)
        return refine.refine(dataset, queries, cand, k)

    best = _bq_gate_escalate(
        run_pair,
        lambda vals, ids: float(stats.neighborhood_recall(
            ids, gt_ids, vals, gt_vals)),
        k, (32, 64, 128))

    def run(qs):
        _, cand = ivf_bq.search(idx, qs, best["k_fetch"],
                                n_probes=best["nprobe"])
        return refine.refine(dataset, qs, cand, k)

    v, _ = run(queries)
    _force(v)
    t0 = time.perf_counter()
    for _ in range(reps):
        v, _ = run(queries)
    _force(v)
    best["qps"] = round(Q / ((time.perf_counter() - t0) / reps), 1)
    out.update(best)
    return out


def _deep10m_crossover(reps: int, scale: float = 1.0) -> dict:
    """10M x 96 (DEEP-shaped) section: exact chunked-scan baseline vs
    IVF-PQ + exact refine at a 0.95 recall gate.

    ``scale`` < 1 is the degraded-retry knob (ISSUE 3): after an
    OOM-classified first attempt the caller re-runs at half the rows —
    same pipeline, honestly smaller shape, marked ``degraded`` upstream."""
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import stats
    from raft_tpu.bench.datasets import sift_like
    from raft_tpu.neighbors import batch_knn, ivf_pq, refine

    # n_lists=4096 at Q=10000: pairs per probed list ≈ 160 ≈ the strip
    # width C, the regime the strip engine is built for (at q=2000 /
    # n_lists=8192 the static worst-case layout allocated ~18 GB of
    # query-side tables — round-4 OOM)
    N, DIM, Q, K = int(10_000_000 * scale), 96, 10_000, 10
    NLIST = 4096
    data_u8, queries_u8 = sift_like(N, DIM, Q, seed=1)
    dataset = jnp.asarray(data_u8)               # uint8 on device (960 MB)
    queries = jnp.asarray(queries_u8, jnp.float32)
    out = {"n": N, "dim": DIM, "q": Q, "k": K, "n_lists": NLIST,
           "dataset": f"deeplike-{N // 1_000_000}m-96-uint8"}
    if scale != 1.0:
        out["scale"] = scale

    # exact ground truth AND the brute baseline: one chunked device scan
    # (32768-row chunks keep the (q, chunk) score block ~1.3 GB)
    gt_vals, gt_ids = batch_knn.search_device_chunked(
        dataset, queries, K, chunk_rows=32768)
    _force(gt_vals)
    t0 = time.perf_counter()
    for _ in range(max(1, reps // 2)):
        v, _ = batch_knn.search_device_chunked(
            dataset, queries, K, chunk_rows=32768)
    _force(v)
    out["brute_chunked"] = {
        "qps": round(Q / ((time.perf_counter() - t0) / max(1, reps // 2)), 1),
        "recall": 1.0}

    t0 = time.perf_counter()
    # list cap 4096 (~1.7x mean): bounds the padded-list HBM footprint —
    # the decoded int8 cache alone is n_lists x mls x 96 B, and the default
    # 4x-mean cap pow2-rounds mls to 8192 (a ~3 GB cache; OOM at 10M)
    idx = ivf_pq.build(dataset, ivf_pq.IvfPqParams(
        n_lists=NLIST, pq_dim=DIM // 2, pq_bits=8,
        kmeans_trainset_fraction=0.1, list_size_cap=4096))
    _force(idx.b_sum)
    out["ivf_pq_build_s"] = round(time.perf_counter() - t0, 1)

    pq = None
    for nprobe in (32, 64, 128):
        _, cand = ivf_pq.search(idx, queries, 2 * K, n_probes=nprobe)
        vals, ids = refine.refine(dataset, queries, cand, K)
        rec = float(stats.neighborhood_recall(ids, gt_ids, vals, gt_vals))
        if pq is None or rec > pq["recall"]:
            pq = {"nprobe": nprobe, "recall": round(rec, 4), "k_fetch": 2 * K}
        if rec >= 0.95:
            break

    def run(qs):
        _, cand = ivf_pq.search(idx, qs, pq["k_fetch"],
                                n_probes=pq["nprobe"])
        return refine.refine(dataset, qs, cand, K)

    v, _ = run(queries)
    _force(v)
    t0 = time.perf_counter()
    for _ in range(reps):
        v, _ = run(queries)
    _force(v)
    pq["qps"] = round(Q / ((time.perf_counter() - t0) / reps), 1)
    out["ivf_pq"] = pq
    out["ann_beats_brute"] = bool(pq["qps"] > out["brute_chunked"]["qps"]
                                  and pq["recall"] >= 0.95)
    # honest extrapolation to the SIFT-1B per-chip share (15.6M rows/chip on
    # v5e-64): scale QPS by measured-rows / target-rows (scan work ∝ rows)
    out["sift1b_per_chip_qps_extrapolated"] = round(
        pq["qps"] * N / 15_625_000, 1)
    return out


def _child_main(platform: str) -> None:
    try:
        if platform == "cpu":
            import jax

            jax.config.update("jax_platforms", "cpu")
        result = run_suite()
    except BaseException:
        sys.stderr.write(traceback.format_exc())
        sys.exit(1)
    _emit(result)


# ---------------------------------------------------------------------------
# Parent mode: orchestration with health probe and timeouts
# ---------------------------------------------------------------------------

def _attempt(platform: str, timeout: float, hb_path=None):
    """Run the measurement subprocess; returns (json_dict | None, err_text)."""
    env = dict(os.environ)
    env["RAFT_TPU_BENCH_CHILD"] = platform
    # per-host telemetry artifact targets (round-8): the child writes its
    # process-stamped metrics + Perfetto trace here; the parent aggregates.
    # Truncated per attempt: a previous run's per-process snapshots must
    # not fold into this one's fleet view (the dedup in obs/aggregate is
    # per (source, process_index) — it cannot tell a stale run's p1..pN
    # files from live ones)
    metrics_dir = os.path.join(_REPO, "results", "metrics")
    trace_dir = os.path.join(_REPO, "results")
    if _PROGRESS is not None:
        _PROGRESS.truncate_dir(metrics_dir)
        # and the per-process traces: a dead 4-host run's p1..p3 traces
        # must not sit next to this run's p0 looking current (prefix
        # scoping keeps the committed round artifacts in results/ untouched)
        _PROGRESS.truncate_dir(trace_dir, suffix=".json",
                               prefix="trace_bench_p")
    env["RAFT_TPU_BENCH_METRICS_DIR"] = metrics_dir
    env["RAFT_TPU_BENCH_TRACE_DIR"] = trace_dir
    if hb_path:
        env["RAFT_TPU_BENCH_HEARTBEAT"] = hb_path
    else:
        env.pop("RAFT_TPU_BENCH_HEARTBEAT", None)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            cwd=_REPO,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired as e:
        return None, f"{platform} attempt timed out after {timeout:.0f}s: {e.stderr or ''}"
    for line in (proc.stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), ""
            except json.JSONDecodeError:
                continue
    return None, (
        f"{platform} attempt rc={proc.returncode}\n"
        f"stdout: {(proc.stdout or '')[-1000:]}\nstderr: {(proc.stderr or '')[-2000:]}"
    )


def _aggregate_fleet():
    """Merge the children's per-process metric files into ONE fleet view
    (results/metrics_fleet.json) via obs/aggregate — loaded by FILE PATH
    (stdlib-only, same rule as progress/health: the parent never takes the
    raft_tpu/jax import lock). Returns the artifact path, or None (a fleet
    view is a nice-to-have; its absence must never cost the metric line)."""
    metrics_dir = os.path.join(_REPO, "results", "metrics")
    try:
        files = sorted(
            os.path.join(metrics_dir, f) for f in os.listdir(metrics_dir)
            if f.endswith(".jsonl"))
        if not files:
            return None
        agg = _load_by_path("_obs_aggregate", "raft_tpu", "obs",
                            "aggregate.py")
        fleet = agg.merge_files(files)
        if not fleet.get("sources"):
            # files existed but held no parseable records (torn writes from
            # a dead child): advertising an empty fleet view would be worse
            # than none
            return None
        out = os.path.join(_REPO, "results", "metrics_fleet.json")
        _PROGRESS.write_artifact(out, fleet)
        return out
    # truly anything — a corrupted aggregate.py (SyntaxError from the
    # file-path load) or a malformed record (TypeError in the merge) must
    # degrade to "no fleet view", never crash the parent between a finished
    # round and _emit(result); classification is unavailable here by design
    # (the parent stays off the raft_tpu/jax import lock)
    except Exception:  # graftlint: ignore[unclassified-except]
        return None


def _stitch_fleet():
    """Fold the children's per-process Perfetto traces into ONE fleet
    timeline (results/trace_fleet.json) via obs/aggregate.stitch_traces —
    per-host pid tracks, host-local span ids namespaced, fleet_trace_id
    attrs left as the cross-host join key, clocks aligned by the flight
    recording's handshake records when present. File-path loaded and
    best-effort, the _aggregate_fleet contract: its absence must never
    cost the metric line."""
    trace_dir = os.path.join(_REPO, "results")
    try:
        files = sorted(
            os.path.join(trace_dir, f) for f in os.listdir(trace_dir)
            if f.startswith("trace_bench_p") and f.endswith(".json"))
        if not files:
            return None
        agg = _load_by_path("_obs_aggregate", "raft_tpu", "obs",
                            "aggregate.py")
        docs = [agg.read_trace(p) for p in files]
        if not any(d is not None for d in docs):
            return None
        offsets = None
        flight_path = os.path.join(_REPO, "results",
                                   "flight_streaming.jsonl")
        if os.path.exists(flight_path):
            offsets = agg.merge_records(
                agg.read_jsonl(flight_path)).get("clock_offsets")
        doc = agg.stitch_traces(docs, clock_offsets=offsets)
        if not doc.get("traceEvents"):
            return None
        out = os.path.join(_REPO, "results", "trace_fleet.json")
        _PROGRESS.write_artifact(out, doc)
        return out
    # same degrade-to-absent contract as _aggregate_fleet above
    except Exception:  # graftlint: ignore[unclassified-except]
        return None


def _parse_args(argv):
    import argparse

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--heartbeat", default=None, metavar="PATH",
                    help="checkpoint JSONL path "
                         "(default results/bench_progress.jsonl)")
    ap.add_argument("--no-heartbeat", action="store_true",
                    help="disable the checkpoint side-channel")
    ap.add_argument("--skip-health", action="store_true",
                    help="skip the pre-TPU device-health probe")
    args, _ = ap.parse_known_args(argv)
    return args


def main():
    global _HB_PATH, _PROGRESS
    child = os.environ.get("RAFT_TPU_BENCH_CHILD")
    if child:
        _child_main(child)
        return
    args = _parse_args(sys.argv[1:])

    t_start = time.monotonic()

    def remaining():
        return WATCHDOG_SECONDS - (time.monotonic() - t_start)

    # parent helpers by file path, loaded BEFORE the watchdog exists: both
    # modules are stdlib-only, so the parent never takes the raft_tpu/jax
    # package import lock (a wedged import would otherwise block _fail)
    _PROGRESS = _load_by_path("_bench_progress",
                              "raft_tpu", "bench", "progress.py")
    health = _load_by_path("_bench_health", "raft_tpu", "obs", "health.py")

    t = threading.Timer(
        WATCHDOG_SECONDS, _fail, args=(f"watchdog: exceeded {WATCHDOG_SECONDS}s", 3)
    )
    t.daemon = True
    t.start()

    hb_path = None
    if not args.no_heartbeat:
        hb_path = os.path.abspath(
            args.heartbeat or os.path.join(_REPO, "results",
                                           "bench_progress.jsonl"))
        _PROGRESS.truncate(hb_path)  # fresh file per run
        _HB_PATH = hb_path
    # metric files are truncated per attempt inside _attempt

    # --- device-health probe BEFORE the measuring child takes the chip
    # (ISSUE 1: the round-5 backend wedge burned the full window with no
    # record); the parent itself never touches JAX ------------------------
    err = None
    if not args.skip_health:
        report = health.probe("default", timeout=HEALTH_PROBE_SECONDS)
        if not report.healthy and "timed out" in report.reason:
            # one retry: the first probe's child may have paid a cold
            # backend start; a genuinely wedged backend times out again
            report = health.probe("default", timeout=HEALTH_PROBE_SECONDS)
        if not report.healthy:
            err = (f"health probe unhealthy after {report.elapsed_s}s: "
                   f"{report.reason}")
    if err is None:
        window = min(TPU_ATTEMPT_SECONDS, remaining() - ORCH_OVERHEAD_SECONDS)
        if window < MIN_ATTEMPT_SECONDS:
            err = (f"derived attempt window {window:.0f}s < "
                   f"{MIN_ATTEMPT_SECONDS:.0f}s minimum")
        else:
            result, err = _attempt("default", window, hb_path)
            if result is not None:
                fleet = _aggregate_fleet()
                if fleet:
                    result["fleet_metrics"] = fleet
                trace = _stitch_fleet()
                if trace:
                    result["fleet_trace"] = trace
                _emit(result)
                return
    # _fail salvages the chip's completed sections from the checkpoint
    # file, then exits non-zero either way
    _fail(f"chip attempt failed: {err}")


if __name__ == "__main__":
    main()
