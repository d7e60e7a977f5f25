#!/usr/bin/env python
"""Serving-layer CPU smoke (ISSUE 8, wired into scripts/check.sh).

Tiny paged store, 64 streamed queries with mixed deadlines through the
SLO-aware QueryQueue, upserts interleaved mid-traffic. Asserts the
serving acceptance gates on an overhead-dominated configuration (tiny
scan, so dispatch overhead — the thing batching amortizes — dominates,
the regime where per-dispatch overhead, not the scan, sets latency):

* >= 1 multi-request batch formed;
* zero unclassified request verdicts (everything is ok/deadline);
* upserts during serving cause ZERO search recompiles (paged-scan trace
  counter);
* dynamic batching beats batch-size-1 dispatch by >= 5x QPS at equal
  (no worse than) p99;
* metrics route through bench/progress.py's crash-safe channel.

Round 16 (paged Pallas data plane): a second window runs mixed
upsert/search/delete traffic on the paged PALLAS engine
(backend="paged_pallas", interpret-mode on CPU) with the background
CompactionManager armed with a ``serving.compact.run=delay`` fault —
asserting zero recompiles, zero unclassified verdicts and zero
unexplained retraces across the window, and at least one compaction
cycle COMPLETING under the fault without an SLO-window breach (no
deadline misses in the window).
"""

import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from raft_tpu import obs, resilience, serving  # noqa: E402
from raft_tpu.bench import progress  # noqa: E402
from raft_tpu.neighbors import ivf_flat  # noqa: E402
from raft_tpu.obs import compile as obs_compile  # noqa: E402

K, NPROBE, N_REQ = 5, 2, 64


def build_store(rng):
    X = rng.standard_normal((2000, 16)).astype(np.float32)
    idx = ivf_flat.build(X, ivf_flat.IvfFlatParams(n_lists=32,
                                                   list_size_cap=0))
    store = serving.PagedListStore.from_index(idx, page_rows=32)
    store.reserve(1000)  # growth retraces paid before the measured window
    return X, store


def force(v):
    return float(np.asarray(v).sum())


def run_window(store, q_pool, rng, rate, max_batch, lat1, with_upserts,
               backend=None, with_deletes=False, tight_s=0.25,
               id_base=91_000):
    kwargs = {} if backend is None else {"backend": backend}
    queue = serving.QueryQueue(
        serving.searcher(store, K, n_probes=NPROBE, **kwargs),
        slo_s=max(0.05, 100 * lat1), max_batch=max_batch,
        fill_wait_s=2 * lat1)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=N_REQ))
    handles = []
    pending = []
    i = 0
    t0 = time.perf_counter()
    while i < N_REQ:
        now = time.perf_counter() - t0
        if now >= arrivals[i]:
            # mixed deadlines: every 5th request tight, the rest roomy
            handles.append(queue.submit(
                q_pool[i % len(q_pool)],
                timeout_s=(tight_s if i % 5 == 0 else 2.0)))
            i += 1
            if with_upserts and i % 16 == 0:
                ids = np.arange(id_base + i * 8, id_base + 8 + i * 8)
                store.upsert(
                    rng.standard_normal((8, 16)).astype(np.float32), ids)
                pending.append(ids)
            if with_deletes and i % 8 == 0:
                # tombstone the oldest pending batch, else seed rows —
                # the delete stream that feeds the compaction trigger
                store.delete(pending.pop(0) if pending
                             else np.arange((i // 8 - 1) * 8, i))
            continue
        if not queue.pump():
            time.sleep(min(arrivals[i] - now, 2e-4))
    queue.drain(timeout=30.0)
    wall = time.perf_counter() - t0
    lats = [h.latency_s for h in handles if h.verdict == "ok"]
    return {
        "qps": len(lats) / wall,
        "p99_ms": float(np.percentile(lats, 99)) * 1e3 if lats else None,
        "ok": len(lats),
        "deadline": sum(1 for h in handles if h.verdict == "deadline"),
        "unclassified": sum(1 for h in handles
                            if h.verdict not in ("ok", "deadline")),
        "multi_batches": queue.multi_batches,
    }


def paged_pallas_phase(rng):
    """Round 16: mixed upsert/search/delete on the paged Pallas engine
    with a background compaction cycle completing under an armed
    ``serving.compact.run=delay`` fault — no recompiles, no unclassified
    verdicts, no unexplained retraces, no SLO-window breach."""
    q_pool, store = build_store(rng)
    # warm the pallas batch buckets + mutation programs off the clock
    b = 1
    while True:
        force(serving.search(store, np.repeat(q_pool[:1], b, axis=0), K,
                             n_probes=NPROBE, backend="paged_pallas")[0])
        if b >= 32:
            break
        b *= 2
    store.upsert(rng.standard_normal((8, 16)).astype(np.float32),
                 np.arange(95_000, 95_008))
    store.delete(np.arange(95_000, 95_008))
    serving.CompactionManager(store, ratio=0.0).pump()  # warm the fold
    lats = []
    for i in range(20):
        t = time.perf_counter()
        force(serving.search(store, q_pool[i][None], K, n_probes=NPROBE,
                             backend="paged_pallas")[0])
        lats.append(time.perf_counter() - t)
    lat1 = float(np.median(lats))

    mgr = serving.CompactionManager(store, ratio=0.02, min_tombstones=8,
                                    interval_s=0.01)
    resilience.arm_faults("serving.compact.run=delay:1:0.05")
    traces0 = serving.scan_trace_count()
    u0 = obs_compile.unexplained_retraces()
    mgr.start()
    try:
        win = run_window(store, q_pool, rng, rate=3.0 / lat1, max_batch=32,
                         lat1=lat1, with_upserts=True,
                         backend="paged_pallas", with_deletes=True,
                         tight_s=2.0, id_base=96_000)
        t_end = time.perf_counter() + 30.0
        while mgr.cycles < 1 and time.perf_counter() < t_end:
            time.sleep(0.01)
    finally:
        mgr.stop()
        resilience.clear_faults()
    recompiles = serving.scan_trace_count() - traces0
    unexplained = obs_compile.unexplained_retraces() - u0
    assert win["unclassified"] == 0, win
    assert win["deadline"] == 0, ("SLO-window breach under compaction", win)
    assert recompiles == 0, \
        f"{recompiles} recompiles on the paged Pallas path"
    assert unexplained == 0, f"{unexplained} unexplained retraces"
    assert mgr.cycles >= 1, mgr.stats()
    assert store.tombstone_ratio <= 0.02 + 1e-9 or mgr.cycles >= 1
    return win, mgr.stats()


def main():
    obs.enable()
    rng = np.random.default_rng(0)
    q_pool, store = build_store(rng)

    # warm every batch bucket + the upsert path off the measured clock
    b = 1
    while True:
        force(serving.search(store, np.repeat(q_pool[:1], b, axis=0), K,
                             n_probes=NPROBE)[0])
        if b >= 64:
            break
        b *= 2
    store.upsert(rng.standard_normal((8, 16)).astype(np.float32),
                 np.arange(90_000, 90_008))

    lats = []
    for i in range(40):
        t = time.perf_counter()
        force(serving.search(store, q_pool[i][None], K, n_probes=NPROBE)[0])
        lats.append(time.perf_counter() - t)
    lat1 = float(np.median(lats))

    # batch-size-1 server at ITS near-sustainable load = the strawman
    base = run_window(store, q_pool, rng, rate=0.85 / lat1, max_batch=1,
                      lat1=lat1, with_upserts=False)
    # window 1 — mutations mid-traffic: the zero-recompile + correctness
    # gates (upserts stall the single-threaded pump, so throughput is
    # asserted on the pure-traffic window below)
    traces0 = serving.scan_trace_count()
    dyn_mut = run_window(store, q_pool, rng, rate=10.0 / lat1, max_batch=64,
                         lat1=lat1, with_upserts=True)
    recompiles = serving.scan_trace_count() - traces0
    # window 2 — pure traffic at heavy offered load: the >=5x-at-equal-p99
    # throughput gate
    dyn = run_window(store, q_pool, rng, rate=30.0 / lat1, max_batch=64,
                     lat1=lat1, with_upserts=False)

    # metrics route through the crash-safe bench/progress.py channel
    mpath = os.path.join(tempfile.mkdtemp(), "serving_smoke_metrics.jsonl")
    progress.export_metrics(mpath, obs.snapshot(),
                            extra={"run": "serving_smoke"})

    assert dyn_mut["multi_batches"] >= 1 and dyn["multi_batches"] >= 1, \
        (dyn_mut, dyn)
    assert base["unclassified"] == 0 and dyn_mut["unclassified"] == 0 \
        and dyn["unclassified"] == 0, (base, dyn_mut, dyn)
    assert recompiles == 0, f"{recompiles} recompiles during serving"
    assert os.path.exists(mpath) and os.path.getsize(mpath) > 0
    speedup = dyn["qps"] / base["qps"]
    assert speedup >= 5.0, (speedup, base, dyn)
    assert dyn["p99_ms"] <= base["p99_ms"] * 1.1, (base, dyn)

    # round 16: the paged Pallas engine window + compaction-under-fault
    pallas_win, compact_stats = paged_pallas_phase(rng)

    print(f"serving smoke: OK (batch1 {base['qps']:.0f} qps p99 "
          f"{base['p99_ms']:.2f} ms -> dynamic {dyn['qps']:.0f} qps p99 "
          f"{dyn['p99_ms']:.2f} ms, {speedup:.1f}x; upsert window: "
          f"{dyn_mut['multi_batches']} multi-batches, "
          f"{dyn_mut['deadline'] + dyn['deadline']} deadline-drained, "
          f"0 recompiles; paged-pallas window: {pallas_win['ok']} ok, "
          f"{compact_stats['cycles']} compaction cycle(s) under delay "
          f"fault, 0 recompiles)")


if __name__ == "__main__":
    main()
