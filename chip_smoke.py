"""Chip smoke: the IVF search path end to end on TPU, in one process.

Drives the library through the entry points a user calls, at the corpus
size of the SIFT-1M shape (1M x 128 uint8, L2, 10k queries, k=10), and
checks every answer against exact brute force:

  1. device check — no TPU, no run (there is no CPU branch);
  2. corpus from ``bench.datasets.sift_like`` (made anew from ``--seed``)
     and exact ground truth from ``neighbors.brute_force``;
  3. IVF-Flat and IVF-PQ (+ ``refine``) offline batch search with
     ``backend="auto"`` over all the queries along n_probes 16, 32, 64 —
     IVF-Flat at every step, IVF-PQ up to the first step with recall@10
     >= 0.95 — the first such step taken as the result, and both families
     asserted to have run only the ``ragged`` engine;
  4. the served path: a ``serving.PagedListStore`` behind a
     ``serving.QueryQueue`` answers 1,000 single-query requests with
     upserts and deletes between rounds — asserted on ``paged_pallas``,
     every request answered, zero recompiles after warm-up, and recall@10
     against brute force over the store's live rows;
  5. no resilience fallback or degrade event fired.

``--chips 4`` runs only the sharded path (``raft_tpu.distributed``) over a
4M x 128 corpus, 1M rows per chip, and compares it with the single-device
result on the same data.

The last line of stdout is one JSON object: ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": ...}}``. Any failure is an
uncaught exception and a non-zero exit, with no such line.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

K = 10
N_LISTS = 1024
PROBE_LADDER = (16, 32, 64)
RECALL_GATE = 0.95
PQ_FETCH = 4 * K
# single-device run: the SIFT-1M shape
N_ROWS, DIM, N_QUERIES = 1_000_000, 128, 10_000
# served phase: rounds x requests, with one upsert and one delete batch per
# round (constant batch shapes: the warm-up compiles them once)
ROUNDS, PER_ROUND, UPSERT_ROWS, DELETE_ROWS, MAX_BATCH = 10, 100, 32, 16, 64
# the sharded phase: 1M rows per chip
ROWS_PER_CHIP = 1_000_000
# resilience events that mean a silent fallback or degradation
_BAD_EVENTS = ("fallback", "degrade", "retry", "error", "fail")

TPU_ENGINES = {"ivf_flat": "ragged", "ivf_pq": "ragged",
               "paged": "paged_pallas"}


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    """The TPU devices, or an exception: there is no CPU branch."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU found: jax.devices()[0].platform is "
            f"{devs[0].platform!r}; chip_smoke.py runs on a TPU only")
    if len(devs) < n_chips:
        raise RuntimeError(f"--chips {n_chips} needs {n_chips} TPU devices, "
                           f"found {len(devs)}")
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    return devs[:n_chips]


def _ready(tree):
    import jax

    return jax.block_until_ready(tree)


def _recall(ids, gt_ids, vals, gt_vals) -> float:
    from raft_tpu import stats

    return float(stats.neighborhood_recall(ids, gt_ids, vals, gt_vals))


def _engines(prefix: str) -> dict:
    """{engine: dispatches} from the ``<prefix>.backend.<engine>`` obs
    counters the search entry points record."""
    from raft_tpu import obs

    head = f"{prefix}.backend."
    return {name[len(head):]: int(v)
            for name, v in obs.snapshot()["counters"].items()
            if name.startswith(head)}


def _in_use(devs) -> list:
    return [int(d.memory_stats()["bytes_in_use"]) for d in devs]


def _peak_bytes(devs) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devs]


def ground_truth(data, queries):
    """Exact (distances, ids) by ``neighbors.brute_force`` on one device."""
    from raft_tpu.neighbors import brute_force

    return _ready(brute_force.search(brute_force.build(data), queries, K))


def _escalate(name: str, search, gt, ladder, full: bool) -> dict:
    """Search the n_probes ``ladder`` over all the queries; the result is
    the first step whose recall@k meets the gate. ``full`` searches every
    step (each is a size users run) instead of stopping there."""
    gt_vals, gt_ids = gt
    passed = None
    for n_probes in ladder:
        if passed is not None and not full:
            break
        t0 = time.perf_counter()
        vals, ids = _ready(search(n_probes))
        dt = time.perf_counter() - t0
        recall = _recall(ids, gt_ids, vals, gt_vals)
        log(f"{name}: n_probes={n_probes} recall@{K}={recall} "
            f"first_search_s={dt:.3f}")
        if passed is None and recall >= RECALL_GATE:
            passed = {"n_probes": n_probes, "recall": recall}
    if passed is None:
        raise RuntimeError(f"{name}: recall@{K} {recall} < {RECALL_GATE} at "
                           f"n_probes={ladder[-1]}")
    return passed


def ivf_phase(data, queries, gt, engines, n_lists=N_LISTS,
              ladder=PROBE_LADDER) -> tuple:
    """IVF-Flat and IVF-PQ+refine offline batch search at the recall gate;
    returns (flat index, {family: result row})."""
    from raft_tpu.neighbors import ivf_flat, ivf_pq, refine

    rows = {}
    t0 = time.perf_counter()
    flat = _ready(ivf_flat.build(data, ivf_flat.IvfFlatParams(
        n_lists=n_lists, group_size=512)))
    build_s = time.perf_counter() - t0
    log(f"ivf_flat: build_s={build_s:.3f} max_list_size={flat.max_list_size}")
    before = _engines("ivf_flat.search")
    row = _escalate("ivf_flat", lambda p: ivf_flat.search(
        flat, queries, K, n_probes=p), gt, ladder, full=True)
    row.update(build_s=build_s, engines=_diff(_engines("ivf_flat.search"),
                                              before))
    rows["ivf_flat"] = row

    t0 = time.perf_counter()
    pq = _ready(ivf_pq.build(data, ivf_pq.IvfPqParams(
        n_lists=n_lists, pq_dim=data.shape[1] // 2, group_size=512)))
    build_s = time.perf_counter() - t0
    log(f"ivf_pq: build_s={build_s:.3f} max_list_size={pq.max_list_size}")

    def pq_search(p):
        _, cand = ivf_pq.search(pq, queries, PQ_FETCH, n_probes=p)
        return refine.refine(data, queries, cand, K)

    before = _engines("ivf_pq.search")
    # IVF-PQ stops at the gate: from n_probes 32 on it needs several query
    # tiles, and a program scanning two tiles halted the v5e core (PR 21);
    # tiles now run one program each (strip_scan.per_tile) — PERF.md says
    # what the chip has shown of that
    row = _escalate("ivf_pq+refine", pq_search, gt, ladder, full=False)
    row.update(build_s=build_s, k_fetch=PQ_FETCH,
               engines=_diff(_engines("ivf_pq.search"), before))
    rows["ivf_pq"] = row
    del pq

    for family, row in rows.items():
        log(f"{family}: engines={row['engines']}")
        if set(row["engines"]) != {engines[family]}:
            raise RuntimeError(f"{family}: expected only the "
                               f"{engines[family]!r} engine, ran "
                               f"{row['engines']}")
    return flat, rows


def _diff(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def served_phase(flat, data_np, queries_np, n_probes: int, engines,
                 seed: int, rounds=ROUNDS, per_round=PER_ROUND,
                 upsert_rows=UPSERT_ROWS, delete_rows=DELETE_ROWS,
                 max_batch=MAX_BATCH) -> dict:
    """A paged store behind a query queue: single-query requests in rounds,
    one upsert and one delete batch between rounds; every answer is checked
    against brute force over the rows live in its round."""
    import jax.numpy as jnp

    from raft_tpu import serving
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import brute_force
    from raft_tpu.obs import compile as obs_compile

    rng = np.random.default_rng(seed + 1)
    n0, dim = data_np.shape
    # every row the store may ever hold, id == position: the corpus, then
    # the upserts (near-copies of corpus rows, so they show up in answers)
    n_up = (rounds + 1) * upsert_rows
    src = rng.integers(0, n0, n_up)
    noise = rng.integers(-3, 4, (n_up, dim))
    ups = np.clip(data_np[src].astype(np.int16) + noise, 0, 255).astype(
        data_np.dtype)
    all_rows = np.concatenate([data_np, ups])
    live = np.zeros(all_rows.shape[0], bool)
    live[:n0] = True

    t0 = time.perf_counter()
    store = serving.PagedListStore.from_index(flat, page_rows=128)
    store.reserve(n_up)
    engine = serving.paged_engine(store, K)
    log(f"served: store_s={time.perf_counter() - t0:.3f} "
        f"stats={store.stats()} engine={engine}")
    if engine != engines["paged"]:
        raise RuntimeError(f"served: paged_backend_auto chose {engine!r}, "
                           f"expected {engines['paged']!r}")

    next_up = [n0]

    def upsert():
        ids = np.arange(next_up[0], next_up[0] + upsert_rows)
        next_up[0] += upsert_rows
        store.upsert(all_rows[ids], ids)
        live[ids] = True

    def delete():
        # half original rows, half earlier upserts
        cand = np.flatnonzero(live)
        ids = np.concatenate([
            rng.choice(cand[cand < n0], delete_rows // 2, replace=False),
            rng.choice(cand[cand >= n0], delete_rows - delete_rows // 2,
                       replace=False)])
        if store.delete(ids) != ids.size:
            raise RuntimeError("served: delete removed fewer rows than asked")
        live[ids] = False

    # warm-up: every queue bucket's scan program, the upsert and delete
    # programs at their steady batch shapes
    b = 1
    while b <= max_batch:
        _ready(serving.search(store, queries_np[:b], K, n_probes=n_probes))
        b *= 2
    upsert()
    delete()
    _ready(serving.search(store, queries_np[:1], K, n_probes=n_probes))
    traces0 = obs_compile.trace_count()
    before = _engines("ivf_flat.search_paged")

    queue = serving.QueryQueue(serving.searcher(store, K, n_probes=n_probes),
                               max_batch=max_batch)
    queue.start()
    handles, masks, t0 = [], [], time.perf_counter()
    try:
        for r in range(rounds):
            upsert()
            delete()
            masks.append(live.copy())
            qi = (r * per_round + np.arange(per_round)) % len(queries_np)
            hs = [queue.submit(queries_np[i]) for i in qi]
            for h in hs:
                h.result(timeout=120.0)
            handles.append((qi, hs))
    finally:
        queue.stop()
    serve_s = time.perf_counter() - t0
    recompiles = obs_compile.trace_count() - traces0
    used = _diff(_engines("ivf_flat.search_paged"), before)
    answered = sum(1 for _, hs in handles for h in hs if h.verdict == "ok")
    asked = rounds * per_round
    log(f"served: requests={asked} answered={answered} batches="
        f"{queue.batches} serve_s={serve_s:.3f} recompiles_after_warmup="
        f"{recompiles} engines={used} store={store.stats()}")
    if answered != asked:
        raise RuntimeError(f"served: {asked - answered} of {asked} requests "
                           "not answered")
    if recompiles:
        raise RuntimeError(
            f"served: {recompiles} recompiles after warm-up: "
            f"{obs_compile.ledger()[-recompiles:]}")
    if set(used) != {engines["paged"]}:
        raise RuntimeError(f"served: expected only {engines['paged']!r}, "
                           f"ran {used}")

    # ground truth per round: brute force over every row, masked to the
    # rows live in that round (one shape, one compile)
    bf = brute_force.build(jnp.asarray(all_rows))
    got_v, got_i, gt_v, gt_i, dead = [], [], [], [], 0
    for (qi, hs), mask in zip(handles, masks):
        gv, gi = brute_force.search(bf, queries_np[qi], K,
                                    filter=Bitset.from_mask(mask))
        gt_v.append(np.asarray(gv))
        gt_i.append(np.asarray(gi))
        got_v.append(np.stack([np.asarray(h.result()[0]).reshape(-1)
                               for h in hs]))
        got_i.append(np.stack([np.asarray(h.result()[1]).reshape(-1)
                               for h in hs]))
        ids = got_i[-1][got_i[-1] >= 0]
        dead += int((~mask[ids]).sum())
    if dead:
        raise RuntimeError(f"served: {dead} answers name rows not live in "
                           "their round")
    recall = _recall(np.concatenate(got_i), np.concatenate(gt_i),
                     np.concatenate(got_v), np.concatenate(gt_v))
    log(f"served: recall@{K}={recall} n_probes={n_probes} "
        f"compile_ledger_traces={obs_compile.trace_count()}")
    if recall < RECALL_GATE:
        raise RuntimeError(f"served: recall@{K} {recall} < {RECALL_GATE}")
    return {"requests": asked, "answered": answered, "engine": engine,
            "recompiles_after_warmup": recompiles, "recall": recall}


def check_no_degradation() -> None:
    from raft_tpu import resilience

    events = resilience.recent_events()
    bad = [e for e in events
           if any(w in e.get("event", "") for w in _BAD_EVENTS)]
    log(f"resilience: events={len(events)} fallback_or_degrade={len(bad)}")
    if bad:
        raise RuntimeError(f"resilience events fired: {bad[:5]}")


def run_single(devs, seed: int) -> None:
    import jax.numpy as jnp

    from raft_tpu.bench.datasets import sift_like

    t0 = time.perf_counter()
    data_np, queries_np = sift_like(N_ROWS, DIM, N_QUERIES, seed=seed)
    data, queries = jnp.asarray(data_np), jnp.asarray(queries_np)
    log(f"corpus: {data_np.shape} {data_np.dtype} queries={queries_np.shape} "
        f"make_s={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    gt = ground_truth(data, queries)
    log(f"ground_truth: brute_force_s={time.perf_counter() - t0:.3f}")

    flat, rows = ivf_phase(data, queries, gt, TPU_ENGINES)
    log(f"peak_bytes_in_use after ivf: {_peak_bytes(devs)}")
    served_phase(flat, data_np, queries_np, rows["ivf_flat"]["n_probes"],
                 TPU_ENGINES, seed)
    log(f"peak_bytes_in_use after served: {_peak_bytes(devs)}")


def run_sharded(devs, seed: int, rows_per_chip=ROWS_PER_CHIP,
                n_queries=N_QUERIES, n_lists=N_LISTS, n_probes=32) -> None:
    """Sharded k-means, IVF-Flat and brute force over a mesh of ``devs``,
    each compared with the single-device result on the same data."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.bench.datasets import sift_like
    from raft_tpu.cluster.kmeans import KMeansParams
    from raft_tpu.comms import Comms, local_mesh
    from raft_tpu.distributed import brute_force as dbf
    from raft_tpu.distributed import ivf_flat as divf
    from raft_tpu.distributed import kmeans as dkm
    from raft_tpu.neighbors import ivf_flat

    world = len(devs)
    n = rows_per_chip * world
    t0 = time.perf_counter()
    data_np, queries_np = sift_like(n, DIM, n_queries, seed=seed)
    queries = jnp.asarray(queries_np)
    log(f"corpus: {data_np.shape} {data_np.dtype} over {world} devices "
        f"make_s={time.perf_counter() - t0:.3f}")
    comms = Comms(local_mesh(world))
    in_use0 = _in_use(devs)

    t0 = time.perf_counter()
    km, _ = dkm.fit(data_np, KMeansParams(n_clusters=n_lists, max_iter=20,
                                          seed=seed), comms=comms)
    inertia = float(km.inertia)
    log(f"distributed.kmeans: fit_s={time.perf_counter() - t0:.3f} "
        f"inertia={inertia} n_iter={int(km.n_iter)}")
    if not np.isfinite(inertia) or not np.isfinite(
            np.asarray(km.centroids)).all():
        raise RuntimeError("distributed.kmeans: non-finite result")
    del km

    t0 = time.perf_counter()
    params = ivf_flat.IvfFlatParams(n_lists=n_lists, group_size=512)
    sidx = divf.build(data_np, params, comms=comms)
    _ready((sidx.list_data, sidx.list_ids, sidx.bias))
    log(f"distributed.ivf_flat: build_s={time.perf_counter() - t0:.3f} "
        f"list_data={sidx.list_data.shape}")
    for name in ("list_data", "list_ids", "bias"):
        arr = getattr(sidx, name)
        if len(arr.sharding.device_set) != world:
            raise RuntimeError(f"distributed.ivf_flat: {name} spans "
                               f"{len(arr.sharding.device_set)} devices")
    in_use1 = _in_use(devs)
    grew = [b - a for a, b in zip(in_use0, in_use1)]
    log(f"bytes_in_use rise per device after build: {grew}")
    if min(grew) <= 0:
        raise RuntimeError(f"index not resident on every device: {grew}")

    data0 = jnp.asarray(data_np)  # the single-device copy, on device 0
    t0 = time.perf_counter()
    gt = ground_truth(data0, queries)
    log(f"ground_truth: brute_force_s={time.perf_counter() - t0:.3f}")

    t0 = time.perf_counter()
    s_vals, s_ids = _ready(divf.search(sidx, queries, K, n_probes=n_probes))
    s_recall = _recall(s_ids, gt[1], s_vals, gt[0])
    log(f"distributed.ivf_flat: n_probes={n_probes} recall@{K}={s_recall} "
        f"first_search_s={time.perf_counter() - t0:.3f}")

    # the one-device reference over the SAME coarse quantizer (a separate
    # build would train different centers and move recall by itself): an
    # empty index on the sharded centers, filled by ivf_flat.extend
    t0 = time.perf_counter()
    mls = 512
    empty = ivf_flat.IvfFlatIndex(
        jax.device_put(sidx.centers, devs[0]),
        jnp.zeros((n_lists, mls, DIM), data0.dtype),
        jnp.full((n_lists, mls), -1, jnp.int32),
        jnp.zeros((n_lists, mls), jnp.float32), params.metric, mls)
    del sidx
    flat = _ready(ivf_flat.extend(empty, data0))
    vals, ids = _ready(ivf_flat.search(flat, queries, K, n_probes=n_probes))
    f_recall = _recall(ids, gt[1], vals, gt[0])
    log(f"ivf_flat (one device, same centers): n_probes={n_probes} "
        f"recall@{K}={f_recall} "
        f"extend_and_search_s={time.perf_counter() - t0:.3f}")
    del flat
    if abs(s_recall - f_recall) > 0.01:
        raise RuntimeError(f"sharded IVF-Flat recall {s_recall} is more "
                           f"than 0.01 from one device's {f_recall}")

    t0 = time.perf_counter()
    bidx = dbf.build(data_np, comms=comms)
    if len(bidx.dataset.sharding.device_set) != world:
        raise RuntimeError("distributed.brute_force: dataset not sharded")
    b_vals, b_ids = (np.asarray(a) for a in dbf.search(bidx, queries, K))
    log(f"distributed.brute_force: search_s={time.perf_counter() - t0:.3f}")
    g_vals, g_ids = np.asarray(gt[0]), np.asarray(gt[1])
    same = b_ids == g_ids
    # ids may differ only where the distance ties (both lists are sorted)
    tie = np.isclose(b_vals, g_vals, rtol=1e-5, atol=1e-3)
    log(f"distributed.brute_force: ids_equal={float(same.mean())} "
        f"mismatches_not_tied={int((~same & ~tie).sum())}")
    if not (same | tie).all():
        raise RuntimeError("sharded brute force disagrees with one device "
                           "beyond ties")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path over four chips")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from raft_tpu import obs, resilience
    from raft_tpu.utils.compile_cache import enable_persistent_cache

    log(f"compile_cache: {enable_persistent_cache()}")
    obs.enable()
    resilience.clear_events()
    t0 = time.perf_counter()
    if args.chips == 1:
        run_single(devs, args.seed)
    else:
        run_sharded(devs, args.seed)
    check_no_degradation()
    log(f"peak_bytes_in_use: {_peak_bytes(devs)} wall_s="
        f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
