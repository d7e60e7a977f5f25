"""Shared shard-preparation helpers for the distributed IVF indexes.

One implementation of the row-sharding, SPMD assign+spill phase, padded
list sizing, local dense fallback scan, cross-shard merge, and the
degraded-mode dispatch gate (:func:`probe_shards` + :class:`SearchResult`)
— ivf_flat and ivf_pq compose these (round-3 review: the two modules had
begun to drift apart with four copies of this logic); brute_force and
cagra share the availability pieces."""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from raft_tpu import obs, resilience
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core.compat import shard_map
from raft_tpu.core.interruptible import InterruptedException
from raft_tpu.neighbors import _packing
from raft_tpu.ops.select_k import select_k
from raft_tpu.resilience.retry import record_event


# ---------------------------------------------------------------------------
# Degraded-mode dispatch: shard probe, coverage accounting, result carrier
# ---------------------------------------------------------------------------


class SearchResult(tuple):
    """A ``(distances, indices)`` pair with availability metadata riding
    along. Unpacks exactly like the plain 2-tuple every caller already
    writes (``vals, ids = search(...)``); degraded-mode consumers read the
    attributes:

    * ``coverage`` — fraction of index rows held by the shards whose
      candidates entered the top-k merge (1.0 on the healthy path).
    * ``degraded`` — True when any shard's candidates were dropped.
    * ``lost_shards`` — the shard ranks dropped from this dispatch.
    """

    def __new__(cls, distances, indices, coverage: float = 1.0,
                degraded: bool = False, lost_shards: Tuple[int, ...] = ()):
        self = tuple.__new__(cls, (distances, indices))
        self.coverage = float(coverage)
        self.degraded = bool(degraded)
        self.lost_shards = tuple(int(s) for s in lost_shards)
        return self

    @property
    def distances(self):
        return self[0]

    @property
    def indices(self):
        return self[1]


@dataclass(frozen=True)
class ShardReport:
    """One dispatch's availability verdict (:func:`probe_shards`)."""

    ok: np.ndarray            # (world,) bool — shards serving this dispatch
    coverage: float           # fraction of rows the serving shards hold
    degraded: bool
    dropped: Tuple[int, ...]  # shard ranks excluded from this dispatch


def shard_rows_held(world: int, n_total: int):
    """Real (unpadded) rows per shard under the one row-partitioning
    convention every distributed index uses: ``rows_per = ceil(n/world)``
    contiguous rows per shard, short tail on the last."""
    rows_per = -(-int(n_total) // int(world))
    return [max(0, min(rows_per, int(n_total) - r * rows_per))
            for r in range(int(world))]


def probe_shards(algo: str, world: int, n_total: int,
                 health: Optional[resilience.ShardHealth] = None,
                 phase: str = "search") -> ShardReport:
    """Host-side per-shard dispatch gate — the availability layer's entry.

    For every shard not already LOST, fires the
    ``distributed.<algo>.<phase>.shard`` faultpoint (the injectable
    stand-in for a dead host's dispatch error; ``phase`` defaults to
    "search" — the five search algos' long-standing sites — and the
    distributed coarse k-means fit passes "fit") and folds the verdict
    into the health registry: a failing shard is dropped from THIS
    dispatch (its candidates never enter the merge) and marked
    SUSPECT/LOST for the next.

    An active hard :class:`~raft_tpu.resilience.Deadline` slices its
    remaining budget evenly across the shards still to be probed — a shard
    that burns its slice (hang-class failure) costs its slice, not the
    query: it is dropped and the remainder re-sliced over the survivors.
    An expired OUTER budget still propagates.

    Raises :class:`~raft_tpu.resilience.ShardQuorumError` when the
    surviving coverage falls below the registry's minimum-coverage quorum.
    """
    health = health or resilience.shard_health()
    site = f"distributed.{algo}.{phase}.shard"
    world = int(world)
    rows = shard_rows_held(world, n_total)
    dl = resilience.active_deadline()
    ok = []
    enabled = obs.enabled()
    # per-shard wall times (round 19, telemetry-gated — NOOP mode pays no
    # clock reads): a failing shard's probe spends exception handling +
    # classification + health bookkeeping where a healthy one spends a
    # bare faultpoint check, so the max/median ratio spikes exactly when a
    # shard drags — the straggler signal the flight recorder windows fold
    shard_times = [] if enabled else None
    probe_attrs = ({"shard": world} if enabled else None)
    probe_span = obs.record_span("distributed::shard_probe",
                                 attrs=probe_attrs)
    with probe_span:
        for r in range(world):
            if health.state(r) == resilience.LOST:
                ok.append(False)
                continue
            t_shard = time.perf_counter() if enabled else 0.0
            try:
                if dl is not None and dl.hard:
                    left = sum(1 for rr in range(r, world)
                               if health.state(rr) != resilience.LOST)
                    slice_s = max(dl.remaining(), 0.0) / max(1, left)
                    with resilience.Deadline(slice_s, hard=True,
                                             label=f"{site}[{r}]"):
                        resilience.faultpoint(site)
                else:
                    resilience.faultpoint(site)
                health.report_success(r)
                ok.append(True)
            except InterruptedException:
                raise  # cross-thread cancel kills the query, never a shard
            except Exception as e:
                kind = resilience.classify(e)
                if kind == resilience.DEADLINE and (
                        dl is None or (dl.hard and dl.reached())):
                    # the QUERY's budget is spent (or there was no per-shard
                    # slice to absorb it) — propagate, don't blame the shard
                    raise
                health.report_failure(r, e)
                ok.append(False)
            if enabled:
                shard_times.append(time.perf_counter() - t_shard)
        if enabled and shard_times:
            ordered = sorted(shard_times)
            med = ordered[len(ordered) // 2]
            skew = round(max(shard_times) / max(med, 1e-9), 3)
            obs.set_gauge("distributed.shard_skew", skew)
            probe_span.set_attr("skew", skew)
    ok_np = np.asarray(ok, dtype=bool)
    covered = sum(rows[r] for r in range(world) if ok_np[r])
    coverage = covered / max(1, int(n_total))
    dropped = tuple(int(r) for r in range(world) if not ok_np[r])
    degraded = bool(dropped)
    if degraded:
        health.check_quorum(coverage, context=site)
        obs.add("distributed.partial_merge")
        record_event("partial_merge", site=site, coverage=round(coverage, 4),
                     dropped=list(dropped))
    return ShardReport(ok_np, coverage, degraded, dropped)


def shard_ok_device(ok: np.ndarray, comms):
    """Place a (world,) serving mask as a (world, 1) fp32 array sharded over
    the mesh axis, so each SPMD shard body reads its own flag (``ok[0, 0]``)
    and masks its candidates out of the merge when it is marked dead. A
    traced array input: flipping the mask never recompiles the search."""
    arr = jnp.asarray(np.asarray(ok, np.float32).reshape(-1, 1))
    return jax.device_put(arr, comms.sharding(comms.axis, None))


def shard_rows(work, comms):
    """Pad rows to a multiple of the communicator size and place them with a
    leading (world,) sharded dimension. Padded rows carry global id -1."""
    world = comms.size
    n, dim = work.shape
    rows_per = -(-n // world)
    n_pad = rows_per * world
    work_p = jnp.pad(work, ((0, n_pad - n), (0, 0)))
    gids = jnp.where(jnp.arange(n_pad) < n, jnp.arange(n_pad), -1).astype(jnp.int32)
    work_sh = jax.device_put(
        work_p.reshape(world, rows_per, dim),
        comms.sharding(comms.axis, None, None))
    gids_sh = jax.device_put(
        gids.reshape(world, rows_per), comms.sharding(comms.axis, None))
    return work_sh, gids_sh, rows_per


def assign_phase(work_sh, gids_sh, centers, km_metric, cap, n_lists, comms):
    """SPMD assign + spill per shard. Returns (labels_sh, counts_np) where
    labels use the sentinel ``n_lists`` for padded rows (dropped at pack)
    and counts_np (world, n_lists) counts real rows only.

    The spill itself runs over ALL local rows (padding included) so its
    rank/offset bookkeeping matches the labels array; the ≤ world-1 padded
    zero rows behave as ordinary data during the spill and are exiled to
    the sentinel afterwards."""

    def body(rows, ids):
        rows, ids = rows[0], ids[0]
        _, labels = kmeans_balanced._assign(rows, centers, km_metric)
        if cap:
            counts_all = jnp.bincount(labels, length=n_lists)
            labels, _ = _packing._spill_core(
                rows, centers, labels, km_metric, cap,
                jnp.zeros(n_lists, jnp.int32), counts_all, 65536)
        valid = ids >= 0
        counts = jax.ops.segment_sum(
            valid.astype(jnp.int32), jnp.where(valid, labels, 0),
            num_segments=n_lists).astype(jnp.int32)
        labels = jnp.where(valid, labels, n_lists)
        return labels[None], counts[None]

    axis = comms.axis
    fn = jax.jit(shard_map(
        body, mesh=comms.mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None)),
        check_vma=False,
    ))
    resilience.faultpoint("distributed.assign_phase")
    assign_attrs = None
    if obs.enabled():
        obs.add("distributed.assign.shards", comms.size)
        obs.add("distributed.assign.rows",
                int(work_sh.shape[0]) * int(work_sh.shape[1]))
        assign_attrs = {"shard": int(comms.size),
                        "rows": int(work_sh.shape[0]) * int(work_sh.shape[1])}
    with obs.record_span("distributed::assign_phase", attrs=assign_attrs):
        labels_sh, counts_sh = fn(work_sh, gids_sh)
        counts_np = np.asarray(counts_sh)
    return labels_sh, counts_np


def round_mls(max_count: int, group: int) -> int:
    """Common padded list size: group-aligned; power-of-two 512-chunks when
    the strip backend's granule is in play (ops/strip_scan.py). Delegates
    to THE shared formula (_packing.round_list_size) so distributed and
    single-host builds can never disagree on mls."""
    from raft_tpu.neighbors._packing import round_list_size

    return round_list_size(max_count, group, pow2_chunks=group == 512)


def scatter_pack(labels, order_payloads, n_lists: int, mls: int):
    """Scatter sorted rows into (n_lists, mls, ...) blocks; sentinel labels
    (== n_lists) scatter out of range and are dropped.

    labels: (rp,) with sentinel for invalid rows. order_payloads: list of
    (init_array, values) pairs already in label-sorted order."""
    rp = labels.shape[0]
    order = jnp.argsort(labels)
    sorted_labels = labels[order]
    counts = jnp.bincount(jnp.minimum(labels, n_lists), length=n_lists + 1)
    offsets = (jnp.cumsum(counts) - counts)[:n_lists]
    off_of = jnp.where(sorted_labels < n_lists,
                       offsets[jnp.minimum(sorted_labels, n_lists - 1)], 0)
    pos = jnp.arange(rp, dtype=jnp.int32) - off_of.astype(jnp.int32)
    tgt_l = jnp.minimum(sorted_labels, n_lists)
    outs = []
    for init, values in order_payloads:
        outs.append(init.at[tgt_l, pos].set(values[order], mode="drop"))
    return outs


def merge_shards(vals, ids, k: int, axis: str, world: int = 0,
                 select_min: bool = True):
    """Cross-shard candidate exchange + exact re-select (knn_merge_parts
    analog, reference neighbors/detail/knn_merge_parts.cuh:140).

    Round-5 (VERDICT r4 #6): for power-of-two worlds the merge is a
    recursive-doubling butterfly — log2(world) rounds of pairwise
    ``ppermute`` + a narrow (2k → k) re-select. Per-link traffic is
    k·log2(world) candidate rows instead of the all_gather's k·world, so
    the merge stops growing linearly in world (the round-4 ICI sweep
    measured ~9× per-link byte growth from 2→8 devices; this is the fix).
    Top-k-merge is associative and commutative and shard id sets are
    disjoint, so the butterfly reduction is exact; every device ends with
    the identical replicated (q, k) result, as before. ``world = 0`` (or a
    non-power-of-two size) falls back to the all_gather merge."""
    bad = jnp.float32(jnp.inf if select_min else -jnp.inf)
    if world > 1 and (world & (world - 1)) == 0:
        step = 1
        while step < world:
            perm = [(i, i ^ step) for i in range(world)]
            ov = jax.lax.ppermute(vals, axis, perm)
            oi = jax.lax.ppermute(ids, axis, perm)
            cat_v = jnp.concatenate([vals, ov], axis=1)
            cat_i = jnp.concatenate([ids, oi], axis=1)
            key = jnp.where(cat_i >= 0, cat_v, bad)
            vals, sel = select_k(key, k, select_min=select_min)
            ids = jnp.take_along_axis(cat_i, sel, axis=1)
            step <<= 1
        return jnp.where(ids >= 0, vals, bad), ids
    all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
    all_ids = jax.lax.all_gather(ids, axis, axis=1, tiled=True)
    key = jnp.where(all_ids >= 0, all_vals, bad)
    out_v, sel = select_k(key, k, select_min=select_min)
    out_i = jnp.take_along_axis(all_ids, sel, axis=1)
    return jnp.where(out_i >= 0, out_v, bad), out_i


@functools.lru_cache(maxsize=64)
def make_tile_fn(mesh, axis, plan, k, kf, dense, interpret, alpha,
                 world=0, scan="strip"):
    """shard_map'd search tile shared by the distributed IVF indexes: local
    scan on the shard's (data, ids, bias[, scale]) operands + butterfly
    merge. ``plan`` is the tile's static strip layout ``(region_starts,
    s_tot, class_layout)`` (ops/strip_scan.static_layout; None for the
    dense scan): the strip tables are planned inside the program, as the
    one-device fused search does. ``scan`` picks the engine: "strip" (fp/int8 B operand — strip
    kernel, or dense gather for sub-512 lists) or "bq" (packed 1-bit codes
    with the per-entry correction scale, ops/bq_scan). Bias carries +inf
    at padding (precomputed at build). ``ok`` is the (world, 1) serving
    mask (shard_ok_device): a dead shard's candidates are blanked to
    (+inf, -1) BEFORE the merge, so the partial merge is exact over the
    survivors."""
    from raft_tpu.ops.strip_scan import _plan_device, _strip_tile_body

    def body(queries, probes, pair_const, cls_ord, data, ids_arr, bias,
             scale, ok):
        ld, li, b = data[0], ids_arr[0], bias[0]
        if not dense:
            region_starts, s_tot, class_layout = plan
            qids, strip_list, pair_strip, pair_slot, _ = _plan_device(
                probes, cls_ord, ld.shape[0], region_starts, s_tot)
        if scan == "bq":
            from raft_tpu.ops import bq_scan

            sc = scale[0]
            if dense:
                vals, ids = bq_scan.bq_dense_scan(
                    queries, probes, ld, sc, b, li, k, alpha, pair_const)
            else:
                vals, ids = bq_scan._bq_tile_body(
                    queries, qids, strip_list, pair_strip, pair_slot,
                    ld, sc, b, li, class_layout, k, kf, alpha, interpret,
                    pair_const, approx_ok=True,
                )
        elif dense:
            vals, ids = dense_local_scan(queries, probes, ld, b, li, k,
                                         alpha, pair_const)
        else:
            vals, ids = _strip_tile_body(
                queries, qids, strip_list, pair_strip, pair_slot,
                ld, b, li, class_layout, k, kf, alpha, interpret,
                pair_const,
            )
        alive = ok[0, 0] > 0
        vals = jnp.where(alive, vals, jnp.inf)
        ids = jnp.where(alive, ids, -1)
        return merge_shards(vals, ids, k, axis, world)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(),
                  P(axis, None, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None), P(axis, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def tiled_search(queries_mat, probes, lens_max, n_lists, k, comms,
                 alpha, dense, interpret, data, ids_arr, bias,
                 pair_const=None, algo="ivf", n_total=0, health=None,
                 scale=None, scan="strip"):
    """Query-tiled SPMD search loop shared by the distributed IVF indexes.
    ``scale`` is the optional (world, n_lists, mls) per-entry multiplicative
    operand (the BQ correction scalar) and ``scan`` the engine selector —
    see :func:`make_tile_fn`.

    Each tile is planned inside its program (ops/strip_scan._plan_device,
    replicated — every shard runs the identical grid from the per-list MAX
    fill) on the static worst-case layout (``static_layout``): the
    one-device fused search's protocol, with no host fetch per tile.
    (PR 21: the dynamic plan — strip counts fetched to the host, tables
    passed in — halted a v5e core in a 10k-query x 32-probe tile, while
    each of that plan's kernel calls ran alone on the chip and the
    one-device search ran 10k x 64.) ``probes`` is a device array — no
    host copy of it exists.

    Returns ``(vals, ids, report)``: the dispatch runs through
    :func:`probe_shards` first, so a dead shard costs coverage (its
    candidates are masked out of every tile's merge), not the query."""
    from raft_tpu.core.resources import current_resources
    from raft_tpu.ops.strip_scan import (class_counts_of, class_info,
                                         fit_q_tile, static_layout)

    if not dense and k > 512:
        raise ValueError(
            f"distributed strip search supports k <= 512, got {k}"
        )
    if n_total <= 0:
        raise ValueError("tiled_search needs the true row count (n_total) "
                         "for coverage accounting")
    report = probe_shards(algo, comms.size, n_total, health=health)
    ok_dev = shard_ok_device(report.ok, comms)
    if scale is None:
        # strip/dense scans ignore the operand: a (world, 1, 1) placeholder
        # keeps the shard_map signature static across engines
        scale = jax.device_put(jnp.zeros((comms.size, 1, 1), jnp.float32),
                               comms.sharding(comms.axis, None, None))
    kf = min(int(k), 512)
    q = queries_mat.shape[0]
    probes = jnp.asarray(probes)
    p = probes.shape[1]
    if pair_const is None:
        pair_const = jnp.zeros((q, p), jnp.float32)
    classes, cls_ord_np = class_info(np.asarray(lens_max),
                                     dim=queries_mat.shape[1])
    class_counts = class_counts_of(cls_ord_np, len(classes))
    cls_ord = jnp.asarray(cls_ord_np)
    workspace = current_resources().workspace_bytes
    if dense:
        q_tile = dense_q_tile(q, p, data.shape[2], queries_mat.shape[1],
                              workspace)
    else:
        q_tile = fit_q_tile(q, p, n_lists, len(classes), kf, workspace,
                            dim=queries_mat.shape[1],
                            class_counts=class_counts)
    out_v, out_i = [], []
    start = 0
    n_tiles = 0
    from raft_tpu.core.interruptible import check_interrupt

    search_attrs = None
    if obs.enabled():
        from raft_tpu.obs import tracing as obs_tracing

        search_attrs = {"shard": int(comms.size), "queries": int(q),
                        "probes": int(q * p),
                        "coverage": round(report.coverage, 4),
                        # fleet-deterministic dispatch id (round 19): every
                        # host stamps the SAME id on the same SPMD dispatch,
                        # so the trace stitcher joins per-host tracks into
                        # one fleet trace on this attr
                        "fleet_trace_id": obs_tracing.fleet_trace_id(
                            "distributed.tiled_search")}
    span = obs.record_span("distributed::tiled_search", attrs=search_attrs)
    with span:
        while start < q:
            check_interrupt()  # per-tile checkpoint: cancel/hard-deadline
            # land between dispatches, not after the full query set
            resilience.faultpoint("distributed.tiled_search.tile")
            qt = min(q_tile, q - start)
            with obs.record_span("distributed::search_tile",
                                 attrs=({"tile": n_tiles, "rows": int(qt)}
                                        if obs.enabled() else None)):
                # dense_local_scan never reads strip tables: no plan
                plan = (None if dense
                        else static_layout(classes, class_counts, qt, p))
                fn = make_tile_fn(comms.mesh, comms.axis, plan, int(k),
                                  kf, dense, interpret, alpha, comms.size,
                                  scan)
                v, i = fn(queries_mat[start:start + qt],
                          jax.lax.slice_in_dim(probes, start, start + qt,
                                               axis=0),
                          pair_const[start:start + qt], cls_ord,
                          data, ids_arr, bias, scale, ok_dev)
            out_v.append(v)
            out_i.append(i)
            start += qt
            n_tiles += 1
        # discovered only after the loop — attach before the span closes
        span.set_attr("tiles", n_tiles)
    if obs.enabled():
        obs.add("distributed.search.shards", comms.size)
        obs.add("distributed.search.queries", q)
        obs.add("distributed.search.probes", q * p)
        obs.add("distributed.search.tiles", n_tiles)
    vals = out_v[0] if len(out_v) == 1 else jnp.concatenate(out_v, 0)
    ids = out_i[0] if len(out_i) == 1 else jnp.concatenate(out_i, 0)
    return vals, ids, report


def dense_q_tile(q: int, p: int, mls: int, dim: int,
                 workspace_bytes: int) -> int:
    """Largest query tile whose :func:`dense_local_scan` workspace fits the
    budget: one probe's (qt, mls, dim) fp32 gather, plus the (p, qt, mls)
    score and id tensors and their transposed copies. Equal tiles share one
    compiled program. (PR 21: sized by the strip engine's model, a 10k-query
    dense tile asked a v5e for 30.30 GB of HBM. Compiled for a v5e at mls
    4096, dim 128, p 32, the temp grows 3.8 MB per query, as this model
    says, over a fixed 2.0 GB: a copy of the shard's fp32 list block that
    no tile size removes.)"""
    per_query = mls * (4 * dim + 16 * p)
    q_tile = max(1, min(q, workspace_bytes // per_query))
    return -(-q // -(-q // q_tile))


def dense_local_scan(queries, probes, ld, bias, li, k: int, alpha: float,
                     pair_const=None):
    """Jittable dense fallback scan for shards too small for the strip
    kernel (max_list_size < 512), and the off-TPU SPMD scan.

    Tiled over the probe axis (``lax.map``): the one-shot formulation
    materialized a (q, p, mls, dim) gather — 2 GB/device at the ICI-bench
    shapes, which collapsed the virtual-mesh weak-scaling run — where one
    probe's (q, mls, dim) block is p× smaller and the loop carries only
    the (p, q, mls) score tensor."""
    q = queries.shape[0]
    qf = queries.astype(jnp.float32)

    def one_probe(j):
        lids = probes[:, j]                              # (q,)
        cand = ld[lids].astype(jnp.float32)              # (q, mls, d)
        ip = jnp.einsum("qd,qmd->qm", qf, cand,
                        preferred_element_type=jnp.float32)
        d = alpha * ip + bias[lids]
        if pair_const is not None:
            d = d + pair_const[:, j, None]
        return d, li[lids]

    p = probes.shape[1]
    d_all, ids_all = lax.map(one_probe, jnp.arange(p))   # (p, q, mls)
    d = jnp.transpose(d_all, (1, 0, 2)).reshape(q, -1)
    flat_ids = jnp.transpose(ids_all, (1, 0, 2)).reshape(q, -1)
    vals, sel = select_k(d, min(k, d.shape[1]), select_min=True)
    ids = jnp.where(jnp.isinf(vals), -1,
                    jnp.take_along_axis(flat_ids, sel, axis=1))
    if ids.shape[1] < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - ids.shape[1])),
                       constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - ids.shape[1])),
                      constant_values=-1)
    return vals, ids
