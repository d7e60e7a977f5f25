"""Multi-device IVF-Flat: globally trained centers, row-sharded lists, one
``shard_map`` search (the raft-dask MNMG model re-expressed as SPMD: one
model per worker, collectives for the merge —
python/raft-dask/raft_dask/common/comms.py:40, docs/source/using_raft_comms.rst;
merge analog knn_merge_parts.cuh:140).

Round-3 redesign (round-3 review, Missing#2): every stage is a mesh-wide SPMD
program — no host fan-out loops, no per-device ``device_put`` — so the same
code runs multi-host, where only the local shard of each array is
addressable:

  * **build**: the coarse quantizer is trained once with data-sharded
    k-means (psum over shards), so every shard agrees on list ids. Then ONE
    shard_map assigns + spills each shard's rows, a host reduction picks the
    global padded list size, and a second shard_map packs each shard's
    padded lists. Shard arrays are stacked on a leading mesh dimension:
    ``list_data (world, n_lists, mls, dim)`` sharded P(axis).
  * **search**: queries are replicated; the host strip plan is built ONCE
    from the per-list MAX length across shards (every shard runs the same
    grid — the padding this adds over per-shard plans is the shard-to-shard
    length variance, small under random row sharding), and one shard_map
    runs the strip kernel on the local shard + butterfly-merges the (world·k)
    candidates + re-selects. Output is replicated.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import Comms, make_comms
from raft_tpu.core.compat import shard_map
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.trace import traced
from raft_tpu.neighbors import _packing
from raft_tpu.neighbors.ivf_flat import IvfFlatParams
from raft_tpu.ops import distance as dist_mod


@dataclass
class ShardedIvfFlatIndex:
    """Row-sharded IVF-Flat: one coarse quantizer, per-shard padded lists
    stacked on a leading (world,) mesh dimension."""

    centers: jax.Array       # (n_lists, dim) replicated
    list_data: jax.Array     # (world, n_lists, mls, dim) sharded P(axis)
    list_ids: jax.Array      # (world, n_lists, mls) int32, GLOBAL row ids
    # per-entry additive scan bias, built once at build time: ‖x‖² for L2 /
    # 0 for ip-family, +inf at padding (per-call rebuilds were one wasted
    # index-sized pass per search)
    bias: jax.Array          # (world, n_lists, mls) fp32, P(axis)
    metric: str
    n_total: int
    comms: Comms
    lens_max: np.ndarray     # host (n_lists,) max per-list fill across shards

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.list_data.shape[2]


@traced("distributed.ivf_flat::build")
def build(
    dataset,
    params: IvfFlatParams = IvfFlatParams(),
    comms: Optional[Comms] = None,
    res: Optional[Resources] = None,
) -> ShardedIvfFlatIndex:
    """Global centers (distributed k-means), then two SPMD phases: assign +
    spill per shard, and pack per shard at a common padded list size."""
    res = res or current_resources()
    comms = comms or make_comms()
    world = comms.size
    axis = comms.axis
    dataset = jnp.asarray(dataset).astype(jnp.float32)
    n, dim = dataset.shape
    if params.n_lists * world > n:
        raise ValueError(f"n_lists={params.n_lists} x {world} shards > n_rows={n}")

    work = dataset
    if params.metric == "cosine":
        work = work / jnp.maximum(jnp.linalg.norm(work, axis=1, keepdims=True), 1e-30)
    km_metric = ("inner_product" if params.metric in ("cosine", "inner_product")
                 else "sqeuclidean")

    # --- global coarse quantizer: data-sharded k-means (psum over shards) --
    from raft_tpu.cluster.kmeans import KMeansParams
    from raft_tpu.distributed import kmeans as dkm

    out, _ = dkm.fit(
        work, KMeansParams(n_clusters=params.n_lists,
                           max_iter=params.kmeans_n_iters, seed=params.seed),
        comms=comms,
    )
    centers = out.centroids
    if params.metric in ("cosine", "inner_product"):
        centers = centers / jnp.maximum(
            jnp.linalg.norm(centers, axis=1, keepdims=True), 1e-30)

    # --- shard rows + SPMD assign/spill phase (shared helpers) -------------
    from raft_tpu.distributed._sharding import (assign_phase, round_mls,
                                                scatter_pack, shard_rows)

    work_sh, gids_sh, rows_per = shard_rows(work, comms)
    group = params.group_size or _packing.auto_group_size(rows_per, params.n_lists)
    cap = params.list_size_cap
    if cap < 0:
        cap = _packing.auto_list_cap(rows_per, params.n_lists, group)
    n_lists = params.n_lists
    labels_sh, counts_np = assign_phase(
        work_sh, gids_sh, centers, km_metric, cap, n_lists, comms)
    mls = round_mls(int(counts_np.max()), group)

    # --- phase 2 (SPMD): pack each shard at the common padded size ---------
    l2 = params.metric in ("sqeuclidean", "euclidean")

    def pack_body(rows, ids, labels):
        rows, ids, labels = rows[0], ids[0], labels[0]
        ld, li = scatter_pack(
            labels,
            [(jnp.zeros((n_lists, mls, rows.shape[1]), rows.dtype), rows),
             (jnp.full((n_lists, mls), -1, jnp.int32), ids)],
            n_lists, mls)
        base = (dist_mod.sqnorm(ld, axis=2) if l2
                else jnp.zeros((n_lists, mls)))
        bias = jnp.where(li >= 0, base, jnp.inf).astype(jnp.float32)
        return ld[None], li[None], bias[None]

    pack_fn = jax.jit(shard_map(
        pack_body, mesh=comms.mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(axis, None)),
        out_specs=(P(axis, None, None, None), P(axis, None, None),
                   P(axis, None, None)),
        check_vma=False,
    ))
    list_data, list_ids, bias = pack_fn(work_sh, gids_sh, labels_sh)
    return ShardedIvfFlatIndex(
        centers, list_data, list_ids, bias,
        params.metric, n, comms, counts_np.max(axis=0).astype(np.int32),
    )


@traced("distributed.ivf_flat::search")
def search(
    index: ShardedIvfFlatIndex,
    queries,
    k: int,
    n_probes: int = 20,
    res: Optional[Resources] = None,
    health=None,
) -> Tuple[jax.Array, jax.Array]:
    """SPMD search: replicated queries, sharded lists, one shard_map per
    query tile. Returns a :class:`~raft_tpu.distributed._sharding.SearchResult`
    — unpacks as global (distances (q, k), row ids (q, k)), replicated on
    every mesh slot, and carries ``coverage``/``degraded`` when shards
    were dropped (``health`` defaults to the process registry)."""
    from raft_tpu.distributed._sharding import SearchResult, tiled_search
    from raft_tpu.neighbors.ivf_flat import _coarse_probes
    from raft_tpu.ops.strip_scan import strip_eligible

    res = res or current_resources()
    queries = jnp.asarray(queries).astype(jnp.float32)
    if queries.shape[1] != index.dim:
        raise ValueError(f"query dim {queries.shape[1]} != index dim {index.dim}")
    if index.metric == "cosine":
        queries = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-30)
    n_probes = int(min(n_probes, index.n_lists))
    l2 = index.metric in ("sqeuclidean", "euclidean")

    probes = _coarse_probes(queries, index.centers, n_probes, index.metric,
                            "exact", res.compute_dtype)
    # off-TPU the strip kernel only exists as the single-threaded Pallas
    # interpreter — it serializes the per-shard scans of a virtual mesh and
    # turns weak-scaling numbers into an emulator artifact (ICI r5 finding:
    # brute scaled at 1.0, IVF at 0.6-0.8 purely from this). The dense
    # XLA scan is the honest off-TPU backend.
    interpret = jax.default_backend() != "tpu"
    vals, ids, report = tiled_search(
        queries, probes, index.lens_max, index.n_lists, int(k),
        index.comms, -2.0 if l2 else -1.0,
        dense=interpret or not strip_eligible(index.max_list_size),
        interpret=interpret,
        data=index.list_data, ids_arr=index.list_ids, bias=index.bias,
        algo="ivf_flat", n_total=index.n_total, health=health,
    )
    if l2:
        vals = jnp.maximum(vals + dist_mod.sqnorm(queries)[:, None], 0.0)
        if index.metric == "euclidean":
            vals = jnp.sqrt(vals)
        vals = jnp.where(ids >= 0, vals, jnp.inf)
    elif index.metric == "cosine":
        vals = jnp.where(ids >= 0, 1.0 + vals, jnp.inf)
    else:
        vals = jnp.where(ids >= 0, -vals, -jnp.inf)
    return SearchResult(vals, ids, coverage=report.coverage,
                        degraded=report.degraded,
                        lost_shards=report.dropped)
