"""Sharded exact kNN: dataset row-sharded over the mesh, cross-shard merge.

The MNMG pattern the reference teaches for brute-force search
(docs/source/using_raft_comms.rst; knn_merge_parts.cuh:140 is the single-GPU
merge primitive): every rank scans its local shard, produces a local top-k,
then ranks exchange candidate lists and re-select — here one
``all_gather`` over the mesh axis followed by an exact ``select_k`` on the
(world * k)-wide candidate matrix, all inside a single ``shard_map`` so XLA
schedules the local gemm and the ICI all-gather as one program.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from raft_tpu.comms.comms import Comms, make_comms, shard_padded
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.compat import shard_map
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.trace import traced
from raft_tpu.neighbors.brute_force import _MAX_METRICS, _tile_distances
from raft_tpu.ops import distance as dist_mod
from raft_tpu.ops.select_k import select_k


@dataclass
class ShardedBruteForceIndex:
    """Row-sharded exact-search index. ``dataset`` is padded to a multiple of
    the communicator size and placed with a row sharding over the mesh axis;
    ``n_total`` is the true (unpadded) row count."""

    dataset: jax.Array  # (n_padded, dim), sharded P(axis, None)
    norms: Optional[jax.Array]  # (n_padded,), sharded P(axis)
    metric: str
    metric_arg: float
    n_total: int
    comms: Comms

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def size(self) -> int:
        return self.n_total


@traced("distributed.brute_force::build")
def build(
    dataset,
    metric: str = "sqeuclidean",
    metric_arg: float = 2.0,
    comms: Optional[Comms] = None,
    res: Optional[Resources] = None,
) -> ShardedBruteForceIndex:
    """Shard the dataset row-wise over the communicator and precompute norms.

    (brute_force-inl.cuh:337 per rank; the sharding is the distribution step
    raft leaves to Dask.)
    """
    res = res or current_resources()
    comms = comms or make_comms(res)
    metric = dist_mod.canonical_metric(metric)
    dataset = jnp.asarray(dataset)
    n = dataset.shape[0]
    dataset, _ = shard_padded(dataset, comms)
    norms = None
    if metric in ("sqeuclidean", "euclidean", "cosine"):
        norms = dist_mod.sqnorm(dataset)  # computed shard-local by XLA
    return ShardedBruteForceIndex(dataset, norms, metric, metric_arg, n, comms)


@functools.lru_cache(maxsize=64)
def _make_search_fn(mesh, axis, metric, metric_arg, k, n_total, select_algo,
                    has_filter, has_norms, compute_dtype, world=0):
    select_min = metric not in _MAX_METRICS
    bad = jnp.float32(jnp.inf if select_min else -jnp.inf)
    needs_norms = metric in ("sqeuclidean", "euclidean", "cosine")

    def body(shard, shard_norms, queries, filter_words, ok):
        rows = shard.shape[0]
        rank = jax.lax.axis_index(axis)
        gids = rank * rows + jnp.arange(rows, dtype=jnp.int32)
        qn = dist_mod.sqnorm(queries) if needs_norms else None
        tn = shard_norms if has_norms else jnp.zeros((rows,), jnp.float32)
        d = _tile_distances(
            queries, qn, shard, tn, metric, metric_arg, compute_dtype
        )
        valid = gids < n_total
        if has_filter:
            valid = valid & Bitset(filter_words, n_total).test(gids)
        d = jnp.where(valid[None, :], d, bad)
        if k > rows:
            # k exceeds this shard's row count (legal: k is validated against
            # the GLOBAL n); pad local candidates so select_k stays in range
            d = jnp.pad(d, ((0, 0), (0, k - rows)), constant_values=bad)
            gids = jnp.pad(gids, (0, k - rows), constant_values=-1)
        vals, sel = select_k(d, k, select_min=select_min, algo=select_algo)
        ids = jnp.where(vals == bad, -1, jnp.take(gids, sel))
        # degraded mode: a dead shard's candidates are blanked before the
        # merge, so the partial merge is exact over the survivors
        alive = ok[0, 0] > 0
        vals = jnp.where(alive, vals, bad)
        ids = jnp.where(alive, ids, -1)
        # cross-shard butterfly merge (knn_merge_parts analog; per-link
        # bytes k·log2(world) — see _sharding.merge_shards)
        from raft_tpu.distributed._sharding import merge_shards

        return merge_shards(vals, ids, k, axis, world, select_min)

    nspec = P(axis) if has_norms else P()
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), nspec, P(), P(), P(axis, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


@traced("distributed.brute_force::search")
def search(
    index: ShardedBruteForceIndex,
    queries,
    k: int,
    filter: Optional[Bitset] = None,
    select_algo: str = "exact",
    res: Optional[Resources] = None,
    health=None,
) -> Tuple[jax.Array, jax.Array]:
    """Sharded exact kNN: (distances (q, k), global indices (q, k)),
    replicated on every mesh slot, as a
    :class:`~raft_tpu.distributed._sharding.SearchResult` (carries
    ``coverage``/``degraded`` when shards were dropped from the merge)."""
    res = res or current_resources()
    queries = jnp.asarray(queries)
    if queries.shape[1] != index.dim:
        raise ValueError(f"query dim {queries.shape[1]} != index dim {index.dim}")
    if not 0 < k <= index.n_total:
        raise ValueError(f"k={k} out of range for n={index.n_total}")
    if filter is not None and filter.n_bits != index.n_total:
        raise ValueError(
            f"filter covers {filter.n_bits} bits but index has {index.n_total} rows"
        )
    comms = index.comms
    fn = _make_search_fn(
        comms.mesh,
        comms.axis,
        index.metric,
        float(index.metric_arg),
        int(k),
        index.n_total,
        select_algo,
        filter is not None,
        index.norms is not None,
        res.compute_dtype if index.metric in dist_mod.EXPANDED_METRICS else None,
        comms.size,
    )
    fwords = filter.bits if filter is not None else jnp.zeros((1,), jnp.uint32)
    norms = (
        index.norms
        if index.norms is not None
        else jnp.zeros((index.dataset.shape[0],), jnp.float32)
    )
    from raft_tpu.distributed._sharding import (SearchResult, probe_shards,
                                                shard_ok_device)

    report = probe_shards("brute_force", comms.size, index.n_total,
                          health=health)
    ok_dev = shard_ok_device(report.ok, comms)
    # query tiles bound the (tile, shard rows) fp32 distance block to the
    # workspace (a v5e compile of a 10k-query call over a 1M-row shard asked
    # for 40 GB, PR 21); equal tiles share one compiled program
    q = queries.shape[0]
    rows = index.dataset.shape[0] // comms.size
    q_tile = max(1, min(q, res.workspace_bytes // (4 * max(rows, 1))))
    q_tile = -(-q // -(-q // q_tile))
    outs = [fn(index.dataset, norms, queries[s:s + q_tile], fwords, ok_dev)
            for s in range(0, q, q_tile)]
    vals = outs[0][0] if len(outs) == 1 else jnp.concatenate(
        [o[0] for o in outs])
    ids = outs[0][1] if len(outs) == 1 else jnp.concatenate(
        [o[1] for o in outs])
    return SearchResult(vals, ids, coverage=report.coverage,
                        degraded=report.degraded,
                        lost_shards=report.dropped)
