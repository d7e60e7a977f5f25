"""Exact re-ranking of ANN candidate lists.

Reference: raft::neighbors::refine (refine-inl.cuh:70; device impl
detail/refine_device.cuh, host impl detail/refine_host-inl.hpp): given a
candidate id list per query (typically an over-fetched ANN result, e.g.
IVF-PQ's approximate top-(k·refine_ratio)), compute exact distances against
the original dataset and keep the best k.

TPU design: one gather of (q_tile, n_cand, dim) candidate rows + a batched
einsum per tile — the gather is the cost, so tiles are sized from the
Resources workspace budget. Candidate id -1 (padding from upstream searches)
is skipped and never dereferenced.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.trace import traced
from raft_tpu.ops import distance as dist_mod
from raft_tpu.ops.select_k import select_k

SUPPORTED_METRICS = ("sqeuclidean", "euclidean", "inner_product", "cosine")


@functools.partial(jax.jit, static_argnames=("k", "metric", "q_tile"))
def _refine_impl(queries, dataset, candidates, k, metric, q_tile):
    with jax.named_scope("refine"):
        q, dim = queries.shape
        n_cand = candidates.shape[1]
        l2 = metric in ("sqeuclidean", "euclidean")

        if metric == "cosine":
            queries = queries / jnp.maximum(jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-30)
            dataset = dataset / jnp.maximum(jnp.linalg.norm(dataset, axis=1, keepdims=True), 1e-30)
        qn = dist_mod.sqnorm(queries) if l2 else None

        def one_tile(args):
            q_blk, qn_blk, cand_blk = args
            safe = jnp.maximum(cand_blk, 0)
            vecs = dataset[safe].astype(jnp.float32)  # (qt, c, dim) gather
            ip = jnp.einsum("qd,qcd->qc", q_blk, vecs, preferred_element_type=jnp.float32)
            if l2:
                vn = dist_mod.sqnorm(vecs, axis=2)
                d = jnp.maximum(qn_blk[:, None] + vn - 2.0 * ip, 0.0)
                if metric == "euclidean":
                    d = jnp.sqrt(d)
            elif metric == "cosine":
                d = 1.0 - ip
            else:
                d = -ip  # inner product: min of negated
            d = jnp.where(cand_blk >= 0, d, jnp.inf)
            vals, sel = select_k(d, k, select_min=True)
            out_ids = jnp.where(jnp.isinf(vals), -1, jnp.take_along_axis(cand_blk, sel, axis=1))
            if metric == "inner_product":
                vals = -vals
            return vals, out_ids

        if qn is None:
            qn = jnp.zeros((q,), jnp.float32)
        if q_tile >= q:
            return one_tile((queries, qn, candidates))
        n_tiles = -(-q // q_tile)
        pad = n_tiles * q_tile - q
        qp = jnp.pad(queries, ((0, pad), (0, 0)))
        qnp = jnp.pad(qn, (0, pad))
        cp = jnp.pad(candidates, ((0, pad), (0, 0)), constant_values=-1)
        vals, ids = lax.map(
            one_tile,
            (
                qp.reshape(n_tiles, q_tile, dim),
                qnp.reshape(n_tiles, q_tile),
                cp.reshape(n_tiles, q_tile, n_cand),
            ),
        )
        return vals.reshape(-1, k)[:q], ids.reshape(-1, k)[:q]


@traced("refine::refine")
def refine(
    dataset,
    queries,
    candidates,
    k: int,
    metric: str = "sqeuclidean",
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Re-rank ``candidates`` (q, n_cand) by exact distance and return the
    top-k (refine-inl.cuh:70 analog). ``candidates`` entries of -1 are
    ignored; outputs use -1/inf sentinels the same way searches do."""
    res = res or current_resources()
    metric = dist_mod.canonical_metric(metric)
    if metric not in SUPPORTED_METRICS:
        raise ValueError(f"refine supports {SUPPORTED_METRICS}, got {metric!r}")
    # keep integer datasets (uint8/int8 big-ann formats) in their storage
    # dtype: the gather below is op-bound, so 1-byte rows cost the same ops
    # at 4× fewer bytes, and casting 10M+ rows to fp32 per call would burn
    # an index-sized HBM allocation (round-4, the 10M bench path)
    dataset = jnp.asarray(dataset)
    if not jnp.issubdtype(dataset.dtype, jnp.integer):
        dataset = dataset.astype(jnp.float32)
    queries = jnp.asarray(queries).astype(jnp.float32)
    candidates = jnp.asarray(candidates, jnp.int32)
    if queries.shape[1] != dataset.shape[1]:
        raise ValueError(f"dim mismatch: {queries.shape[1]} != {dataset.shape[1]}")
    if candidates.shape[0] != queries.shape[0]:
        raise ValueError("candidates must have one row per query")
    if not 0 < k <= candidates.shape[1]:
        raise ValueError(f"k={k} out of range for n_candidates={candidates.shape[1]}")
    per_query = max(1, candidates.shape[1] * (dataset.shape[1] + 4) * 4)
    q_tile = int(max(1, min(queries.shape[0], res.workspace_bytes // per_query)))
    with obs.record_launch("refine::launch", "refine.launches"):
        return _refine_impl(queries, dataset, candidates, int(k), metric,
                            q_tile)


def refine_host(dataset, queries, candidates, k: int,
                metric: str = "sqeuclidean") -> Tuple:
    """Pure-numpy exact re-rank for CPU serving pipelines (the reference's
    refine_host, detail/refine_host-inl.hpp): same contract as
    :func:`refine` but never touches an accelerator — the companion of the
    HNSW export story (build on TPU, re-rank candidates wherever the
    serving CPU lives).
    """
    import numpy as np

    metric = dist_mod.canonical_metric(metric)
    if metric not in SUPPORTED_METRICS:
        raise ValueError(f"refine_host supports {SUPPORTED_METRICS}, got {metric!r}")
    dataset = np.asarray(dataset, np.float32)
    queries = np.asarray(queries, np.float32)
    cand = np.asarray(candidates, np.int64)
    if not 0 < k <= cand.shape[1]:
        raise ValueError(f"k={k} out of range for n_candidates={cand.shape[1]}")
    if metric == "cosine":
        queries = queries / np.maximum(
            np.linalg.norm(queries, axis=1, keepdims=True), 1e-30)
        dataset = dataset / np.maximum(
            np.linalg.norm(dataset, axis=1, keepdims=True), 1e-30)
    rows = dataset[np.clip(cand, 0, dataset.shape[0] - 1)]  # (q, c, d)
    ip = np.einsum("qd,qcd->qc", queries, rows)
    if metric in ("sqeuclidean", "euclidean"):
        d = (np.sum(queries**2, 1)[:, None] + np.sum(rows**2, 2) - 2.0 * ip)
        d = np.maximum(d, 0.0)
        if metric == "euclidean":
            d = np.sqrt(d)
    elif metric == "cosine":
        d = 1.0 - ip
    else:  # inner_product: rank by max → negate for the shared min-select
        d = -ip
    d = np.where(cand >= 0, d, np.inf)
    sel = np.argsort(d, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(d, sel, axis=1)
    ids = np.take_along_axis(cand, sel, axis=1).astype(np.int32)
    ids = np.where(np.isfinite(vals), ids, -1)
    if metric == "inner_product":
        vals = np.where(ids >= 0, -vals, -np.inf)
    else:
        vals = np.where(ids >= 0, vals, np.inf)
    return vals.astype(np.float32), ids
