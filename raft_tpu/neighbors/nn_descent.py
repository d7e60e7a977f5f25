"""NN-descent ("GNND") — the all-neighbors kNN-graph builder CAGRA uses.

Reference: raft::neighbors::experimental::nn_descent
(nn_descent.cuh:59 build; detail/nn_descent.cuh:342 GNND class, :1191
local_join, :1215 host-buffered sample/update loop), itself the GPU
formulation of Wang et al., "Fast k-NN Graph Construction by GPU based
NN-Descent" (CIKM'21). Parameters mirror nn_descent_types.hpp:49-54
(graph_degree / intermediate_graph_degree / max_iterations /
termination_threshold).

TPU design — no atomics, no per-thread queues; everything is batched sort /
gather / matmul:

* The graph state is three dense (n, K) arrays (ids / dists / is_new) —
  K = intermediate_graph_degree, rows sorted by distance.
* Per iteration, each node samples up to S "new" and S "old" neighbors from
  its forward list and up to S from the reverse adjacency of those samples
  (the reference's in/out sampling, detail/nn_descent.cuh:1215).
* The local join materializes each node's sampled union U (4S ids), gathers
  their vectors and computes the (4S, 4S) pair distances with ONE batched
  einsum per node block — the MXU replacement for the warp-tiled join
  (detail/nn_descent.cuh:1191).
* Candidate edges (new x new, new x old, both directions) are distributed to
  their target nodes by sort + ``segment_take`` (the scatter-free analog of
  atomic list appends) and merged with ``merge_topk_dedup`` (sort-based
  bitonic-merge/dedup replacement).
* The whole iteration is one jitted program; the host loop only reads the
  scalar update counter for the termination test (termination_threshold) and
  the interruptible cancellation point.

**Status on the TPU runtime (round-4 decision, VERDICT r3 #8):** this
host-driven loop is CPU-capable but NOT the production TPU graph builder —
its per-iteration dispatch pattern measured impractical on the TPU
runtime and its large sort/gather working set can fault the TPU worker at
bench scale (round 3). The production CAGRA builder on TPU is the IVF
candidate search + device-resident neighbor-of-neighbor sweeps
(cagra._build_knn_ivf_pq + cagra.refine_knn_graph — the latter IS the
NN-descent local join recast as fixed-shape device blocks). This module
remains for CPU builds and API parity with
raft::neighbors::experimental::nn_descent.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.interruptible import check_interrupt
from raft_tpu.core.logger import get_logger
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.trace import traced
from raft_tpu.ops.segment import merge_topk_dedup, segment_take
from raft_tpu.utils.tiling import ceil_div

_log = get_logger()


@dataclass(frozen=True)
class NNDescentParams:
    """Mirror of nn_descent::index_params (nn_descent_types.hpp:49-54)."""

    graph_degree: int = 64
    intermediate_graph_degree: int = 128
    max_iterations: int = 20
    termination_threshold: float = 1e-4
    # GNND's per-node sample size (the segment-size analog); join cost per
    # node scales with ~6*sample_size^2 edges.
    sample_size: int = 16
    seed: int = 0

    def __post_init__(self):
        if self.graph_degree <= 0 or self.intermediate_graph_degree < self.graph_degree:
            raise ValueError(
                "need 0 < graph_degree <= intermediate_graph_degree "
                f"(got {self.graph_degree}, {self.intermediate_graph_degree})"
            )
        if self.sample_size <= 0:
            raise ValueError("sample_size must be positive")


def _pair_indices(S2: int, S4: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static (a, b) index pairs into the per-node union U of size S4
    (first S2 entries are NEW, rest OLD): new x new unordered pairs plus the
    full new x old grid — the GNND join rule (new entries must meet
    everything, old x old pairs were already joined)."""
    import numpy as np

    pa, pb = [], []
    for i in range(S2):
        for j in range(i + 1, S2):  # new x new
            pa.append(i)
            pb.append(j)
        for j in range(S2, S4):  # new x old
            pa.append(i)
            pb.append(j)
    return jnp.asarray(np.array(pa, np.int32)), jnp.asarray(np.array(pb, np.int32))


def _sample(key, ids, flags, S, want_new):
    """Sample up to S per-row ids where flag==want_new; returns (n,S) ids
    (-1 padded) and the source positions (n,S) (for demotion)."""
    n, K = ids.shape
    eligible = (flags == want_new) & (ids >= 0)
    r = jax.random.uniform(key, (n, K))
    # eligible entries first (key 0), random order among them
    order = jnp.argsort(jnp.where(eligible, r, 2.0 + r), axis=1)[:, :S]
    picked = jnp.take_along_axis(eligible, order, axis=1)
    out = jnp.where(picked, jnp.take_along_axis(ids, order, axis=1), -1)
    return out, jnp.where(picked, order, -1)


def _reverse_sample(key, sample_ids, n, S):
    """Up to S reverse-adjacency sources per node from a forward sample:
    edge (i -> sample_ids[i, j]) contributes source i to node
    sample_ids[i, j]'s reverse list (random subset per node, like the
    reference's reverse-graph sampling)."""
    ns, w = sample_ids.shape
    src = jnp.broadcast_to(jnp.arange(ns, dtype=jnp.int32)[:, None], (ns, w)).reshape(-1)
    tgt = sample_ids.reshape(-1)
    keys = jnp.where(tgt >= 0, tgt, n).astype(jnp.int32)
    # randomize within each target's span so the cap keeps a random subset
    r = jax.random.uniform(key, keys.shape)
    order = jnp.lexsort((r, keys))
    valid, rsrc = segment_take(keys[order], n, S, src[order])
    return jnp.where(valid, rsrc, -1)


def _init_state(key, X, norms, K, block_rows):
    """Random initial graph: K distinct-ish random neighbors per node."""
    n = X.shape[0]
    ids = jax.random.randint(key, (n, K), 0, n, dtype=jnp.int32)
    # self-edges shifted off; duplicate ids resolved by the first merge pass
    ids = jnp.where(ids == jnp.arange(n, dtype=jnp.int32)[:, None], (ids + 1) % n, ids)
    dists = _block_pair_dists(X, norms, ids, block_rows)
    # dedup via a merge against an empty candidate set
    empty_ids = jnp.full((n, 1), -1, jnp.int32)
    empty_d = jnp.full((n, 1), jnp.inf, jnp.float32)
    ids, dists, _, flags = merge_topk_dedup(
        ids,
        dists,
        empty_ids,
        empty_d,
        K,
        exclude_self=jnp.arange(n, dtype=jnp.int32),
        payload=jnp.ones((n, K), jnp.bool_),
        cand_payload=jnp.zeros((n, 1), jnp.bool_),
    )
    return ids, dists, flags


def _block_pair_dists(X, norms, ids, block_rows):
    """d2(i, ids[i, :]) computed in row blocks (memory-bounded gather)."""
    n, K = ids.shape
    nb = ceil_div(n, block_rows)
    pad = nb * block_rows - n
    ids_p = jnp.pad(ids, ((0, pad), (0, 0))).reshape(nb, block_rows, K)
    rows_p = jnp.pad(jnp.arange(n, dtype=jnp.int32), (0, pad)).reshape(nb, block_rows)

    def step(_, inp):
        bids, brows = inp
        xb = X[brows]  # (B, dim)
        xn = X[jnp.maximum(bids, 0)]  # (B, K, dim)
        ip = jnp.einsum("bd,bkd->bk", xb, xn)
        d = norms[brows][:, None] + norms[jnp.maximum(bids, 0)] - 2.0 * ip
        return None, jnp.maximum(d, 0.0)

    _, d = lax.scan(step, None, (ids_p, rows_p))
    d = d.reshape(nb * block_rows, K)[:n]
    return jnp.where(ids >= 0, d, jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("K", "S", "n_blocks", "cand_cap")
)
def _iteration(X, norms, ids, dists, is_new, key, K, S, n_blocks, cand_cap):
    """One NN-descent round; returns (ids, dists, is_new, n_updates)."""
    n = X.shape[0]
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)

    fwd_new, new_pos = _sample(k1, ids, is_new, S, want_new=True)
    fwd_old, _ = _sample(k2, ids, is_new, S, want_new=False)
    rev_new = _reverse_sample(k3, fwd_new, n, S)
    rev_old = _reverse_sample(k4, fwd_old, n, S)
    # demote sampled new entries (they join this round; GNND flag flip);
    # mode="drop" discards the -1 (not sampled) positions
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], new_pos.shape)
    is_new = is_new.at[rows, new_pos].set(False, mode="drop")

    NEW = jnp.concatenate([fwd_new, rev_new], axis=1)  # (n, 2S)
    OLD = jnp.concatenate([fwd_old, rev_old], axis=1)
    U = jnp.concatenate([NEW, OLD], axis=1)  # (n, 4S)
    S2, S4 = 2 * S, 4 * S
    pa, pb = _pair_indices(S2, S4)

    block = ceil_div(n, n_blocks)
    pad = n_blocks * block - n
    U_p = jnp.pad(U, ((0, pad), (0, 0)), constant_values=-1).reshape(
        n_blocks, block, S4
    )

    def join_block(carry, Ub):
        ids_c, dists_c, flags_c, updates = carry
        Us = jnp.maximum(Ub, 0)
        xu = X[Us]  # (B, 4S, dim)
        nu = norms[Us]  # (B, 4S)
        ip = jnp.einsum("bsd,btd->bst", xu, xu)
        D = jnp.maximum(nu[:, :, None] + nu[:, None, :] - 2.0 * ip, 0.0)
        a = Ub[:, pa]  # (B, P)
        b = Ub[:, pb]
        d = D[:, pa, pb]
        ok = (a >= 0) & (b >= 0) & (a != b)
        # both directions, flattened
        src = jnp.concatenate([a, b], axis=1).reshape(-1)
        tgt = jnp.concatenate([b, a], axis=1).reshape(-1)
        dd = jnp.concatenate([d, d], axis=1).reshape(-1)
        okk = jnp.concatenate([ok, ok], axis=1).reshape(-1)
        keys = jnp.where(okk, tgt, n).astype(jnp.int32)
        order = jnp.lexsort((dd, keys))
        valid, csrc, cd = segment_take(keys[order], n, cand_cap, src[order], dd[order])
        cand_ids = jnp.where(valid, csrc, -1)
        cand_d = jnp.where(valid, cd, jnp.inf)
        ids2, dists2, from_cand, flags2 = merge_topk_dedup(
            ids_c,
            dists_c,
            cand_ids,
            cand_d,
            K,
            exclude_self=jnp.arange(n, dtype=jnp.int32),
            payload=flags_c,
            cand_payload=jnp.ones(cand_ids.shape, jnp.bool_),
        )
        return (ids2, dists2, flags2, updates + jnp.sum(from_cand)), None

    (ids, dists, is_new, updates), _ = lax.scan(
        join_block, (ids, dists, is_new, jnp.int32(0)), U_p
    )
    return ids, dists, is_new, updates


@traced("nn_descent::build")
def build(
    dataset,
    params: NNDescentParams = NNDescentParams(),
    res: Optional[Resources] = None,
    return_distances: bool = False,
):
    """Build the (n, graph_degree) approximate kNN graph (nn_descent.cuh:59).

    L2 (sqeuclidean) metric, matching the reference builder. Returns int32
    neighbor ids sorted by distance (and the distances when requested).
    """
    res = res or current_resources()
    X = jnp.asarray(dataset, jnp.float32)
    n, dim = X.shape
    if n < 2:
        raise ValueError(f"need at least 2 rows, got {n}")
    K = int(min(params.intermediate_graph_degree, n - 1))
    deg = int(min(params.graph_degree, K))
    S = int(min(params.sample_size, K))
    norms = jnp.sum(X * X, axis=1)

    # memory budget: the join materializes ~(block, 4S, dim) gathers and
    # ~12*S^2*block edge triples; bound both by workspace_bytes
    per_node = 4 * S * dim * 4 + 12 * S * S * 12
    block = max(256, int(res.workspace_bytes // max(per_node, 1) // 4))
    n_blocks = max(1, ceil_div(n, block))
    cand_cap = 2 * S

    key = jax.random.key(params.seed)
    kinit, key = jax.random.split(key)
    ids, dists, is_new = _init_state(kinit, X, norms, K, block_rows=4096)

    threshold = params.termination_threshold * n * K
    from raft_tpu.resilience import active_deadline

    for it in range(params.max_iterations):
        # deadline checkpoint (ISSUE 3): descent is anytime — every round
        # only improves the graph — so an expiring budget returns the
        # current graph marked degraded instead of dying to the watchdog
        dl = active_deadline()
        if dl is not None and it > 0 and dl.reached():
            dl.mark_degraded("nn_descent.build")
            break
        check_interrupt()
        kit, key = jax.random.split(key)
        ids, dists, is_new, updates = _iteration(
            X, norms, ids, dists, is_new, kit, K, S, n_blocks, cand_cap
        )
        n_updates = int(updates)
        _log.debug("nn_descent iter %d: %d updates", it, n_updates)
        if n_updates <= threshold:
            break

    if return_distances:
        return ids[:, :deg], dists[:, :deg]
    return ids[:, :deg]
