"""CAGRA-class graph ANN index: NN-descent build, detour pruning, best-first search.

Reference: raft::neighbors::cagra — build (cagra.cuh:274 →
detail/cagra/cagra_build.cuh:296: kNN graph via IVF-PQ+refine or NN-descent,
then graph::optimize = detour-count pruning + reverse-edge add,
detail/cagra/graph_core.cuh:320, rev-graph kernel :191); search
(cagra.cuh:299 → detail/cagra/cagra_search.cuh:104, single-CTA persistent
best-first kernel detail/cagra/search_single_cta_kernel-inl.cuh:466 with
pickup_next_parents :51, bitonic top-k merge :405, visited hashmap
detail/cagra/hashmap.hpp). Params mirror cagra_types.hpp:55-134
(intermediate_graph_degree=128, graph_degree=64, itopk_size=64,
search_width=1, max/min_iterations, num_random_samplings).

TPU redesign (SURVEY.md §7 hard-part 2 — data-dependent traversal vs XLA
static shapes):

* **Build**: NN-descent (nn_descent.py) gives the intermediate graph with
  distances; pruning streams the detour-count computation as a
  ``lax.scan`` over rank positions (K² comparisons per node per step)
  instead of the GPU's per-edge bitwise kernel — everything static-shape.
* **Search**: a fixed-capacity itopk candidate buffer per query, advanced by
  a ``lax.while_loop``; each step expands the best ``search_width``
  unvisited entries, gathers their graph rows, computes distances with one
  batched einsum across the whole query batch (MXU-friendly: the per-query
  matvec becomes a (Q, w·deg, dim) batched contraction), and merges via
  compare-matrix dedup + a narrow top-k — the hashmap+bitonic-sort
  replacement. Termination: all itopk entries visited, or max_iterations.
* The visited set is the buffer's per-slot flag (the single-CTA parent bit);
  a node evicted and later re-inserted may be re-expanded — a bounded waste
  the GPU hashmap avoids, accepted here to keep shapes static.

**Round-5 compressed traversal** (the production path at scale; the
reference's CAGRA-Q compressed-dataset search is the analog,
cagra_types.hpp's int8/uint8 dataset + vpq compression):

XLA row gathers on this hardware are op-bound (~12 ns/row regardless of
row width or dtype), so the exact loop's q·w·deg per-iteration
neighbor-vector gathers — not FLOPs or HBM bytes — are the entire cost.
The round-5 layout makes the gather count per iteration q·w instead:

* each node's record inlines its neighbors' vectors, compressed to
  ``compress_dim``-d int8 via a PCA projection
  (``nbr_codes[i, j] = quantize(proj(X[graph[i, j]]))``; top principal
  axes — measured +10 recall points over a random subspace at p=dim/3 on
  siftlike) — one contiguous per-parent fetch yields all deg candidate
  vectors, 64× fewer gather ops at graph_degree 64;
* traversal distances are computed from the codes on the MXU
  (projected-space ranking only); the final answer is exactly re-ranked
  over the itopk buffer against the raw dataset — the same
  compressed-search + refine split as CAGRA-Q;
* seeding is centroid-guided: one (q, n_centroids) MXU gemm against the
  build-time coarse centroids picks per-query entry points (their stored
  nearest-dataset-row representatives), replacing random seeds and their
  gather storm — fewer iterations to reach the query's neighborhood;
* the itopk merge runs on the mantissa-packed iter select
  (ops/select_k.iter_topk_min_packed) — 2 VPU ops per pass over a
  (q, itopk + w·deg) row instead of lax.top_k's full sort.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

import os as _os

from raft_tpu import obs
from raft_tpu.core.bitset import Bitset
from raft_tpu.core.trace import traced
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.serialize import load_arrays, save_arrays
from raft_tpu.neighbors import nn_descent as nnd
from raft_tpu.ops.cagra_hop import MAX_FUSED_ROWS, fused_hop
from raft_tpu.ops.segment import merge_topk_dedup, segment_take
# hoisted to module scope (code-review r6): the loop-body copies of this
# import re-executed on every trace of the compressed search
from raft_tpu.ops.select_k import iter_topk_min, iter_topk_min_packed
from raft_tpu.utils.tiling import ceil_div


@dataclass(frozen=True)
class CagraParams:
    """cagra::index_params analog (cagra_types.hpp:55-63)."""

    intermediate_graph_degree: int = 128
    graph_degree: int = 64
    # "auto": exact one-pass kNN below ``brute_threshold`` rows, IVF-PQ +
    # refine above (the reference's default builder, cagra_build.cuh:87).
    # "nn_descent" (detail/nn_descent.cuh) remains available but its
    # host-driven iteration loop is dispatch-bound on this TPU runtime —
    # the IVF-PQ path is the production TPU builder.
    build_algo: str = "auto"  # "auto" | "ivf_pq" | "nn_descent" | "brute"
    nn_descent_niter: int = 20
    brute_threshold: int = 65536
    # IVF builder knobs (0 = auto-sized from n/dim). The "ivf_pq" algo uses
    # an IVF-FLAT scan (exact in-list distances, no refine pass) while the
    # raw dataset fits comfortably in HBM, and the PQ+refine pipeline above
    # that — same candidate-generation structure as the reference's
    # cagra_build.cuh:87, picked by memory footprint.
    ivf_pq_n_lists: int = 0
    ivf_pq_n_probes: int = 0
    ivf_pq_refine_rate: float = 2.0
    # device-resident neighbor-of-neighbor refinement sweeps after an
    # approximate (IVF) build — the NN-descent local join recast with
    # static shapes (detail/nn_descent.cuh:1215); lifts graph recall toward
    # exact. -1 = auto: 0 after the exact-distance IVF-Flat candidate scan
    # (measured 0.97 graph recall at 1M — sweeps add ~1.5 points of graph
    # recall but no search recall), 2 after the PQ+refine builder whose
    # candidate recall is lower
    graph_refine_iters: int = -1
    graph_refine_sample: int = 448
    # compressed-traversal payload (round 5, the CAGRA-Q analog): inline
    # each node's neighbors as compress_dim-d int8 codes so search gathers
    # one record per expanded parent instead of one row per neighbor.
    # "auto" = on above compress_threshold rows (the payload costs
    # n·graph_degree·compress_dim bytes of HBM — worth it exactly when the
    # gather count dominates, i.e. at scale).
    compress: str = "auto"  # "auto" | "on" | "off"
    compress_dim: int = 0  # 0 = auto: min(64, dim)
    compress_threshold: int = 200_000
    seed: int = 0

    def __post_init__(self):
        if self.graph_degree <= 0:
            raise ValueError("graph_degree must be positive")
        if self.intermediate_graph_degree < self.graph_degree:
            raise ValueError("intermediate_graph_degree < graph_degree")
        if self.build_algo not in ("auto", "ivf_pq", "nn_descent", "brute"):
            raise ValueError(f"unknown build_algo {self.build_algo!r}")
        if self.compress not in ("auto", "on", "off"):
            raise ValueError(f"unknown compress mode {self.compress!r}")


@dataclass(frozen=True)
class CagraSearchParams:
    """cagra::search_params analog (cagra_types.hpp:77-118)."""

    itopk_size: int = 64
    max_iterations: int = 0  # 0 = auto-sized from itopk/search_width
    min_iterations: int = 0
    search_width: int = 1
    num_random_samplings: int = 1
    # "auto" rides the unfused compressed loop whenever the index carries
    # the inlined-int8-codes payload (the fused one-kernel hop,
    # ops/cagra_hop.py, does not compile for the chip yet); "fused"/"compressed"
    # force their loop (both error if the payload is absent); "exact"
    # forces full-precision traversal (the pre-round-5 loop)
    traversal: str = "auto"  # "auto" | "fused" | "compressed" | "exact"
    # exact re-rank depth for the compressed loop: the final answer ranks
    # the best refine_topk buffer entries against the raw dataset
    # (0 = the whole itopk buffer — safest; shrink to trade a little
    # recall for q·refine_topk fewer exit gathers)
    refine_topk: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.itopk_size <= 0 or self.search_width <= 0:
            raise ValueError("itopk_size and search_width must be positive")
        if self.traversal not in ("auto", "fused", "compressed", "exact"):
            raise ValueError(f"unknown traversal mode {self.traversal!r}")


@jax.tree_util.register_pytree_node_class
@dataclass
class CagraIndex:
    """Graph index: dataset + fixed-degree kNN graph (cagra_types.hpp:55-134).

    The optional round-5 fields carry the compressed-traversal payload
    (None on indexes built with ``compress="off"`` or loaded from pre-r5
    files — those search via the exact loop):

    * ``proj``/``code_scale``: the (dim, p) PCA projection (orthonormal
      rotation when p == dim) and int8 quantization scale;
    * ``nbr_codes``: (n, graph_degree, p) int8 — node i's record inlines
      the projected codes of all its graph neighbors;
    * ``centroids``/``centroid_reps``: coarse centers from the IVF builder
      + each center's nearest dataset row, for guided seeding.
    """

    dataset: jax.Array  # (n, dim) fp32 (or uint8/int8 for integer inputs)
    graph: jax.Array  # (n, graph_degree) int32 neighbor ids
    norms: jax.Array  # (n,) squared L2 norms
    proj: Optional[jax.Array] = None  # (dim, p) fp32
    code_scale: Optional[jax.Array] = None  # () fp32
    nbr_codes: Optional[jax.Array] = None  # (n, graph_degree, p) int8
    centroids: Optional[jax.Array] = None  # (c, dim) fp32
    centroid_reps: Optional[jax.Array] = None  # (c,) int32
    # fraction of centered data variance the projection keeps — scales
    # full-space seed distances into projected space (PCA keeps more than
    # the random-subspace p/dim; None = legacy p/dim)
    proj_energy: Optional[jax.Array] = None  # () fp32

    @property
    def size(self) -> int:
        return self.dataset.shape[0]

    @property
    def dim(self) -> int:
        return self.dataset.shape[1]

    @property
    def graph_degree(self) -> int:
        return self.graph.shape[1]

    def tree_flatten(self):
        return (self.dataset, self.graph, self.norms, self.proj,
                self.code_scale, self.nbr_codes, self.centroids,
                self.centroid_reps, self.proj_energy), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- persistence (cagra_serialize.cuh analog) ---------------------------
    def save(self, path) -> None:
        arrays = {"dataset": self.dataset, "graph": self.graph,
                  "norms": self.norms}
        for name in ("proj", "code_scale", "nbr_codes", "centroids",
                     "centroid_reps", "proj_energy"):
            v = getattr(self, name)
            if v is not None:
                arrays[name] = v
        save_arrays(path, {"kind": "cagra", "metric": "sqeuclidean"}, arrays)

    @classmethod
    def load(cls, path) -> "CagraIndex":
        meta, arrays = load_arrays(path)
        if meta.get("kind") != "cagra":
            raise ValueError(f"not a cagra index: {meta.get('kind')}")
        opt = {
            name: jnp.asarray(arrays[name])
            for name in ("proj", "code_scale", "nbr_codes", "centroids",
                         "centroid_reps", "proj_energy")
            if name in arrays
        }
        return cls(
            jnp.asarray(arrays["dataset"]),
            jnp.asarray(arrays["graph"]),
            jnp.asarray(arrays["norms"]),
            **opt,
        )


# ---------------------------------------------------------------------------
# Build: kNN graph + optimize (prune)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("out_degree", "n_blocks"))
def optimize(graph: jax.Array, out_degree: int, n_blocks: int = 1) -> jax.Array:
    """Prune an intermediate kNN graph to ``out_degree`` (graph::optimize,
    detail/cagra/graph_core.cuh:320).

    Two stages, mirroring the reference:

    1. **Detour-count pruning**: edge (s→t) at rank j is detourable through
       u at rank i<j when t appears in u's list at rank m<j (a 2-hop path of
       strictly better-ranked edges). Keep the ``out_degree`` edges with the
       fewest detours (rank as tie-break). Computed as a ``lax.scan`` over
       rank position j with K² membership tests per node — static shapes,
       streamed memory.
    2. **Reverse-edge add** (rev-graph kernel analog, graph_core.cuh:191):
       the final list interleaves the best half of the pruned forward edges
       with up to degree/2 reverse edges (dedup'd, forward edges fill any
       remainder) so that every node stays reachable.
    """
    n, K = graph.shape
    block = ceil_div(n, n_blocks)
    pad = n_blocks * block - n
    g_pad = jnp.pad(graph, ((0, pad), (0, 0)), constant_values=-1)

    def count_block(_, gb):
        # gb: (B, K) neighbor ids of this node block
        two_hop = graph[jnp.maximum(gb, 0)]  # (B, K, K): neighbors of neighbors

        def step(j, counts):
            t = gb[:, j]  # (B,) target id at rank j
            # membership of t among each better-ranked neighbor's prefix:
            # hit[b, i, m] = (two_hop[b, i, m] == t[b]) & (i < j) & (m < j)
            hit = two_hop == t[:, None, None]
            ii = jnp.arange(K)[None, :, None] < j
            mm = jnp.arange(K)[None, None, :] < j
            c = jnp.sum(hit & ii & mm, axis=(1, 2)).astype(jnp.int32)
            return counts.at[:, j].set(c)

        counts = lax.fori_loop(0, K, step, jnp.zeros(gb.shape, jnp.int32))
        return None, counts

    _, counts = lax.scan(
        count_block, None, g_pad.reshape(n_blocks, block, K)
    )
    counts = counts.reshape(-1, K)[:n]

    # keep out_degree edges with fewest detours (rank breaks ties);
    # invalid (-1) entries sort last
    rank = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[None, :], graph.shape)
    key = jnp.where(graph >= 0, counts * K + rank, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, axis=1)[:, :out_degree]
    fwd = jnp.take_along_axis(graph, order, axis=1)  # (n, out_degree)

    # reverse candidates of the pruned graph, capped at out_degree per node,
    # better-ranked sources first
    half = max(1, out_degree // 2)
    src = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[:, None], fwd.shape
    ).reshape(-1)
    tgt = fwd.reshape(-1)
    rnk = jnp.broadcast_to(
        jnp.arange(out_degree, dtype=jnp.int32)[None, :], fwd.shape
    ).reshape(-1)
    keys = jnp.where(tgt >= 0, tgt, n).astype(jnp.int32)
    order = jnp.lexsort((rnk, keys))
    valid, rev = segment_take(keys[order], n, half, src[order])
    rev = jnp.where(valid, rev, -1)

    # interleave: forward first-half at priority 0..half-1, reverse at
    # half..half+half-1, forward second-half last; dedup by id keeps the
    # best priority
    prio_fwd = jnp.where(
        jnp.arange(out_degree)[None, :] < half,
        jnp.arange(out_degree, dtype=jnp.int32)[None, :],
        (jnp.arange(out_degree, dtype=jnp.int32) + 2 * half)[None, :],
    ).astype(jnp.float32)
    prio_fwd = jnp.broadcast_to(prio_fwd, fwd.shape)
    prio_fwd = jnp.where(fwd >= 0, prio_fwd, jnp.inf)
    prio_rev = jnp.broadcast_to(
        (jnp.arange(half, dtype=jnp.int32) + half)[None, :].astype(jnp.float32),
        rev.shape,
    )
    prio_rev = jnp.where(rev >= 0, prio_rev, jnp.inf)
    out_ids, _, _ = merge_topk_dedup(
        fwd, prio_fwd, rev, prio_rev, out_degree,
        exclude_self=jnp.arange(n, dtype=jnp.int32),
    )
    return out_ids


def _drop_self(ids, row_start: int, ideg: int):
    """Remove each row's self-match and compact to ideg columns (stable)."""
    rows = row_start + jnp.arange(ids.shape[0], dtype=jnp.int32)
    ids = jnp.where(ids == rows[:, None], -1, ids)
    order = jnp.argsort(jnp.where(ids < 0, 2, 0), axis=1, stable=True)[:, :ideg]
    return jnp.take_along_axis(ids, order, axis=1)


def _flat_builder_fits(n: int, dim: int) -> bool:
    """IVF-Flat candidate scan (exact distances, no refine) while the raw
    fp32 dataset stays ≤ 2 GB of HBM; PQ+refine above. Shared by the build
    path selection and the auto graph-refine-sweep decision — one predicate
    so the two cannot desync (code-review r4)."""
    return n * dim * 4 <= (2 << 30)


def _build_knn_ivf_pq(X, ideg: int, params: "CagraParams", res):
    """Intermediate kNN graph via an IVF candidate search — the reference's
    scalable builder (cagra_build.cuh:87 build_knn_graph: ivf_pq::build,
    batched ivf_pq::search over the dataset itself, refine at
    ``refine_rate`` over-fetch). O(n·√n̄) instead of the O(n²) brute pass;
    the only TPU-viable route past ~1M rows (nn_descent's per-iteration
    host dispatch loop measured impractical on this runtime, round 3).

    TPU adaptation: while the raw fp32 dataset fits comfortably in HBM
    (≤ 2 GB), candidates come from an IVF-FLAT scan instead — exact
    in-list distances, so the refine pass disappears and the per-pair
    fetch width drops from refine_rate·(ideg+1) to ideg+2 (the in-kernel
    top-k cost is linear in that width). Above the threshold, the PQ +
    exact-refine pipeline, as in the reference."""
    n, dim = X.shape
    n_lists = params.ivf_pq_n_lists or int(
        max(16, min(65536, round((n / 976) ** 0.5) ** 2, n // 64)))
    n_probes = params.ivf_pq_n_probes or max(8, n_lists // 16)
    from raft_tpu.core.interruptible import check_interrupt

    out = []
    if _flat_builder_fits(n, dim):
        from raft_tpu.neighbors import ivf_flat as flm

        # ideg+1 covers the self-match slot: after _drop_self at least
        # ideg non-self neighbors remain whether or not self was fetched
        kf = ideg + 1
        idx = flm.build(X, flm.IvfFlatParams(
            n_lists=n_lists,
            kmeans_trainset_fraction=float(min(1.0, max(0.1, 200_000 / n))),
            group_size=512, seed=params.seed,
        ), res=res)
        B = int(max(4096, min(n, res.workspace_bytes
                              // max(kf * (dim + 8) * 4, 1))))
        for s in range(0, n, B):
            check_interrupt()
            qb = lax.slice_in_dim(X, s, min(s + B, n), axis=0)
            _, ids = flm.search(idx, qb, kf, n_probes=n_probes, res=res)
            out.append(_drop_self(ids, s, ideg))
    else:
        from raft_tpu.neighbors import ivf_pq as pqm
        from raft_tpu.neighbors import refine as refm

        kf = int(min(max(ideg + 2,
                         round(params.ivf_pq_refine_rate * (ideg + 1))), 512))
        idx = pqm.build(X, pqm.IvfPqParams(
            n_lists=n_lists, pq_dim=max(8, dim // 2), pq_bits=8,
            kmeans_trainset_fraction=float(min(1.0, max(0.1, 200_000 / n))),
            seed=params.seed,
        ), res=res)
        B = int(max(4096, min(n, res.workspace_bytes
                              // max(kf * (dim + 8) * 4, 1))))
        for s in range(0, n, B):
            check_interrupt()
            qb = lax.slice_in_dim(X, s, min(s + B, n), axis=0)
            _, cand = pqm.search(idx, qb, kf, n_probes=n_probes, res=res)
            _, ids = refm.refine(X, qb, cand, min(ideg + 1, kf), res=res)
            out.append(_drop_self(ids, s, ideg))
    graph = jnp.concatenate(out, axis=0) if len(out) > 1 else out[0]
    # the coarse centers double as the search's guided-seeding table
    return graph, idx.centers


@functools.partial(jax.jit, static_argnames=("sample", "block"))
def _refine_graph_block(X, graph, start, key, sample: int, block: int):
    """One node block of the neighbor-of-neighbor sweep: candidates = own
    current list + ``sample`` random 2-hop neighbors, exact distances, keep
    the best ideg (dedup'd)."""
    n, dim = X.shape
    ideg = graph.shape[1]
    rows = start + jnp.arange(block, dtype=jnp.int32)
    rows_c = jnp.minimum(rows, n - 1)
    own = graph[rows_c]                                    # (B, ideg)
    two_hop = graph[jnp.maximum(own, 0)]                   # (B, ideg, ideg)
    pick = jax.random.randint(key, (block, sample), 0, ideg * ideg)
    cand2 = jnp.take_along_axis(
        two_hop.reshape(block, ideg * ideg), pick, axis=1)
    cands = jnp.concatenate([own, cand2], axis=1)          # (B, ideg+sample)
    cands = jnp.where(cands == rows[:, None], -1, cands)   # drop self
    xv = X[jnp.maximum(cands, 0)].astype(jnp.float32)
    qv = X[rows_c].astype(jnp.float32)
    d = jnp.sum(xv * xv, axis=2) - 2.0 * jnp.einsum(
        "bcd,bd->bc", xv, qv, preferred_element_type=jnp.float32)
    d = jnp.where(cands >= 0, d, jnp.inf)
    # dedup-then-select, merge_topk_dedup style: a GOOD graph's 2-hop
    # candidates repeat heavily (shared neighbors), so any fixed top-m
    # window can fill with copies before ideg uniques appear — the round-4
    # bug that silently halved graph degree at 1M. The id-primary lexsort
    # makes every duplicate adjacent regardless of multiplicity; the second
    # sort restores distance order over the surviving first copies.
    order = jnp.lexsort((d, cands), axis=-1)
    si = jnp.take_along_axis(cands, order, axis=1)
    sd = jnp.take_along_axis(d, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((block, 1), jnp.bool_), si[:, 1:] == si[:, :-1]], axis=1)
    sd = jnp.where(dup | (si < 0), jnp.inf, sd)
    order2 = jnp.argsort(sd, axis=1)[:, :ideg]
    out = jnp.take_along_axis(si, order2, axis=1)
    keep = jnp.take_along_axis(sd, order2, axis=1) < jnp.inf
    return jnp.where(keep, out, -1)


def refine_knn_graph(X, graph, iters: int, sample: int, seed: int,
                     res) -> jax.Array:
    """Device-resident NN-descent-style refinement of an intermediate kNN
    graph (the local-join of detail/nn_descent.cuh:1215, recast as
    fixed-shape blocks: candidates = current neighbors + sampled 2-hop
    neighbors, exact distances on the MXU, sort-free dedup). Each sweep is
    a handful of dispatches over node blocks — unlike the host-driven
    nn_descent loop, whose per-iteration dispatches dominate on TPU."""
    from raft_tpu.core.interruptible import check_interrupt

    n, dim = X.shape
    ideg = graph.shape[1]
    width = ideg + sample
    block = int(max(1024, min(n,
                              res.workspace_bytes // max(width * (dim + 4) * 4, 1))))
    key = jax.random.key(seed ^ 0x5EED)
    for it in range(iters):
        parts = []
        for s in range(0, n, block):
            check_interrupt()
            key, sub = jax.random.split(key)
            g = _refine_graph_block(X, graph, s, sub, int(sample), block)
            b = min(block, n - s)
            parts.append(g[:b] if b < block else g)
        graph = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return graph



def _sync(x) -> None:
    """Wait for ``x`` on the device.

    Gated on telemetry: the per-phase syncs exist so ``_build_timings_s`` /
    ``cagra.build.*`` timers measure completion rather than dispatch. With
    telemetry off the build pipeline stays fully async — no host round-trip
    between phases — and ``_build_timings_s`` records dispatch times only.
    Callers that consume the phase timings (bench.py's cagra section) enable
    obs around the build so their recorded numbers stay completion-based.
    """
    if not obs.enabled():
        return
    jax.block_until_ready(x)

@traced("cagra::build")
def build(
    dataset,
    params: CagraParams = CagraParams(),
    res: Optional[Resources] = None,
) -> CagraIndex:
    """Build a CAGRA index (cagra.cuh:274 → cagra_build.cuh:296): kNN graph
    via IVF-PQ+refine (or exact for small n, or NN-descent), then optimize
    to graph_degree.

    Phase wall-clock (knn_graph / refine_sweeps / optimize / compress) is
    recorded on the returned index as ``_build_timings_s`` — the bench
    surfaces it so build-time work has a profile to attack (VERDICT r4 #3).
    """
    import time as _time

    from raft_tpu.resilience import faultpoint

    faultpoint("cagra.build")
    res = res or current_resources()
    X = jnp.asarray(dataset, jnp.float32)
    n, dim = X.shape
    ideg = int(min(params.intermediate_graph_degree, n - 1))
    deg = int(min(params.graph_degree, ideg))

    algo = params.build_algo
    if algo == "auto":
        algo = "brute" if n <= params.brute_threshold else "ivf_pq"

    timings = {}
    t0 = _time.perf_counter()
    centroids = None
    if algo == "brute" or n <= 2048:
        # exact graph for small datasets: one tiled MXU pass beats training
        # an IVF index at this scale
        from raft_tpu.neighbors.brute_force import knn

        _, ids = knn(X, X, ideg + 1, metric="sqeuclidean", res=res)
        graph = _drop_self(ids, 0, ideg)
        _sync(graph)
        timings["knn_graph"] = _time.perf_counter() - t0
    elif algo == "ivf_pq":
        graph, centroids = _build_knn_ivf_pq(X, ideg, params, res)
        _sync(graph)
        timings["knn_graph"] = _time.perf_counter() - t0
        sweeps = params.graph_refine_iters
        if sweeps < 0:  # auto: the flat candidate scan is already ~exact
            sweeps = 0 if _flat_builder_fits(n, dim) else 2
        if sweeps > 0:
            t0 = _time.perf_counter()
            graph = refine_knn_graph(
                X, graph, int(sweeps),
                int(params.graph_refine_sample), params.seed, res)
            _sync(graph)
            timings["refine_sweeps"] = _time.perf_counter() - t0
    else:
        graph = nnd.build(
            X,
            nnd.NNDescentParams(
                graph_degree=ideg,
                intermediate_graph_degree=min(int(1.5 * ideg), n - 1),
                max_iterations=params.nn_descent_niter,
                seed=params.seed,
            ),
            res=res,
        )
        timings["knn_graph"] = _time.perf_counter() - t0

    # detour-prune in blocks bounded by workspace: scan materializes
    # (block, K, K) two-hop ids (int32)
    t0 = _time.perf_counter()
    per_node = ideg * ideg * 4 * 2
    block = max(128, int(res.workspace_bytes // max(per_node, 1) // 2))
    n_blocks = max(1, ceil_div(n, block))
    pruned = optimize(graph, deg, n_blocks=n_blocks)
    norms = jnp.sum(X * X, axis=1)
    _sync(pruned)
    timings["optimize"] = _time.perf_counter() - t0
    # integer datasets (uint8/int8, the big-ann formats) are stored in their
    # own dtype — 4× less HBM; the search upcasts gathered rows on the fly
    # (cagra_types.hpp supports int8/uint8 datasets the same way)
    store = jnp.asarray(dataset)
    if not jnp.issubdtype(store.dtype, jnp.integer):
        store = X

    compress = params.compress == "on" or (
        params.compress == "auto" and n >= params.compress_threshold)
    out = CagraIndex(store, pruned, norms)
    if compress:
        t0 = _time.perf_counter()
        out = _attach_compression(out, X, params, centroids, res)
        _sync(out.nbr_codes)
        timings["compress"] = _time.perf_counter() - t0
    out._build_timings_s = {k: round(v, 2) for k, v in timings.items()}
    if obs.enabled():
        obs.add("cagra.build.nodes", n)
        for phase, secs in timings.items():
            obs.record_timing(f"cagra.build.{phase}", secs)
    return out


def _attach_compression(index: CagraIndex, X, params: CagraParams,
                        centroids, res) -> CagraIndex:
    """Build the round-5 compressed-traversal payload: a PCA projection to
    ``compress_dim`` (orthonormal basis when compress_dim == dim), per-node
    inlined neighbor codes, and the centroid seeding table (computing
    centers with a quick balanced k-means when the builder didn't produce
    any)."""
    n, dim = X.shape
    p = int(params.compress_dim) or min(64, dim)
    p = min(p, dim)
    key = jax.random.key(params.seed ^ 0xC0DE)
    if p < dim:
        # PCA projection: descriptor data is strongly correlated, so the
        # top-p principal axes keep far more of the distance signal than a
        # random p-subspace (measured +10 recall points at p=dim/3 on
        # siftlike). Sample covariance on ≤256k rows via the in-repo
        # helpers (stats.cov fuses the centering; ops.linalg.eig_dc's
        # sign_flip keeps eigenvector signs — and hence saved index
        # bytes — deterministic across backends).
        from raft_tpu.ops.linalg import eig_dc
        from raft_tpu.stats import cov as stats_cov

        m = min(n, 262_144)
        rows = (jax.random.randint(key, (m,), 0, n)
                if m < n else jnp.arange(n))
        c = jax.jit(stats_cov, static_argnames="sample")(X[rows],
                                                         sample=False)
        vals, vecs = eig_dc(c)  # ascending eigenvalues
        proj = vecs[:, ::-1][:, :p]  # (dim, p) top components
        energy = jnp.sum(vals[-p:]) / jnp.maximum(jnp.sum(vals), 1e-30)
    else:
        # no reduction: any orthonormal basis is exact; skip the eigh
        g = jax.random.normal(key, (dim, p), jnp.float32)
        proj, _ = jnp.linalg.qr(g)
        energy = jnp.float32(1.0)
    # seeding table first: its brute kNN runs with a workspace-sized score
    # tile, and doing it BEFORE the n·deg·p code payload exists keeps the
    # two HBM spikes from stacking (1M×128/deg=64/p=64 peaked out a 16 GB
    # chip otherwise)
    reps = None
    if centroids is None and n > 4 * 1024:
        from raft_tpu.cluster import kmeans_balanced

        c = int(max(16, min(1024, n // 256)))
        frac = float(min(1.0, max(0.05, 100_000 / n)))
        # with-replacement draw: choice(replace=False) compiles an
        # O(n log n) permutation (the round-3 kmeans_balanced finding);
        # duplicate trainset rows are harmless to k-means
        rows = (jax.random.randint(jax.random.key(params.seed ^ 0x5EED5),
                                   (int(frac * n),), 0, n)
                if frac < 1.0 else slice(None))
        centroids = kmeans_balanced.fit(
            X[rows], c, kmeans_balanced.KMeansBalancedParams(), res=res)
    if centroids is not None:
        from raft_tpu.neighbors.brute_force import knn

        _, rep_ids = knn(centroids, X, 1, metric="sqeuclidean", res=res)
        reps = rep_ids[:, 0].astype(jnp.int32)

    xp = X @ proj  # (n, p)
    scale = jnp.maximum(jnp.max(jnp.abs(xp)) / 127.0, 1e-12)
    codes = jnp.clip(jnp.round(xp / scale), -127, 127).astype(jnp.int8)
    del xp
    # inline the neighbors' codes in row blocks (one big gather would hold
    # gather temporaries on top of the 4 GB output at 1M×64×64)
    blk = int(max(65536, res.workspace_bytes
                  // max(index.graph_degree * p * 2, 1)))
    parts = []
    for s in range(0, n, blk):
        gb = index.graph[s:s + blk]
        nc = codes[jnp.maximum(gb, 0)]
        parts.append(jnp.where(gb[..., None] >= 0, nc, jnp.int8(0)))
    nbr_codes = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return CagraIndex(index.dataset, index.graph, index.norms,
                      proj=proj, code_scale=scale, nbr_codes=nbr_codes,
                      centroids=centroids, centroid_reps=reps,
                      proj_energy=energy)


@traced("cagra::build_from_graph")
def build_from_graph(dataset, graph) -> CagraIndex:
    """Wrap a prebuilt kNN graph (the from-serialized / interop path)."""
    X = jnp.asarray(dataset, jnp.float32)
    return CagraIndex(X, jnp.asarray(graph, jnp.int32), jnp.sum(X * X, axis=1))


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


# internal tuning knob for the compressed loop's merge (see merge() in
# _search_impl_compressed); 0 forces the slack+re-select path everywhere
_CAGRA_DEDUP_LIMIT = int(_os.environ.get("RAFT_TPU_CAGRA_DEDUP_LIMIT", "512"))


def _merge_candidates(bids, bd, bvis, cids, cd, itopk: int, packed: bool,
                      dedup_limit: int):
    """Buffer ∪ candidates → new (ids, d, vis): the ONE merge both
    traversal loops share (code-review r5 — the two hand-tuned copies had
    already diverged once). Candidate-side duplicates are masked exactly
    pre-select while the (q, b, b) compare tensor stays VPU-cheap
    (b ≤ dedup_limit); wider candidate sets select itopk + slack, mask
    later duplicate copies among the survivors, and compact with one
    narrow re-select — so duplicate copies never occupy itopk slots
    (ADVICE r4 cagra.py:536, now fixed for BOTH loops). ``packed`` picks
    the mantissa-packed iter select (2 VPU ops/pass) over ``lax.top_k``;
    top_k/packed are both stable, so the first copy — the buffer's,
    carrying its visited flag — is the one kept."""
    inf = jnp.float32(jnp.inf)
    dup_buf = jnp.any(cids[:, :, None] == bids[:, None, :], axis=2)
    bb = cids.shape[1]
    if bb <= dedup_limit:
        eq = cids[:, :, None] == cids[:, None, :]
        tri = jnp.tril(jnp.ones((bb, bb), jnp.bool_), k=-1)
        dup_self = jnp.any(eq & tri[None], axis=2)
        cd = jnp.where(dup_buf | dup_self | (cids < 0), inf, cd)
        slack = 0
    else:
        cd = jnp.where(dup_buf | (cids < 0), inf, cd)
        # capped at bb: the select reads itopk + slack of itopk + bb
        slack = min(bb, max(8, itopk // 4))
    allv = jnp.concatenate([bd, cd], axis=1)
    alli = jnp.concatenate([bids, cids], axis=1)
    allvis = jnp.concatenate(
        [bvis, jnp.zeros(cids.shape, jnp.bool_)], axis=1)

    def select(vals, kk):
        if packed:
            return iter_topk_min_packed(vals, kk)
        nv, sel = lax.top_k(-vals, kk)
        return -nv, sel

    nv, sel = select(allv, itopk + slack)
    ni = jnp.take_along_axis(alli, sel, axis=1)
    nvis = jnp.take_along_axis(allvis, sel, axis=1)
    if slack:
        w2 = itopk + slack
        dup = jnp.any(
            (ni[:, :, None] == ni[:, None, :])
            & (jnp.arange(w2)[None, None, :]
               < jnp.arange(w2)[None, :, None]), axis=2)
        nv = jnp.where(dup, inf, nv)
        nv, sel2 = select(nv, itopk)
        ni = jnp.take_along_axis(ni, sel2, axis=1)
        nvis = jnp.take_along_axis(nvis, sel2, axis=1)
    ni = jnp.where(jnp.isinf(nv), -1, ni)
    return ni, nv, nvis


@functools.partial(
    jax.jit,
    static_argnames=("k", "itopk", "width", "max_iter", "min_iter", "n_rand"),
)
def _search_impl(
    dataset, graph, queries, key, filter_bits, n_bits,
    k, itopk, width, max_iter, min_iter, n_rand,
):
    """Round-4 loop body, rebuilt from on-device microbenchmarks:

    * the round-3 sort-based merge (merge_topk_dedup: one 2-key variadic
      lexsort + argsort + 6 take_along_axis) measured ~12 ms/iteration at
      (q=2000, itopk=64) — 4× the gather it was merging. Narrow-row
      ``lax.top_k`` measured 0.44 ms at width 128, so the merge is now
      concat + top_k + two payload gathers, with dedup done by a
      (q, b, itopk) compare matrix instead of the sort.
    * per-entry norms come from the gathered rows (‖x‖² = Σx²) instead of a
      second (q, b) row gather of a norms table — the row gather is
      op-bound (~12 ns/row regardless of dtype/width), so dropping the
      second gather cut the distance stage ~40%.
    * visited marking is a compare against the picked positions, not a
      scatter.
    """
    n, dim = dataset.shape
    q = queries.shape[0]
    deg = graph.shape[1]
    b = width * deg
    qf = queries.astype(jnp.float32)
    inf = jnp.float32(jnp.inf)
    iota_itopk = jnp.arange(itopk, dtype=jnp.int32)

    def batch_dists(ids):
        """(q, m) ranking scores ‖x‖² − 2⟨q, x⟩ (query norm added at the
        end — it cannot change per-query ranking)."""
        xv = dataset[jnp.maximum(ids, 0)].astype(jnp.float32)  # (q, m, dim)
        ip = jnp.einsum("qmd,qd->qm", xv, qf,
                        preferred_element_type=jnp.float32)
        d = jnp.sum(xv * xv, axis=2) - 2.0 * ip
        return jnp.where(ids >= 0, d, inf)

    def merge(bids, bd, bvis, cids, cd):
        # shared buffer∪candidate merge; exact select (the hashmap +
        # bitonic-merge replacement)
        return _merge_candidates(bids, bd, bvis, cids, cd, itopk,
                                 packed=False, dedup_limit=320)

    # ---- init: random seeds (num_random_samplings analog) -----------------
    n_seed = min(itopk * n_rand, n)
    seed_ids = jax.random.randint(key, (q, n_seed), 0, n, dtype=jnp.int32)
    seed_d = batch_dists(seed_ids)
    buf_ids, buf_d, buf_vis = merge(
        jnp.full((q, itopk), -1, jnp.int32),
        jnp.full((q, itopk), inf, jnp.float32),
        jnp.ones((q, itopk), jnp.bool_),
        seed_ids, seed_d,
    )

    def cond(state):
        ids_b, _, vis, it = state
        frontier_open = jnp.any(~vis & (ids_b >= 0))
        return (it < max_iter) & (frontier_open | (it < min_iter))

    def body(state):
        ids_b, d_b, vis, it = state
        # pickup_next_parents (:51): best `width` unvisited buffer entries
        pkey = jnp.where(vis | (ids_b < 0), inf, d_b)
        _, ppos = lax.top_k(-pkey, width)  # positions of best unvisited
        parent_ids = jnp.take_along_axis(ids_b, ppos, axis=1)  # (q, w)
        parent_ok = jnp.take_along_axis(pkey, ppos, axis=1) < inf
        # mark them visited (compare, not scatter: TPU scatters serialize)
        vis = vis | jnp.any(
            iota_itopk[None, None, :] == ppos[:, :, None], axis=1)
        # expand: gather graph rows → (q, w*deg) candidates
        gr = graph[jnp.maximum(parent_ids, 0)]  # (q, w, deg)
        nbrs = jnp.where(parent_ok[:, :, None] & (gr >= 0), gr, -1)
        nbrs = nbrs.reshape(q, b)
        nd = batch_dists(nbrs)
        ids2, d2, vis2 = merge(ids_b, d_b, vis, nbrs, nd)
        return ids2, d2, vis2, it + 1

    buf_ids, buf_d, _, _ = lax.while_loop(
        cond, body, (buf_ids, buf_d, buf_vis, jnp.int32(0))
    )

    # ---- output: filter + top-k from the buffer; add back ‖q‖² ------------
    # (always re-select: wide-width merges can leave dedup holes mid-buffer)
    if filter_bits is not None:
        allowed = Bitset(filter_bits, n_bits).test(buf_ids)
        buf_d = jnp.where(allowed, buf_d, inf)
    _, sel = lax.top_k(-buf_d, k)
    buf_d = jnp.take_along_axis(buf_d, sel, axis=1)
    buf_ids = jnp.take_along_axis(buf_ids, sel, axis=1)
    qn = jnp.sum(qf * qf, axis=1)
    out_d = buf_d[:, :k]
    out_ids = jnp.where(jnp.isinf(out_d), -1, buf_ids[:, :k])
    out_d = jnp.where(jnp.isinf(out_d), inf,
                      jnp.maximum(out_d + qn[:, None], 0.0))
    return out_d, out_ids


def _seed_compressed(dataset, proj, code_scale, centroids, reps, proj_energy,
                     qf, qp, key, itopk: int, n_rand: int, merge):
    """Seed the compressed-traversal buffer (shared by the unfused loop and
    the fused driver — one implementation so seeds stay bit-identical):
    centroid-guided when the payload carries a seeding table, random rows
    projected on the fly otherwise. Returns the merged (ids, d, vis)."""
    n, dim = dataset.shape
    p = proj.shape[1]
    q = qf.shape[0]
    inf = jnp.float32(jnp.inf)
    if centroids is not None:
        # guided: one (q, c) MXU gemm, zero gathers. Centroid distances
        # live in the FULL space; scale by the projection's captured
        # variance fraction (proj_energy: PCA's kept-eigenvalue share, or
        # p/dim for a legacy random subspace) and shift into the buffer's
        # code-unit convention (‖·‖² − 2⟨qp,·⟩ == (proj dist − ‖qp·s‖²)/s²)
        # so seed scores merge monotonically with code scores.
        c = centroids.shape[0]
        cd_full = (jnp.sum(centroids * centroids, axis=1)[None, :]
                   - 2.0 * qf @ centroids.T)  # + ‖q‖², constant, dropped
        n_seed = min(itopk, c)
        s2 = code_scale * code_scale
        qp_n = jnp.sum(qp * qp, axis=1)
        frac = (proj_energy if proj_energy is not None
                else jnp.float32(p / dim))
        cd_code = (cd_full * frac) / s2 + (
            jnp.sum(qf * qf, axis=1) * frac / s2 - qp_n)[:, None]
        sv, spos = iter_topk_min_packed(cd_code, n_seed)
        seed_ids = reps[spos].astype(jnp.int32)
        seed_d = sv
    else:
        # random seeding (num_random_samplings analog): gather raw rows,
        # project on the fly
        n_seed = min(itopk * n_rand, n)
        seed_ids = jax.random.randint(key, (q, n_seed), 0, n,
                                      dtype=jnp.int32)
        xv = dataset[jnp.maximum(seed_ids, 0)].astype(jnp.float32)
        xp = jnp.einsum("qmd,dp->qmp", xv, proj,
                        preferred_element_type=jnp.float32) / code_scale
        seed_d = jnp.sum(xp * xp, axis=2) - 2.0 * jnp.einsum(
            "qmp,qp->qm", xp, qp, preferred_element_type=jnp.float32)

    return merge(
        jnp.full((q, itopk), -1, jnp.int32),
        jnp.full((q, itopk), inf, jnp.float32),
        jnp.ones((q, itopk), jnp.bool_),
        seed_ids, seed_d,
    )


def _exact_rerank(dataset, qf, buf_ids, filter_bits, n_bits, k: int, rt: int):
    """Exact re-rank of the buffer head against the raw dataset — the
    CAGRA-Q refinement exit both compressed traversals share. The buffer is
    ascending post-merge, so its head IS the best ``rt`` candidates."""
    inf = jnp.float32(jnp.inf)
    r_ids = buf_ids[:, :rt]
    xv = dataset[jnp.maximum(r_ids, 0)].astype(jnp.float32)  # (q, rt, dim)
    ip = jnp.einsum("qmd,qd->qm", xv, qf, preferred_element_type=jnp.float32)
    d_exact = jnp.sum(xv * xv, axis=2) - 2.0 * ip
    d_exact = jnp.where(r_ids >= 0, d_exact, inf)
    if filter_bits is not None:
        allowed = Bitset(filter_bits, n_bits).test(r_ids)
        d_exact = jnp.where(allowed, d_exact, inf)
    out_d, sel = iter_topk_min(d_exact, k)
    out_ids = jnp.take_along_axis(r_ids, sel, axis=1)
    qn = jnp.sum(qf * qf, axis=1)
    out_ids = jnp.where(jnp.isinf(out_d), -1, out_ids)
    out_d = jnp.where(jnp.isinf(out_d), inf,
                      jnp.maximum(out_d + qn[:, None], 0.0))
    return out_d, out_ids


@functools.partial(
    jax.jit,
    static_argnames=("k", "itopk", "width", "max_iter", "min_iter", "n_rand",
                     "refine_topk"),
)
def _search_impl_compressed(
    dataset, graph, nbr_codes, proj, code_scale, centroids, reps,
    proj_energy, queries, key, filter_bits, n_bits,
    k, itopk, width, max_iter, min_iter, n_rand, refine_topk,
):
    """Round-5 traversal over inlined neighbor codes (module docstring).

    Cost shape per iteration at (q, w, deg, p): q·w graph-row gathers +
    q·w code-record gathers (the ONLY per-row-op-bound work — the exact
    loop paid q·w·deg), one (q, w·deg, p) int8→bf16 MXU contraction, a
    compare-matrix dedup, and a mantissa-packed itopk select over
    itopk + w·deg entries. Distances are projected-space ranking scores;
    the exit re-ranks the best ``refine_topk`` buffer entries exactly.
    """
    n, dim = dataset.shape
    q = queries.shape[0]
    deg = graph.shape[1]
    p = proj.shape[1]
    b = width * deg
    qf = queries.astype(jnp.float32)
    qp = (qf @ proj) / code_scale  # query in code units
    inf = jnp.float32(jnp.inf)
    iota_itopk = jnp.arange(itopk, dtype=jnp.int32)

    def code_dists(codes, ids):
        """(q, m) projected ranking scores ‖c‖² − 2⟨qp, c⟩ from int8 codes
        (query-norm term dropped: constant per query)."""
        cf = codes.astype(jnp.bfloat16)
        ip = jnp.einsum("qmp,qp->qm", cf, qp.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        nrm = jnp.einsum("qmp,qmp->qm", cf, cf,
                         preferred_element_type=jnp.float32)
        return jnp.where(ids >= 0, nrm - 2.0 * ip, inf)

    def merge(bids, bd, bvis, cids, cd):
        # shared buffer∪candidate merge; mantissa-packed select.
        # _CAGRA_DEDUP_LIMIT (internal tuning knob): whether candidate
        # dedup pays the (q, b, b) compare tensor pre-select or the
        # slack + re-select path — the crossover is hardware-dependent
        return _merge_candidates(bids, bd, bvis, cids, cd, itopk,
                                 packed=True,
                                 dedup_limit=_CAGRA_DEDUP_LIMIT)

    # ---- seeds (shared with the fused driver) -----------------------------
    buf_ids, buf_d, buf_vis = _seed_compressed(
        dataset, proj, code_scale, centroids, reps, proj_energy,
        qf, qp, key, itopk, n_rand, merge)

    def cond(state):
        ids_b, _, vis, it = state
        frontier_open = jnp.any(~vis & (ids_b >= 0))
        return (it < max_iter) & (frontier_open | (it < min_iter))

    def body(state):
        ids_b, d_b, vis, it = state
        pkey = jnp.where(vis | (ids_b < 0), inf, d_b)
        pv, ppos = iter_topk_min_packed(pkey, width)
        parent_ids = jnp.take_along_axis(ids_b, ppos, axis=1)  # (q, w)
        parent_ok = ~jnp.isinf(pv)
        vis = vis | jnp.any(
            iota_itopk[None, None, :] == ppos[:, :, None], axis=1)
        pid_c = jnp.maximum(parent_ids, 0)
        gr = graph[pid_c]  # (q, w, deg) — q·w row gathers
        codes = nbr_codes[pid_c]  # (q, w, deg, p) — q·w record gathers
        nbrs = jnp.where(parent_ok[:, :, None] & (gr >= 0), gr, -1)
        nbrs = nbrs.reshape(q, b)
        nd = code_dists(codes.reshape(q, b, p), nbrs)
        ids2, d2, vis2 = merge(ids_b, d_b, vis, nbrs, nd)
        return ids2, d2, vis2, it + 1

    buf_ids, buf_d, _, _ = lax.while_loop(
        cond, body, (buf_ids, buf_d, buf_vis, jnp.int32(0))
    )

    # ---- exit: exact re-rank of the buffer head (shared with fused) -------
    return _exact_rerank(dataset, qf, buf_ids, filter_bits, n_bits, k,
                         refine_topk)



# ---------------------------------------------------------------------------
# Round-6 fused traversal: the compressed loop with its five per-hop ops
# (graph gather, code gather, int8 einsum, dedup, merge) collapsed into one
# Pallas kernel (ops/cagra_hop.py). The host drives hops in chunks so every
# dispatch carries a `cagra::hop` span + faultpoint, while termination stays
# on-device (each chunk is a lax.while_loop that no-ops once the frontier
# closes — no host sync in the hop loop).
# ---------------------------------------------------------------------------

# hops per chunk dispatch: large enough that chunk overhead amortizes, small
# enough that spans/deadline checkpoints see the traversal progressing
_CAGRA_HOP_CHUNK = int(_os.environ.get("RAFT_TPU_CAGRA_HOP_CHUNK", "8"))
# queries per kernel grid step (VMEM-bound: the (b, b) dedup compare and the
# (q_block·w, deg, p) code scratch scale with it)
_CAGRA_QBLOCK = int(_os.environ.get("RAFT_TPU_CAGRA_QBLOCK", "32"))
# parents ride the kernel's scalar-prefetch channel (SMEM): cap the query
# tile so the (q_tile, w) int32 table stays small
_FUSED_MAX_TILE = 4096


@functools.partial(jax.jit, static_argnames=("itopk", "n_rand"))
def _fused_init(dataset, proj, code_scale, centroids, reps, proj_energy,
                queries, key, itopk, n_rand):
    """Project queries into code units and seed the buffer — identical ops
    to the unfused loop's preamble (seeds shared via _seed_compressed), with
    the visited flags widened to fp32 for the kernel."""
    qf = queries.astype(jnp.float32)
    qp = (qf @ proj) / code_scale

    def merge(bids, bd, bvis, cids, cd):
        return _merge_candidates(bids, bd, bvis, cids, cd, itopk,
                                 packed=True,
                                 dedup_limit=_CAGRA_DEDUP_LIMIT)

    buf_ids, buf_d, buf_vis = _seed_compressed(
        dataset, proj, code_scale, centroids, reps, proj_energy,
        qf, qp, key, itopk, n_rand, merge)
    return buf_ids, buf_d, buf_vis.astype(jnp.float32), qp


@functools.partial(
    jax.jit,
    static_argnames=("itopk", "width", "min_iter", "q_block", "interpret"),
)
def _fused_hop_chunk(graph, nbr_codes, qp, buf_ids, buf_d, buf_vis, it,
                     budget, itopk, width, min_iter, q_block, interpret):
    """Up to ``budget - it`` fused hops in one dispatch. Parent pickup is
    the same packed top-width as the unfused body; everything after it —
    gathers, distances, dedup, merge — happens inside the fused_hop kernel.
    Once the frontier closes the while_loop exits immediately, so chunks
    dispatched after termination cost one condition evaluation."""
    inf = jnp.float32(jnp.inf)
    iota_itopk = jnp.arange(itopk, dtype=jnp.int32)

    def cond(state):
        ids_b, _, vis, i = state
        frontier_open = jnp.any((vis == 0) & (ids_b >= 0))
        return (i < budget) & (frontier_open | (i < min_iter))

    def body(state):
        ids_b, d_b, vis, i = state
        # pickup_next_parents: best `width` unvisited buffer entries
        pkey = jnp.where((vis > 0) | (ids_b < 0), inf, d_b)
        pv, ppos = iter_topk_min_packed(pkey, width)
        parent_ids = jnp.take_along_axis(ids_b, ppos, axis=1)  # (q, w)
        parents = jnp.where(jnp.isinf(pv), -1, parent_ids)
        picked = jnp.any(
            iota_itopk[None, None, :] == ppos[:, :, None], axis=1)
        vis = jnp.where(picked, jnp.float32(1.0), vis)
        ids2, d2, vis2 = fused_hop(
            ids_b, d_b, vis, parents, qp, graph, nbr_codes,
            q_block=q_block, interpret=interpret)
        return ids2, d2, vis2, i + 1

    return lax.while_loop(cond, body, (buf_ids, buf_d, buf_vis, it))


@functools.partial(jax.jit, static_argnames=("k", "rt"))
def _fused_finish(dataset, queries, buf_ids, filter_bits, n_bits, k, rt):
    qf = queries.astype(jnp.float32)
    return _exact_rerank(dataset, qf, buf_ids, filter_bits, n_bits, k, rt)


def _run_fused_tile(index: "CagraIndex", qs, key, fb, k, itopk, width,
                    max_iter, min_iter, n_rand, rt, q_block, interpret):
    """One query tile through the fused traversal: init → chunked hop
    dispatches (each with a `cagra::hop` span and an armable faultpoint at
    the host dispatch site) → exact exit re-rank. Returns (d, ids, hops)."""
    from raft_tpu.resilience import faultpoint

    buf_ids, buf_d, buf_vis, qp = _fused_init(
        index.dataset, index.proj, index.code_scale, index.centroids,
        index.centroid_reps, index.proj_energy, qs, key, itopk, n_rand)
    it = jnp.int32(0)
    for start in range(0, max_iter, _CAGRA_HOP_CHUNK):
        budget = min(start + _CAGRA_HOP_CHUNK, max_iter)
        faultpoint("cagra.search.hop")
        with obs.record_span("cagra::hop",
                             attrs={"budget": budget, "width": width}):
            buf_ids, buf_d, buf_vis, it = _fused_hop_chunk(
                index.graph, index.nbr_codes, qp, buf_ids, buf_d, buf_vis,
                it, jnp.int32(budget), itopk=itopk, width=width,
                min_iter=min_iter, q_block=q_block, interpret=interpret)
    out_d, out_ids = _fused_finish(
        index.dataset, qs, buf_ids, fb, index.size, int(k), rt)
    return out_d, out_ids, it


def _resolve_traversal(params: CagraSearchParams, has_payload: bool,
                       k: int, itopk: int, size: int = 0,
                       allow_fused: bool = True, b: int = 0):
    """Resolve the traversal mode + exact-re-rank depth once for every
    search wrapper (single-device and distributed share this — the two
    copies had already drifted, code-review r5). Returns
    ``(mode, refine_topk)`` with refine_topk = 0 for the exact loop.

    "auto" picks the unfused compressed loop when the codes are inlined:
    Mosaic (jax 0.9) refuses the fused hop kernel on TPU v5e — its
    batched int8 contraction and its (q_block·w, deg) → (q_block, w·deg)
    candidate relayout (tests/test_chip_compile.py) — so the fused loop
    runs only when asked for (``traversal="fused"``, interpret mode in
    the tests). Fused falls
    back to the unfused compressed loop when the caller can't host the
    kernel (``allow_fused=False`` — distributed shard bodies), the index
    exceeds the kernel's exact-id bound (MAX_FUSED_ROWS), or the candidate
    set ``b`` (width·degree) is past _CAGRA_DEDUP_LIMIT — there the
    unfused merge switches to its slack+re-select dedup, and fused
    results could no longer be bit-identical to it (which is both the
    parity contract and what makes the mid-batch fallback seamless).

    Parity scope: with a centroid seeding table (every index past the
    small-n threshold) fused per-query results are bit-identical to the
    unfused loop regardless of batch shape. Small centroid-less indexes
    seed by ``jax.random.randint`` at the (possibly q-block-padded) tile
    shape, so there parity additionally needs q to be a tile/block
    multiple — a different draw yields different (equally valid) seeds,
    not wrong results."""
    mode = params.traversal
    fused_capable = (has_payload and allow_fused
                     and 0 < size < MAX_FUSED_ROWS
                     and 0 < b <= _CAGRA_DEDUP_LIMIT)
    if mode == "auto":
        if has_payload:
            mode = "compressed"
        else:
            mode = "exact"
    elif mode in ("compressed", "fused") and not has_payload:
        raise ValueError(
            f"traversal={mode!r} needs the compression payload "
            "(build with CagraParams.compress)")
    if mode == "fused" and not fused_capable:
        mode = "compressed"
    rt = 0
    if mode in ("compressed", "fused"):
        rt = int(params.refine_topk) or itopk
        if not k <= rt <= itopk:
            raise ValueError(
                f"refine_topk={rt} must be in [k={k}, itopk={itopk}]")
    return mode, rt


@traced("cagra::search")
def search(
    index: CagraIndex,
    queries,
    k: int,
    params: CagraSearchParams = CagraSearchParams(),
    filter: Optional[Bitset] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Best-first graph search (cagra.cuh:299); returns (distances, indices).

    Graph traversal visits filtered-out nodes (they route) but never returns
    them (the reference applies its sample filter the same way). Internal
    buffer = itopk_size candidates per query; k must not exceed it.
    """
    res = res or current_resources()
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim != 2 or queries.shape[1] != index.dim:
        raise ValueError(f"queries must be (q, {index.dim})")
    itopk = int(min(params.itopk_size, index.size))
    if not 0 < k <= itopk:
        raise ValueError(f"k={k} must be in (0, itopk_size={itopk}]")
    if filter is not None and filter.n_bits != index.size:
        raise ValueError(
            f"filter covers {filter.n_bits} bits but index has {index.size} rows"
        )
    width = int(params.search_width)
    max_iter = int(params.max_iterations) or max(16, itopk // width)
    min_iter = int(min(params.min_iterations, max_iter))
    key = jax.random.key(params.seed)
    b = width * index.graph_degree
    mode, rt = _resolve_traversal(params, index.nbr_codes is not None,
                                  int(k), itopk, size=index.size, b=b)

    # query tiling: one traversal's live set is ~per_q bytes/query (the
    # (b, b) dedup compares + gathered codes/vectors + merge passes);
    # un-tiled q=10k runs RESOURCE_EXHAUST a 16 GB chip. Tiles dispatch
    # back-to-back (no host sync between them), so the loop costs no
    # dispatch-amortization at large q.
    p = index.proj.shape[1] if index.proj is not None else index.dim
    if mode == "fused":
        # the kernel block-streams the traversal state, so only the exit
        # re-rank gather and the per-query buffer/qp rows count against the
        # workspace — tiles grow ~10× vs the unfused loop and the q-block
        # grid keeps the MXU fed across the whole batch
        per_q = 6 * rt * index.dim + 24 * itopk + 4 * p + 8 * width
    elif mode == "compressed":
        per_q = b * b + 4 * b * p + 8 * (itopk + b) + 4 * itopk * index.dim
    else:
        per_q = b * b + 6 * b * index.dim + 8 * (itopk + b)
    nq = queries.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    q_tile = int(max(256, min(nq, res.workspace_bytes // max(per_q, 1))))
    if mode == "fused":
        # parents ride the kernel's SMEM scalar-prefetch channel: bound the
        # tile, then align it to the kernel's query-block grid (pad rows
        # traverse as zero-queries and are sliced off below)
        q_tile = min(q_tile, _FUSED_MAX_TILE)
    n_tiles = ceil_div(nq, q_tile)
    q_tile = ceil_div(nq, n_tiles)  # equalize; pad the tail tile below so
    # every dispatch shares ONE compiled shape
    q_block = 0
    if mode == "fused":
        q_block = int(max(8, min(_CAGRA_QBLOCK, q_tile)))
        q_tile = ceil_div(q_tile, q_block) * q_block

    if obs.enabled():
        obs.add("cagra.search.queries", nq)
        obs.add("cagra.search.tiles", n_tiles)
        obs.add("cagra.search.iterations", nq * max_iter)
        obs.add(f"cagra.search.traversal.{mode}", 1)
        if mode == "fused":
            # roofline note (round 15): the fused hop's static FLOP/byte
            # model + the q-block occupancy stats — the "does the kernel
            # underfill the MXU" number the ROADMAP has been guessing at
            from raft_tpu.obs import roofline as obs_roofline
            from raft_tpu.ops.cagra_hop import occupancy_stats

            obs_roofline.note_dispatch(
                "cagra.fused_hop",
                {"q": q_tile, "width": width,
                 "degree": index.graph_degree, "proj_dim": p,
                 "itopk": itopk, "hops": _CAGRA_HOP_CHUNK},
                occupancy=occupancy_stats(
                    min(nq, q_tile), q_block, width, index.graph_degree,
                    p, itopk))

    from raft_tpu import resilience
    from raft_tpu.core.interruptible import check_interrupt
    from raft_tpu.resilience import faultpoint

    faultpoint("cagra.search")
    fb = filter.bits if filter is not None else None
    n_rand = int(max(1, params.num_random_samplings))
    interpret = jax.default_backend() != "tpu"
    outs = []
    for ti, s in enumerate(range(0, nq, q_tile)):
        check_interrupt()  # tiles dispatch back-to-back; this is the only
        # host checkpoint a multi-tile search passes through
        qs = queries[s:s + q_tile]
        if qs.shape[0] < q_tile:
            qs = jnp.pad(qs, ((0, q_tile - qs.shape[0]), (0, 0)))
        tkey = jax.random.fold_in(key, ti) if ti else key
        if mode == "fused":
            try:
                od, oi, hops = _run_fused_tile(
                    index, qs, tkey, fb, int(k), itopk, width, max_iter,
                    min_iter, n_rand, rt, q_block, interpret)
                # int(hops) blocks on the tile's last chunk, so the count
                # is opt-in on top of telemetry: back-to-back QPS loops
                # stay pipelined, and the bench samples hops only inside
                # its per-batch latency pass (which forces every call
                # anyway)
                if obs.enabled() and _os.environ.get(
                        "RAFT_TPU_CAGRA_COUNT_HOPS"):
                    obs.add("cagra.search.hops", int(hops))
                outs.append((od, oi))
                continue
            except Exception as e:
                # classified fallback to the unfused compressed loop (the
                # round-7 recovery contract: a failed kernel dispatch —
                # injected or real, e.g. a Mosaic lowering gap on an
                # unusual shape — degrades to the slower traversal instead
                # of sinking the search)
                kind = resilience.classify(e)
                if kind == resilience.DEADLINE:
                    # expired scopes / cooperative cancels are NEVER
                    # retried (resilience contract): re-running the tile
                    # on the slower loop only digs the hole deeper
                    raise
                resilience.record_event(
                    "fused_fallback", site="cagra.search.hop", kind=kind,
                    error=repr(e)[:200])
                if obs.enabled():
                    obs.add(f"cagra.search.fused_fallback.{kind}")
                mode = "compressed"
        if mode == "compressed":
            outs.append(_search_impl_compressed(
                index.dataset, index.graph, index.nbr_codes, index.proj,
                index.code_scale, index.centroids, index.centroid_reps,
                index.proj_energy, qs, tkey, fb, index.size,
                int(k), itopk, width, max_iter, min_iter, n_rand, rt,
            ))
        else:
            outs.append(_search_impl(
                index.dataset, index.graph, qs, tkey, fb, index.size,
                int(k), itopk, width, max_iter, min_iter, n_rand,
            ))
    if len(outs) == 1:
        # the fused q-block alignment can pad even a single tile
        return outs[0][0][:nq], outs[0][1][:nq]
    return (jnp.concatenate([o[0] for o in outs], axis=0)[:nq],
            jnp.concatenate([o[1] for o in outs], axis=0)[:nq])
