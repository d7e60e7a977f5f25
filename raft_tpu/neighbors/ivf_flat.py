"""IVF-Flat: inverted-file index with uncompressed (flat) vectors.

Reference surface: raft::neighbors::ivf_flat — build (ivf_flat-inl.cuh:65 →
detail/ivf_flat_build.cuh, kmeans_balanced trainer at :384), search
(ivf_flat-inl.cuh:516 → detail/ivf_flat_search-inl.cuh:38: coarse distance +
select_k of n_probes lists :130 → interleaved list scan :149 → final select_k
:194), extend, serialize (ivf_flat_serialize.cuh); params ivf_flat_types.hpp
(n_lists, kmeans_n_iters, kmeans_trainset_fraction, adaptive_centers).

TPU design. The reference stores each list as variable-length interleaved
groups of 32 vectors (kIndexGroupSize, ivf_flat_types.hpp:47) and launches one
CTA per (query, probe). Variable-length anything is hostile to XLA's static
shapes, so lists here are **padded dense blocks**: one (n_lists, max_list_size,
dim) array with per-entry validity given by ``list_ids >= 0``. Balanced
k-means (cluster/kmeans_balanced.py) bounds the skew, so the padding overhead
is a small constant factor; max_list_size is rounded up to a multiple of 32
(the kIndexGroupSize analog — keeps the scan dimension MXU/VPU aligned).

Search is two select_k stages around one gather+batched-matmul scan:
coarse distances ride the MXU as a single (q, n_lists) gemm; the list scan
gathers (q_tile, n_probes, max_list_size, dim) candidate blocks from HBM and
reduces them with an einsum — HBM-bandwidth-bound, tiled over queries by the
Resources workspace budget so the gather never blows past the budget.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import obs
from raft_tpu.obs import compile as obs_compile
from raft_tpu.obs import roofline as obs_roofline
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import _entry, _filtering
from raft_tpu.neighbors import _packing
from raft_tpu.neighbors._packing import pack_lists, unpack_lists
from raft_tpu.core.trace import traced
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.serialize import load_arrays, save_arrays
from raft_tpu.ops import distance as dist_mod
from raft_tpu.ops.select_k import probe_algo, select_k
from raft_tpu.utils.tiling import map_row_tiles

SUPPORTED_METRICS = ("sqeuclidean", "euclidean", "inner_product", "cosine")


@dataclass(frozen=True)
class IvfFlatParams:
    """Build params (ivf_flat_types.hpp index_params analog)."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    # per-list occupancy cap: -1 = auto (4× mean, group-aligned), 0 = off.
    # Overflow rows spill to their second-nearest list (_packing.spill_to_cap)
    list_size_cap: int = -1
    # list padding granule: 0 = auto (512 == strip_scan.MC when the mean
    # list is large enough to amortize it — required for the ragged TPU
    # backend — else 64, kIndexGroupSize-style, to keep small indexes small)
    group_size: int = 0
    seed: int = 0

    def __post_init__(self):
        m = dist_mod.canonical_metric(self.metric)
        if m not in SUPPORTED_METRICS:
            raise ValueError(f"ivf_flat supports {SUPPORTED_METRICS}, got {self.metric!r}")
        object.__setattr__(self, "metric", m)


@jax.tree_util.register_pytree_node_class
@dataclass
class IvfFlatIndex:
    """Cluster centers + padded per-list vector blocks.

    ``list_ids[l, j] == -1`` marks padding; valid entries hold the source row
    id. ``list_norms`` caches per-entry squared L2 norms for the L2 scan.
    For cosine, vectors and centers are stored L2-normalized and the scan runs
    as inner product (the reference normalizes the same way for
    CosineExpanded).
    """

    centers: jax.Array  # (n_lists, dim) fp32
    list_data: jax.Array  # (n_lists, max_list_size, dim)
    list_ids: jax.Array  # (n_lists, max_list_size) int32, -1 = padding
    list_norms: Optional[jax.Array]  # (n_lists, max_list_size) fp32, L2 only
    metric: str
    # list padding granule used at build; extend() reuses it instead of
    # inferring from max_list_size (ADVICE.md round-2). 0 = unknown (legacy).
    group_size: int = 0

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.list_data.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_ids >= 0))

    def list_sizes(self) -> jax.Array:
        return jnp.sum(self.list_ids >= 0, axis=1).astype(jnp.int32)

    def tree_flatten(self):
        return (self.centers, self.list_data, self.list_ids, self.list_norms), (self.metric, self.group_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # -- persistence (ivf_flat_serialize.cuh analog) -----------------------
    def save(self, path) -> None:
        arrays = {
            "centers": self.centers,
            "list_data": self.list_data,
            "list_ids": self.list_ids,
        }
        if self.list_norms is not None:
            arrays["list_norms"] = self.list_norms
        save_arrays(path, {"kind": "ivf_flat", "metric": self.metric,
                           "group_size": self.group_size}, arrays)

    @classmethod
    def load(cls, path) -> "IvfFlatIndex":
        meta, arrays = load_arrays(path)
        if meta.get("kind") != "ivf_flat":
            raise ValueError(f"not an ivf_flat index: {meta.get('kind')}")
        return cls(
            jnp.asarray(arrays["centers"]),
            jnp.asarray(arrays["list_data"]),
            jnp.asarray(arrays["list_ids"]),
            jnp.asarray(arrays["list_norms"]) if "list_norms" in arrays else None,
            meta["metric"],
            int(meta.get("group_size", 0)),
        )


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------


def _pack_lists(dataset, row_ids, labels, n_lists: int, group: int = 0):
    """Padded per-list blocks (the ivf_list fill, detail/ivf_flat_build.cuh
    build_index; group rounding per kIndexGroupSize / strip_scan.MC)."""
    if group <= 0:
        group = _packing.auto_group_size(dataset.shape[0], n_lists)
    return pack_lists(dataset, row_ids, labels, n_lists, group,
                      pow2_chunks=group == 512)


@traced("ivf_flat::build")
def build(
    dataset,
    params: IvfFlatParams = IvfFlatParams(),
    res: Optional[Resources] = None,
) -> IvfFlatIndex:
    """Train the coarse quantizer and fill the lists (ivf_flat-inl.cuh:65).

    Trains balanced k-means on a ``kmeans_trainset_fraction`` subsample
    (ivf_flat_types.hpp:55), then assigns every row to its nearest center.
    """
    res = res or current_resources()
    dataset = jnp.asarray(dataset)
    n, dim = dataset.shape
    if params.n_lists > n:
        raise ValueError(f"n_lists={params.n_lists} > n_rows={n}")

    work = dataset.astype(jnp.float32)
    if params.metric == "cosine":
        work = work / jnp.maximum(jnp.linalg.norm(work, axis=1, keepdims=True), 1e-30)

    km_metric = "inner_product" if params.metric in ("cosine", "inner_product") else "sqeuclidean"
    km = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=km_metric, seed=params.seed
    )

    n_train = max(params.n_lists, int(n * params.kmeans_trainset_fraction))
    # phase span (round-8): parented under the @traced entry span, so trace
    # exports break a build into train vs pack time
    with obs.record_span("ivf_flat::coarse_train"):
        if n_train < n:
            key = jax.random.key(params.seed)
            # with-replacement sampling: the ~n_train²/2n duplicate rate is
            # noise for k-means, and it avoids the O(n log n) permutation
            # program that choice(replace=False) compiles (round-3: ~25 s of
            # XLA compile)
            train_rows = jax.random.randint(key, (n_train,), 0, n)
            centers = kmeans_balanced.fit(work[train_rows], params.n_lists, km, res=res)
            labels = kmeans_balanced.predict(work, centers, km, res=res)
        else:
            centers, labels = kmeans_balanced.fit_predict(work, params.n_lists, km, res=res)

    if obs.enabled():
        obs.add("ivf_flat.build.rows", n)
        obs.add("ivf_flat.build.lists", params.n_lists)

    group = params.group_size or _packing.auto_group_size(n, params.n_lists)
    cap = params.list_size_cap
    if cap < 0:
        cap = _packing.auto_list_cap(n, params.n_lists, group)
    if cap:
        labels = _packing.spill_to_cap(work, centers, labels, km_metric, cap)

    # integer datasets (uint8/int8, the big-ann on-disk formats) are stored
    # in their own dtype — 4× less HBM than fp32; every scan upcasts to the
    # bf16 compute type on the fly (exact for |v| <= 256)
    store = (dataset if (jnp.issubdtype(dataset.dtype, jnp.integer)
                         and params.metric != "cosine") else work)
    with obs.record_span("ivf_flat::pack"):
        row_ids = jnp.arange(n, dtype=jnp.int32)
        list_data, list_ids = _pack_lists(store, row_ids, labels, params.n_lists, group)
        list_norms = None
        if params.metric in ("sqeuclidean", "euclidean"):
            list_norms = dist_mod.sqnorm(list_data, axis=2)
    return IvfFlatIndex(centers, list_data, list_ids, list_norms, params.metric, group)


@traced("ivf_flat::extend")
def extend(index: IvfFlatIndex, new_vectors, new_ids=None, res: Optional[Resources] = None) -> IvfFlatIndex:
    """Add vectors to an existing index (ivf_flat extend,
    detail/ivf_flat_build.cuh extend). Assigns to the fixed centers and
    repacks the lists (padded blocks are immutable, so extension is a repack
    rather than the reference's in-place list append)."""
    res = res or current_resources()
    new_vectors = jnp.asarray(new_vectors).astype(jnp.float32)
    if new_vectors.shape[1] != index.dim:
        raise ValueError(f"dim mismatch: {new_vectors.shape[1]} != {index.dim}")
    if index.metric == "cosine":
        new_vectors = new_vectors / jnp.maximum(
            jnp.linalg.norm(new_vectors, axis=1, keepdims=True), 1e-30
        )

    old_vecs, old_ids, old_labels = unpack_lists(index.list_data, index.list_ids)

    if new_ids is None:
        start = int(jnp.max(old_ids) + 1) if old_ids.size else 0
        new_ids = jnp.arange(start, start + new_vectors.shape[0], dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    km_metric = (
        "inner_product" if index.metric in ("cosine", "inner_product") else "sqeuclidean"
    )
    new_labels = kmeans_balanced.predict(
        new_vectors, index.centers, kmeans_balanced.KMeansBalancedParams(metric=km_metric), res=res
    )
    # persisted granule; legacy indexes (group_size 0) fall back to inference
    group = index.group_size or (512 if index.max_list_size % 512 == 0 else 64)
    total = int(old_ids.shape[0]) + int(new_vectors.shape[0])
    cap = _packing.auto_list_cap(total, index.n_lists, group)
    new_labels = _packing.spill_to_cap(
        new_vectors, index.centers, new_labels, km_metric, cap,
        base_counts=index.list_sizes(),
    )

    if (jnp.issubdtype(index.list_data.dtype, jnp.integer)
            and new_vectors.dtype != index.list_data.dtype):
        # keep the integer-storage invariant (4× HBM) instead of silently
        # promoting the whole index to fp32; integer datasets extend with
        # integer rows, so the round/clip is exact in the expected case —
        # and warn when it is NOT (ADVICE r3: fractional / out-of-range
        # vectors used to lose precision with no signal)
        info = jnp.iinfo(index.list_data.dtype)
        new_store = jnp.clip(jnp.round(new_vectors), info.min, info.max) \
            .astype(index.list_data.dtype)
        # one scalar fetch — extend() is a whole-index repack with host
        # syncs already, so the round-trip is noise here (review r4 noted)
        err = float(jnp.max(jnp.abs(new_store.astype(jnp.float32)
                                    - new_vectors)))
        if err > 0.5:
            from raft_tpu.core.logger import get_logger

            get_logger().warning(
                f"ivf_flat.extend: quantizing float vectors into "
                f"{index.list_data.dtype} storage loses up to {err:.3g} "
                "per component (out-of-range or fractional inputs); "
                "rebuild with fp32 storage if that matters")
    else:
        new_store = new_vectors.astype(index.list_data.dtype) \
            if new_vectors.dtype != index.list_data.dtype else new_vectors
    all_vecs = jnp.concatenate([old_vecs, new_store])
    all_ids = jnp.concatenate([old_ids, new_ids])
    all_labels = jnp.concatenate([old_labels, new_labels])
    list_data, list_ids = _pack_lists(all_vecs, all_ids, all_labels, index.n_lists, group)
    list_norms = None
    if index.metric in ("sqeuclidean", "euclidean"):
        list_norms = dist_mod.sqnorm(list_data, axis=2)
    return IvfFlatIndex(index.centers, list_data, list_ids, list_norms, index.metric, group)


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


def _lens_np(index):
    """Host-cached per-list entry counts: planning needs them every search
    call, and refetching would cost a device sync per call."""
    cached = getattr(index, "_lens_np_cache", None)
    if cached is None or cached.shape[0] != index.n_lists:
        import numpy as np

        cached = np.asarray(index.list_sizes())
        try:
            index._lens_np_cache = cached
        except AttributeError:  # frozen/immutable containers: just recompute
            pass
    return cached


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "metric", "select_algo", "compute_dtype"),
)
def _coarse_probes(queries, centers, n_probes, metric, select_algo, compute_dtype):
    """Stage 1 alone: each query's top-n_probes list ids (q, p) int32
    (detail/ivf_flat_search-inl.cuh:130)."""
    with jax.named_scope("coarse_probe"):
        if metric in ("sqeuclidean", "euclidean"):
            coarse = dist_mod._expanded_distance(
                queries, centers, "sqeuclidean", compute_dtype, "highest"
            )
        else:
            coarse = -dist_mod.matmul_t(queries, centers, compute_dtype,
                                        "highest")
        _, probes = select_k(coarse, n_probes, select_min=True,
                             algo=select_algo)
    return probes


@functools.partial(jax.jit, static_argnames=("mode",))
def _ragged_bias(list_ids, list_norms, filter, mode: str):
    """Per-entry additive bias for the ragged scan: ‖x‖² for L2, 0 for
    ip/cosine; +inf at padding and filtered-out entries (the shared
    :func:`_filtering.apply_filter_bias` rule — one copy across the
    families)."""
    base = list_norms if mode == "l2" else jnp.zeros_like(list_ids, jnp.float32)
    bias = jnp.where(list_ids >= 0, base, jnp.inf).astype(jnp.float32)
    return _filtering.apply_filter_bias(bias, list_ids, filter)


@functools.partial(jax.jit, static_argnames=("metric",))
def _finalize_ragged(vals, ids, queries, metric):
    """One fused dispatch for the score finalization (each eager op here
    used to cost a runtime dispatch of its own)."""
    with jax.named_scope("finalize"):
        if metric in ("sqeuclidean", "euclidean"):
            vals = jnp.maximum(vals + dist_mod.sqnorm(queries)[:, None], 0.0)
            if metric == "euclidean":
                vals = jnp.sqrt(vals)
            return jnp.where(ids >= 0, vals, jnp.inf), ids
        if metric == "cosine":
            return jnp.where(ids >= 0, 1.0 + vals, jnp.inf), ids
        # inner_product: flip back to "larger is better" values
        return jnp.where(ids >= 0, -vals, -jnp.inf), ids


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "select_algo",
                     "compute_dtype", "classes", "class_counts",
                     "interpret"),
)
def _ragged_fused(queries, centers, list_data, bias, list_ids, cls_ord,
                  k, n_probes, metric, select_algo, compute_dtype,
                  classes, class_counts, interpret):
    """The ENTIRE ragged search of one query tile — coarse gemm, device
    strip planning, strip kernel, merge, finalize — as one jit: one
    runtime dispatch per tile, zero host syncs (round-4; the per-tile grid-count fetch used to serialize every
    call on a device round trip, which is why an index probing 3% of the
    data lost to brute force at 1M rows)."""
    from raft_tpu.ops.strip_scan import strip_search_traced

    # ledger registration for the TPU-default backend too (trace time
    # only): a retrace on the platform of record must not be invisible
    obs_compile.trace_event(
        "ivf_flat.search_ragged", queries=queries, centers=centers,
        list_data=list_data, bias=bias, list_ids=list_ids, cls_ord=cls_ord,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "classes": classes, "class_counts": class_counts,
                "interpret": interpret})

    sa = probe_algo(select_algo, interpret, centers.shape[0])
    probes = _coarse_probes(queries, centers, n_probes, metric, sa,
                            compute_dtype)
    l2 = metric in ("sqeuclidean", "euclidean")
    vals, ids = strip_search_traced(
        queries, probes, list_data, bias, list_ids, cls_ord,
        classes, class_counts, int(k), int(k), -2.0 if l2 else -1.0,
        interpret,
    )
    return _finalize_ragged(vals, ids, queries, metric)


def _ragged_plan_static(index, n_probes, k, res, dim):
    """Host-cached static planning facts for the fused path: length classes,
    per-class list counts, the device class-ordinal array, and the query
    tile size. All derive from build-time state (list lengths), so they are
    cached on the index instance."""
    import numpy as np

    from raft_tpu.ops import strip_scan as ss

    cached = getattr(index, "_ragged_static_cache", None)
    if cached is None:
        lens_np = _lens_np(index)
        classes, cls_ord_np = ss.class_info(lens_np, dim=dim)
        classes = tuple(classes)  # hashable: jit static arg
        cached = (classes, ss.class_counts_of(cls_ord_np, len(classes)),
                  jnp.asarray(cls_ord_np))
        try:
            index._ragged_static_cache = cached
        except AttributeError:
            pass
    classes, class_counts, cls_ord = cached
    q_tile = ss.fit_q_tile(1 << 30, n_probes, index.n_lists, len(classes),
                           int(k), res.workspace_bytes, dim=dim,
                           class_counts=class_counts)
    return classes, class_counts, cls_ord, q_tile


def _search_ragged(index, queries, k, n_probes, filter, select_algo, res):
    """Strip-scan path (ops/strip_scan.py): work ∝ actual probed entries —
    no per-list cap, no padded-length scan, per-pair top-k fused in-kernel,
    the whole search one fused dispatch."""
    l2 = index.metric in ("sqeuclidean", "euclidean")
    # the unfiltered bias depends only on build-time state: cache it on the
    # index (one dispatch per search otherwise)
    if filter is None:
        bias = getattr(index, "_bias_cache", None)
        if bias is None:
            bias = _ragged_bias(index.list_ids, index.list_norms, None,
                                "l2" if l2 else "ip")
            try:
                # lazy caches are instance attrs OUTSIDE tree_flatten: they
                # drop on any tree_map/jit round-trip (rebuilt) and assume
                # the index is immutable-after-build — mutate list_data /
                # list_ids only through extend(), which returns a NEW index
                # (ADVICE r3)
                index._bias_cache = bias
            except AttributeError:
                pass
    else:
        bias = _ragged_bias(index.list_ids, index.list_norms, filter,
                            "l2" if l2 else "ip")
    classes, class_counts, cls_ord, q_tile = _ragged_plan_static(
        index, n_probes, k, res, index.dim)
    from raft_tpu.ops.strip_scan import note_topk_routes, per_tile

    if obs.enabled():
        interpret = jax.default_backend() != "tpu"
        note_topk_routes(classes, int(k), False, queries.shape[0], q_tile,
                         index.n_lists, n_probes, int(k),
                         probe_algo(select_algo, interpret, index.n_lists),
                         interpret)
    return per_tile(lambda lo, hi: _ragged_fused(
        queries[lo:hi], index.centers, index.list_data, bias,
        index.list_ids, cls_ord, int(k), n_probes, index.metric,
        select_algo, res.compute_dtype, classes, class_counts,
        jax.default_backend() != "tpu",
    ), queries.shape[0], q_tile, "ivf_flat::launch",
        "ivf_flat.search.launches")


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "q_tile", "select_algo", "compute_dtype"),
)
def _search_impl(
    queries, centers, list_data, list_ids, list_norms, filter,
    k, n_probes, metric, q_tile, select_algo, compute_dtype,
):
    # compile-ledger registration: runs at trace time only, so every
    # (re)trace of this program lands attributed (obs/compile.py)
    obs_compile.trace_event(
        "ivf_flat.search", queries=queries, centers=centers,
        list_data=list_data, list_ids=list_ids, list_norms=list_norms,
        filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "q_tile": q_tile, "select_algo": select_algo,
                "compute_dtype": compute_dtype})
    q, dim = queries.shape
    n_lists, max_size, _ = list_data.shape
    select_min = metric != "inner_product"
    bad = jnp.float32(jnp.inf if select_min else -jnp.inf)

    # ---- stage 1: coarse quantizer (one gemm over all centers) ------------
    if metric in ("sqeuclidean", "euclidean"):
        # explicit full precision for probe ranking (ADVICE.md: backend-
        # default bf16 coarse distances can mis-rank probe lists)
        coarse = dist_mod._expanded_distance(
            queries, centers, "sqeuclidean", compute_dtype, "highest"
        )
        qn = dist_mod.sqnorm(queries)
    else:  # cosine (pre-normalized) and inner_product probe by max ip
        coarse = -dist_mod.matmul_t(queries, centers, compute_dtype, "highest")
        qn = None
    _, probes = select_k(coarse, n_probes, select_min=True, algo=select_algo)  # (q, p)

    # ---- stage 2: tiled gather + scan + final select_k --------------------
    def scan_tile(args):
        q_blk, qn_blk, probe_blk = args
        cand = list_data[probe_blk]  # (qt, p, m, d) gather
        ids = list_ids[probe_blk]  # (qt, p, m)
        ip = jnp.einsum(
            "qd,qpmd->qpm", q_blk, cand, preferred_element_type=jnp.float32
        )
        if metric in ("sqeuclidean", "euclidean"):
            norms = list_norms[probe_blk]
            d = jnp.maximum(qn_blk[:, None, None] + norms - 2.0 * ip, 0.0)
            if metric == "euclidean":
                d = jnp.sqrt(d)
        elif metric == "cosine":
            d = 1.0 - ip  # inputs are pre-normalized
        else:
            d = ip  # inner_product: ranked by max
        flat_ids = ids.reshape(ids.shape[0], -1)
        d = d.reshape(flat_ids.shape)
        valid = flat_ids >= 0
        if filter is not None:
            valid = valid & filter.test(flat_ids)
        d = jnp.where(valid, d, bad)
        vals, sel = select_k(d, k, select_min=select_min, algo=select_algo)
        out_ids = jnp.where(vals == bad, -1, jnp.take_along_axis(flat_ids, sel, axis=1))
        return vals, out_ids

    if qn is None:
        qn = jnp.zeros((q,), jnp.float32)  # unused, keeps the scan signature static
    return map_row_tiles(scan_tile, (queries, qn, probes), q_tile)


@traced("ivf_flat::search")
def search(
    index: IvfFlatIndex,
    queries,
    k: int,
    n_probes: int = 20,
    filter: Optional[Bitset] = None,
    select_algo: str = "exact",
    backend: str = "auto",
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Probe ``n_probes`` lists per query and return the top-k
    (ivf_flat-inl.cuh:516 / detail/ivf_flat_search-inl.cuh:38).

    Returns ``(distances (q,k), indices (q,k))``; indices are source row ids,
    ``-1`` where fewer than k valid candidates were found. ``filter`` excludes
    rows by id (bitset_filter analog, sample_filter.cuh:31).

    ``backend``: "ragged" (chunk-table Pallas scan, work ∝ probed entries —
    the TPU default), "gather" (jnp gather+einsum scan — the exact-fp32
    oracle path and CPU default), or "auto".
    """
    res = res or current_resources()
    from raft_tpu.ops.strip_scan import occupancy_stats, strip_eligible

    aligned = strip_eligible(index.max_list_size) and k <= 512
    if backend == "auto":
        backend = "ragged" if jax.default_backend() == "tpu" and aligned else "gather"
    if backend not in ("ragged", "gather"):
        raise ValueError(f"unknown backend {backend!r}")
    queries, n_probes, filter, scan_attrs = _entry.prepare(
        "ivf_flat.search", queries, k, n_probes, filter, backend,
        dim=index.dim, n_lists=index.n_lists, metric=index.metric,
        rows=index.max_list_size, count_rows=True,
        k_error="k={k} out of range for n_probes={n_probes} x "
                "max_list_size={rows}")
    if obs.enabled():
        # roofline note (round 15): static FLOP/byte model of this
        # dispatch, plus the strip planner's occupancy on the ragged path
        occ = None if backend != "ragged" else _entry.list_occupancy(
            index, int(queries.shape[0]), n_probes, k, res,
            functools.partial(occupancy_stats, dim=index.dim))
        obs_roofline.note_dispatch(
            "ivf_flat.search",
            {"q": int(queries.shape[0]), "dim": index.dim,
             "n_lists": index.n_lists,
             "max_list_size": index.max_list_size,
             "n_probes": int(n_probes), "k": int(k),
             "dtype": str(index.list_data.dtype)},
            occupancy=occ)
    from raft_tpu.resilience import faultpoint

    faultpoint("ivf_flat.search.scan")
    # one scan-phase span either way (attrs built under the gate above so
    # the telemetry-off path stays a single branch)
    scan_span = obs.record_span("ivf_flat::scan", attrs=scan_attrs)
    if backend == "ragged":
        if not aligned:
            raise ValueError(
                f"ragged backend needs max_list_size = a power-of-two "
                f"multiple of 512, got {index.max_list_size}; rebuild with "
                "group_size=512 (or use backend='gather')"
            )
        with scan_span:
            return _search_ragged(index, queries, int(k), n_probes, filter,
                                  select_algo, res)

    # query-tile size: the (qt, p, m, d) gather is the big intermediate
    per_query = max(1, n_probes * index.max_list_size * (index.dim + 2) * 4)
    q_tile = int(max(1, min(queries.shape[0], res.workspace_bytes // per_query)))
    with scan_span, obs.record_launch("ivf_flat::launch",
                                      "ivf_flat.search.launches"):
        vals, ids = _search_impl(
            queries,
            index.centers,
            index.list_data,
            index.list_ids,
            index.list_norms,
            filter,
            int(k),
            n_probes,
            index.metric,
            q_tile,
            select_algo,
            res.compute_dtype,
        )
    return vals, ids


# ---------------------------------------------------------------------------
# Paged search (serving layer): scan a PagedListStore's vector pages
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "q_tile", "select_algo",
                     "compute_dtype"),
)
def _paged_impl(
    queries, centers, pages, page_ids, page_aux, table, filter,
    k, n_probes, metric, q_tile, select_algo, compute_dtype,
):
    """Paged-store scan: the gather-backend search (:func:`_search_impl`)
    re-shaped over (page-table, page) instead of a padded list axis. The
    per-candidate math is kept IDENTICAL (same coarse gemm, same einsum
    contraction, same bias/clamp/select sequence) so a fully-compacted
    store is bit-parity with the packed scan; the ``ids >= 0`` mask covers
    both fill-count tails and tombstones. All operand shapes derive from
    CAPACITY (page pool, table width) — appends and tombstones re-dispatch
    this same program."""
    # ledger registration (runs at trace time only): a growth retrace
    # lands attributed to the operand that grew (pages / table)
    obs_compile.trace_event(
        "ivf_flat.paged_scan", queries=queries, centers=centers,
        pages=pages, page_ids=page_ids, page_aux=page_aux, table=table,
        filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "q_tile": q_tile, "select_algo": select_algo,
                "compute_dtype": compute_dtype})
    q, dim = queries.shape
    select_min = metric != "inner_product"
    bad = jnp.float32(jnp.inf if select_min else -jnp.inf)

    if metric in ("sqeuclidean", "euclidean"):
        coarse = dist_mod._expanded_distance(
            queries, centers, "sqeuclidean", compute_dtype, "highest"
        )
        qn = dist_mod.sqnorm(queries)
    else:
        coarse = -dist_mod.matmul_t(queries, centers, compute_dtype, "highest")
        qn = None
    _, probes = select_k(coarse, n_probes, select_min=True, algo=select_algo)

    def scan_tile(args):
        q_blk, qn_blk, probe_blk = args
        tbl = table[probe_blk]                     # (qt, p, W)
        safe = jnp.maximum(tbl, 0)
        cand = pages[safe]                          # (qt, p, W, R, d)
        ids = jnp.where(tbl[..., None] >= 0, page_ids[safe], -1)
        ip = jnp.einsum(
            "qd,qpwrd->qpwr", q_blk, cand, preferred_element_type=jnp.float32
        )
        if metric in ("sqeuclidean", "euclidean"):
            norms = page_aux[safe]
            d = jnp.maximum(qn_blk[:, None, None, None] + norms - 2.0 * ip, 0.0)
            if metric == "euclidean":
                d = jnp.sqrt(d)
        elif metric == "cosine":
            d = 1.0 - ip  # inputs are pre-normalized
        else:
            d = ip  # inner_product: ranked by max
        flat_ids = ids.reshape(ids.shape[0], -1)
        d = d.reshape(flat_ids.shape)
        valid = flat_ids >= 0
        if filter is not None:
            valid = valid & filter.test(flat_ids)
        d = jnp.where(valid, d, bad)
        vals, sel = select_k(d, k, select_min=select_min, algo=select_algo)
        out_ids = jnp.where(vals == bad, -1,
                            jnp.take_along_axis(flat_ids, sel, axis=1))
        return vals, out_ids

    if qn is None:
        qn = jnp.zeros((q,), jnp.float32)  # unused, keeps the signature static
    return map_row_tiles(scan_tile, (queries, qn, probes), q_tile)


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "select_algo",
                     "compute_dtype", "interpret", "impl"),
)
def _paged_fused(queries, centers, pages, bias_pool, page_ids, table,
                 chain_pages, filter, k, n_probes, metric, select_algo,
                 compute_dtype, interpret, impl):
    """The ENTIRE paged Pallas search of one query tile — coarse gemm,
    device strip planning, page-table DMA kernel, merge, finalize — as one
    jit (the ``_ragged_fused`` shape over page chains): mutable paged
    storage scanned in place at strip-kernel throughput. Every operand is
    CAPACITY-shaped, so steady-state upserts/deletes re-dispatch this
    same program (zero-recompile serving contract)."""
    from raft_tpu.ops.strip_scan import paged_strip_search_traced

    # ledger registration (trace time only): a growth retrace lands
    # attributed to the pool/table operand that grew (obs/compile.py)
    obs_compile.trace_event(
        "ivf_flat.paged_pallas", queries=queries, centers=centers,
        pages=pages, bias_pool=bias_pool, page_ids=page_ids, table=table,
        chain_pages=chain_pages, filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "interpret": interpret, "impl": impl})
    # same coarse select as the packed ragged path (parity: probe choice
    # decides the candidate set)
    sa = probe_algo(select_algo, interpret, centers.shape[0])
    probes = _coarse_probes(queries, centers, n_probes, metric, sa,
                            compute_dtype)
    # the store's bias pool is already +inf at dead slots; the filter
    # masks live rows by their source id (the shared
    # _filtering.apply_filter_bias rule)
    bias = _filtering.apply_filter_bias(bias_pool, page_ids, filter)
    l2 = metric in ("sqeuclidean", "euclidean")
    vals, ids = paged_strip_search_traced(
        queries, probes, pages, bias, page_ids, table, chain_pages,
        int(k), int(k), -2.0 if l2 else -1.0, interpret, impl=impl)
    return _finalize_ragged(vals, ids, queries, metric)


def paged_backend_auto(store, k: int) -> str:
    """Engine selection for a paged search: the Pallas page-table scan on
    TPU when the store's layout can feed it, the jnp gather scan
    otherwise (and on CPU, where gather is the exact-fp32 oracle path)."""
    from raft_tpu.ops.strip_scan import paged_eligible

    if jax.default_backend() != "tpu" or store.kind == "ivf_bq":
        # ivf_bq: Mosaic refuses the paged BQ kernel's code-page DMA — a
        # (page_rows, rot_dim/8) uint8 slice under the 128-lane tiling
        # (tests/test_chip_compile.py) — so BQ stores scan by paged_jnp
        return "gather" if store.kind != "ivf_bq" else "paged_jnp"
    if store.kind == "ivf_pq":
        row_bytes = getattr(store, "_cache_dim", 0)
    else:
        row_bytes = int(store.pages.shape[-1]) * store.pages.dtype.itemsize
    # compiled-mode DMA alignment: lane-offset bias copies want whole
    # 128-lane tiles per page (the default page height); narrower pages
    # stay on the gather path outside interpret mode
    if store.page_rows % 128 != 0:
        return "gather" if store.kind != "ivf_bq" else "paged_jnp"
    if not paged_eligible(store.table_width, store.page_rows, row_bytes,
                          int(k)):
        return "gather" if store.kind != "ivf_bq" else "paged_jnp"
    return "paged_pallas"


def _paged_plan_static(store, n_probes: int, k: int, res, dim: int):
    """Query-tile sizing for the paged strip engines — the
    ``_ragged_plan_static`` twin over the capacity layout (one length
    class, ``class_counts = (n_lists,)``)."""
    from raft_tpu.ops import strip_scan as ss

    return ss.fit_q_tile(1 << 30, n_probes, store.n_lists, 1, int(k),
                         res.workspace_bytes, dim=dim,
                         class_counts=(store.n_lists,))


@traced("ivf_flat::search_paged")
def search_paged(
    store,
    queries,
    k: int,
    n_probes: int = 20,
    filter: Optional[Bitset] = None,
    select_algo: str = "exact",
    backend: str = "auto",
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """k-NN over a mutable paged vector store
    (:class:`raft_tpu.serving.PagedListStore`, kind ``"ivf_flat"``): same
    contract as :func:`search`, but the store keeps serving while rows
    stream in/out — no repack, and steady-state mutations never recompile
    this scan (its shapes depend only on store capacity).

    ``backend``: "paged_pallas" (page-table DMA strip kernel — the TPU
    engine, interpret-mode elsewhere), "paged_jnp" (its pure-jnp
    bit-parity reference), "gather" (jnp gather scan — the exact-fp32
    oracle, CPU default), or "auto"."""
    if store.kind != "ivf_flat":
        raise ValueError(f"expected an ivf_flat store, got {store.kind!r}")
    res = res or current_resources()
    if backend == "auto":
        backend = paged_backend_auto(store, k)
    if backend not in ("gather", "paged_pallas", "paged_jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    # one ATOMIC store snapshot: pool/table read separately could tear
    # against a concurrent upsert's capacity growth
    if backend == "gather":
        pages, page_ids, page_aux, table = store.scan_state()
    else:
        pages, bias_pool, _, page_ids, table, chain_pages = \
            store.paged_scan_state()
    width = int(table.shape[1])
    queries, n_probes, filter, scan_attrs = _entry.prepare(
        "ivf_flat.search_paged", queries, k, n_probes, filter, backend,
        dim=store.dim, n_lists=store.n_lists, metric=store.metric,
        rows=width * store.page_rows,
        standing_filter=getattr(store, "filter", None),
        attrs={"table_width": width})
    if obs.enabled():
        # roofline note (round 15): the gather scan's per-(query, probe)
        # capacity-padded chain cost — no cross-query sharing, which is
        # exactly what the paged Pallas engine buys back — or the paged
        # Pallas model with the planner's occupancy
        q_obs = int(queries.shape[0])
        shapes = {"q": q_obs, "dim": store.dim, "n_lists": store.n_lists,
                  "page_rows": store.page_rows, "table_width": width,
                  "n_probes": int(n_probes), "k": int(k),
                  "dtype": str(pages.dtype)}
        if backend == "gather":
            obs_roofline.note_dispatch("ivf_flat.paged_scan", shapes)
        else:
            obs_roofline.note_dispatch(
                "ivf_flat.paged_pallas", shapes,
                occupancy=_entry.page_occupancy(
                    store, width, q_obs, n_probes, k, res,
                    int(pages.shape[-1]) * pages.dtype.itemsize, store.dim))
    from raft_tpu.ops.strip_scan import per_tile
    from raft_tpu.resilience import faultpoint

    if backend != "gather":
        interpret = jax.default_backend() != "tpu"
        q_tile = _paged_plan_static(store, n_probes, k, res, store.dim)
        impl = "pallas" if backend == "paged_pallas" else "jnp"
        faultpoint("ivf_flat.search_paged.scan")
        with obs.record_span("ivf_flat::paged_pallas", attrs=scan_attrs):
            with obs_compile.watch():
                return per_tile(lambda lo, hi: _paged_fused(
                    queries[lo:hi], store.centers, pages, bias_pool,
                    page_ids, table, chain_pages, filter, int(k), n_probes,
                    store.metric, select_algo, res.compute_dtype, interpret,
                    impl), queries.shape[0], q_tile)
    # the (qt, p, W, R, d) page gather is the big intermediate
    per_query = max(1, n_probes * width * store.page_rows * (store.dim + 2) * 4)
    q_tile = int(max(1, min(queries.shape[0],
                            res.workspace_bytes // per_query)))
    faultpoint("ivf_flat.search_paged.scan")
    with obs.record_span("ivf_flat::paged_scan", attrs=scan_attrs):
        # ledger watch: a dispatch that (re)traces gets its wall-clock
        # stamped onto the ledger record (steady state stamps nothing)
        with obs_compile.watch():
            return _paged_impl(
                queries, store.centers, pages, page_ids, page_aux, table,
                filter, int(k), n_probes, store.metric,
                q_tile, select_algo, res.compute_dtype,
            )


def split_list_rows(rows, n_iter: int = 8):
    """Deterministic 2-means split of one overfull list's rows — the
    maintenance re-cluster's hot-list splitter (serving/maintenance.py).

    Seeding is data-derived (the two extreme rows along the max-variance
    coordinate) and Lloyd runs a few rounds on the host: the input is one
    list (thousands of rows at most), so there is nothing worth
    dispatching, and no RNG keeps the split reproducible across runs —
    the same no-clock/no-global-RNG determinism contract as the shadow
    sampler's hashing.

    Returns ``(centers (2, dim) float32, assign (n,) int32)``. Degenerate
    inputs (all rows identical) collapse onto one side; callers skip the
    split when ``assign`` is constant.
    """
    rows = np.asarray(rows, np.float32)
    if rows.ndim != 2 or rows.shape[0] < 2:
        raise ValueError("split_list_rows needs a (n >= 2, dim) row matrix")
    mu = rows.mean(axis=0)
    coord = rows[:, int(((rows - mu) ** 2).mean(axis=0).argmax())]
    centers = np.stack([rows[int(coord.argmin())], rows[int(coord.argmax())]])
    assign = np.zeros(rows.shape[0], np.int32)
    for it in range(max(1, int(n_iter))):
        d0 = ((rows - centers[0]) ** 2).sum(axis=1)
        d1 = ((rows - centers[1]) ** 2).sum(axis=1)
        new = (d1 < d0).astype(np.int32)
        if it > 0 and np.array_equal(new, assign):
            break
        assign = new
        for side in (0, 1):
            sel = rows[assign == side]
            if sel.shape[0]:
                centers[side] = sel.mean(axis=0)
    return centers, assign
