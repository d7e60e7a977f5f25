"""IVF-PQ: inverted-file index with product-quantized residual vectors.

Reference surface: raft::neighbors::ivf_pq — build (ivf_pq-inl.cuh:273 →
detail/ivf_pq_build.cuh:1729: kmeans_balanced coarse trainer :1828, random
rotation make_rotation_matrix :119, codebook training train_per_subset :392,
encode process_and_fill_codes :1319), search (detail/ivf_pq_search.cuh:731:
select_clusters :69 → LUT-based scan ivfpq_search_worker :420 →
select_k :586 → optional refine re-rank refine-inl.cuh:70); params
ivf_pq_types.hpp:36-264 (pq_bits 4..8, pq_dim, codebook per-subspace).

TPU design — the LUT scan rearranged so the per-probe work is additive
constants plus a *per-query-only* table:

    d²(q, x_j∈list l) ≈ |q - c_l|²                      (stage-1 coarse value)
                      + Σ_s −2·(Rq)_s·cb[s, code_js]    (query-only LUT A)
                      + Σ_s (2·(Rc_l)_s·cb[s, code_js]
                             + |cb[s, code_js]|²)        (b_sum: baked at build)

The reference rebuilds a LUT per (query, probe) from the rotated residual
(ivf_pq_search.cuh:420); splitting the residual LUT into A (query half) and
b_sum (list half, a per-entry scalar precomputed at build) removes the
per-probe LUT entirely: search-time work is one gemm for A, the stage-1
coarse gemm, and a code→A lookup. The lookup itself has two backends:

  * jnp gather (`take_along_axis`) — correct everywhere, the CPU/test oracle;
  * the Pallas list-centric kernel (ops/pq_scan.py) — queries batched as the
    MXU N-dimension against in-VMEM one-hot code blocks (used on TPU).

Codes are stored tightly bit-packed (pq_bits 4..8, pack_codes) in padded
dense lists like ivf_flat (XLA static shapes; kIndexGroupSize-aligned);
search reads an int8 RESIDUAL reconstruction cache (rot_dim bytes/entry,
see _decode_lists) through the strip kernel, with the exact per-pair
center term applied at the merge.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import obs
from raft_tpu.obs import compile as obs_compile
from raft_tpu.obs import roofline as obs_roofline
from raft_tpu.cluster import kmeans_balanced
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import _entry, _filtering
from raft_tpu.neighbors import _packing
from raft_tpu.core.logger import get_logger
from raft_tpu.core.trace import traced
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.serialize import load_arrays, save_arrays
from raft_tpu.ops import distance as dist_mod
from raft_tpu.ops import linalg
from raft_tpu.ops.pq_scan import group_probed_pairs, pq_scan
from raft_tpu.ops.select_k import probe_algo, select_k
from raft_tpu.utils.tiling import map_row_tiles

_log = get_logger()

SUPPORTED_METRICS = ("sqeuclidean", "euclidean", "inner_product", "cosine")


@dataclass(frozen=True)
class IvfPqParams:
    """Build params (ivf_pq_types.hpp index_params analog)."""

    n_lists: int = 1024
    pq_dim: int = 0  # 0 = auto: dim/2 rounded up to a multiple of 8
    pq_bits: int = 8  # codebook size = 2**pq_bits, 4..8 like the reference
    # "subspace": one codebook per sub-dimension (codebook_gen::PER_SUBSPACE)
    # "cluster": one codebook per IVF list, shared across sub-dimensions
    # (codebook_gen::PER_CLUSTER, ivf_pq_types.hpp:36)
    codebook_kind: str = "subspace"
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    codebook_n_iters: int = 25
    # per-list occupancy cap: -1 = auto (4× mean, group-aligned), 0 = off
    # (_packing.spill_to_cap overflow policy)
    list_size_cap: int = -1
    # list padding granule: 0 = auto (_packing.auto_group_size)
    group_size: int = 0
    seed: int = 0

    def __post_init__(self):
        m = dist_mod.canonical_metric(self.metric)
        if m not in SUPPORTED_METRICS:
            raise ValueError(f"ivf_pq supports {SUPPORTED_METRICS}, got {self.metric!r}")
        object.__setattr__(self, "metric", m)
        if not 4 <= self.pq_bits <= 8:
            raise ValueError(f"pq_bits must be in [4, 8], got {self.pq_bits}")
        if self.codebook_kind not in ("subspace", "cluster"):
            raise ValueError(
                f"codebook_kind must be 'subspace'|'cluster', got "
                f"{self.codebook_kind!r}")


@jax.tree_util.register_pytree_node_class
@dataclass
class IvfPqIndex:
    """Coarse centers + rotation + per-subspace codebooks + packed code lists.

    ``b_sum`` carries the list-side half of the L2 LUT decomposition (zeros
    for inner-product metrics). ``list_ids[l, j] == -1`` marks padding.
    """

    centers: jax.Array  # (n_lists, dim) fp32 — unrotated, for stage 1
    rotation: jax.Array  # (rot_dim, rot_dim) orthogonal
    codebooks: jax.Array  # (pq_dim, n_codes, dsub) fp32
    list_codes: jax.Array  # (n_lists, max_list_size, pq_dim) uint8
    list_ids: jax.Array  # (n_lists, max_list_size) int32
    b_sum: jax.Array  # (n_lists, max_list_size) fp32
    # (n_lists, max_list_size, rot_dim) int8 strip-scan cache (+ host-side
    # float scale in ``decoded_scale``); None until the first strip search
    # (lazy: rot_dim bytes/slot, wasted on CPU/gather deployments). The
    # quantized-reconstruction analog of the reference's fp8-compressed LUT
    # (detail/ivf_pq_fp_8bit.cuh): only the cross term -2⟨q, x̂⟩ is
    # approximated — the ‖x̂‖² half rides exactly in b_sum.
    decoded: Optional[jax.Array]
    metric: str
    pq_bits: int
    # list padding granule used at build; extend() reuses it instead of
    # inferring from max_list_size (ADVICE.md round-2: inference can silently
    # flip the granule and change backend eligibility). 0 = unknown (legacy).
    group_size: int = 0
    decoded_scale: Optional[jax.Array] = None  # 0-d fp32 dequant scale
    # "subspace" (codebooks (pq_dim, n_codes, dsub)) or "cluster"
    # (codebooks (n_lists, n_codes, dsub), ivf_pq_types.hpp:36 PER_CLUSTER)
    codebook_kind: str = "subspace"
    pq_dim_hint: int = 0  # explicit pq_dim (cluster kind can't derive it)

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        return self.rotation.shape[0]

    @property
    def pq_dim(self) -> int:
        return self.pq_dim_hint or self.codebooks.shape[0]

    @property
    def n_codes(self) -> int:
        return self.codebooks.shape[1]

    @property
    def max_list_size(self) -> int:
        return self.list_codes.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_ids >= 0))

    def list_sizes(self) -> jax.Array:
        return jnp.sum(self.list_ids >= 0, axis=1).astype(jnp.int32)

    def tree_flatten(self):
        return (
            self.centers, self.rotation, self.codebooks,
            self.list_codes, self.list_ids, self.b_sum, self.decoded,
            self.decoded_scale,
        ), (self.metric, self.pq_bits, self.group_size, self.codebook_kind,
            self.pq_dim_hint)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (centers, rotation, codebooks, list_codes, list_ids, b_sum,
         decoded, decoded_scale) = children
        metric, pq_bits, group_size, codebook_kind, pq_dim_hint = aux
        return cls(centers, rotation, codebooks, list_codes, list_ids,
                   b_sum, decoded, metric, pq_bits, group_size,
                   decoded_scale=decoded_scale, codebook_kind=codebook_kind,
                   pq_dim_hint=pq_dim_hint)

    # -- persistence (ivf_pq_serialize.cuh analog) -------------------------
    def save(self, path) -> None:
        save_arrays(
            path,
            {"kind": "ivf_pq", "metric": self.metric, "pq_bits": self.pq_bits,
             "group_size": self.group_size,
             "codebook_kind": self.codebook_kind,
             "pq_dim_hint": self.pq_dim_hint},
            {
                "centers": self.centers,
                "rotation": self.rotation,
                "codebooks": self.codebooks,
                "list_codes": self.list_codes,
                "list_ids": self.list_ids,
                "b_sum": self.b_sum,
            },
        )

    @classmethod
    def load(cls, path) -> "IvfPqIndex":
        # `decoded` is derived data — recomputed here, never serialized
        meta, arrays = load_arrays(path)
        if meta.get("kind") != "ivf_pq":
            raise ValueError(f"not an ivf_pq index: {meta.get('kind')}")
        centers = jnp.asarray(arrays["centers"])
        rotation = jnp.asarray(arrays["rotation"])
        codebooks = jnp.asarray(arrays["codebooks"])
        list_codes = jnp.asarray(arrays["list_codes"])
        list_ids = jnp.asarray(arrays["list_ids"])
        return cls(
            centers, rotation, codebooks, list_codes, list_ids,
            jnp.asarray(arrays["b_sum"]), None,
            meta["metric"],
            int(meta["pq_bits"]),
            int(meta.get("group_size", 0)),
            codebook_kind=meta.get("codebook_kind", "subspace"),
            pq_dim_hint=int(meta.get("pq_dim_hint", 0)),
        )


# ---------------------------------------------------------------------------
# Build pieces
# ---------------------------------------------------------------------------


def _auto_pq_dim(dim: int) -> int:
    pq = max(1, dim // 2)
    return -(-pq // 8) * 8 if pq >= 8 else pq


def packed_width(pq_dim: int, pq_bits: int) -> int:
    """Bytes per encoded vector at ``pq_bits`` bits per sub-dimension."""
    return -(-pq_dim * pq_bits // 8)


def pack_codes(codes, pq_bits: int):
    """(…, pq_dim) uint8 codes → (…, ceil(pq_dim·bits/8)) tightly packed
    uint8 (ivf_pq_types.hpp stores pq_bits 4..8 packed; round-2 VERDICT
    Missing#3: one byte per sub-dim forfeited PQ's memory edge below 8
    bits). Little-endian bit order within the stream."""
    if pq_bits == 8:
        return codes
    pq_dim = codes.shape[-1]
    nbytes = packed_width(pq_dim, pq_bits)
    c32 = codes.astype(jnp.uint32)
    bit0 = jnp.arange(pq_dim, dtype=jnp.uint32) * pq_bits
    out = jnp.zeros(codes.shape[:-1] + (nbytes,), jnp.uint32)
    for b in range(2):  # a field spans at most 2 bytes for bits <= 8
        byte = (bit0 >> 3) + b
        shift = jnp.where(b == 0, bit0 & 7, 0)
        down = jnp.where(b == 0, 0, 8 - (bit0 & 7))
        part = jnp.where(b == 0, c32 << shift, c32 >> down) & 0xFF
        valid = byte < nbytes
        out = out.at[..., jnp.where(valid, byte, 0)].add(
            jnp.where(valid, part, 0))
    return out.astype(jnp.uint8)


def unpack_codes(packed, pq_dim: int, pq_bits: int):
    """Inverse of :func:`pack_codes` → (…, pq_dim) uint8."""
    if pq_bits == 8:
        return packed
    nbytes = packed.shape[-1]
    p32 = packed.astype(jnp.uint32)
    bit0 = jnp.arange(pq_dim, dtype=jnp.uint32) * pq_bits
    byte = bit0 >> 3
    r = bit0 & 7
    lo = jnp.take(p32, byte, axis=-1) >> r
    hi_byte = jnp.minimum(byte + 1, nbytes - 1)
    hi = jnp.take(p32, hi_byte, axis=-1) << (8 - r)
    hi = jnp.where(byte + 1 < nbytes, hi, 0)
    mask = (1 << pq_bits) - 1
    return ((lo | hi) & mask).astype(jnp.uint8)


# promoted to ops/linalg (round 17, with the SRHT rotation family); these
# re-export shims keep the long-standing public names importable from here
make_rotation_matrix = linalg.make_rotation_matrix
pad_rot = linalg.pad_rot


@functools.partial(jax.jit, static_argnames=("n_codes", "n_iters"))
def _train_codebooks(resid_sub, key, n_codes, n_iters):
    """Per-subspace Lloyd k-means (train_per_subset analog,
    detail/ivf_pq_build.cuh:392).

    resid_sub: (pq_dim, n_train, dsub) rotated residuals. Sequential
    `lax.map` over subspaces — each holds an (n_train, n_codes) distance
    block; mapping (not vmapping) keeps only one block live at a time.
    """
    pq_dim, n_train, dsub = resid_sub.shape

    def one_subspace(args):
        X, key = args
        # with-replacement init: valid even when n_train < n_codes (tiny
        # datasets leave dead codes, harmless), and avoids the O(n log n)
        # permutation program choice(replace=False) would compile
        rows = jax.random.randint(key, (n_codes,), 0, n_train)
        centers0 = X[rows]

        def step(_, centers):
            d2 = (
                dist_mod.sqnorm(X)[:, None]
                + dist_mod.sqnorm(centers)[None, :]
                - 2.0 * dist_mod.matmul_t(X, centers)
            )
            labels = jnp.argmin(d2, axis=1)
            sums = jax.ops.segment_sum(X, labels, num_segments=n_codes)
            counts = jax.ops.segment_sum(jnp.ones(n_train), labels, num_segments=n_codes)
            return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], centers)

        return lax.fori_loop(0, n_iters, step, centers0)

    keys = jax.random.split(key, pq_dim)
    return lax.map(one_subspace, (resid_sub, keys))


def _encode(resid_rot, codebooks, chunk: int = 8192):
    """resid_rot (n, pq_dim, dsub) → codes (n, pq_dim) uint8: per-subspace
    nearest codebook entry (process_and_fill_codes analog,
    detail/ivf_pq_build.cuh:1319). Chunked over rows so the (chunk, pq_dim,
    n_codes) distance block stays bounded."""
    n = resid_rot.shape[0]
    cn = jnp.sum(codebooks * codebooks, axis=2)  # (s, c)

    def enc(chunk_rows):
        ip = jnp.einsum(
            "nsd,scd->nsc", chunk_rows, codebooks, preferred_element_type=jnp.float32
        )
        return jnp.argmin(cn[None] - 2.0 * ip, axis=2).astype(jnp.uint8)

    if n <= chunk:
        return enc(resid_rot)
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    padded = jnp.pad(resid_rot, ((0, pad), (0, 0), (0, 0)))
    out = lax.map(enc, padded.reshape(n_chunks, chunk, *resid_rot.shape[1:]))
    return out.reshape(-1, resid_rot.shape[1])[:n]


@functools.partial(jax.jit, static_argnames=("n_codes", "n_iters", "n_lists"))
def _train_codebooks_cluster(resid_sub, labels, key, n_codes, n_iters,
                             n_lists):
    """Per-CLUSTER Lloyd k-means (codebook_gen::PER_CLUSTER,
    ivf_pq_types.hpp:36): one (n_codes, dsub) codebook per IVF list, trained
    on ALL sub-vectors of that list's residuals pooled across subspaces.

    resid_sub: (n_train, pq_dim, dsub); labels: (n_train,) list ids. The
    whole EM is segment reductions keyed by label·n_codes + code — one
    fused program, no per-cluster host loop."""
    n_train, pq_dim, dsub = resid_sub.shape
    sub = resid_sub.reshape(n_train * pq_dim, dsub)
    sub_label = jnp.repeat(labels.astype(jnp.int32), pq_dim)
    nseg = n_lists * n_codes

    # init: per (list, seed) slot, a random member sub-vector of that list
    # (segment-argmax of per-slot uniforms; only 8 seed rows are drawn —
    # round-3 review: an (n_codes, n·pq_dim) uniform was multi-GB)
    n_seed = min(n_codes, 8)
    u = jax.random.uniform(key, (n_seed, sub.shape[0]))

    def init_code(u_c):
        top = jax.ops.segment_max(u_c, sub_label, num_segments=n_lists)
        is_rep = u_c >= top[sub_label]
        rep = jax.ops.segment_min(
            jnp.where(is_rep, jnp.arange(sub.shape[0], dtype=jnp.int32),
                      sub.shape[0] - 1),
            sub_label, num_segments=n_lists)
        return sub[rep]                                   # (n_lists, dsub)

    cb0 = jnp.stack([init_code(u[c]) for c in range(n_seed)], axis=1)
    if n_codes > n_seed:  # jitter copies of the seeds: Lloyd separates them
        reps = -(-n_codes // n_seed)
        jit_key = jax.random.fold_in(key, 1)
        noise = jax.random.normal(jit_key, (n_lists, n_seed * reps, dsub)) * 0.05
        spread = jnp.std(sub) + 1e-6
        cb0 = (jnp.tile(cb0, (1, reps, 1)) + noise * spread)[:, :n_codes]

    # chunk the per-row assignment so the (chunk, s, n_codes) distance block
    # stays bounded (review: unchunked it was multi-GB at default sizes)
    chunk = max(256, min(n_train, 4_000_000 // max(pq_dim * n_codes, 1)))
    n_chunks = -(-n_train // chunk)
    pad = n_chunks * chunk - n_train

    def step(_, cb):
        rows_p = jnp.pad(resid_sub, ((0, pad), (0, 0), (0, 0)))
        lb_p = jnp.pad(labels, (0, pad))

        def one(args):
            rows, lb = args
            cb_l = cb[lb]                                  # (chunk, nc, d)
            d2 = (jnp.sum(cb_l * cb_l, axis=2)[:, None, :]
                  - 2.0 * jnp.einsum("nsd,ncd->nsc", rows, cb_l,
                                     preferred_element_type=jnp.float32))
            return jnp.argmin(d2, axis=2).astype(jnp.int32)

        code = lax.map(one, (rows_p.reshape(n_chunks, chunk, pq_dim, dsub),
                             lb_p.reshape(n_chunks, chunk)))
        code = code.reshape(-1, pq_dim)[:n_train]          # (n_train, s)
        seg = sub_label * n_codes + code.reshape(-1)
        sums = jax.ops.segment_sum(sub, seg, num_segments=nseg)
        cnts = jax.ops.segment_sum(jnp.ones(sub.shape[0]), seg,
                                   num_segments=nseg)
        new = sums / jnp.maximum(cnts, 1.0)[:, None]
        new = new.reshape(n_lists, n_codes, dsub)
        return jnp.where(cnts.reshape(n_lists, n_codes, 1) > 0, new, cb)

    return lax.fori_loop(0, n_iters, step, cb0)


def _encode_cluster(resid_rot, labels, codebooks, chunk: int = 8192):
    """Per-cluster encode: each row scores against ITS list's codebook."""
    n, pq_dim, dsub = resid_rot.shape
    cn = jnp.sum(codebooks * codebooks, axis=2)            # (L, c)

    def enc(args):
        rows, lb = args
        cb_l = codebooks[lb]                               # (chunk, c, d)
        ip = jnp.einsum("nsd,ncd->nsc", rows, cb_l,
                        preferred_element_type=jnp.float32)
        return jnp.argmin(cn[lb][:, None, :] - 2.0 * ip, axis=2).astype(jnp.uint8)

    if n <= chunk:
        return enc((resid_rot, labels))
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    rows_p = jnp.pad(resid_rot, ((0, pad), (0, 0), (0, 0)))
    lb_p = jnp.pad(labels, (0, pad))
    out = lax.map(enc, (rows_p.reshape(n_chunks, chunk, pq_dim, dsub),
                        lb_p.reshape(n_chunks, chunk)))
    return out.reshape(-1, pq_dim)[:n]


def _pack_lists(codes, row_ids, labels, n_lists: int, group: int = 0):
    if group <= 0:
        group = _packing.auto_group_size(codes.shape[0], n_lists, floor=128)
    return _packing.pack_lists(codes, row_ids, labels, n_lists, group,
                               pow2_chunks=group == 512)


# legacy private alias (pre-promotion call sites across the repo and old
# user code imported `_pad_rot` from here)
_pad_rot = linalg.pad_rot


@traced("ivf_pq::build")
def build(
    dataset,
    params: IvfPqParams = IvfPqParams(),
    res: Optional[Resources] = None,
) -> IvfPqIndex:
    """Train coarse centers, rotation, codebooks; encode and pack the lists
    (ivf_pq-inl.cuh:273 / detail/ivf_pq_build.cuh:1729)."""
    res = res or current_resources()
    dataset = jnp.asarray(dataset).astype(jnp.float32)
    n, dim = dataset.shape
    if params.n_lists > n:
        raise ValueError(f"n_lists={params.n_lists} > n_rows={n}")
    pq_dim = params.pq_dim or _auto_pq_dim(dim)
    if pq_dim > dim:
        raise ValueError(f"pq_dim={pq_dim} > dim={dim}")
    dsub = -(-dim // pq_dim)
    rot_dim = pq_dim * dsub
    n_codes = 1 << params.pq_bits

    work = dataset
    if params.metric == "cosine":
        work = work / jnp.maximum(jnp.linalg.norm(work, axis=1, keepdims=True), 1e-30)

    # --- coarse quantizer (ivf_pq_build.cuh:1828) --------------------------
    km_metric = "inner_product" if params.metric in ("cosine", "inner_product") else "sqeuclidean"
    km = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=km_metric, seed=params.seed
    )
    key = jax.random.key(params.seed)
    k_train, k_rot, k_cb = jax.random.split(key, 3)
    n_train = max(params.n_lists, int(n * params.kmeans_trainset_fraction))
    # phase spans (round-8): the @traced entry span parents these via the
    # tracing contextvar, so a Perfetto export shows WHERE inside a build
    # the time went (entry → phase → tile), not just that it ran
    with obs.record_span("ivf_pq::coarse_train"):
        if n_train < n:
            # with-replacement: duplicates are noise for k-means, and it
            # avoids the O(n log n) permutation program
            # choice(replace=False) compiles
            train_rows = jax.random.randint(k_train, (n_train,), 0, n)
            trainset = work[train_rows]
            centers = kmeans_balanced.fit(trainset, params.n_lists, km, res=res)
            labels = kmeans_balanced.predict(work, centers, km, res=res)
        else:
            trainset = work
            centers, labels = kmeans_balanced.fit_predict(work, params.n_lists, km, res=res)

    # --- rotation + codebooks (ivf_pq_build.cuh:119,:392) ------------------
    with obs.record_span("ivf_pq::codebook_train"):
        rotation = make_rotation_matrix(k_rot, rot_dim)
        train_labels = kmeans_balanced.predict(trainset, centers, km, res=res)
        resid = _pad_rot(trainset - centers[train_labels], rot_dim) @ rotation.T
        cb_rows = min(resid.shape[0], 65536)
        resid_cb = resid[:cb_rows].reshape(cb_rows, pq_dim, dsub)
        if params.codebook_kind == "cluster":
            codebooks = _train_codebooks_cluster(
                resid_cb, train_labels[:cb_rows], k_cb, n_codes,
                params.codebook_n_iters, params.n_lists)
        else:
            codebooks = _train_codebooks(
                resid_cb.transpose(1, 0, 2), k_cb, n_codes,
                params.codebook_n_iters)

    if obs.enabled():
        obs.add("ivf_pq.build.rows", n)
        obs.add("ivf_pq.build.lists", params.n_lists)

    group = params.group_size or _packing.auto_group_size(n, params.n_lists, floor=128)
    cap = params.list_size_cap
    if cap < 0:
        cap = _packing.auto_list_cap(n, params.n_lists, group)
    if cap:
        labels = _packing.spill_to_cap(work, centers, labels, km_metric, cap)

    # --- encode + pack, pq_bits-tight (ivf_pq_build.cuh:1319) --------------
    # residuals + encode in row chunks: one (n, rot_dim) fp32 residual
    # array is ~4 GB at 10M x 96 — materializing it whole next to `work`
    # OOM'd the 10M bench (round-4); chunking bounds the transient to the
    # workspace while `codes` (uint8) stays small
    enc_chunk = int(max(65536, res.workspace_bytes // max(rot_dim * 16, 1)))
    enc_attrs = ({"rows": int(n), "chunk": enc_chunk}
                 if obs.enabled() else None)
    with obs.record_span("ivf_pq::encode", attrs=enc_attrs):
        codes_parts = []
        for s in range(0, n, enc_chunk):
            e = min(s + enc_chunk, n)
            with obs.record_span("ivf_pq::encode_tile",
                                 attrs=({"rows": int(e - s)}
                                        if obs.enabled() else None)):
                wch = lax.slice_in_dim(work, s, e, axis=0)
                lch = lax.slice_in_dim(labels, s, e, axis=0)
                resid = _pad_rot(wch - centers[lch], rot_dim) @ rotation.T
                resid = resid.reshape(e - s, pq_dim, dsub)
                raw = (_encode_cluster(resid, lch, codebooks)
                       if params.codebook_kind == "cluster"
                       else _encode(resid, codebooks))
                codes_parts.append(pack_codes(raw, params.pq_bits))
        codes = (jnp.concatenate(codes_parts) if len(codes_parts) > 1
                 else codes_parts[0])
    with obs.record_span("ivf_pq::pack"):
        row_ids = jnp.arange(n, dtype=jnp.int32)
        list_codes, list_ids = _pack_lists(codes, row_ids, labels,
                                           params.n_lists, group)
        b_sum = _compute_b_sum(centers, rotation, codebooks, list_codes,
                               list_ids, params.metric, pq_dim,
                               params.pq_bits,
                               cluster=params.codebook_kind == "cluster")
    return IvfPqIndex(
        centers, rotation, codebooks, list_codes, list_ids, b_sum, None,
        params.metric, params.pq_bits, group,
        codebook_kind=params.codebook_kind, pq_dim_hint=pq_dim,
    )


# promoted to _packing (round 17: the ivf_bq streamed build shares them);
# the private aliases keep this module's long-standing names working
_chunk_ranks = _packing.chunk_ranks


@functools.partial(
    jax.jit,
    static_argnames=("pq_dim", "pq_bits", "cluster", "code_w"),
    donate_argnums=(0, 1),
)
def _scatter_chunk(list_codes, list_ids, chunk, labels, base, row_start,
                   centers, rotation, codebooks,
                   pq_dim, pq_bits, cluster, code_w):
    """One streamed-build chunk: encode + offset-scatter into the donated
    packed blocks (build_streaming pass 2). ``base`` is the per-list write
    offset accumulated over previous chunks; the in-chunk rank comes from
    one chunk-local sort, so no global position array ever exists."""
    m, dim = chunk.shape
    n_lists, mls = list_ids.shape
    dsub = codebooks.shape[-1]
    rot_dim = pq_dim * dsub
    safe = jnp.minimum(labels, n_lists - 1)
    resid = _pad_rot(chunk - centers[safe], rot_dim) @ rotation.T
    resid = resid.reshape(m, pq_dim, dsub)
    raw = (_encode_cluster(resid, safe, codebooks) if cluster
           else _encode(resid, codebooks))
    codes = pack_codes(raw, pq_bits)
    # chunk-local rank within each list; sentinel labels (== n_lists, the
    # diversion drop marker) and overflow past mls route to row mls, which
    # mode="drop" discards
    order, sorted_labels, rank_sorted = _chunk_ranks(labels, n_lists)
    safe_sl = jnp.minimum(sorted_labels, n_lists - 1)
    pos = base[safe_sl].astype(jnp.int32) + rank_sorted
    pos = jnp.where((sorted_labels < n_lists) & (pos < mls), pos, mls)
    list_codes = list_codes.at[safe_sl, pos].set(
        codes[order], mode="drop")
    ids = row_start + jnp.arange(m, dtype=jnp.int32)
    list_ids = list_ids.at[safe_sl, pos].set(
        ids[order], mode="drop")
    return list_codes, list_ids


_assign_top2 = _packing.assign_top2


@functools.partial(
    jax.jit,
    static_argnames=("pq_dim", "pq_bits", "cluster", "cache_dim"),
    donate_argnums=(0, 1, 2),
)
def _scatter_chunk_cache(cache, list_ids, b_sum, chunk, labels, base,
                         row_start, centers, rotation, codebooks, rc_t,
                         pq_dim, pq_bits, cluster, cache_dim):
    """Streamed-build chunk for ``store="cache"``: encode → reconstruct →
    int8-truncate to ``cache_dim`` rotated coords, then offset-scatter the
    cache + ids + per-entry b_sum into the donated blocks. The codes are
    transient — at 100M×96 keeping BOTH packed codes and the cache busts
    HBM, and truncating the cache is the quantize-harder decision
    (detail/ivf_pq_fp_8bit.cuh analog: precision traded for memory, exact
    refine absorbs it)."""
    m, dim = chunk.shape
    n_lists, mls = list_ids.shape
    dsub = codebooks.shape[-1]
    rot_dim = pq_dim * dsub
    safe = jnp.minimum(labels, n_lists - 1)
    resid = _pad_rot(chunk - centers[safe], rot_dim) @ rotation.T
    resid3 = resid.reshape(m, pq_dim, dsub)
    raw = (_encode_cluster(resid3, safe, codebooks) if cluster
           else _encode(resid3, codebooks))
    packed = pack_codes(raw, pq_bits)
    scale = jnp.maximum(jnp.max(jnp.abs(codebooks)), 1e-30) / 127.0
    # decode through GL pseudo-lists: one giant take over the whole chunk
    # is the gather shape class that faulted the TPU runtime (round-2
    # finding); 64 slices keep each take at the proven per-list scale
    GL = 64
    mp = -(-m // GL) * GL
    packed_p = jnp.pad(packed, ((0, mp - m), (0, 0)))
    rec = _decode_lists_scaled(
        codebooks, packed_p.reshape(GL, mp // GL, packed.shape[-1]),
        scale, pq_dim, pq_bits, cluster)
    rot_dim_full = pq_dim * dsub
    rec_t = rec.reshape(mp, rot_dim_full)[:m, :cache_dim]
    rf = rec_t.astype(jnp.float32) * scale
    # truncated-space b_sum: 2⟨(Rc_l)[:cd], r̂_t⟩ + ‖r̂_t‖² (the scan's
    # −2⟨q_rot[:cd], r̂_t⟩ completes the cross term; ‖Rc‖² rides
    # _ragged_bias_pq, −2⟨q,c⟩ rides pair_const — both exact)
    b = (2.0 * jnp.einsum("md,md->m", rc_t[safe], rf,
                          preferred_element_type=jnp.float32)
         + jnp.einsum("md,md->m", rf, rf,
                      preferred_element_type=jnp.float32))
    order, sorted_labels, rank_sorted = _chunk_ranks(labels, n_lists)
    safe_sl = jnp.minimum(sorted_labels, n_lists - 1)
    pos = base[safe_sl].astype(jnp.int32) + rank_sorted
    pos = jnp.where((sorted_labels < n_lists) & (pos < mls), pos, mls)
    cache = cache.at[safe_sl, pos].set(rec_t[order], mode="drop")
    ids = row_start + jnp.arange(m, dtype=jnp.int32)
    list_ids = list_ids.at[safe_sl, pos].set(ids[order], mode="drop")
    b_sum = b_sum.at[safe_sl, pos].set(b[order], mode="drop")
    return cache, list_ids, b_sum


@traced("ivf_pq::build_streaming")
def build_streaming(
    chunk_fn,
    n: int,
    dim: int,
    params: IvfPqParams = IvfPqParams(),
    res: Optional[Resources] = None,
    chunk_rows: int = 0,
    train_rows: int = 0,
    store: str = "codes",
    cache_dim: int = 0,
) -> IvfPqIndex:
    """Out-of-HBM build: the dataset visits the device one chunk at a time
    (the 100M-row single-chip configuration, BASELINE DEEP-100M row).

    ``chunk_fn(start, end) -> (end-start, dim) array`` supplies rows — a
    file reader (bench/io.py readers), a generator, or a host array slice.
    It is called once per chunk per pass (twice total), so it must be
    deterministic.

    Differences from :func:`build`, all forced by the memory budget:

    * quantizers train on ``train_rows`` sampled rows (default ≤2M) — the
      reference trains on a host-side subsample for the same reason
      (ivf_pq_build.cuh:1729);
    * pass 1 streams assignments (labels are kept, ~4 B/row); pass 2
      encodes each chunk and scatters at precomputed per-list offsets into
      DONATED blocks — peak HBM is the index + one chunk, never the raw
      matrix (vs extend()'s whole-index repack per call, O(n²) over a
      chunk stream);
    * the list cap (``params.list_size_cap``) is enforced by ONE-PASS
      capacity diversion: a row whose nearest list is full goes to its
      second-nearest (:func:`_assign_top2` — the streaming analog of
      _packing.spill_to_cap's first alternative round); rows whose second
      choice is also full are DROPPED and counted
      (``index._streaming_dropped``) — at the auto cap this is empty;
    * ``store="codes"`` keeps packed codes (search via ``backend="pallas"``
      or lazy cache decode); ``store="cache"`` keeps ONLY the int8
      strip-scan cache, truncated to the first ``cache_dim`` rotated
      coordinates — the quantize-harder memory decision
      (detail/ivf_pq_fp_8bit.cuh analog) that makes 100M×96 fit one 16 GB
      chip next to its own transients; such an index searches at full
      strip speed but cannot extend() or re-derive codes.
    """
    import numpy as np

    res = res or current_resources()
    if params.metric == "cosine":
        raise ValueError("build_streaming: cosine needs normalized chunks; "
                         "normalize inside chunk_fn and use inner_product")
    if store not in ("codes", "cache"):
        raise ValueError(f"unknown store mode {store!r}")
    if store == "cache" and params.codebook_kind == "cluster":
        raise ValueError(
            "store='cache' supports subspace codebooks only (the chunked "
            "decode regroups rows across lists, which a per-list codebook "
            "cannot follow); use store='codes' for PER_CLUSTER")
    pq_dim = params.pq_dim or _auto_pq_dim(dim)
    dsub = -(-dim // pq_dim)
    rot_dim = pq_dim * dsub
    cd = int(cache_dim) or rot_dim
    if not 0 < cd <= rot_dim:
        raise ValueError(f"cache_dim={cd} out of range (1..{rot_dim})")
    n_codes = 1 << params.pq_bits
    cluster = params.codebook_kind == "cluster"
    km_metric = ("inner_product" if params.metric == "inner_product"
                 else "sqeuclidean")
    km = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=km_metric, seed=params.seed)
    chunk = int(chunk_rows) or int(
        max(262_144, min(n, res.workspace_bytes // max(dim * 12, 1))))
    chunk = min(chunk, n)
    starts = list(range(0, n, chunk))
    group = params.group_size or _packing.auto_group_size(
        n, params.n_lists, floor=128)
    cap = params.list_size_cap
    if cap < 0:
        cap = _packing.auto_list_cap(n, params.n_lists, group)

    from raft_tpu.core.interruptible import check_interrupt

    # --- quantizers on a strided sample ------------------------------------
    t_rows = int(train_rows) or int(min(2_000_000, max(
        params.n_lists * 32, n * params.kmeans_trainset_fraction)))
    t_rows = min(t_rows, n)
    per = max(1, t_rows // len(starts))
    train_parts = [jnp.asarray(chunk_fn(s, min(s + per, n)), jnp.float32)
                   for s in starts]
    trainset = (jnp.concatenate(train_parts) if len(train_parts) > 1
                else train_parts[0])
    del train_parts
    centers = kmeans_balanced.fit(trainset, params.n_lists, km, res=res)
    key = jax.random.key(params.seed)
    _, k_rot, k_cb = jax.random.split(key, 3)
    rotation = make_rotation_matrix(k_rot, rot_dim)
    train_labels = kmeans_balanced.predict(trainset, centers, km, res=res)
    cb_rows = min(trainset.shape[0], 65536)
    resid = (_pad_rot(trainset[:cb_rows] - centers[train_labels[:cb_rows]],
                      rot_dim) @ rotation.T).reshape(cb_rows, pq_dim, dsub)
    if cluster:
        codebooks = _train_codebooks_cluster(
            resid, train_labels[:cb_rows], k_cb, n_codes,
            params.codebook_n_iters, params.n_lists)
    else:
        codebooks = _train_codebooks(resid.transpose(1, 0, 2), k_cb,
                                     n_codes, params.codebook_n_iters)
    del trainset, train_labels, resid

    # --- pass 1: streamed assignment (+ capacity diversion under a cap) ----
    n_lists = params.n_lists
    run = np.zeros(n_lists, np.int64)
    counts_np = np.zeros((len(starts), n_lists), np.int64)
    labels_chunks = []
    dropped = 0
    for ci, s in enumerate(starts):
        check_interrupt()
        e = min(s + chunk, n)
        rows = jnp.asarray(chunk_fn(s, e), jnp.float32)
        if cap:
            l1, l2 = _assign_top2(rows, centers, metric=km_metric)
            labels = _divert_to_cap(l1, l2, jnp.asarray(run, jnp.int32),
                                    jnp.int32(cap), n_lists)
        else:
            labels = kmeans_balanced.predict(rows, centers, km, res=res)
        labels_chunks.append(labels)
        c = np.asarray(jnp.bincount(jnp.minimum(labels, n_lists),
                                    length=n_lists + 1))
        counts_np[ci] = c[:n_lists]
        dropped += int(c[n_lists])
        run += c[:n_lists]
        del rows
    totals = counts_np.sum(axis=0)
    mls = int(max(group, -(-int(totals.max()) // group) * group))
    if group == 512:  # strip backend block-divisibility (pow2 chunks)
        mls = 512 * (1 << (mls // 512 - 1).bit_length())
    base_np = np.cumsum(counts_np, axis=0) - counts_np  # per-chunk offsets
    if dropped:
        from raft_tpu.core.logger import get_logger

        get_logger().warning(
            "build_streaming: %d row(s) overflowed both their nearest and "
            "second-nearest capped lists and were dropped (cap=%d); raise "
            "list_size_cap or n_lists.", dropped, cap)

    # --- pass 2: encode + offset-scatter into donated blocks ---------------
    list_ids = jnp.full((n_lists, mls), -1, jnp.int32)
    if store == "cache":
        cache = jnp.zeros((n_lists, mls, cd), jnp.int8)
        b_sum = jnp.full((n_lists, mls), jnp.inf, jnp.float32)
        rc_t = ((_pad_rot(centers, rot_dim) @ rotation.T)[:, :cd])
        for ci, s in enumerate(starts):
            check_interrupt()
            e = min(s + chunk, n)
            rows = jnp.asarray(chunk_fn(s, e), jnp.float32)
            cache, list_ids, b_sum = _scatter_chunk_cache(
                cache, list_ids, b_sum, rows, labels_chunks[ci],
                jnp.asarray(base_np[ci], jnp.int32), jnp.int32(s),
                centers, rotation, codebooks, rc_t,
                pq_dim, params.pq_bits, cluster, cd)
            del rows
        scale = jnp.maximum(jnp.max(jnp.abs(codebooks)), 1e-30) / 127.0
        if params.metric in ("inner_product",):
            b_sum = jnp.where(list_ids >= 0, 0.0, jnp.inf)
        out = IvfPqIndex(
            centers, rotation, codebooks,
            jnp.zeros((n_lists, mls, 0), jnp.uint8), list_ids, b_sum,
            cache, params.metric, params.pq_bits, group,
            decoded_scale=scale, codebook_kind=params.codebook_kind,
            pq_dim_hint=pq_dim)
    else:
        code_w = packed_width(pq_dim, params.pq_bits)
        list_codes = jnp.zeros((n_lists, mls, code_w), jnp.uint8)
        for ci, s in enumerate(starts):
            check_interrupt()
            e = min(s + chunk, n)
            rows = jnp.asarray(chunk_fn(s, e), jnp.float32)
            list_codes, list_ids = _scatter_chunk(
                list_codes, list_ids, rows, labels_chunks[ci],
                jnp.asarray(base_np[ci], jnp.int32), jnp.int32(s),
                centers, rotation, codebooks,
                pq_dim, params.pq_bits, cluster, code_w)
            del rows
        b_sum = _compute_b_sum(centers, rotation, codebooks, list_codes,
                               list_ids, params.metric, pq_dim,
                               params.pq_bits, cluster=cluster)
        out = IvfPqIndex(
            centers, rotation, codebooks, list_codes, list_ids, b_sum,
            None, params.metric, params.pq_bits, group,
            codebook_kind=params.codebook_kind, pq_dim_hint=pq_dim)
    out._streaming_dropped = dropped
    return out


_divert_to_cap = _packing.divert_to_cap


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits", "cluster"))
def _decode_lists(codebooks, list_codes, pq_dim=None, pq_bits: int = 8,
                  cluster: bool = False):
    """int8-quantized RESIDUAL reconstruction cb[codes] per entry, in
    rotated space — the strip-scan cache at rot_dim bytes/entry (the
    quantized-reconstruction analog of the reference's fp8-compressed LUT,
    detail/ivf_pq_fp_8bit.cuh: precision traded for bandwidth, re-ranked by
    refine; the residual matmul is 2·rot_dim FLOP/entry where the one-hot
    LUT scan pays 2·pq_dim·n_codes for the same ranking).

    Residual-only (round 3): the −2⟨q, R·c_l⟩ half of the cross term is
    constant within a (query, probe) pair, so the merge adds it exactly
    AFTER extraction (strip_search's pair_const) — the cache only carries
    codebook entries, whose max|·| is a far tighter int8 scale than the
    full reconstruction's. The scale is max|codebooks|/127 — exact, data
    independent, and identical on every shard for free.

    Returns (cache int8 (n_lists, m, rot_dim), scale 0-d fp32)."""
    scale = jnp.maximum(jnp.max(jnp.abs(codebooks)), 1e-30) / 127.0
    return _decode_lists_scaled(codebooks, list_codes, scale, pq_dim,
                                pq_bits, cluster), scale


def _codes_view(list_codes, pq_dim, pq_bits):
    """Per-list unpacked (m, pq_dim) codes from possibly bit-packed rows."""
    if pq_dim is None or list_codes.shape[-1] == pq_dim:
        return list_codes
    return unpack_codes(list_codes, pq_dim, pq_bits)


def _decode_lists_scaled(codebooks, list_codes, scale, pq_dim=None,
                         pq_bits: int = 8, cluster: bool = False):
    """int8 residual cache at a given dequant scale. ``cluster`` selects the
    PER_CLUSTER codebook layout (one codebook per list)."""
    n_lists, max_size = list_codes.shape[0], list_codes.shape[1]
    n_codes, dsub = codebooks.shape[1], codebooks.shape[2]
    if pq_dim is None:
        pq_dim = list_codes.shape[-1]
    rot_dim = pq_dim * dsub
    cb_q = jnp.clip(jnp.round(codebooks / scale), -127, 127).astype(jnp.int8)

    if cluster:
        def quant_one(args):
            cb_l, codes_l = args  # (c, d), (m, ·)
            codes_l = _codes_view(codes_l, pq_dim, pq_bits)
            resid = jnp.take(cb_l, codes_l.astype(jnp.int32), axis=0)
            return resid.reshape(max_size, rot_dim)

        return lax.map(quant_one, (cb_q, list_codes))

    cb_flat = cb_q.reshape(pq_dim * n_codes, dsub)
    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)[None, :]

    def quant_one(codes_l):
        codes_l = _codes_view(codes_l, pq_dim, pq_bits)
        resid = jnp.take(cb_flat, codes_l.astype(jnp.int32) + s_off, axis=0)
        return resid.reshape(max_size, rot_dim)

    return lax.map(quant_one, list_codes)


def _compute_b_sum(centers, rotation, codebooks, list_codes, list_ids, metric,
                   pq_dim=None, pq_bits: int = 8, cluster: bool = False):
    """List-side LUT half, baked per entry: Σ_s (2·(Rc_l)_s·cb[s,code] +
    |cb[s,code]|²) for L2; zeros for inner-product metrics (module docstring
    derivation). Padding entries get +inf so the scan output self-masks."""
    n_lists, max_size = list_codes.shape[0], list_codes.shape[1]
    if pq_dim is None:
        pq_dim = list_codes.shape[-1]
    pad_inf = jnp.where(list_ids >= 0, 0.0, jnp.inf).astype(jnp.float32)
    if metric in ("inner_product", "cosine"):
        return pad_inf
    dsub = codebooks.shape[2]
    n_codes = codebooks.shape[1]
    rot_dim = pq_dim * dsub
    rc = (_pad_rot(centers, rot_dim) @ rotation.T).reshape(n_lists, pq_dim, dsub)
    # B[l, s, c] = 2 (Rc_l)_s · cb[s or l, c] + |cb|²
    if cluster:
        B = 2.0 * jnp.einsum("lsd,lcd->lsc", rc, codebooks,
                             preferred_element_type=jnp.float32)
        B = B + jnp.sum(codebooks * codebooks, axis=2)[:, None, :]
    else:
        B = 2.0 * jnp.einsum("lsd,scd->lsc", rc, codebooks,
                             preferred_element_type=jnp.float32)
        B = B + jnp.sum(codebooks * codebooks, axis=2)[None]
    # per-list flat gather (take from a 1-d table per list — avoids the
    # (l, m, s, n_codes) broadcast a take_along_axis would materialize)
    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)[None, :]

    def one_list(args):
        B_l, codes_l = args  # (s, c), (m, ·)
        codes_l = _codes_view(codes_l, pq_dim, pq_bits)
        flat_idx = codes_l.astype(jnp.int32) + s_off
        return jnp.sum(jnp.take(B_l.reshape(-1), flat_idx, axis=0), axis=1)

    return lax.map(one_list, (B, list_codes)) + pad_inf


@traced("ivf_pq::extend")
def extend(index: IvfPqIndex, new_vectors, new_ids=None, res: Optional[Resources] = None) -> IvfPqIndex:
    """Encode new vectors with the existing quantizers and repack
    (ivf_pq extend analog)."""
    res = res or current_resources()
    if index.list_codes.shape[-1] == 0:
        raise ValueError(
            "cache-only streamed index (build_streaming store='cache') "
            "keeps no codes and cannot extend(); rebuild with "
            "store='codes'")
    new_vectors = jnp.asarray(new_vectors).astype(jnp.float32)
    if new_vectors.shape[1] != index.dim:
        raise ValueError(f"dim mismatch: {new_vectors.shape[1]} != {index.dim}")
    if index.metric == "cosine":
        new_vectors = new_vectors / jnp.maximum(
            jnp.linalg.norm(new_vectors, axis=1, keepdims=True), 1e-30
        )
    km_metric = "inner_product" if index.metric in ("cosine", "inner_product") else "sqeuclidean"
    labels = kmeans_balanced.predict(
        new_vectors, index.centers, kmeans_balanced.KMeansBalancedParams(metric=km_metric), res=res
    )
    # persisted granule; legacy indexes (group_size 0) fall back to inference
    group = index.group_size or (512 if index.max_list_size % 512 == 0 else 128)
    total = index.size + int(new_vectors.shape[0])
    cap = _packing.auto_list_cap(total, index.n_lists, group)
    # spill BEFORE encoding: residuals are taken against the assigned center
    labels = _packing.spill_to_cap(
        new_vectors, index.centers, labels, km_metric, cap,
        base_counts=index.list_sizes(),
    )
    dsub = index.codebooks.shape[2]
    cluster = index.codebook_kind == "cluster"
    resid = _pad_rot(new_vectors - index.centers[labels], index.rot_dim) @ index.rotation.T
    resid3 = resid.reshape(new_vectors.shape[0], index.pq_dim, dsub)
    if cluster:
        codes = _encode_cluster(resid3, labels, index.codebooks)
    else:
        codes = _encode(resid3, index.codebooks)
    codes = pack_codes(codes, index.pq_bits)

    old_valid = index.list_ids.reshape(-1) >= 0
    old_codes = index.list_codes.reshape(-1, index.list_codes.shape[-1])[old_valid]
    if old_codes.shape[-1] != packed_width(index.pq_dim, index.pq_bits):
        # legacy pre-packing index (pq_bits < 8 stored one byte/subdim):
        # repack so widths match the newly encoded rows
        old_codes = pack_codes(old_codes, index.pq_bits)
    old_ids = index.list_ids.reshape(-1)[old_valid]
    old_labels = jnp.repeat(
        jnp.arange(index.n_lists, dtype=jnp.int32), index.max_list_size
    )[old_valid]
    if new_ids is None:
        start = int(jnp.max(old_ids) + 1) if old_ids.size else 0
        new_ids = jnp.arange(start, start + new_vectors.shape[0], dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    all_codes = jnp.concatenate([old_codes, codes])
    all_ids = jnp.concatenate([old_ids, new_ids])
    all_labels = jnp.concatenate([old_labels, labels])
    list_codes, list_ids = _pack_lists(all_codes, all_ids, all_labels, index.n_lists, group)
    b_sum = _compute_b_sum(
        index.centers, index.rotation, index.codebooks, list_codes, list_ids,
        index.metric, index.pq_dim, index.pq_bits, cluster=cluster,
    )
    return IvfPqIndex(
        index.centers, index.rotation, index.codebooks, list_codes, list_ids,
        b_sum, None, index.metric, index.pq_bits, group,
        codebook_kind=index.codebook_kind, pq_dim_hint=index.pq_dim,
    )


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("l2",))
def _ragged_bias_pq(b_sum, centers, rotation, list_ids, filter, l2: bool):
    """Per-entry bias for the decoded scan: ‖x̂‖² = ‖R·c_l‖² + b_sum for L2
    (b_sum already carries +inf at padding), 0/+inf for ip/cosine; filtered
    entries get +inf."""
    if l2:
        rot_dim = rotation.shape[0]
        rc2 = dist_mod.sqnorm(_pad_rot(centers, rot_dim) @ rotation.T)
        bias = rc2[:, None] + b_sum
    else:
        bias = b_sum
    return _filtering.apply_filter_bias(bias, list_ids, filter)


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "select_algo",
                     "compute_dtype", "l2", "classes", "class_counts",
                     "interpret"),
)
def _ragged_fused_pq(queries, centers, rotation, b_sum, list_ids, decoded,
                     decoded_scale, filter, cls_ord,
                     k, n_probes, metric, select_algo, compute_dtype, l2,
                     classes, class_counts, interpret):
    """The whole PQ strip search of one query tile as ONE dispatch (round-4; see ivf_flat.
    _ragged_fused): prep + device planning + int8 strip kernel + finalize,
    zero host syncs. The in-kernel tournament top-k is allowed
    (approx_ok=True): this path over-fetches and exact-re-ranks via
    neighbors/refine, which absorbs its ~1e-4/row bin-collision loss."""
    from raft_tpu.ops.strip_scan import strip_search_traced

    # ledger registration for the TPU-default backend too (trace time
    # only): a retrace on the platform of record must not be invisible
    obs_compile.trace_event(
        "ivf_pq.search_ragged", queries=queries, centers=centers,
        rotation=rotation, b_sum=b_sum, list_ids=list_ids, decoded=decoded,
        decoded_scale=decoded_scale, filter=filter, cls_ord=cls_ord,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "l2": l2, "classes": classes, "class_counts": class_counts,
                "interpret": interpret})

    sa = probe_algo(select_algo, interpret, centers.shape[0])
    probes, qr_scaled, bias, pair_const = _pq_search_prep(
        queries, centers, rotation, b_sum, list_ids, decoded_scale,
        filter, n_probes, metric, sa, compute_dtype, l2,
    )
    # truncated cache (build_streaming store="cache", cache_dim < rot_dim):
    # the cache keeps only the leading rotated coords, so the query operand
    # drops the same tail — b_sum was built in the truncated space and the
    # center terms (‖Rc‖², −2⟨q,c⟩) stay exact
    if decoded.shape[-1] < qr_scaled.shape[-1]:
        qr_scaled = qr_scaled[:, :decoded.shape[-1]]
    vals, ids = strip_search_traced(
        qr_scaled, probes, decoded, bias, list_ids, cls_ord,
        classes, class_counts, int(k), int(k), -2.0 if l2 else -1.0,
        interpret, pair_const=pair_const, approx_ok=True,
    )
    from raft_tpu.neighbors.ivf_flat import _finalize_ragged

    # shared fused finalizer: same score algebra — ‖Rq‖² == ‖q‖²
    # (orthogonal rotation; padding adds nothing), and cosine/ip scan
    # values use the same alpha=-1 convention
    return _finalize_ragged(vals, ids, queries, metric)


def _search_ragged_pq(index, queries, k, n_probes, filter, select_algo, res):
    """int8 residual-cache strip scan (ops/strip_scan.py): same ranking as
    the LUT formulation, at 2·rot_dim MXU FLOPs and rot_dim HBM bytes per
    probed entry instead of 2·pq_dim·n_codes FLOPs. The dequant scale folds
    into the query operand; the exact −2⟨q, R·c_l⟩ pair term rides the
    merge's pair_const (see _decode_lists)."""
    from raft_tpu.neighbors.ivf_flat import _ragged_plan_static

    if index.decoded is None:
        # lazy decode-cache fill, kept on the index instance
        index.decoded, index.decoded_scale = _decode_lists(
            index.codebooks, index.list_codes, pq_dim=index.pq_dim,
            pq_bits=index.pq_bits, cluster=index.codebook_kind == "cluster",
        )
    l2 = index.metric in ("sqeuclidean", "euclidean")
    # plan with the dim the kernel actually scans: a truncated streamed
    # cache (store="cache", cache_dim < rot_dim) narrows the fetch classes
    classes, class_counts, cls_ord, q_tile = _ragged_plan_static(
        index, n_probes, k, res, int(index.decoded.shape[-1]))
    from raft_tpu.ops.strip_scan import note_topk_routes, per_tile

    if obs.enabled():
        interpret = jax.default_backend() != "tpu"
        note_topk_routes(classes, int(k), True, queries.shape[0], q_tile,
                         index.n_lists, n_probes, int(k),
                         probe_algo(select_algo, interpret, index.n_lists),
                         interpret)
    return per_tile(lambda lo, hi: _ragged_fused_pq(
        queries[lo:hi], index.centers, index.rotation, index.b_sum,
        index.list_ids, index.decoded, index.decoded_scale, filter, cls_ord,
        int(k), n_probes, index.metric, select_algo, res.compute_dtype, l2,
        classes, class_counts, jax.default_backend() != "tpu",
    ), queries.shape[0], q_tile, "ivf_pq::launch", "ivf_pq.search.launches")


def _pq_probe_prep(queries, centers, rotation, n_probes, select_algo, l2,
                   rotation_kind: str = "dense"):
    """Probe selection + query rotation + the exact per-pair center term —
    THE one copy of the op sequence both the packed strip path and the
    paged Pallas path consume (bitwise parity between them is the paged
    plane's acceptance contract, so this math must not fork).
    ``rotation_kind`` selects the apply (ops/linalg.rotate_rows): the
    dense gemm, or the SRHT butterfly ivf_bq's Hadamard indexes carry."""
    with jax.named_scope("coarse_probe"):
        ip_c = dist_mod.matmul_t(queries, centers, None, "highest")
        if l2:
            # expanded L2 from the single gemm (review: _expanded_distance
            # would recompute the same q×n_lists inner products)
            coarse = (dist_mod.sqnorm(queries)[:, None]
                      + dist_mod.sqnorm(centers)[None, :] - 2.0 * ip_c)
        else:
            coarse = -ip_c
        _, probes = select_k(coarse, n_probes, select_min=True,
                             algo=select_algo)
    qr = linalg.rotate_rows(queries, rotation, rotation_kind)
    alpha = -2.0 if l2 else -1.0
    pair_const = alpha * jnp.take_along_axis(ip_c, probes, axis=1)
    return probes, qr, pair_const


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "metric", "select_algo", "compute_dtype",
                     "l2"),
)
def _pq_search_prep(queries, centers, rotation, b_sum, list_ids,
                    decoded_scale, filter, n_probes, metric, select_algo,
                    compute_dtype, l2):
    probes, qr, pair_const = _pq_probe_prep(
        queries, centers, rotation, n_probes, select_algo, l2)
    bias = _ragged_bias_pq(b_sum, centers, rotation, list_ids, filter, l2)
    return probes, qr * decoded_scale, bias, pair_const


def _query_luts(queries, rotation, codebooks, metric, lut_dtype):
    """Per-query LUT A (q, pq_dim, n_codes): the query-only half of the scan
    (module docstring). One einsum — rides the MXU."""
    q = queries.shape[0]
    pq_dim, n_codes, dsub = codebooks.shape
    rq = (_pad_rot(queries, pq_dim * dsub) @ rotation.T).reshape(q, pq_dim, dsub)
    A = jnp.einsum("qsd,scd->qsc", rq, codebooks, preferred_element_type=jnp.float32)
    if metric in ("sqeuclidean", "euclidean"):
        A = -2.0 * A
    else:  # inner product family: score = coarse_ip + Σ (Rq)·cb; negate → min
        A = -A
    return A.astype(lut_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "q_tile", "select_algo",
                     "compute_dtype", "pq_dim", "pq_bits", "cluster"),
)
def _search_impl_jnp(
    queries, centers, rotation, codebooks, list_codes, list_ids, b_sum, filter,
    k, n_probes, metric, q_tile, select_algo, compute_dtype,
    pq_dim, pq_bits, cluster,
):
    """Gather-backend search: stage-1 coarse gemm + per-query LUT + code
    lookup via take_along_axis, tiled over queries."""
    # compile-ledger registration: runs at trace time only (obs/compile.py)
    obs_compile.trace_event(
        "ivf_pq.search", queries=queries, centers=centers,
        rotation=rotation, codebooks=codebooks, list_codes=list_codes,
        list_ids=list_ids, b_sum=b_sum, filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "q_tile": q_tile, "select_algo": select_algo,
                "compute_dtype": compute_dtype, "pq_dim": pq_dim,
                "pq_bits": pq_bits, "cluster": cluster})
    q, dim = queries.shape
    n_lists, max_size = list_codes.shape[0], list_codes.shape[1]
    l2 = metric in ("sqeuclidean", "euclidean")

    # stage 1: coarse distances; keep probed values (they're the d² constant)
    if l2:
        coarse = dist_mod._expanded_distance(
            queries, centers, "sqeuclidean", compute_dtype, "highest"
        )
    else:
        coarse = -dist_mod.matmul_t(queries, centers, compute_dtype, "highest")
    coarse_vals, probes = select_k(coarse, n_probes, select_min=True, algo=select_algo)

    n_codes = codebooks.shape[1]
    dsub = codebooks.shape[2]
    if not cluster:
        luts = _query_luts(queries, rotation, codebooks, metric, jnp.float32)
        luts = luts.reshape(q, -1)  # (q, s*nc) flat per-query tables
    else:
        # per-cluster codebooks: the LUT varies by list, so it is built per
        # probed pair inside the tile scan; precompute rotated queries here
        luts = (_pad_rot(queries, pq_dim * dsub) @ rotation.T).reshape(
            q, pq_dim, dsub)

    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)[None, None, :]

    def scan_tile(args):
        q_lut, probe_blk, cvals_blk = args  # (qt, ·), (qt, p), (qt, p)
        codes = _codes_view(list_codes[probe_blk], pq_dim, pq_bits) \
            .astype(jnp.int32)                           # (qt, p, m, s)
        ids = list_ids[probe_blk]  # (qt, p, m)
        if cluster:
            # per-pair LUT A[q, p, s, c] = sign·⟨(Rq)_s, cb_probe[c]⟩, then a
            # doubly-vmapped flat-table take (no broadcast materialization)
            cb_p = codebooks[probe_blk]                  # (qt, p, c, d)
            A = jnp.einsum("qsd,qpcd->qpsc", q_lut, cb_p,
                           preferred_element_type=jnp.float32)
            A = ((-2.0 if l2 else -1.0) * A).reshape(
                codes.shape[0], codes.shape[1], pq_dim * n_codes)
            flat_idx = codes + s_off[None]               # (qt, p, m, s)
            picked = jax.vmap(jax.vmap(
                lambda t, i: jnp.take(t, i, axis=0)))(A, flat_idx)
        else:
            # LUT lookup: out[q,p,m] = Σ_s q_lut[q, s*nc + codes[q,p,m,s]]
            # (per-query 1-d table take under vmap — no broadcast
            # materialization)
            flat_idx = codes + s_off[None]
            picked = jax.vmap(lambda lut, idx: jnp.take(lut, idx, axis=0))(q_lut, flat_idx)
        d = jnp.sum(picked, axis=3) + b_sum[probe_blk] + cvals_blk[:, :, None]
        if l2:
            d = jnp.maximum(d, 0.0)
            if metric == "euclidean":
                d = jnp.sqrt(d)
        flat_ids = ids.reshape(ids.shape[0], -1)
        d = d.reshape(flat_ids.shape)
        valid = flat_ids >= 0
        if filter is not None:
            valid = valid & filter.test(flat_ids)
        d = jnp.where(valid, d, jnp.inf)
        vals, sel = select_k(d, k, select_min=True, algo=select_algo)
        out_ids = jnp.where(jnp.isinf(vals), -1, jnp.take_along_axis(flat_ids, sel, axis=1))
        return vals, out_ids

    if q_tile >= q:
        vals, ids = scan_tile((luts, probes, coarse_vals))
    else:
        n_tiles = -(-q // q_tile)
        pad = n_tiles * q_tile - q
        lp = jnp.pad(luts, ((0, pad),) + ((0, 0),) * (luts.ndim - 1))
        pp = jnp.pad(probes, ((0, pad), (0, 0)))
        cp = jnp.pad(coarse_vals, ((0, pad), (0, 0)))
        vals, ids = lax.map(
            scan_tile,
            (
                lp.reshape((n_tiles, q_tile) + luts.shape[1:]),
                pp.reshape(n_tiles, q_tile, n_probes),
                cp.reshape(n_tiles, q_tile, n_probes),
            ),
        )
        vals = vals.reshape(-1, k)[:q]
        ids = ids.reshape(-1, k)[:q]
    if not l2:
        vals = -vals  # back to raw inner product (bigger = closer)
    return vals, ids


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "metric", "q_tile", "qpl_cap", "select_algo",
        "compute_dtype", "interpret", "pq_dim", "pq_bits",
    ),
)
def _search_impl_pallas(
    queries, centers, rotation, codebooks, list_codes, list_ids, b_sum, filter,
    k, n_probes, metric, q_tile, qpl_cap, select_algo, compute_dtype, interpret,
    pq_dim=None, pq_bits=8,
):
    """Pallas-backend search: list-centric scan kernel (ops/pq_scan.py).
    Subspace codebooks only (the kernel's LUT is per query, not per list)."""
    # ledger registration (trace time only): the qpl_cap escalation retry
    # DELIBERATELY retraces — the ledger attributes it to static.qpl_cap
    obs_compile.trace_event(
        "ivf_pq.search_pallas", queries=queries, centers=centers,
        rotation=rotation, codebooks=codebooks, list_codes=list_codes,
        list_ids=list_ids, b_sum=b_sum, filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "q_tile": q_tile, "qpl_cap": qpl_cap,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "interpret": interpret, "pq_dim": pq_dim,
                "pq_bits": pq_bits})
    q, dim = queries.shape
    n_lists, max_size = list_codes.shape[0], list_codes.shape[1]
    if pq_dim is None:
        pq_dim = list_codes.shape[-1]
    n_codes = codebooks.shape[1]
    l2 = metric in ("sqeuclidean", "euclidean")

    if l2:
        coarse = dist_mod._expanded_distance(
            queries, centers, "sqeuclidean", compute_dtype, "highest"
        )
    else:
        coarse = -dist_mod.matmul_t(queries, centers, compute_dtype, "highest")
    coarse_vals, probes = select_k(coarse, n_probes, select_min=True, algo=select_algo)

    luts = _query_luts(queries, rotation, codebooks, metric, jnp.bfloat16)
    luts = luts.reshape(q, -1)  # (q, f)
    codes_t = jnp.transpose(
        _codes_view(list_codes, pq_dim, pq_bits), (0, 2, 1)
    )  # (L, s, m), list dim minor

    def scan_tile(args):
        luts_t, probe_blk, cvals_blk, qmask = args  # (qt, f), (qt, p), (qt, p), (qt,)
        qt = probe_blk.shape[0]
        qids, slot = group_probed_pairs(probe_blk, n_lists, qpl_cap)
        # count real (query, probe) pairs beyond the per-list cap (ADVICE.md:
        # silent drops degrade recall under probe skew; surfaced to search()
        # which retries with a larger cap or falls back to the gather path)
        n_dropped = jnp.sum((slot < 0) & qmask[:, None])
        luts_g = jnp.where(
            (qids >= 0)[:, :, None], luts_t[jnp.maximum(qids, 0)], jnp.bfloat16(0)
        )
        # kernel output already includes b_sum and +inf at padding entries
        grouped = pq_scan(luts_g, codes_t, b_sum, n_codes, interpret)  # (L, qpl, m)
        scores = grouped[probe_blk, jnp.maximum(slot, 0)]  # (qt, p, m)
        # dropped pairs (slot -1) and the coarse constant in one fused pass
        d = scores + jnp.where(slot >= 0, cvals_blk, jnp.inf)[:, :, None]
        d = d.reshape(qt, -1)
        if filter is not None:
            ids_full = list_ids[probe_blk].reshape(qt, -1)
            d = jnp.where(filter.test(ids_full), d, jnp.inf)
        vals, sel = select_k(d, k, select_min=True, algo=select_algo)
        # map only the k winners: flat pos -> (probe slot, in-list pos) -> id
        win_list = jnp.take_along_axis(probe_blk, sel // max_size, axis=1)
        out_ids = list_ids[win_list, sel % max_size]
        out_ids = jnp.where(jnp.isinf(vals), -1, out_ids)
        if l2:
            vals = jnp.maximum(vals, 0.0)
            if metric == "euclidean":
                vals = jnp.sqrt(vals)
        return vals, out_ids, n_dropped

    if q_tile >= q:
        vals, ids, dropped = scan_tile(
            (luts, probes, coarse_vals, jnp.ones((q,), jnp.bool_))
        )
    else:
        n_tiles = -(-q // q_tile)
        pad = n_tiles * q_tile - q
        lp = jnp.pad(luts, ((0, pad), (0, 0)))
        pp = jnp.pad(probes, ((0, pad), (0, 0)))
        cp = jnp.pad(coarse_vals, ((0, pad), (0, 0)))
        qm = jnp.pad(jnp.ones((q,), jnp.bool_), (0, pad))
        vals, ids, dropped = lax.map(
            scan_tile,
            (
                lp.reshape(n_tiles, q_tile, luts.shape[1]),
                pp.reshape(n_tiles, q_tile, n_probes),
                cp.reshape(n_tiles, q_tile, n_probes),
                qm.reshape(n_tiles, q_tile),
            ),
        )
        vals = vals.reshape(-1, k)[:q]
        ids = ids.reshape(-1, k)[:q]
        dropped = jnp.sum(dropped)
    if not l2:
        vals = -vals
    return vals, ids, dropped


@traced("ivf_pq::search")
def search(
    index: IvfPqIndex,
    queries,
    k: int,
    n_probes: int = 20,
    filter: Optional[Bitset] = None,
    select_algo: str = "exact",
    backend: str = "auto",
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Approximate k-NN over the PQ-compressed lists
    (detail/ivf_pq_search.cuh:731). Returns (distances, indices); distances
    are PQ approximations — pipe through :mod:`raft_tpu.neighbors.refine`
    for exact re-ranking (the reference does the same, refine-inl.cuh:70).
    """
    res = res or current_resources()
    from raft_tpu.ops.strip_scan import occupancy_stats, strip_eligible

    aligned = strip_eligible(index.max_list_size) and k <= 512
    pallas_ok = index.max_list_size % 128 == 0
    if index.list_codes.shape[-1] == 0:
        # cache-only streamed index: the int8 strip cache IS the payload —
        # no codes for the LUT/gather backends to read
        if not aligned:
            raise ValueError(
                "cache-only streamed index needs a strip-eligible "
                f"max_list_size (power-of-two multiple of 512 and k <= "
                f"512), got {index.max_list_size} / k={k}")
        backend = "ragged"
    if backend == "auto":
        # ragged decoded scan on TPU (the fast path); jnp gather elsewhere
        # (the exact-fp32 oracle; its take_along_axis crashes the TPU
        # runtime at large shapes, so it is never auto-picked there);
        # misaligned (old / small-group) indexes fall back to the LUT
        # kernel on TPU, and — if even 128-alignment is missing (legacy
        # 64-granule index, ADVICE.md round-2 high finding) — to the gather
        # path, which such small-list indexes can afford
        if jax.default_backend() == "tpu":
            backend = "ragged" if aligned else ("pallas" if pallas_ok else "gather")
        else:
            backend = "gather"
    if backend not in ("ragged", "pallas", "gather"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "pallas" and index.codebook_kind == "cluster":
        # the LUT kernel's table is per query; PER_CLUSTER tables are per
        # list — served by the strip cache / gather paths instead
        backend = "ragged" if aligned and jax.default_backend() == "tpu" else "gather"
    queries, n_probes, filter, scan_attrs = _entry.prepare(
        "ivf_pq.search", queries, k, n_probes, filter, backend,
        dim=index.dim, n_lists=index.n_lists, metric=index.metric,
        rows=index.max_list_size, count_rows=True)
    if obs.enabled():
        # roofline note (round 15): static FLOP/byte model + the strip
        # planner's occupancy on the ragged path
        rot_dim_obs = int(index.rotation.shape[0])
        occ = None if backend != "ragged" else _entry.list_occupancy(
            index, int(queries.shape[0]), n_probes, k, res,
            functools.partial(occupancy_stats, dim=rot_dim_obs))
        obs_roofline.note_dispatch(
            "ivf_pq.search",
            {"q": int(queries.shape[0]), "dim": index.dim,
             "n_lists": index.n_lists,
             "max_list_size": index.max_list_size,
             "pq_dim": index.pq_dim, "pq_bits": index.pq_bits,
             "n_probes": int(n_probes), "k": int(k),
             "rot_dim": rot_dim_obs},
            occupancy=occ)
    from raft_tpu.resilience import faultpoint

    faultpoint("ivf_pq.search.scan")
    # one scan-phase span regardless of backend (entered exactly once);
    # attrs are built inside the enabled gate above so the off path stays
    # one branch
    scan_span = obs.record_span("ivf_pq::scan", attrs=scan_attrs)
    if backend == "ragged":
        if not aligned:
            raise ValueError(
                f"ragged backend needs max_list_size = a power-of-two "
                f"multiple of 512, got {index.max_list_size}; rebuild with "
                "group_size=512 (or use backend='pallas'/'gather')"
            )
        # cosine included in _finalize_ragged's fused dispatch
        with scan_span:
            return _search_ragged_pq(
                index, queries, int(k), n_probes, filter, select_algo, res
            )
    if backend == "pallas":
        if not pallas_ok:
            raise ValueError(
                f"pallas backend needs max_list_size % 128 == 0, got "
                f"{index.max_list_size}; rebuild with group_size=128 "
                "(or use backend='gather')"
            )
        p = n_probes
        n_codes = index.codebooks.shape[1]
        # per (list, slot): fp32 scores row + the bf16 gathered LUT row
        # (ADVICE.md: the luts_g block dominates at pq_bits=8 and must be
        # part of the budget)
        per_slot = index.max_list_size * 4 + index.pq_dim * n_codes * 2

        def _align16(v):
            return -(-max(16, int(v)) // 16) * 16

        # initial sizing: cap = 2x the mean per-list load; the workspace
        # constraint is on cap (the (n_lists, cap, ·) scores/LUT blocks),
        # shrinking the query tile shrinks the cap a tile needs
        q_tile = queries.shape[0]
        qpl_cap = _align16(2 * q_tile * p // index.n_lists)
        while index.n_lists * qpl_cap * per_slot > res.workspace_bytes and q_tile > 64:
            q_tile //= 2
            qpl_cap = _align16(2 * q_tile * p // index.n_lists)
        qpl_cap = min(qpl_cap, _align16(q_tile))

        # drop-detect + escalate (ADVICE.md medium finding — silent drops
        # degraded recall). A query probes each list at most once, so
        # cap >= q_tile provably cannot drop: the loop terminates with zero
        # drops. The gather backend is NOT a fallback here — large-shape
        # take_along_axis crashes the TPU runtime.
        with scan_span:
            while True:
                with obs.record_launch("ivf_pq::launch",
                                       "ivf_pq.search.launches"):
                    vals, ids, dropped = _search_impl_pallas(
                        queries, index.centers, index.rotation,
                        index.codebooks, index.list_codes, index.list_ids,
                        index.b_sum, filter, int(k), n_probes, index.metric,
                        int(q_tile), int(qpl_cap), select_algo,
                        res.compute_dtype, jax.default_backend() != "tpu",
                        index.pq_dim, index.pq_bits,
                    )
                dropped = int(dropped)
                if dropped == 0:
                    break
                if qpl_cap >= q_tile:
                    raise RuntimeError(
                        f"ivf_pq pallas scan dropped {dropped} pairs at "
                        f"qpl_cap={qpl_cap} >= q_tile={q_tile}; this cannot "
                        "happen — please report"
                    )
                qpl_cap = min(_align16(2 * qpl_cap), _align16(q_tile))
                if index.n_lists * qpl_cap * per_slot > res.workspace_bytes:
                    _log.warning(
                        "ivf_pq pallas scan exceeding workspace budget to avoid "
                        "dropping pairs (qpl_cap=%d); consider a larger "
                        "Resources.workspace_bytes", qpl_cap,
                    )
                _log.warning(
                    "ivf_pq pallas scan dropped %d probed pairs (skewed probes); "
                    "retrying with qpl_cap=%d (one retrace)", dropped, qpl_cap,
                )
    if backend == "gather":
        # tile budget: the (qt, p, m, s) code gather dominates
        per_query = max(1, n_probes * index.max_list_size * (index.pq_dim * 5 + 8))
        q_tile = int(max(1, min(queries.shape[0], res.workspace_bytes // per_query)))
        with scan_span, obs.record_launch("ivf_pq::launch",
                                          "ivf_pq.search.launches"):
            vals, ids = _search_impl_jnp(
                queries, index.centers, index.rotation, index.codebooks,
                index.list_codes, index.list_ids, index.b_sum, filter,
                int(k), n_probes, index.metric, q_tile, select_algo,
                res.compute_dtype, index.pq_dim, index.pq_bits,
                index.codebook_kind == "cluster",
            )
    if index.metric == "cosine":
        vals = jnp.where(ids >= 0, 1.0 - vals, jnp.inf)
    return vals, ids


# ---------------------------------------------------------------------------
# Paged search (serving layer): scan a PagedListStore's encoded pages
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits"))
def _row_b_sum(centers, rotation, codebooks, codes, labels, pq_dim, pq_bits):
    """Per-row list-side LUT half for freshly encoded rows: the SAME
    B[l, s, c] table and Σ_s reduction as :func:`_compute_b_sum`, gathered
    by each row's label — paged↔packed parity needs the aux bitwise
    equal, not merely close. Subspace codebooks only (the serving store's
    constraint)."""
    n_lists = centers.shape[0]
    n_codes = codebooks.shape[1]
    dsub = codebooks.shape[2]
    rot_dim = pq_dim * dsub
    rc = (_pad_rot(centers, rot_dim) @ rotation.T).reshape(n_lists, pq_dim, dsub)
    B = 2.0 * jnp.einsum("lsd,scd->lsc", rc, codebooks,
                         preferred_element_type=jnp.float32)
    B = B + jnp.sum(codebooks * codebooks, axis=2)[None]
    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)[None, :]
    flat_idx = _codes_view(codes, pq_dim, pq_bits).astype(jnp.int32) + s_off
    picked = jnp.take_along_axis(
        B.reshape(n_lists, -1)[labels], flat_idx, axis=1)
    return jnp.sum(picked, axis=1)


@jax.jit
def _center_rot_sqnorm(centers, rotation):
    """‖R·c̃_l‖² per list — the per-list constant of the decoded-cache
    scan bias (:func:`_ragged_bias_pq`'s ``rc2``), shared with the paged
    store so its per-row bias pool stays bitwise-parity with the packed
    formula."""
    return dist_mod.sqnorm(_pad_rot(centers, rotation.shape[0]) @ rotation.T)


@functools.partial(jax.jit, static_argnames=("pq_dim", "pq_bits"))
def _decode_code_rows(codebooks, codes, scale, pq_dim, pq_bits):
    """int8 decoded-residual rows for freshly encoded codes — the per-row
    twin of :func:`_decode_lists_scaled` (same quantized codebook, same
    flat gather), so a paged store's incremental cache rows are bitwise
    identical to the packed decode of the same codes. Subspace codebooks
    only (the serving store's constraint)."""
    n_codes, dsub = codebooks.shape[1], codebooks.shape[2]
    rot_dim = pq_dim * dsub
    cb_q = jnp.clip(jnp.round(codebooks / scale), -127, 127).astype(jnp.int8)
    cb_flat = cb_q.reshape(pq_dim * n_codes, dsub)
    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)[None, :]
    cv = _codes_view(codes, pq_dim, pq_bits)
    resid = jnp.take(cb_flat, cv.astype(jnp.int32) + s_off, axis=0)
    return resid.reshape(codes.shape[0], rot_dim)


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "q_tile", "select_algo",
                     "compute_dtype", "pq_dim", "pq_bits"),
)
def _paged_impl(
    queries, centers, rotation, codebooks, pages, page_ids, page_aux, table,
    filter, k, n_probes, metric, q_tile, select_algo, compute_dtype,
    pq_dim, pq_bits,
):
    """Paged-store scan: the gather-backend LUT search
    (:func:`_search_impl_jnp`) re-shaped over (page-table, page) instead of
    a padded list axis. Every per-candidate op is kept identical so a
    fully-compacted store is bit-parity with the packed scan; empty page
    slots self-mask through the +inf aux (the packed padding convention)
    and the ``ids >= 0`` validity mask covers tombstones. All operand
    shapes derive from CAPACITY (page pool, table width) — appends and
    tombstones re-dispatch this same program."""
    # ledger registration (runs at trace time only): a growth retrace
    # lands attributed to the operand that grew (pages / table)
    obs_compile.trace_event(
        "ivf_pq.paged_scan", queries=queries, centers=centers,
        rotation=rotation, codebooks=codebooks, pages=pages,
        page_ids=page_ids, page_aux=page_aux, table=table, filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "q_tile": q_tile, "select_algo": select_algo,
                "compute_dtype": compute_dtype, "pq_dim": pq_dim,
                "pq_bits": pq_bits})
    q, dim = queries.shape
    l2 = metric in ("sqeuclidean", "euclidean")
    if l2:
        coarse = dist_mod._expanded_distance(
            queries, centers, "sqeuclidean", compute_dtype, "highest"
        )
    else:
        coarse = -dist_mod.matmul_t(queries, centers, compute_dtype, "highest")
    coarse_vals, probes = select_k(coarse, n_probes, select_min=True,
                                   algo=select_algo)
    n_codes = codebooks.shape[1]
    luts = _query_luts(queries, rotation, codebooks, metric, jnp.float32)
    luts = luts.reshape(q, -1)
    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)

    def scan_tile(args):
        q_lut, probe_blk, cvals_blk = args  # (qt, ·), (qt, p), (qt, p)
        tbl = table[probe_blk]                        # (qt, p, W)
        safe = jnp.maximum(tbl, 0)
        codes = _codes_view(pages[safe], pq_dim, pq_bits) \
            .astype(jnp.int32)                        # (qt, p, W, R, s)
        ids = jnp.where(tbl[..., None] >= 0, page_ids[safe], -1)
        flat_idx = codes + s_off[None, None, None, None, :]
        picked = jax.vmap(lambda lut, idx: jnp.take(lut, idx, axis=0))(
            q_lut, flat_idx)
        d = jnp.sum(picked, axis=4) + page_aux[safe] \
            + cvals_blk[:, :, None, None]
        if l2:
            d = jnp.maximum(d, 0.0)
            if metric == "euclidean":
                d = jnp.sqrt(d)
        flat_ids = ids.reshape(ids.shape[0], -1)
        d = d.reshape(flat_ids.shape)
        valid = flat_ids >= 0
        if filter is not None:
            valid = valid & filter.test(flat_ids)
        d = jnp.where(valid, d, jnp.inf)
        vals, sel = select_k(d, k, select_min=True, algo=select_algo)
        out_ids = jnp.where(jnp.isinf(vals), -1,
                            jnp.take_along_axis(flat_ids, sel, axis=1))
        return vals, out_ids

    vals, ids = map_row_tiles(scan_tile, (luts, probes, coarse_vals), q_tile)
    if not l2:
        vals = -vals  # back to raw inner product (bigger = closer)
    return vals, ids


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "select_algo",
                     "compute_dtype", "interpret", "impl"),
)
def _paged_fused_pq(queries, centers, rotation, cache_pool, bias_pool,
                    page_ids, table, chain_pages, decoded_scale, filter,
                    k, n_probes, metric, select_algo, compute_dtype,
                    interpret, impl):
    """The ENTIRE paged PQ Pallas search of one query tile as one jit: coarse gemm + query
    rotation, device strip planning, the page-table DMA kernel over the
    int8 decoded-residual cache pool, merge, finalize — the
    ``_ragged_fused_pq`` shape over page chains. All operands are
    capacity-shaped (zero-recompile serving contract); the exact
    −2⟨q, R·c_l⟩ term rides the merge's pair_const exactly like the
    packed path."""
    from raft_tpu.ops.strip_scan import paged_strip_search_traced

    obs_compile.trace_event(
        "ivf_pq.paged_pallas", queries=queries, centers=centers,
        rotation=rotation, cache_pool=cache_pool, bias_pool=bias_pool,
        page_ids=page_ids, table=table, chain_pages=chain_pages,
        decoded_scale=decoded_scale, filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "interpret": interpret, "impl": impl})
    l2 = metric in ("sqeuclidean", "euclidean")
    sa = probe_algo(select_algo, interpret, centers.shape[0])
    # the packed path's shared probe prep (bitwise parity by
    # construction); the bias comes from the store-maintained pool —
    # already rc2 + b_sum per row — instead of _ragged_bias_pq
    probes, qr, pair_const = _pq_probe_prep(
        queries, centers, rotation, n_probes, sa, l2)
    alpha = -2.0 if l2 else -1.0
    bias = _filtering.apply_filter_bias(bias_pool, page_ids, filter)
    vals, ids = paged_strip_search_traced(
        qr * decoded_scale, probes, cache_pool, bias, page_ids, table,
        chain_pages, int(k), int(k), alpha, interpret,
        pair_const=pair_const, impl=impl)
    from raft_tpu.neighbors.ivf_flat import _finalize_ragged

    return _finalize_ragged(vals, ids, queries, metric)


@traced("ivf_pq::search_paged")
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
    """Approximate k-NN over a mutable paged code store
    (:class:`raft_tpu.serving.PagedListStore`, kind ``"ivf_pq"``): same
    contract as :func:`search`, but the store keeps serving while rows
    stream in/out — no repack, and steady-state mutations never recompile
    this scan (its shapes depend only on store capacity).

    ``backend``: "paged_pallas" (page-table DMA strip kernel over the
    int8 decoded cache pool — the TPU engine, interpret-mode elsewhere),
    "paged_jnp" (its bit-parity jnp reference), "gather" (LUT gather scan
    — CPU default), or "auto"."""
    if store.kind != "ivf_pq":
        raise ValueError(f"expected an ivf_pq store, got {store.kind!r}")
    res = res or current_resources()
    from raft_tpu.neighbors.ivf_flat import (_paged_plan_static,
                                             paged_backend_auto)

    if backend == "auto":
        backend = paged_backend_auto(store, k)
    if backend not in ("gather", "paged_pallas", "paged_jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    # one ATOMIC store snapshot: pool/table read separately could tear
    # against a concurrent upsert's capacity growth
    if backend == "gather":
        pages, page_ids, page_aux, table = store.scan_state()
    else:
        cache_pool, bias_pool, _, page_ids, table, chain_pages = \
            store.paged_scan_state()
    width = int(table.shape[1])
    queries, n_probes, filter, scan_attrs = _entry.prepare(
        "ivf_pq.search_paged", queries, k, n_probes, filter, backend,
        dim=store.dim, n_lists=store.n_lists, metric=store.metric,
        rows=width * store.page_rows,
        standing_filter=getattr(store, "filter", None),
        attrs={"table_width": width})
    if obs.enabled():
        # roofline note (round 15): the LUT-scan cost over the capacity-
        # padded page chains (no cross-query sharing on this path), or
        # the paged Pallas model with the planner's occupancy
        q_obs = int(queries.shape[0])
        shapes = {"q": q_obs, "dim": store.dim, "n_lists": store.n_lists,
                  "page_rows": store.page_rows, "table_width": width,
                  "pq_dim": store.pq_dim, "pq_bits": store.pq_bits,
                  "n_probes": int(n_probes), "k": int(k),
                  "rot_dim": int(store.rotation.shape[0])}
        if backend == "gather":
            obs_roofline.note_dispatch("ivf_pq.paged_scan", shapes)
        else:
            obs_roofline.note_dispatch(
                "ivf_pq.paged_pallas", shapes,
                occupancy=_entry.page_occupancy(
                    store, width, q_obs, n_probes, k, res,
                    store._cache_dim, store._cache_dim))
    from raft_tpu.ops.strip_scan import per_tile
    from raft_tpu.resilience import faultpoint

    if backend != "gather":
        interpret = jax.default_backend() != "tpu"
        q_tile = _paged_plan_static(store, n_probes, k, res,
                                    store._cache_dim)
        impl = "pallas" if backend == "paged_pallas" else "jnp"
        faultpoint("ivf_pq.search_paged.scan")
        with obs.record_span("ivf_pq::paged_pallas", attrs=scan_attrs):
            with obs_compile.watch():
                # cosine is already folded by _finalize_ragged inside the
                # fused dispatch (the packed ragged path's convention)
                return per_tile(lambda lo, hi: _paged_fused_pq(
                    queries[lo:hi], store.centers, store.rotation,
                    cache_pool, bias_pool, page_ids, table, chain_pages,
                    store.decoded_scale, filter, int(k), n_probes,
                    store.metric, select_algo, res.compute_dtype,
                    interpret, impl), queries.shape[0], q_tile)
    # the (qt, p, W, R, s) unpacked-code gather dominates the working set
    per_query = max(1, n_probes * width * store.page_rows
                    * (store.pq_dim * 5 + 8))
    q_tile = int(max(1, min(queries.shape[0],
                            res.workspace_bytes // per_query)))
    faultpoint("ivf_pq.search_paged.scan")
    with obs.record_span("ivf_pq::paged_scan", attrs=scan_attrs):
        # ledger watch: a dispatch that (re)traces gets its wall-clock
        # stamped onto the ledger record (steady state stamps nothing)
        with obs_compile.watch():
            vals, ids = _paged_impl(
                queries, store.centers, store.rotation, store.codebooks,
                pages, page_ids, page_aux, table, filter,
                int(k), n_probes, store.metric, q_tile, select_algo,
                res.compute_dtype, store.pq_dim, store.pq_bits,
            )
    if store.metric == "cosine":
        vals = jnp.where(ids >= 0, 1.0 - vals, jnp.inf)
    return vals, ids


def reconstruct_rows(centers, rotation, codebooks, codes, labels,
                     pq_dim: int, pq_bits: int, dim: Optional[int] = None):
    """Approximate original vectors from packed PQ codes: the exact float
    codeword per subspace (NOT the int8 scan cache), un-rotated back to
    the input space and re-centered by each row's list centroid.
    Assignment-grade — the maintenance re-cluster's row source when the
    raw vectors are gone. Re-encoding a reconstruction against the SAME
    centers reproduces the codes exactly (the codeword is each subspace's
    nearest codeword to itself); against moved centers it is the
    principled nearest re-quantization."""
    codes = jnp.asarray(codes)
    labels = jnp.asarray(labels, jnp.int32)
    n_codes, dsub = int(codebooks.shape[1]), int(codebooks.shape[2])
    cb_flat = jnp.asarray(codebooks).reshape(pq_dim * n_codes, dsub)
    s_off = (jnp.arange(pq_dim, dtype=jnp.int32) * n_codes)[None, :]
    cv = _codes_view(codes, pq_dim, pq_bits).astype(jnp.int32)
    resid_rot = jnp.take(cb_flat, cv + s_off, axis=0).reshape(
        codes.shape[0], pq_dim * dsub)
    resid = linalg.unrotate_rows(resid_rot, rotation, "dense")
    d = int(centers.shape[1]) if dim is None else int(dim)
    return centers[labels] + resid[:, :d]
