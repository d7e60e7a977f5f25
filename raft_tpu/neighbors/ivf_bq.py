"""IVF-BQ: inverted-file index with RaBitQ-style 1-bit quantized vectors.

Reference surface: IVF-RaBitQ (PAPERS.md, "GPU-Native Approximate Nearest
Neighbor Search with IVF-RaBitQ") — a random rotation, per-residual sign
codes (1 bit/dim), and an UNBIASED distance estimator built from two
per-row correction scalars, beating PQ on both build time and search
throughput. The build has no codebook training at all: encode is one
rotation matmul + a sign, which is why BQ builds are a kmeans-only cost.

Estimator. Let c_l be the coarse center of list l, r = x − c_l the
residual, u = R·r̃ its random rotation (R orthogonal ⇒ ‖u‖ = ‖r‖), and
b = sign(u) ∈ {−1, +1}^D the stored code. RaBitQ's quotient estimator for
an inner product against any query-side vector v is

    ⟨u, v⟩  ≈  ⟨b, v⟩ · f,      f = ‖u‖² / ‖u‖₁        (unbiased over R;
                                                         property-tested in
                                                         tests/test_ivf_bq.py)

(f·b is u's least-squares projection onto b; the random rotation makes the
orthogonal error mean-free against any fixed v). From it, expanded L2:

    d̂²(q, x) = ‖q‖²                                   (finalize, shared)
             − 2⟨q, c_l⟩                               (pair_const, exact)
             + ‖c_l‖² + ‖u‖² + 2·f·⟨b, R·c̃_l⟩         (bias: baked per row)
             − 2·f·⟨b, R·q̃⟩                           (the scan matmul)

so search-time work is the coarse gemm (which also yields the exact
−2⟨q, c_l⟩ term) plus ONE ±1 contraction per probed strip — the
ops/bq_scan.py engine. Per-row storage: rot_dim/8 code bytes + 8 bytes of
correction scalars (f and the bias) — 32× compression on the code bytes
against fp32, 4× against the r04 IVF-PQ configuration (pq_dim = dim/2 at
8 bits). The estimate ranks candidates; callers hold the recall gate by
over-fetching and exact re-ranking through neighbors/refine
(:func:`search_refined`), exactly like the IVF-PQ headline path.

Storage is the shared padded-list layout (_packing.pack_lists) at a FIXED
512 granule / pow2 chunks: code rows are rot_dim/8 bytes, so strip-aligned
padding costs almost nothing and every IVF-BQ index is strip-eligible —
the packed kernel and the pure-jnp reference are the only two scan paths.
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
from raft_tpu.core.trace import traced
from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.core.serialize import load_arrays, save_arrays
from raft_tpu.neighbors import _packing
from raft_tpu.ops import distance as dist_mod
from raft_tpu.ops import linalg
from raft_tpu.ops.bq_scan import (extend_query_planes, multibit_width,
                                  pack_code_planes, pack_sign_bits)
from raft_tpu.ops.select_k import probe_algo

# legacy alias (pre-round-17 this module imported ivf_pq's private helper;
# the shared copy now lives in ops/linalg — satellite 1)
_pad_rot = linalg.pad_rot

SUPPORTED_METRICS = ("sqeuclidean", "euclidean", "inner_product", "cosine")

#: compile-ledger entry for the fused scan — a trace-count delta of zero
#: across repeated searches is the steady-state zero-recompile contract
#: asserted by the bench section and the check.sh smoke; every retrace
#: additionally lands in the ledger with the operand shape-diff that
#: caused it (obs/compile.py, the round-11 replacement for the ad-hoc
#: _BQ_TRACES counter)
_LEDGER_ENTRY = "ivf_bq.search"


def scan_trace_count() -> int:
    """(Re)traces of the fused BQ search program — a thin shim over the
    compile ledger (public name and delta semantics unchanged)."""
    from raft_tpu.obs import compile as obs_compile

    return obs_compile.trace_count(_LEDGER_ENTRY)


@dataclass(frozen=True)
class IvfBqParams:
    """Build params (IvfFlatParams shape — BQ has no codebook knobs; the
    degrees of freedom are the rotation representation and the code width).

    ``rotation_kind``: "dense" (explicit QR rotation matrix — the legacy
    representation) or "hadamard" (SRHT: sign diagonal + fast Walsh–
    Hadamard butterfly, O(d·log d) apply — the billion-scale build
    default; see ops/linalg). ``bits`` (1–4): bits per rotated dimension —
    1 is the classic sign code, 2–4 stack extra bit-planes for the
    high-recall/no-refine regime (module docstring)."""

    n_lists: int = 1024
    metric: str = "sqeuclidean"
    kmeans_n_iters: int = 20
    kmeans_trainset_fraction: float = 0.5
    # per-list occupancy cap: -1 = auto (4× mean, group-aligned), 0 = off
    list_size_cap: int = -1
    bits: int = 1
    rotation_kind: str = "dense"
    seed: int = 0

    def __post_init__(self):
        m = dist_mod.canonical_metric(self.metric)
        if m not in SUPPORTED_METRICS:
            raise ValueError(f"ivf_bq supports {SUPPORTED_METRICS}, got {self.metric!r}")
        object.__setattr__(self, "metric", m)
        if not 1 <= self.bits <= 4:
            raise ValueError(f"bits must be in [1, 4], got {self.bits}")
        if self.rotation_kind not in linalg.ROTATION_KINDS:
            raise ValueError(
                f"rotation_kind must be one of {linalg.ROTATION_KINDS}, "
                f"got {self.rotation_kind!r}")


#: fixed list granule: code rows are tiny (rot_dim/8 bytes), so the strip
#: backend's 512/pow2 alignment is near-free and every index stays
#: strip-eligible (no gather fallback path to carry)
_GROUP = 512


@jax.tree_util.register_pytree_node_class
@dataclass
class IvfBqIndex:
    """Coarse centers + rotation + packed sign codes + correction scalars.

    ``list_codes[l, j]`` holds row j's ``bits`` packed code planes over
    rot_dim dimensions (bit-plane-major per plane, ops/bq_scan
    pack_code_planes; bits=1 is the classic pack_sign_bits layout).
    ``list_scale`` is the per-row unbiasing factor f = ‖u‖²/⟨L, u⟩ (for
    bits=1, ⟨b, u⟩ = ‖u‖₁; 0 at padding); ``list_bias`` the per-row
    additive term of the estimator (module docstring; +inf at padding so
    the scan self-masks). ``list_ids[l, j] == -1`` marks padding.
    ``rotation`` is the dense orthogonal matrix for
    ``rotation_kind="dense"`` or the SRHT (rot_dim,) sign diagonal for
    ``rotation_kind="hadamard"`` (ops/linalg.rotate_rows applies either)."""

    centers: jax.Array     # (n_lists, dim) fp32 — for stage 1, unrotated
    rotation: jax.Array    # (rot_dim, rot_dim) dense | (rot_dim,) signs
    list_codes: jax.Array  # (n_lists, max_list_size, bits·rot_dim/8) uint8
    list_ids: jax.Array    # (n_lists, max_list_size) int32, -1 = padding
    list_scale: jax.Array  # (n_lists, max_list_size) fp32
    list_bias: jax.Array   # (n_lists, max_list_size) fp32, +inf at padding
    metric: str
    bits: int = 1
    rotation_kind: str = "dense"

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @property
    def rot_dim(self) -> int:
        # dense (rot_dim, rot_dim) and hadamard (rot_dim,) agree on axis 0
        return self.rotation.shape[0]

    @property
    def max_list_size(self) -> int:
        return self.list_codes.shape[1]

    @property
    def size(self) -> int:
        return int(jnp.sum(self.list_ids >= 0))

    @property
    def code_bytes_per_row(self) -> int:
        return int(self.list_codes.shape[-1])

    def list_sizes(self) -> jax.Array:
        return jnp.sum(self.list_ids >= 0, axis=1).astype(jnp.int32)

    def tree_flatten(self):
        return (self.centers, self.rotation, self.list_codes, self.list_ids,
                self.list_scale, self.list_bias), (self.metric, self.bits,
                                                   self.rotation_kind)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # -- persistence (v2 crash-safe container, core/serialize) -------------
    def save(self, path) -> None:
        save_arrays(
            path,
            {"kind": "ivf_bq", "metric": self.metric, "bits": self.bits,
             "rotation_kind": self.rotation_kind},
            {
                "centers": self.centers,
                "rotation": self.rotation,
                "list_codes": self.list_codes,
                "list_ids": self.list_ids,
                "list_scale": self.list_scale,
                "list_bias": self.list_bias,
            },
        )

    @classmethod
    def load(cls, path) -> "IvfBqIndex":
        meta, arrays = load_arrays(path)
        if meta.get("kind") != "ivf_bq":
            raise ValueError(f"not an ivf_bq index: {meta.get('kind')}")
        # legacy (pre-rotation_kind) files carry neither field: they were
        # written by the dense-QR 1-bit build, so the defaults ARE their
        # true description — old indexes load unchanged
        rkind = meta.get("rotation_kind", "dense")
        if rkind not in linalg.ROTATION_KINDS:
            # classified (resilience.classify → FATAL ValueError): a file
            # from a NEWER format revision must fail loudly by name, never
            # decode garbage through the wrong apply
            raise ValueError(
                f"unknown ivf_bq rotation_kind {rkind!r} (supported: "
                f"{linalg.ROTATION_KINDS}); the file may come from a newer "
                "format revision")
        return cls(
            jnp.asarray(arrays["centers"]),
            jnp.asarray(arrays["rotation"]),
            jnp.asarray(arrays["list_codes"]),
            jnp.asarray(arrays["list_ids"]),
            jnp.asarray(arrays["list_scale"]),
            jnp.asarray(arrays["list_bias"]),
            meta["metric"],
            int(meta.get("bits", 1)),
            rkind,
        )


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------


def auto_rot_dim(dim: int, rotation_kind: str = "dense") -> int:
    """Rotation width: dim rounded up to whole code bytes (dense), or to
    the next power of two (hadamard — the Walsh–Hadamard butterfly's
    width, which is also whole bytes at ≥ 8)."""
    if rotation_kind == "hadamard":
        return linalg.hadamard_rot_dim(dim)
    return -(-dim // 8) * 8


def _make_rotation(key, rot_dim: int, rotation_kind: str) -> jax.Array:
    """The rotation operand for either representation (the dense QR matrix
    or the SRHT sign diagonal), from one key — the single derivation
    build/build_streaming/distributed-build all share."""
    if rotation_kind == "hadamard":
        return linalg.make_srht_signs(key, rot_dim)
    return linalg.make_rotation_matrix(key, rot_dim)


def _encode_math(rows, labels, centers, rotation, rc, c2, l2: bool,
                 bits: int = 1, rotation_kind: str = "dense"):
    """Encode one row chunk (plain traceable body — :func:`_encode_chunk`
    is its jitted wrapper; the streamed-build scatter calls this inline):
    rotate the residual, quantize to ``bits``-bit levels, bake the two
    correction scalars. Returns (packed codes (m, bits·nb) uint8,
    scale (m,) fp32, bias (m,) fp32). The one definition of the
    estimator's build side — extend(), build_streaming() and the
    distributed build reuse it so the scalars cannot drift between
    flows."""
    u = linalg.rotate_rows(rows - centers[labels], rotation, rotation_kind)
    norm2 = jnp.einsum("md,md->m", u, u, preferred_element_type=jnp.float32)
    if bits == 1:
        signs = jnp.where(u >= 0, jnp.int8(1), jnp.int8(-1))
        packed = pack_sign_bits(signs)
        # ⟨b, u⟩ = ‖u‖₁ for the sign code — kept as the abs-sum so 1-bit
        # scalars stay bit-identical with every pre-multi-bit index
        proj = jnp.sum(jnp.abs(u), axis=1)
        levels_f = signs.astype(jnp.float32)
    else:
        # symmetric uniform quantizer over [−t, t], t = max|u| per row:
        # code c ∈ [0, 2^bits), dequantized LEVEL L = 2c − (2^bits−1)
        # (odd integers; bits=1 would reduce to sign). The estimator stays
        # the RaBitQ quotient f = ‖u‖²/⟨L, u⟩, which makes f·L the exact
        # projection of u onto its own code direction — unbiased over the
        # rotation by the same argument as the sign code.
        t = jnp.maximum(jnp.max(jnp.abs(u), axis=1, keepdims=True), 1e-30)
        c = jnp.clip(jnp.floor((u / t + 1.0) * (0.5 * (1 << bits))),
                     0, (1 << bits) - 1).astype(jnp.uint8)
        packed = pack_code_planes(c, bits)
        levels_f = 2.0 * c.astype(jnp.float32) - jnp.float32((1 << bits) - 1)
        proj = jnp.einsum("md,md->m", levels_f, u,
                          preferred_element_type=jnp.float32)
    # f = ‖u‖²/⟨L, u⟩ — the RaBitQ unbiasing quotient; a zero residual
    # (row == its center) gets f = 0, which makes the estimate exact
    # (⟨L, u⟩ ≥ 0 always: levels are monotone in u per dimension)
    scale = norm2 / jnp.maximum(proj, 1e-30)
    if l2:
        # 2·f·⟨L, R·c̃_l⟩ completes the −2⟨q−c, r⟩ cross term exactly at
        # the per-row level; ‖c‖² + ‖u‖² are the expanded-L2 constants
        g = jnp.einsum("md,md->m", levels_f, rc[labels],
                       preferred_element_type=jnp.float32)
        bias = c2[labels] + norm2 + 2.0 * scale * g
    else:
        bias = jnp.zeros_like(scale)
    return packed, scale, bias


_encode_chunk = functools.partial(jax.jit, static_argnames=(
    "l2", "bits", "rotation_kind"))(_encode_math)


def _encode_rows(work, labels, centers, rotation, metric, bits: int = 1,
                 rotation_kind: str = "dense", chunk: int = 262_144):
    """Chunked encode over all rows (the 15M-row resident build must never
    hold an (n, rot_dim) fp32 residual array — the ivf_pq enc_chunk
    lesson)."""
    n = work.shape[0]
    l2 = metric in ("sqeuclidean", "euclidean")
    rc = linalg.rotate_rows(centers, rotation, rotation_kind)
    c2 = dist_mod.sqnorm(centers)
    parts = []
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        parts.append(_encode_chunk(
            lax.slice_in_dim(work, s, e, axis=0),
            lax.slice_in_dim(labels, s, e, axis=0),
            centers, rotation, rc, c2, l2, bits, rotation_kind))
    if len(parts) == 1:
        return parts[0]
    return tuple(jnp.concatenate([p[i] for p in parts]) for i in range(3))


@traced("ivf_bq::build")
def build(
    dataset,
    params: IvfBqParams = IvfBqParams(),
    res: Optional[Resources] = None,
) -> IvfBqIndex:
    """Train the coarse quantizer, rotate, sign-encode and pack the lists.

    The whole build beyond kmeans is one rotation matmul + sign + two
    reductions per row — no codebook training (the IVF-RaBitQ build-time
    headline)."""
    res = res or current_resources()
    dataset = jnp.asarray(dataset)
    n, dim = dataset.shape
    if params.n_lists > n:
        raise ValueError(f"n_lists={params.n_lists} > n_rows={n}")
    rot_dim = auto_rot_dim(dim, params.rotation_kind)

    work = dataset.astype(jnp.float32)
    if params.metric == "cosine":
        work = work / jnp.maximum(jnp.linalg.norm(work, axis=1, keepdims=True), 1e-30)

    km_metric = ("inner_product" if params.metric in ("cosine", "inner_product")
                 else "sqeuclidean")
    km = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=km_metric, seed=params.seed)
    key = jax.random.key(params.seed)
    k_train, k_rot = jax.random.split(key)
    n_train = max(params.n_lists, int(n * params.kmeans_trainset_fraction))
    with obs.record_span("ivf_bq::coarse_train"):
        if n_train < n:
            # with-replacement sampling (see ivf_flat.build: duplicates are
            # noise for k-means, and choice(replace=False) compiles an
            # O(n log n) permutation program)
            train_rows = jax.random.randint(k_train, (n_train,), 0, n)
            centers = kmeans_balanced.fit(work[train_rows], params.n_lists, km, res=res)
            labels = kmeans_balanced.predict(work, centers, km, res=res)
        else:
            centers, labels = kmeans_balanced.fit_predict(work, params.n_lists, km, res=res)

    if obs.enabled():
        obs.add("ivf_bq.build.rows", n)
        obs.add("ivf_bq.build.lists", params.n_lists)

    cap = params.list_size_cap
    if cap < 0:
        cap = _packing.auto_list_cap(n, params.n_lists, _GROUP)
    if cap:
        labels = _packing.spill_to_cap(work, centers, labels, km_metric, cap)

    rotation = _make_rotation(k_rot, rot_dim, params.rotation_kind)
    enc_attrs = ({"rows": int(n), "bits": int(params.bits),
                  "rotation_kind": params.rotation_kind}
                 if obs.enabled() else None)
    with obs.record_span("ivf_bq::encode", attrs=enc_attrs):
        codes, scale, bias = _encode_rows(work, labels, centers, rotation,
                                          params.metric, params.bits,
                                          params.rotation_kind)
    with obs.record_span("ivf_bq::pack"):
        row_ids = jnp.arange(n, dtype=jnp.int32)
        list_codes, list_ids = _packing.pack_lists(
            codes, row_ids, labels, params.n_lists, _GROUP, pow2_chunks=True)
        aux, _ = _packing.pack_lists(
            jnp.stack([scale, bias], axis=1), row_ids, labels,
            params.n_lists, _GROUP, pow2_chunks=True)
        list_scale = aux[:, :, 0]
        list_bias = jnp.where(list_ids >= 0, aux[:, :, 1], jnp.inf)
    return IvfBqIndex(centers, rotation, list_codes, list_ids, list_scale,
                      list_bias, params.metric, params.bits,
                      params.rotation_kind)


@traced("ivf_bq::extend")
def extend(index: IvfBqIndex, new_vectors, new_ids=None,
           res: Optional[Resources] = None) -> IvfBqIndex:
    """Encode new vectors with the existing quantizers and repack. The old
    rows' codes and correction scalars are carried as-is (codes cannot
    reconstruct vectors, so extension is a repack of payloads, never a
    re-encode)."""
    res = res or current_resources()
    new_vectors = jnp.asarray(new_vectors).astype(jnp.float32)
    if new_vectors.shape[1] != index.dim:
        raise ValueError(f"dim mismatch: {new_vectors.shape[1]} != {index.dim}")
    if index.metric == "cosine":
        new_vectors = new_vectors / jnp.maximum(
            jnp.linalg.norm(new_vectors, axis=1, keepdims=True), 1e-30)
    km_metric = ("inner_product" if index.metric in ("cosine", "inner_product")
                 else "sqeuclidean")
    labels = kmeans_balanced.predict(
        new_vectors, index.centers,
        kmeans_balanced.KMeansBalancedParams(metric=km_metric), res=res)
    total = index.size + int(new_vectors.shape[0])
    cap = _packing.auto_list_cap(total, index.n_lists, _GROUP)
    labels = _packing.spill_to_cap(
        new_vectors, index.centers, labels, km_metric, cap,
        base_counts=index.list_sizes(),
    )
    new_codes, new_scale, new_bias = _encode_rows(
        new_vectors, labels, index.centers, index.rotation, index.metric,
        index.bits, index.rotation_kind)

    old_codes, old_ids, old_labels = _packing.unpack_lists(
        index.list_codes, index.list_ids)
    old_aux, _, _ = _packing.unpack_lists(
        jnp.stack([index.list_scale,
                   jnp.where(index.list_ids >= 0, index.list_bias, 0.0)],
                  axis=2),
        index.list_ids)
    if new_ids is None:
        start = int(jnp.max(old_ids) + 1) if old_ids.size else 0
        new_ids = jnp.arange(start, start + new_vectors.shape[0], dtype=jnp.int32)
    else:
        new_ids = jnp.asarray(new_ids, jnp.int32)

    all_codes = jnp.concatenate([old_codes, new_codes])
    all_aux = jnp.concatenate(
        [old_aux, jnp.stack([new_scale, new_bias], axis=1)])
    all_ids = jnp.concatenate([old_ids, new_ids])
    all_labels = jnp.concatenate([old_labels, labels])
    list_codes, list_ids = _packing.pack_lists(
        all_codes, all_ids, all_labels, index.n_lists, _GROUP,
        pow2_chunks=True)
    aux, _ = _packing.pack_lists(all_aux, all_ids, all_labels,
                                 index.n_lists, _GROUP, pow2_chunks=True)
    return IvfBqIndex(
        index.centers, index.rotation, list_codes, list_ids, aux[:, :, 0],
        jnp.where(list_ids >= 0, aux[:, :, 1], jnp.inf), index.metric,
        index.bits, index.rotation_kind)


# ---------------------------------------------------------------------------
# Streamed build (the billion-scale fast path)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_chunk_bq(list_codes, list_ids, list_scale, list_bias,
                      codes, scale, bias, labels, base, row_start):
    """One streamed-build chunk's offset-scatter into the DONATED packed
    blocks (build_streaming pass 2). ``base`` is the per-list write offset
    accumulated over previous chunks; the in-chunk rank comes from one
    chunk-local sort (_packing.chunk_ranks — the ONE copy of the position
    math), so no global position array ever exists. Encode runs OUTSIDE
    (non-donating :func:`_encode_chunk`), so its OOM-degraded retry can
    never invalidate a donated block."""
    m = labels.shape[0]
    n_lists, mls = list_ids.shape
    order, sorted_labels, rank_sorted = _packing.chunk_ranks(labels, n_lists)
    safe_sl = jnp.minimum(sorted_labels, n_lists - 1)
    pos = base[safe_sl].astype(jnp.int32) + rank_sorted
    # sentinel labels (== n_lists, the diversion drop marker) and overflow
    # past mls route to row mls, which mode="drop" discards
    pos = jnp.where((sorted_labels < n_lists) & (pos < mls), pos, mls)
    list_codes = list_codes.at[safe_sl, pos].set(codes[order], mode="drop")
    ids = row_start + jnp.arange(m, dtype=jnp.int32)
    list_ids = list_ids.at[safe_sl, pos].set(ids[order], mode="drop")
    list_scale = list_scale.at[safe_sl, pos].set(scale[order], mode="drop")
    list_bias = list_bias.at[safe_sl, pos].set(bias[order], mode="drop")
    return list_codes, list_ids, list_scale, list_bias


def _encode_chunk_degradable(rows, labels, centers, rotation, rc, c2, l2,
                             bits, rotation_kind, floor: int = 4096):
    """One chunk through :func:`_encode_chunk` behind the
    ``ivf_bq.build.encode_chunk`` faultpoint, with the round-7 OOM
    recovery: an OOM-classified failure halves the encode sub-chunk (down
    to ``floor``) and re-encodes in parts — per-row math is row-independent
    so the degraded result is bit-identical, only the dispatch count
    grows. DEADLINE/FATAL classes propagate classified."""
    from raft_tpu import resilience

    m = rows.shape[0]
    # small chunks still get at least one halving before the floor bites
    # (the floor exists to stop meaningless 64-row dispatch storms, not to
    # veto recovery outright) — max with 64 AFTER the m//2 clamp, so even
    # a 256-row chunk halves once instead of dying on its first OOM
    floor = max(64, min(floor, m // 2))
    sub = m
    while True:
        try:
            resilience.faultpoint("ivf_bq.build.encode_chunk")
            if sub >= m:
                return _encode_chunk(rows, labels, centers, rotation, rc,
                                     c2, l2, bits, rotation_kind)
            parts = []
            for s in range(0, m, sub):
                e = min(s + sub, m)
                parts.append(_encode_chunk(
                    lax.slice_in_dim(rows, s, e, axis=0),
                    lax.slice_in_dim(labels, s, e, axis=0),
                    centers, rotation, rc, c2, l2, bits, rotation_kind))
            return tuple(jnp.concatenate([p[i] for p in parts])
                         for i in range(3))
        except Exception as e:
            kind = resilience.classify(e)
            if kind == resilience.OOM and sub > floor:
                sub = max(floor, sub // 2)
                obs.add("ivf_bq.build.degraded_chunk")
                resilience.record_event(
                    "degraded_chunk", site="ivf_bq.build.encode_chunk",
                    chunk_rows=sub)
                continue
            raise


@traced("ivf_bq::build_streaming")
def build_streaming(
    chunk_fn,
    n: int,
    dim: int,
    params: IvfBqParams = IvfBqParams(),
    res: Optional[Resources] = None,
    chunk_rows: int = 0,
    train_rows: int = 0,
) -> IvfBqIndex:
    """Out-of-HBM IVF-BQ build: the dataset visits the device one chunk at
    a time (the SIFT-1B per-chip-share configuration — peak residency is
    the packed index + ONE chunk's encode transient, never the raw
    (n, dim) matrix; obs.costmodel.predict_build_streaming_bytes is the
    closed-form bound, asserted in tier-1).

    ``chunk_fn(start, end) -> (end-start, dim) array`` supplies rows — a
    file reader (bench/io.py), a generator, or a host array slice. It is
    called once per chunk per pass (twice total), so it must be
    deterministic. ``chunk_rows`` defaults to the workspace-budget
    formula, overridable via ``RAFT_TPU_BQ_BUILD_CHUNK``.

    Rides the ``ivf_pq.build_streaming`` cache-only pattern: quantizers
    train on ``train_rows`` sampled rows (default ≤ 2M; ``>= n`` streams
    the WHOLE dataset through training, in which case the output is
    BIT-IDENTICAL — codes, scales, ids — to one-shot :func:`build` at
    ``kmeans_trainset_fraction=1`` and ``list_size_cap=0``, the
    check.sh/tier-1 parity contract); pass 1 streams label assignment
    (capacity diversion under a cap: nearest-full rows take their
    second-nearest, doubly-full rows are DROPPED and counted on
    ``index._streaming_dropped``); pass 2 encodes each chunk through the
    shared :func:`_encode_chunk` (the ``ivf_bq.build.encode_chunk``
    faultpoint with OOM→halve-chunk degraded retry, round-7 gate) and
    offset-scatters into DONATED blocks."""
    import os

    import numpy as np

    res = res or current_resources()
    if params.metric == "cosine":
        raise ValueError("build_streaming: cosine needs normalized chunks; "
                         "normalize inside chunk_fn and use inner_product")
    rot_dim = auto_rot_dim(dim, params.rotation_kind)
    nb_total = multibit_width(rot_dim, params.bits)
    km_metric = ("inner_product" if params.metric == "inner_product"
                 else "sqeuclidean")
    km = kmeans_balanced.KMeansBalancedParams(
        n_iters=params.kmeans_n_iters, metric=km_metric, seed=params.seed)
    env_chunk = int(os.environ.get("RAFT_TPU_BQ_BUILD_CHUNK", "0") or 0)
    chunk = int(chunk_rows) or env_chunk or int(
        max(262_144, min(n, res.workspace_bytes // max(dim * 12, 1))))
    chunk = min(chunk, n)
    starts = list(range(0, n, chunk))
    cap = params.list_size_cap
    if cap < 0:
        cap = _packing.auto_list_cap(n, params.n_lists, _GROUP)

    from raft_tpu.core.interruptible import check_interrupt

    # --- quantizers (same key derivation as build(): bit-identity) ---------
    key = jax.random.key(params.seed)
    _k_train, k_rot = jax.random.split(key)
    rotation = _make_rotation(k_rot, rot_dim, params.rotation_kind)
    t_rows = int(train_rows) or int(min(2_000_000, max(
        params.n_lists * 32, n * params.kmeans_trainset_fraction)))
    t_rows = min(t_rows, n)
    with obs.record_span("ivf_bq::coarse_train"):
        if t_rows >= n:
            # full-data training: read whole chunks so the trainset IS the
            # dataset in order (the bit-identity-with-build() contract)
            train_parts = [jnp.asarray(chunk_fn(s, min(s + chunk, n)),
                                       jnp.float32) for s in starts]
        else:
            per = max(1, t_rows // len(starts))
            train_parts = [jnp.asarray(chunk_fn(s, min(s + per, n)),
                                       jnp.float32) for s in starts]
        trainset = (jnp.concatenate(train_parts) if len(train_parts) > 1
                    else train_parts[0])
        del train_parts
        centers = kmeans_balanced.fit(trainset, params.n_lists, km, res=res)
        del trainset
    if obs.enabled():
        obs.add("ivf_bq.build.rows", n)
        obs.add("ivf_bq.build.lists", params.n_lists)
        obs.add("ivf_bq.build.streamed_chunks", len(starts))

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
            l1, l2_ = _packing.assign_top2(rows, centers, metric=km_metric)
            labels = _packing.divert_to_cap(
                l1, l2_, jnp.asarray(run, jnp.int32), jnp.int32(cap),
                n_lists)
        else:
            labels = kmeans_balanced.predict(rows, centers, km, res=res)
        labels_chunks.append(labels)
        # deliberate per-chunk host fetch (ivf_pq.build_streaming precedent):
        # the streamed build is host-driven by design — the (n_lists,) count
        # steers cap diversion and the pass-2 offsets, amortized by the
        # chunk's assign gemm
        c = np.asarray(jnp.bincount(jnp.minimum(labels, n_lists),  # graftlint: ignore[loop-host-transfer]
                                    length=n_lists + 1))
        counts_np[ci] = c[:n_lists]
        dropped += int(c[n_lists])
        run += c[:n_lists]
        del rows
    totals = counts_np.sum(axis=0)
    # strip-eligible padded size: 512 granule, pow2 chunks — THE shared
    # pack_lists formula, so one-shot and streamed builds agree on mls
    mls = _packing.round_list_size(int(totals.max()), _GROUP,
                                   pow2_chunks=True)
    base_np = np.cumsum(counts_np, axis=0) - counts_np  # per-chunk offsets
    if dropped:
        from raft_tpu.core.logger import get_logger

        get_logger().warning(
            "ivf_bq.build_streaming: %d row(s) overflowed both their "
            "nearest and second-nearest capped lists and were dropped "
            "(cap=%d); raise list_size_cap or n_lists.", dropped, cap)

    # --- pass 2: encode + offset-scatter into donated blocks ---------------
    l2 = params.metric in ("sqeuclidean", "euclidean")
    rc = linalg.rotate_rows(centers, rotation, params.rotation_kind)
    c2 = dist_mod.sqnorm(centers)
    list_codes = jnp.zeros((n_lists, mls, nb_total), jnp.uint8)
    list_ids = jnp.full((n_lists, mls), -1, jnp.int32)
    list_scale = jnp.zeros((n_lists, mls), jnp.float32)
    list_bias = jnp.full((n_lists, mls), jnp.inf, jnp.float32)
    for ci, s in enumerate(starts):
        check_interrupt()
        e = min(s + chunk, n)
        rows = jnp.asarray(chunk_fn(s, e), jnp.float32)
        labels = labels_chunks[ci]
        safe = jnp.minimum(labels, n_lists - 1)
        with obs.record_span("ivf_bq::encode_chunk",
                             attrs=({"rows": int(e - s), "chunk": ci}
                                    if obs.enabled() else None)):
            codes, scale, bias = _encode_chunk_degradable(
                rows, safe, centers, rotation, rc, c2, l2, params.bits,
                params.rotation_kind)
            list_codes, list_ids, list_scale, list_bias = _scatter_chunk_bq(
                list_codes, list_ids, list_scale, list_bias, codes, scale,
                bias, labels, jnp.asarray(base_np[ci], jnp.int32),
                jnp.int32(s))
        del rows
    out = IvfBqIndex(centers, rotation, list_codes, list_ids, list_scale,
                     list_bias, params.metric, params.bits,
                     params.rotation_kind)
    out._streaming_dropped = dropped
    return out


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("n_probes", "metric", "select_algo", "compute_dtype",
                     "l2", "bits", "rotation_kind"),
)
def _bq_search_prep(queries, centers, rotation, list_bias, list_ids, filter,
                    n_probes, metric, select_algo, compute_dtype, l2,
                    bits: int = 1, rotation_kind: str = "dense"):
    """Stage 1 + operand prep: ONE coarse gemm feeds both the probe ranking
    and the exact per-pair center term (ivf_pq's shared ``_pq_probe_prep``
    — one copy of the math, so the packed and paged engines cannot
    drift); the rotated query — plane-extended for multi-bit codes
    (ops/bq_scan.extend_query_planes) — is the scan's A operand.
    ``list_bias`` / ``list_ids`` may equally be a paged store's
    (capacity, page_rows) pools — the masking is shape-agnostic."""
    from raft_tpu.neighbors.ivf_pq import _pq_probe_prep

    probes, qr, pair_const = _pq_probe_prep(
        queries, centers, rotation, n_probes, select_algo, l2,
        rotation_kind)
    qr = extend_query_planes(qr, bits)
    bias = _filtering.apply_filter_bias(list_bias, list_ids, filter)
    return probes, qr, bias, pair_const


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "select_algo",
                     "compute_dtype", "classes", "class_counts",
                     "interpret", "impl", "bits", "rotation_kind"),
)
def _bq_fused(queries, centers, rotation, list_codes, list_scale, list_bias,
              list_ids, filter, cls_ord, k, n_probes, metric, select_algo,
              compute_dtype, classes, class_counts, interpret, impl,
              bits: int = 1, rotation_kind: str = "dense"):
    """The ENTIRE BQ search of one query tile — coarse gemm, device strip
    planning, packed scan, merge, finalize — as one jit: one runtime
    dispatch, zero host syncs (the round-4 _ragged_fused shape). The in-kernel tournament
    top-k is allowed (approx_ok=True): this path over-fetches and
    exact-re-ranks via neighbors/refine, which absorbs its ~1e-4/row
    bin-collision loss (the IVF-PQ precedent)."""
    from raft_tpu.ops.bq_scan import bq_strip_search_traced

    # ledger registration: runs at trace time only (obs/compile.py)
    obs_compile.trace_event(
        _LEDGER_ENTRY, queries=queries, centers=centers, rotation=rotation,
        list_codes=list_codes, list_scale=list_scale, list_bias=list_bias,
        list_ids=list_ids, filter=filter, cls_ord=cls_ord,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "classes": classes, "class_counts": class_counts,
                "interpret": interpret, "impl": impl,
                "bits": bits, "rotation_kind": rotation_kind})
    l2 = metric in ("sqeuclidean", "euclidean")
    sa = probe_algo(select_algo, interpret, centers.shape[0])
    probes, qr, bias, pair_const = _bq_search_prep(
        queries, centers, rotation, list_bias, list_ids, filter,
        n_probes, metric, sa, compute_dtype, l2, bits, rotation_kind,
    )
    vals, ids = bq_strip_search_traced(
        qr, probes, list_codes, list_scale, bias, list_ids, cls_ord,
        classes, class_counts, int(k), int(k), -2.0 if l2 else -1.0,
        interpret, pair_const=pair_const, approx_ok=True, impl=impl,
    )
    from raft_tpu.neighbors.ivf_flat import _finalize_ragged

    # shared fused finalizer: ‖Rq̃‖² == ‖q‖² (orthogonal rotation,
    # zero-padding adds nothing), same alpha conventions as the fp scans
    return _finalize_ragged(vals, ids, queries, metric)


@traced("ivf_bq::search")
def search(
    index: IvfBqIndex,
    queries,
    k: int,
    n_probes: int = 20,
    filter: Optional[Bitset] = None,
    select_algo: str = "exact",
    backend: str = "auto",
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Approximate k-NN over the 1-bit compressed lists. Returns
    (distances, indices); distances are UNBIASED estimates, not exact —
    pipe through :mod:`raft_tpu.neighbors.refine` (or call
    :func:`search_refined`) for the recall-gated configuration.

    ``backend``: "packed" (the bq_scan Pallas kernel — the TPU path,
    interpret-mode elsewhere), "reference" (the pure-jnp scan, bit-identical
    to "packed" — the CPU default and parity oracle), or "auto".

    Recovery contract (round-7 invariant): the scan dispatch carries the
    ``ivf_bq.search.scan`` faultpoint; an OOM-classified failure retries at
    half the query tile (down to a floor) with ``ivf_bq.search.degraded_tile``
    counting the degradation, DEADLINE/FATAL classes propagate classified.
    """
    res = res or current_resources()
    if backend == "auto":
        backend = "packed" if jax.default_backend() == "tpu" else "reference"
    if backend not in ("packed", "reference"):
        raise ValueError(f"unknown backend {backend!r}")
    impl = "pallas" if backend == "packed" else "jnp"
    queries, n_probes, filter, scan_attrs = _entry.prepare(
        "ivf_bq.search", queries, k, n_probes, filter, backend,
        dim=index.dim, n_lists=index.n_lists, metric=index.metric,
        rows=index.max_list_size, k_cap=512,
        k_error="k={k} out of range (1..min(n_probes·max_list_size, 512)) "
                "for n_probes={n_probes} x max_list_size={rows}")
    if obs.enabled():
        # roofline note (round 15): packed-scan FLOP/byte model + strip
        # occupancy at the scan's real planning width (rot_dim)
        from raft_tpu.ops.bq_scan import occupancy_stats

        obs_roofline.note_dispatch(
            "ivf_bq.search",
            {"q": int(queries.shape[0]), "dim": index.dim,
             "n_lists": index.n_lists,
             "max_list_size": index.max_list_size,
             "n_probes": int(n_probes), "k": int(k),
             "rot_dim": index.rot_dim, "bits": index.bits,
             "rotation_kind": index.rotation_kind},
            occupancy=_entry.list_occupancy(
                index, int(queries.shape[0]), n_probes, k, res,
                functools.partial(occupancy_stats, rot_dim=index.rot_dim,
                                  bits=index.bits)))
    from raft_tpu import resilience
    from raft_tpu.neighbors.ivf_flat import _ragged_plan_static

    # plan with the scan's REAL row width (the bf16 unpacked block the
    # kernel holds in VMEM is bits·rot_dim wide — every extra bit-plane
    # widens the MXU contraction)
    classes, class_counts, cls_ord, q_tile = _ragged_plan_static(
        index, n_probes, k, res, index.rot_dim * index.bits)
    q_tile = min(q_tile, queries.shape[0])
    interpret = jax.default_backend() != "tpu"
    from raft_tpu.ops.strip_scan import per_tile

    while True:
        try:
            resilience.faultpoint("ivf_bq.search.scan")
            # ledger watch: a (re)tracing dispatch gets its wall-clock
            # stamped on the ledger record (steady state stamps nothing)
            with obs.record_span("ivf_bq::scan", attrs=scan_attrs), \
                    obs_compile.watch():
                return per_tile(lambda lo, hi: _bq_fused(
                    queries[lo:hi], index.centers, index.rotation,
                    index.list_codes, index.list_scale, index.list_bias,
                    index.list_ids, filter, cls_ord, int(k), n_probes,
                    index.metric, select_algo, res.compute_dtype, classes,
                    class_counts, interpret, impl, index.bits,
                    index.rotation_kind,
                ), queries.shape[0], q_tile)
        except Exception as e:
            kind = resilience.classify(e)
            if kind == resilience.OOM and q_tile > 64:
                # degraded-tile retry: half the query tile halves the
                # per-dispatch working set; the result is identical, only
                # the dispatch count grows
                q_tile = max(64, q_tile // 2)
                obs.add("ivf_bq.search.degraded_tile")
                resilience.record_event("degraded_tile",
                                        site="ivf_bq.search.scan",
                                        q_tile=q_tile)
                continue
            raise


# ---------------------------------------------------------------------------
# Paged search (serving layer): scan a PagedListStore's packed sign pages
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "metric", "select_algo",
                     "compute_dtype", "interpret", "impl",
                     "bits", "rotation_kind"),
)
def _paged_fused_bq(queries, centers, rotation, codes_pool, scale_pool,
                    bias_pool, page_ids, table, chain_pages, filter,
                    k, n_probes, metric, select_algo, compute_dtype,
                    interpret, impl, bits: int = 1,
                    rotation_kind: str = "dense"):
    """The ENTIRE paged BQ search of one query tile as one jit: coarse gemm + rotation,
    device strip planning, the page-table DMA ±1 kernel, merge, finalize —
    the ``_bq_fused`` shape over page chains. Capacity-shaped operands
    (zero-recompile serving contract); the exact −2⟨q, c_l⟩ term rides
    pair_const exactly like the packed path."""
    from raft_tpu.ops.bq_scan import paged_bq_search_traced

    obs_compile.trace_event(
        "ivf_bq.paged_pallas", queries=queries, centers=centers,
        rotation=rotation, codes_pool=codes_pool, scale_pool=scale_pool,
        bias_pool=bias_pool, page_ids=page_ids, table=table,
        chain_pages=chain_pages, filter=filter,
        static={"k": k, "n_probes": n_probes, "metric": metric,
                "select_algo": select_algo, "compute_dtype": compute_dtype,
                "interpret": interpret, "impl": impl,
                "bits": bits, "rotation_kind": rotation_kind})
    l2 = metric in ("sqeuclidean", "euclidean")
    sa = probe_algo(select_algo, interpret, centers.shape[0])
    # THE packed path's prep (one copy — probes/rotation/pair_const are
    # bitwise parity by construction); the bias/ids operands are simply
    # the store's pools instead of the packed arrays
    probes, qr, bias, pair_const = _bq_search_prep(
        queries, centers, rotation, bias_pool, page_ids, filter,
        n_probes, metric, sa, compute_dtype, l2, bits, rotation_kind,
    )
    alpha = -2.0 if l2 else -1.0
    vals, ids = paged_bq_search_traced(
        qr, probes, codes_pool, scale_pool, bias, page_ids, table,
        chain_pages, int(k), int(k), alpha, interpret,
        pair_const=pair_const, impl=impl)
    from raft_tpu.neighbors.ivf_flat import _finalize_ragged

    return _finalize_ragged(vals, ids, queries, metric)


@traced("ivf_bq::search_paged")
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
    """Approximate k-NN over a mutable paged 1-bit code store
    (:class:`raft_tpu.serving.PagedListStore`, kind ``"ivf_bq"``): same
    estimator contract as :func:`search`, over a store that keeps serving
    while rows stream in/out — no repack, zero recompiles on steady-state
    mutations.

    ``backend``: "paged_pallas" (page-table DMA ±1 kernel — the TPU
    engine, interpret-mode elsewhere), "paged_jnp" (its bit-parity jnp
    reference — the CPU default), or "auto"."""
    if store.kind != "ivf_bq":
        raise ValueError(f"expected an ivf_bq store, got {store.kind!r}")
    res = res or current_resources()
    from raft_tpu.neighbors.ivf_flat import (_paged_plan_static,
                                             paged_backend_auto)

    if backend == "auto":
        backend = paged_backend_auto(store, k)
    if backend not in ("paged_pallas", "paged_jnp"):
        raise ValueError(f"unknown backend {backend!r}")
    # one ATOMIC store snapshot (the scan_state contract)
    codes_pool, bias_pool, scale_pool, page_ids, table, chain_pages = \
        store.paged_scan_state()
    width = int(table.shape[1])
    queries, n_probes, filter, scan_attrs = _entry.prepare(
        "ivf_bq.search_paged", queries, k, n_probes, filter, backend,
        dim=store.dim, n_lists=store.n_lists, metric=store.metric,
        rows=width * store.page_rows, k_cap=512,
        standing_filter=getattr(store, "filter", None),
        attrs={"table_width": width})
    rot_dim = int(store.rotation.shape[0])
    bits = int(getattr(store, "bq_bits", 1))
    rotation_kind = getattr(store, "rotation_kind", "dense")
    if obs.enabled():
        q_obs = int(queries.shape[0])
        obs_roofline.note_dispatch(
            "ivf_bq.paged_pallas",
            {"q": q_obs, "dim": store.dim, "n_lists": store.n_lists,
             "page_rows": store.page_rows, "table_width": width,
             "n_probes": int(n_probes), "k": int(k), "rot_dim": rot_dim,
             "bits": bits, "rotation_kind": rotation_kind},
            occupancy=_entry.page_occupancy(
                store, width, q_obs, n_probes, k, res,
                int(codes_pool.shape[-1]), rot_dim * bits))
    from raft_tpu.ops.strip_scan import per_tile
    from raft_tpu.resilience import faultpoint

    interpret = jax.default_backend() != "tpu"
    q_tile = _paged_plan_static(store, n_probes, k, res, rot_dim * bits)
    impl = "pallas" if backend == "paged_pallas" else "jnp"
    faultpoint("ivf_bq.search_paged.scan")
    with obs.record_span("ivf_bq::paged_pallas", attrs=scan_attrs):
        with obs_compile.watch():
            return per_tile(lambda lo, hi: _paged_fused_bq(
                queries[lo:hi], store.centers, store.rotation, codes_pool,
                scale_pool, bias_pool, page_ids, table, chain_pages,
                filter, int(k), n_probes, store.metric, select_algo,
                res.compute_dtype, interpret, impl, bits, rotation_kind),
                queries.shape[0], q_tile)


@traced("ivf_bq::search_refined")
def search_refined(
    index: IvfBqIndex,
    dataset,
    queries,
    k: int,
    n_probes: int = 20,
    refine_ratio: int = 4,
    filter: Optional[Bitset] = None,
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The recall-gated configuration: over-fetch ``k·refine_ratio``
    estimated candidates, then exact re-rank against ``dataset`` through
    neighbors/refine (refine-inl.cuh:70 analog — the same pipe the IVF-PQ
    headline uses). ``dataset`` is the caller-held original row matrix; the
    index itself stores only 1-bit codes."""
    from raft_tpu.neighbors import refine as refine_mod

    if refine_ratio < 1:
        raise ValueError(f"refine_ratio must be >= 1, got {refine_ratio}")
    k_fetch = min(int(k) * int(refine_ratio), 512)
    if filter is not None:
        # widen the over-fetch too: at low pass rates k·refine_ratio
        # candidates shrink to k·refine_ratio·pass_rate survivors
        _, k_fetch, _, _ = _filtering.widen_plan(
            filter, n_probes, index.n_lists, k_fetch=k_fetch, k_cap=512)
    _, cand = search(index, queries, k_fetch, n_probes=n_probes,
                     filter=filter, res=res)
    return refine_mod.refine(dataset, queries, cand, int(k),
                             metric=index.metric, res=res)


def reconstruct_rows(centers, rotation, codes, scale, labels, bits: int = 1,
                     rotation_kind: str = "dense", dim: Optional[int] = None):
    """Approximate original vectors from packed BQ codes:
    ``x̂ = c_label + R⁻¹(f·L)``, where ``f·L`` is the RaBitQ estimator's
    projection of the rotated residual onto its own code direction — the
    best reconstruction the code carries. Assignment-grade (maintenance
    re-clustering's row source when the raw vectors are gone), NOT
    bit-exact: re-encoding a reconstruction is near-idempotent but the
    scan estimates remain approximate either way."""
    from raft_tpu.ops.bq_scan import unpack_code_levels, unpack_sign_bits

    rot_dim = int(rotation.shape[-1])
    if bits == 1:
        levels = unpack_sign_bits(jnp.asarray(codes), rot_dim)
    else:
        levels = unpack_code_levels(jnp.asarray(codes), rot_dim, bits)
    u_hat = jnp.asarray(scale, jnp.float32)[:, None] * levels.astype(jnp.float32)
    resid = linalg.unrotate_rows(u_hat, rotation, rotation_kind)
    d = int(centers.shape[1]) if dim is None else int(dim)
    return centers[jnp.asarray(labels, jnp.int32)] + resid[:, :d]
