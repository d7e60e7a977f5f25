"""Top-k selection — the load-bearing primitive for all ANN search.

Reference: raft::matrix::select_k (matrix/select_k.cuh:84) with algorithm
choices enumerated in matrix/select_k_types.hpp:36-66 — radix "AIR top-k"
(detail/select_radix.cuh) and warp-sort (detail/select_warpsort.cuh).

TPU design: radix select does not map to the VPU (no per-lane scatter/atomics);
the idiomatic backends are
  * ``"exact"`` — `lax.top_k` (XLA's sort-based top-k; exact, any k);
  * ``"iter"`` — k masked-extrema passes (exact, VPU-friendly): on TPU,
    lax.top_k lowers to a full per-row sort, measured ~10× slower than k
    sequential min+mask passes for the small k ANN uses (k ≤ 64). Matches
    lax.top_k exactly, including lowest-index tie-breaks;
  * ``"approx"`` — `lax.approx_min_k`/`approx_max_k`, the TPU partial-reduce
    top-k from the TPU-KNN paper (PAPERS.md: "TPU-KNN: K Nearest Neighbor
    Search at Peak FLOP/s") — ~recall_target accuracy at much higher
    throughput; the right default inside ANN search pipelines where candidate
    lists are over-fetched anyway.

All operate row-wise on a (batch, n) matrix, like the reference.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def iter_topk_min(values, k: int):
    """k masked-min passes over the last axis: (vals, idx) exactly matching
    ``lax.top_k(-values, k)`` semantics (ascending values, lowest index on
    ties, distinct indices even on +inf tails) without the sort. The
    per-pass work is ~4 elementwise VPU ops over the full block — for
    k ≤ ~64 this beats TPU top_k's O(n log n) sort by a wide margin.

    NaN inputs are sanitized to +inf at entry (ADVICE r3: an all-NaN row
    used to emit out-of-range indices; lax.top_k's NaN order is
    implementation-defined anyway, so +inf-tail semantics is the sane
    contract)."""
    v = values
    v = jnp.where(jnp.isnan(v), jnp.inf, v)
    n = v.shape[-1]
    cols = lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    # explicit taken-mask (not just an inf overwrite): +inf input values are
    # indistinguishable from extracted slots, and top_k still returns
    # DISTINCT indices for them in ascending order
    taken = jnp.zeros(v.shape, jnp.bool_)
    vs, idxs = [], []
    for _ in range(k):
        masked = jnp.where(taken, jnp.inf, v)
        mn = jnp.min(masked, axis=-1, keepdims=True)
        am = jnp.min(jnp.where((masked <= mn) & ~taken, cols, n), axis=-1)
        vs.append(mn[..., 0])
        idxs.append(am)
        taken = taken | (cols == am[..., None])
    return jnp.stack(vs, -1), jnp.stack(idxs, -1).astype(jnp.int32)


def _pack_bits_for(n: int) -> int:
    b = 1
    while (1 << b) < n:
        b += 1
    return b


def pack_clamp_for(bits: int) -> float:
    """Largest finite fp32 whose truncated mantissa survives OR-ing any
    ``bits``-wide index without overflowing into the exponent."""
    import numpy as _np

    return float(_np.array((0x7F7FFFFF >> bits) << bits, _np.uint32)
                 .view(_np.float32))


def pack_values(v, bits: int):
    """Pack per-position column ids into the low ``bits`` mantissa bits of
    fp32 ``v`` (last axis). Shared by iter_topk_min_packed and the strip
    kernel's in-kernel extraction (ops/strip_scan._pack_scores) so the
    clamp/NaN/±inf invariants live in one place:

    * NaN → +inf → clamped (a NaN would poison every min pass);
    * ±inf → ±max-finite-packable (OR-ing bits into an inf mantissa mints
      NaN — the code-review r4 -inf finding);
    * packed values within a row are unique (distinct column bits), so a
      min + equality-mask pass extracts exactly one element.
    Perturbation ≤ 2^-(23-bits) relative, for negatives too (mantissa grows
    → more negative, same bound)."""
    clamp = pack_clamp_for(bits)
    mask = (1 << bits) - 1
    cols = lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    v = jnp.where(jnp.isnan(v), jnp.inf, v)
    v = jnp.clip(v, -clamp, clamp)
    return lax.bitcast_convert_type(
        (lax.bitcast_convert_type(v, jnp.int32) & jnp.int32(~mask)) | cols,
        jnp.float32)


def iter_topk_min_packed(values, k: int):
    """Approximate iter_topk_min at HALF the per-pass cost: the column index
    rides the low mantissa bits of the fp32 value, so each pass is one min
    reduction + one equality mask — no argmin reconstruction.

    Values are perturbed by ≤ 2^-(23-b) relative (b = ceil(log2 n) index
    bits; 10 bits → 1.2e-4) — noise on the order of this repo's bf16 scan
    contract, NOT an exact select. Packed values within a row are unique,
    so ties and +inf tails still yield distinct in-range indices. NaN → +inf.
    """
    v = values.astype(jnp.float32)
    n = v.shape[-1]
    b = _pack_bits_for(n)
    mask = (1 << b) - 1
    clamp = pack_clamp_for(b)
    pv = pack_values(v, b)
    vs, idxs = [], []
    for _ in range(k):
        mn = jnp.min(pv, axis=-1)
        mb = lax.bitcast_convert_type(mn, jnp.int32)
        idxs.append(mb & jnp.int32(mask))
        vs.append(lax.bitcast_convert_type(mb & jnp.int32(~mask),
                                           jnp.float32))
        pv = jnp.where(pv == mn[..., None], jnp.inf, pv)
    out_v = jnp.stack(vs, -1)
    # restore the ±inf the packing clamped away (code-review r4: a clamped
    # +inf sentinel — filtered/padding entries — must NOT come back as a
    # finite ~3.4e38 "hit"; downstream isfinite masks depend on it).
    # clamp's low mantissa bits are zero, so clamped unpacked values equal
    # it exactly; the compare uses the static python float
    out_v = jnp.where(out_v >= clamp, jnp.inf, out_v)
    out_v = jnp.where(out_v <= -clamp, -jnp.inf, out_v)
    return out_v, jnp.stack(idxs, -1).astype(jnp.int32)


_RESIDENT_BLOCK_BYTES = 1 << 20  # one f32 row block; the pipeline holds two


def resident_ok(n: int, k: int) -> bool:
    """Whether a packed top-k of ``k`` over ``n`` columns fits
    :func:`resident_topk_min_packed`: k ≤ 64 and the packed route's n ≤
    8192 (its callers add: on TPU only)."""
    return 0 < k <= min(n, 64) and n <= (1 << 13)


def probe_algo(select_algo: str, interpret: bool, n_lists: int) -> str:
    """The coarse probe's select in every IVF search: "exact" rides the
    packed passes on the chip (half the VPU passes, and on TPU the resident
    kernel) only while n_lists keeps the index bits cheap: the perturbation
    is 2^-(23-ceil(log2 n_lists)) relative — ≤ 5e-4 at 4096 lists, where
    it only reorders boundary-tie lists (recall-neutral, measured). Larger
    n_lists would steal real mantissa (ADVICE r4 medium), so "exact" is
    honored literally there."""
    if select_algo == "exact" and not interpret and n_lists <= 4096:
        return "packed"
    return select_algo


def _resident_rows(rows: int, w: int) -> int:
    """Rows of one block: the most, up to 512, whose (rows, w) f32 block
    stays within _RESIDENT_BLOCK_BYTES (256 at w = 640 or 1024, 32 at
    8192), or all the rows where fewer."""
    r = 512
    while r > 8 and r * w * 4 > _RESIDENT_BLOCK_BYTES:
        r //= 2
    return rows if rows <= r else r


def _resident_kernel(x_ref, v_ref, i_ref, *, n: int, k: int, w: int,
                     bits: int):
    """One row block: pack, then all k passes in VMEM on the extraction
    route the strip kernel takes at (k, w) (ops/strip_scan._topk_block).
    Lanes at or past ``n`` (the block's padding to whole 128-lane slabs,
    read past the array's end) are set to +inf: packed, they sort after
    every real column, +inf ones included."""
    from raft_tpu.ops import strip_scan as ss

    x = x_ref[...]
    if w > n:
        cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(cols < n, x, jnp.inf)
    vals, idx = ss._topk_block(x, k, w, False, bits)
    # the -inf restore of iter_topk_min_packed (the +inf one is the
    # extraction's own)
    v_ref[...] = jnp.where(vals <= -pack_clamp_for(bits), -jnp.inf, vals)
    i_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def resident_topk_min_packed(values, k: int, interpret: bool = False):
    """:func:`iter_topk_min_packed` of a (rows, n) array as one Pallas
    kernel: each row block is read from HBM once and packed with the same
    ``pack_values(v, _pack_bits_for(n))``, and all k passes run in VMEM,
    where XLA makes each pass a reduce fusion that reads the whole array
    again. Values and indices equal iter_topk_min_packed's bit for bit:
    the same packed values leave in the same order, on the direct passes
    or, where :func:`strip_scan.topk_route` picks it for (k, n rounded up
    to 128 lanes), the sorted lane bins. Needs :func:`resident_ok`."""
    from raft_tpu.ops import strip_scan as ss

    v = values.astype(jnp.float32)
    rows, n = v.shape
    assert resident_ok(n, k), (n, k)
    w = -(-n // 128) * 128
    if ss.topk_route(k, w) != "sorted":
        w = n   # the direct passes take the row as it is
    r = _resident_rows(rows, w)
    out = jax.ShapeDtypeStruct((rows, k), jnp.float32)
    return pl.pallas_call(
        functools.partial(_resident_kernel, n=n, k=k, w=w,
                          bits=_pack_bits_for(n)),
        grid=(pl.cdiv(rows, r),),
        in_specs=[pl.BlockSpec((r, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((r, k), lambda i: (i, 0))] * 2,
        out_shape=(out, out.update(dtype=jnp.int32)),
        interpret=interpret,
        name="select_k_resident",
    )(v)


@functools.partial(jax.jit, static_argnames=("k", "select_min", "algo", "recall_target"))
def _select_k_impl(values, k, select_min, algo, recall_target):
    if algo == "approx":
        if select_min:
            vals, idx = lax.approx_min_k(values, k, recall_target=recall_target)
        else:
            vals, idx = lax.approx_max_k(values, k, recall_target=recall_target)
    elif algo in ("packed", "resident"):
        topk = (resident_topk_min_packed if algo == "resident"
                else iter_topk_min_packed)
        vals, idx = topk(values if select_min else -values, k)
        if not select_min:
            vals = -vals
    elif algo == "iter":
        vals, idx = iter_topk_min(values if select_min else -values, k)
        if not select_min:
            vals = -vals
    else:
        if select_min:
            neg_vals, idx = lax.top_k(-values, k)
            vals = -neg_vals
        else:
            vals, idx = lax.top_k(values, k)
    return vals, idx.astype(jnp.int32)


def select_k(
    values,
    k: int,
    select_min: bool = True,
    indices=None,
    algo: str = "exact",
    recall_target: float = 0.95,
) -> Tuple[jax.Array, jax.Array]:
    """Select k smallest (or largest) per row of ``values`` (batch, n).

    Returns ``(selected_values, selected_indices)`` with int32 indices. If
    ``indices`` (batch, n) is given, returned indices are gathered from it —
    the candidate-id remap used by IVF search's two-stage select (reference
    detail/ivf_flat_search-inl.cuh:130,194).

    ``algo``: "exact" (lax.top_k) | "iter" (k masked-min passes; exact,
    the fast TPU route for small k) | "packed" (mantissa-packed iter —
    half the passes' cost, values perturbed ≤ 2^-(23-ceil(log2 n))
    relative — ~1e-4 at n=1024, ~1e-3 at the n=8192 fallback bound; on
    TPU at k ≤ 64 one VMEM-resident kernel, resident_topk_min_packed) |
    "approx"
    (TPU partial-reduce; ``recall_target`` trades recall for speed).
    "exact" auto-routes to "iter" for k <= 64 on TPU — same results,
    ~10x faster.
    """
    values = jnp.asarray(values)
    squeeze = values.ndim == 1
    if squeeze:
        values = values[None, :]
    if not 0 < k <= values.shape[-1]:
        raise ValueError(f"k={k} out of range for n={values.shape[-1]}")
    if algo not in ("exact", "iter", "approx", "packed"):
        raise ValueError(f"unknown select_k algo {algo!r}")
    # iter does k full passes over the row — a win over top_k's sort only
    # while the row is narrow (k·n stays small); wide rows (brute-force over
    # the whole dataset) must keep the single-sort top_k
    if (algo == "exact" and k <= 64 and values.shape[-1] <= 8192
            and jax.default_backend() == "tpu"
            and jnp.issubdtype(values.dtype, jnp.floating)):
        algo = "iter"
    if (algo in ("iter", "packed")
            and not jnp.issubdtype(values.dtype, jnp.floating)):
        algo = "exact"  # the inf mask needs a floating dtype
    if algo == "packed" and values.shape[-1] > (1 << 13):
        # packing always happens in fp32 regardless of input dtype, and the
        # perturbation is 2^-(23-ceil(log2 n)) relative: 13 index bits keep
        # it ≤ ~1e-3; wider rows would steal 14-16 mantissa bits (~1e-2
        # worst case — inconsistent with the documented contract, ADVICE
        # r4), so they fall back to the exact iter select
        algo = "iter"
    if (algo == "packed" and jax.default_backend() == "tpu"
            and resident_ok(values.shape[-1], k)):
        algo = "resident"   # the same passes, in one VMEM-resident kernel
    vals, idx = _select_k_impl(values, int(k), bool(select_min), algo, float(recall_target))
    if indices is not None:
        indices = jnp.asarray(indices)
        if squeeze and indices.ndim == 1:
            indices = indices[None, :]
        idx = jnp.take_along_axis(indices, idx, axis=1)
    if squeeze:
        return vals[0], idx[0]
    return vals, idx


def merge_topk(
    vals_a, idx_a, vals_b, idx_b, select_min: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Merge two per-row top-k lists into one (the knn_merge_parts analog,
    reference neighbors/detail/knn_merge_parts.cuh:140)."""
    vals = jnp.concatenate([vals_a, vals_b], axis=-1)
    idx = jnp.concatenate([idx_a, idx_b], axis=-1)
    k = vals_a.shape[-1]
    return select_k(vals, k, select_min=select_min, indices=idx)
