"""Packed 1-bit (RaBitQ-style) list scan — the IVF-BQ search engine.

Reference analog: the RaBitQ scan (IVF-RaBitQ, PAPERS.md) evaluates
binary-code distance estimates per probed list entry; on GPU that is an
XOR/popcount loop over packed sign words. On TPU there is no popcount
datapath worth feeding — but the identity

    popcount-form  ⟨q, b⟩  ≡  matmul-form  q · (2·bits − 1)ᵀ

turns the binarized scan into a dense ±1 contraction, which is exactly the
TPU-KNN peak-FLOP/s formulation (PAPERS.md): saturate the MXU with a
(queries × codes) matmul instead of emulating bit tricks on the VPU.

Two implementations of the same scan, bit-identical by construction:

  * ``impl="pallas"`` — a strip kernel riding ops/strip_scan's ragged-strip
    planning (work ∝ probed entries, per-pair top-kf fused in-kernel): the
    stored codes stay 1 bit/dim in HBM, each grid step DMAs one packed
    (w, rot_dim/8) uint8 block into VMEM, unpacks it to ±1 int8 there
    (8 shift-and-mask VPU ops), upcasts to bf16 and runs ONE MXU matmul
    against the (C, rot_dim) query block. HBM traffic per probed entry is
    rot_dim/8 bytes — 32× under fp32, 8× under the IVF-PQ int8 cache.
  * ``impl="jnp"`` — the pure-jnp reference path: the SAME per-strip
    compute (:func:`_score_topk`, shared code) driven by ``lax.map``
    instead of ``pl.pallas_call``. CPU default, and the bit-parity oracle
    the interpret-mode kernel is tested against.

Scores are ``alpha · ⟨q_rot, ±1⟩ · scale + bias``: the per-entry ``scale``
operand carries the RaBitQ correction scalar (‖u‖²/‖u‖₁ — what makes the
1-bit estimator unbiased, see neighbors/ivf_bq.py) and is the one structural
addition over the fp strip kernel; everything downstream (tournament top-kf,
sub-block revisits, the two-gather merge) is shared with ops/strip_scan.

Bit layout: rotated dimension ``d`` lives at bit ``d // nb`` of byte
``d % nb`` (``nb = rot_dim // 8``) — bit-PLANE-major, so the in-kernel
unpack is eight full-width 2-D shift-and-mask ops plus one lane-axis
concatenate, never a (w, nb, 8) relayout. :func:`pack_sign_bits` /
:func:`unpack_sign_bits` are the only functions that know this layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.ops import strip_scan as ss
from raft_tpu.ops.strip_scan import C, MC


def packed_width(rot_dim: int) -> int:
    """Bytes per 1-bit-encoded row (rot_dim must be a multiple of 8)."""
    if rot_dim % 8:
        raise ValueError(f"rot_dim must be a multiple of 8, got {rot_dim}")
    return rot_dim // 8


def pack_sign_bits(signs) -> jax.Array:
    """(…, rot_dim) sign vectors (> 0 ⇒ bit 1) → (…, rot_dim/8) uint8 in
    the bit-plane-major layout (module docstring)."""
    rot_dim = signs.shape[-1]
    nb = packed_width(rot_dim)
    bits = (signs > 0).astype(jnp.uint32)
    planes = bits.reshape(signs.shape[:-1] + (8, nb))
    weights = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))[:, None]
    return jnp.sum(planes * weights, axis=-2).astype(jnp.uint8)


def unpack_sign_bits(packed, rot_dim: int) -> jax.Array:
    """Inverse of :func:`pack_sign_bits` → (…, rot_dim) int8 in {-1, +1}."""
    nb = packed_width(rot_dim)
    if packed.shape[-1] != nb:
        raise ValueError(f"expected {nb} packed bytes, got {packed.shape[-1]}")
    return _unpack_pm1(packed)


def _unpack_pm1(packed):
    """(…, nb) packed bytes → (…, 8·nb) ±1 int8. 2-D-friendly: eight
    shift-and-masks + one minor-axis concat (each a full-width vector op in
    Mosaic — no (…, nb, 8) relayout, see module docstring)."""
    w = packed.astype(jnp.int32)
    planes = [((w >> j) & 1) for j in range(8)]
    bits = jnp.concatenate(planes, axis=-1)
    return (2 * bits - 1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Multi-bit (2–4 bit) extended codes — extra bit-planes, same kernels
# ---------------------------------------------------------------------------
#
# A B-bit code c ∈ {0 … 2^B−1} per rotated dimension dequantizes to the odd
# integer LEVEL  L = 2c − (2^B−1) = Σ_p 2^p · (2·bit_p(c) − 1):  the level-
# weighted contraction ⟨q, L⟩ decomposes EXACTLY into B ±1 contractions, one
# per bit-plane, weighted 2^p. Storage stacks each plane as its own packed
# group of rot_dim/8 bytes (pack_sign_bits layout per plane), so the kernels'
# existing byte-block DMA + `_unpack_pm1` + one MXU matmul work UNCHANGED at
# the wider byte width bits·rot_dim/8; the level weights ride the QUERY
# operand (:func:`extend_query_planes` — built once per dispatch, outside the
# kernel), which is why the high-recall multi-bit scan is "still just a
# wider MXU contraction" (TPU-KNN's peak-FLOP/s framing). For B = 1 the
# level set is {−1, +1} and everything degenerates to the original layout.


def multibit_width(rot_dim: int, bits: int) -> int:
    """Bytes per B-bit-encoded row: ``bits`` stacked sign planes."""
    if not 1 <= int(bits) <= 4:
        raise ValueError(f"bits must be in [1, 4], got {bits}")
    return int(bits) * packed_width(rot_dim)


def pack_code_planes(codes, bits: int) -> jax.Array:
    """(…, rot_dim) uint8 codes in [0, 2^bits) → (…, bits·rot_dim/8) uint8:
    plane p (bit p of every code) packed via :func:`pack_sign_bits` into its
    own contiguous nb-byte group. bits=1 gives exactly the 1-bit layout."""
    if not 1 <= int(bits) <= 4:
        raise ValueError(f"bits must be in [1, 4], got {bits}")
    codes = codes.astype(jnp.uint8)
    planes = [pack_sign_bits((((codes >> p) & 1).astype(jnp.int8) * 2 - 1))
              for p in range(int(bits))]
    return planes[0] if bits == 1 else jnp.concatenate(planes, axis=-1)


def unpack_code_levels(packed, rot_dim: int, bits: int) -> jax.Array:
    """Inverse view of :func:`pack_code_planes` → (…, rot_dim) int32 LEVELS
    (odd integers in [−(2^bits−1), 2^bits−1]); bits=1 gives ±1."""
    nb = packed_width(rot_dim)
    if packed.shape[-1] != int(bits) * nb:
        raise ValueError(
            f"expected {int(bits) * nb} packed bytes, got {packed.shape[-1]}")
    lv = None
    for p in range(int(bits)):
        pm1 = _unpack_pm1(packed[..., p * nb:(p + 1) * nb]).astype(jnp.int32)
        lv = pm1 if lv is None else lv + (1 << p) * pm1
    return lv


def extend_query_planes(queries_rot, bits: int) -> jax.Array:
    """(q, rot_dim) rotated queries → (q, bits·rot_dim) plane-weighted query
    operand, ordered to match ``_unpack_pm1`` over a (w, bits·nb) packed
    block: unpacked position ``j·bits·nb + p·nb + r`` is bit j of plane p's
    byte r = plane p of dimension ``j·nb + r``, so the slot carries
    ``2^p · q[j·nb + r]``. Then ⟨ext(q), ±1-planes⟩ == ⟨q, levels⟩ exactly.
    bits=1 is the identity."""
    bits = int(bits)
    if bits == 1:
        return queries_rot
    q, rot_dim = queries_rot.shape
    nb = packed_width(rot_dim)
    w = (2.0 ** jnp.arange(bits)).astype(queries_rot.dtype)
    a = queries_rot.reshape(q, 8, 1, nb) * w[None, None, :, None]
    return a.reshape(q, 8 * bits * nb)


def _score_topk(a, b_packed, scale_row, bias_row, alpha: float, kf: int,
                w: int, approx_ok: bool):
    """One strip's scores + fused top-kf — THE shared compute of both
    implementations (kernel refs and jnp gathers feed the same ops, which
    is what makes the two paths bit-identical).

    a: (C, rot_dim) bf16 query block; b_packed: (w, nb) uint8 codes;
    scale_row / bias_row: (1, w) fp32. Scores = alpha·(A@(±1)ᵀ)·scale +
    bias, smaller is better; bias carries +inf at padding."""
    b = _unpack_pm1(b_packed).astype(jnp.bfloat16)
    s = lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = alpha * s * scale_row + bias_row
    return ss._topk_block(s, kf, w, approx_ok)


def _bq_strip_kernel(sl_ref, lv_ref, a_ref, b_ref, scale_ref, bias_ref,
                     *refs, alpha, kf, w, n_sub, start, approx_ok):
    """One strip (× one sub-block when n_sub > 1): in-VMEM unpack + MXU
    matmul + fused top-kf. Mirrors strip_scan._strip_kernel with the packed
    B operand and the per-entry scale: grid step ``i`` scans plan strip
    ``start + i``, candidates are stored lane-dense into the tile's shared
    buffers (``refs`` ends with the two output refs), padding strips
    (strip_list == -1) skip the body via ``pl.when`` exactly like the fp
    kernel, and dead sub-blocks (``lv_ref`` word 0: every bias lane +inf —
    filtered out / tombstoned / padding) skip the unpack+matmul and write
    the all-dead extraction constant on first visit (bit parity argument
    in strip_scan._strip_kernel)."""
    outv_ref, oute_ref = refs[-2:]
    slv = sl_ref[start + pl.program_id(0)]
    j = pl.program_id(1) if n_sub > 1 else 0
    lvv = lv_ref[jnp.maximum(slv, 0) * n_sub + (j if n_sub > 1 else 0)]

    @pl.when((slv >= 0) & (lvv > 0))
    def _compute():
        nv, ne = _score_topk(a_ref[0], b_ref[0], scale_ref[0], bias_ref[0],
                             alpha, kf, w, approx_ok)
        ss._store_candidates(outv_ref, oute_ref, nv, ne + j * w, j, n_sub,
                             kf)

    first = (j == 0) if n_sub > 1 else True

    @pl.when((slv >= 0) & (lvv == 0) & first)
    def _dead_first():
        ss._store_dead(outv_ref, oute_ref, kf)


@functools.partial(
    jax.jit,
    static_argnames=("w_blocks", "n_sub", "start", "count", "alpha", "kf",
                     "interpret", "approx_ok"),
)
def _bq_class_call(strip_list, a_grouped, list_codes, scale3, bias3, cand,
                   w_blocks: int, n_sub: int, start: int, count: int,
                   alpha: float, kf: int, interpret: bool,
                   approx_ok: bool = False):
    """Run one length-class through the Pallas kernel: grid (count,) or
    (count, n_sub) over (C, W) strips from plan strip ``start``, writing
    the tile's shared candidate buffers ``cand`` (None: allocate them) —
    strip_scan._strip_class_call's protocol, with the packed B block and
    the scale operand."""
    s_tot, c, rot_dim = a_grouped.shape
    w = w_blocks * MC
    nb = list_codes.shape[-1]
    n_lists = bias3.shape[0]

    # per-(list, sub-block) liveness words: all-+inf-bias sub-blocks skip
    # their DMAs and compute (strip_scan._strip_class_call convention)
    fin = jnp.isfinite(bias3[:, 0, : n_sub * w]).reshape(n_lists, n_sub, w)
    sub_live = jnp.any(fin, axis=2).astype(jnp.int32).reshape(-1)

    grid, a_map, b_map, sb_map, o_map = ss._class_maps(start, count, s_tot,
                                                       n_sub)
    out_shape, out_specs, cand_specs, cand_ops, aliases = ss._candidate_io(
        cand, 6, s_tot, kf, o_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, rot_dim), a_map),
            pl.BlockSpec((1, w, nb), b_map),
            pl.BlockSpec((1, 1, w), sb_map),
            pl.BlockSpec((1, 1, w), sb_map),
        ] + cand_specs,
        out_specs=out_specs,
    )
    return pl.pallas_call(
        functools.partial(_bq_strip_kernel, alpha=alpha, kf=kf, w=w,
                          n_sub=n_sub, start=start, approx_ok=approx_ok),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(strip_list, sub_live, a_grouped, list_codes, scale3, bias3, *cand_ops)


@functools.partial(
    jax.jit,
    static_argnames=("w_blocks", "n_sub", "start", "count", "alpha", "kf",
                     "approx_ok"),
)
def _bq_class_jnp(strip_list, a_grouped, list_codes, scale3, bias3, cand,
                  w_blocks: int, n_sub: int, start: int, count: int,
                  alpha: float, kf: int, approx_ok: bool = False):
    """Pure-jnp reference for one length-class: the SAME per-(strip,
    sub-block) op sequence as the kernel (shared :func:`_score_topk`, same
    ``_extract_topk`` sub-block merge), driven by a sequential ``lax.map``
    over the class's strips so memory stays bounded, written into the
    shared candidate buffers like :func:`_bq_class_call`'s. Padding strips
    (sl < 0) compute against list 0 — their outputs, like the kernel's
    unwritten garbage, are never read by the merge."""
    w = w_blocks * MC

    def one_strip(args):
        sl, a = args
        l = jnp.maximum(sl, 0)

        def sub(j, carry):
            ov, oe = carry
            blk = lax.dynamic_slice_in_dim(list_codes[l], j * w, w, axis=0)
            sc = lax.dynamic_slice_in_dim(scale3[l, 0], j * w, w)[None, :]
            bi = lax.dynamic_slice_in_dim(bias3[l, 0], j * w, w)[None, :]
            nv, ne = _score_topk(a, blk, sc, bi, alpha, kf, w, approx_ok)
            ne = ne + j * w
            if n_sub == 1:
                return nv, ne
            cv = jnp.concatenate([ov, nv], axis=1)
            ce = jnp.concatenate([oe, ne], axis=1)
            mv, me = ss._extract_topk(cv, ce, kf)
            # j == 0 initializes exactly like the kernel's first write —
            # never through the merge (bit parity of the merged offsets)
            return (jnp.where(j == 0, nv, mv), jnp.where(j == 0, ne, me))

        init = (jnp.full((C, kf), jnp.inf, jnp.float32),
                jnp.zeros((C, kf), jnp.int32))
        return tuple(x.T for x in lax.fori_loop(0, n_sub, sub, init))

    out = lax.map(one_strip, (
        lax.slice_in_dim(strip_list, start, start + count, axis=0),
        lax.slice_in_dim(a_grouped, start, start + count, axis=0)))
    if cand is None:
        shape = (strip_list.shape[0] + 1, kf, C)
        cand = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.int32))
    return tuple(lax.dynamic_update_slice_in_dim(b, o, start, axis=0)
                 for b, o in zip(cand, out))


def _bq_tile_body(queries_rot, qids, strip_list, pair_strip, pair_slot,
                  list_codes, scale, bias, list_ids, class_layout,
                  k: int, kf: int, alpha: float, interpret: bool,
                  pair_const=None, approx_ok: bool = False,
                  impl: str = "pallas"):
    """One query tile of the packed scan: group the query side per strip,
    run every length class through the chosen implementation, then the
    shared two-gather merge (strip_scan.merge_strip_candidates). Plain
    traceable function so SPMD callers can run it inside shard_map
    (distributed/ivf_bq)."""
    n_lists, m = list_codes.shape[0], list_codes.shape[1]
    a_grouped = jnp.where(
        (qids >= 0)[:, :, None],
        queries_rot[jnp.clip(qids, 0), :],
        0,
    ).astype(jnp.bfloat16)                           # (S_pad, C, rot_dim)
    bias3 = bias.reshape(n_lists, 1, m)
    scale3 = scale.reshape(n_lists, 1, m)

    cand = None
    for (w_blocks, n_sub, start, count) in class_layout:
        if impl == "pallas":
            cand = _bq_class_call(strip_list, a_grouped, list_codes, scale3,
                                  bias3, cand, w_blocks, n_sub, start, count,
                                  alpha, kf, interpret, approx_ok)
        else:
            cand = _bq_class_jnp(strip_list, a_grouped, list_codes, scale3,
                                 bias3, cand, w_blocks, n_sub, start, count,
                                 alpha, kf, approx_ok)
    return ss.merge_strip_candidates(*cand, strip_list, pair_strip,
                                     pair_slot, list_ids, k, kf, interpret,
                                     pair_const)


def bq_strip_search_traced(queries_rot, probes, list_codes, scale, bias,
                           list_ids, cls_ord, classes, class_counts,
                           k: int, kf: int, alpha: float,
                           interpret: bool, pair_const=None,
                           approx_ok: bool = False, impl: str = "pallas"):
    """Sync-free packed strip search of ONE query tile (all of
    ``queries_rot``) — fully traceable so callers fuse coarse quantizer +
    device planning + scan + finalize into ONE dispatch (the
    strip_scan.strip_search_traced protocol, packed-B edition), and split a
    search into tiles with :func:`strip_scan.per_tile`.

    queries_rot: (q, rot_dim) ROTATED queries. list_codes: (n_lists, m,
    rot_dim/8) packed sign codes. scale / bias: (n_lists, m) per-entry
    fp32 correction scalar and additive term (+inf bias at padding).
    ``impl`` picks the Pallas kernel or the pure-jnp reference — identical
    results either way (tests/test_bq_scan.py asserts bit parity)."""
    q, p = probes.shape
    region_starts, s_tot, layout = ss.static_layout(
        classes, class_counts, q, p)
    qids, strip_list, pair_strip, pair_slot, _ = ss._plan_device(
        probes, cls_ord, list_codes.shape[0], region_starts, s_tot)
    return _bq_tile_body(
        queries_rot, qids, strip_list, pair_strip, pair_slot, list_codes,
        scale, bias, list_ids, layout, int(k), kf, float(alpha),
        bool(interpret), pair_const, approx_ok, impl)


# ---------------------------------------------------------------------------
# Paged packed scan (serving): the ±1 engine over PagedListStore page chains
# ---------------------------------------------------------------------------


def _paged_bq_score_topk(a, packed_block, scale_row, bias_row, live_rows,
                         alpha: float, kf: int, w: int, approx_ok: bool):
    """One paged packed block's scores + fused top-kf — shared by the
    kernel and the jnp reference (bit parity by construction, the
    :func:`_score_topk` pattern with the paged live-lane mask)."""
    b = _unpack_pm1(packed_block).astype(jnp.bfloat16)
    s = lax.dot_general(a.astype(jnp.bfloat16), b, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = alpha * s * scale_row + bias_row
    lanes = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(lanes < live_rows, s, jnp.inf)
    return ss._topk_block(s, kf, w, approx_ok)


def _paged_bq_kernel(sl_ref, tbl_ref, chain_ref, lv_ref, a_ref, codes_hbm,
                     scale_hbm, bias_hbm, outv_ref, oute_ref, code_s,
                     scale_s, bias_s, csem, ssem, bsem, *, alpha, kf, w,
                     n_sub, ppf, page_rows, table_width, approx_ok):
    """One (strip × page sub-block) of the paged ±1 scan: DMA the live
    code/scale/bias pages HBM→VMEM, unpack to ±1 in VMEM, one MXU matmul +
    fused top-kf (strip_scan._paged_strip_kernel with the packed B operand
    and the per-row scale). ``lv_ref`` carries the per-(list, sub-block)
    filter-liveness words — a dead sub-block (every row +inf-biased)
    issues no DMAs and skips ranking, same contract as the fp paged
    kernel."""
    i = pl.program_id(0)
    slv = sl_ref[i]
    j = pl.program_id(1) if n_sub > 1 else 0
    l = jnp.maximum(slv, 0)
    chain = jnp.where(slv >= 0, chain_ref[l], 0)
    lvv = lv_ref[l * n_sub + (j if n_sub > 1 else 0)]
    base = j * ppf
    nv = jnp.clip(chain - base, 0, ppf) * lvv
    R = page_rows

    def issue(t, _):
        pid = tbl_ref[l * table_width + base + t]
        pltpu.make_async_copy(codes_hbm.at[pid],
                              code_s.at[pl.ds(t * R, R)], csem).start()
        pltpu.make_async_copy(scale_hbm.at[pid],
                              scale_s.at[0, pl.ds(t * R, R)], ssem).start()
        pltpu.make_async_copy(bias_hbm.at[pid],
                              bias_s.at[0, pl.ds(t * R, R)], bsem).start()
        return 0

    def drain(t, _):
        pid = tbl_ref[l * table_width + base + t]
        pltpu.make_async_copy(codes_hbm.at[pid],
                              code_s.at[pl.ds(t * R, R)], csem).wait()
        pltpu.make_async_copy(scale_hbm.at[pid],
                              scale_s.at[0, pl.ds(t * R, R)], ssem).wait()
        pltpu.make_async_copy(bias_hbm.at[pid],
                              bias_s.at[0, pl.ds(t * R, R)], bsem).wait()
        return 0

    lax.fori_loop(0, nv, issue, 0)
    lax.fori_loop(0, nv, drain, 0)

    @pl.when((slv >= 0) & ((j == 0) | ((base < chain) & (lvv > 0))))
    def _compute():
        bv, be = _paged_bq_score_topk(a_ref[0], code_s[...], scale_s[...],
                                      bias_s[...], nv * R, alpha, kf, w,
                                      approx_ok)
        ss._store_candidates(outv_ref, oute_ref, bv, be + j * w, j, n_sub,
                             kf)


@functools.partial(
    jax.jit,
    static_argnames=("ppf", "n_sub", "page_rows", "table_width", "alpha",
                     "kf", "interpret", "approx_ok"),
)
def _paged_bq_class_call(strip_list, table_flat, chain_pages, sub_live,
                         a_grouped, codes, scale_pool, bias_pool, ppf: int,
                         n_sub: int, page_rows: int, table_width: int,
                         alpha: float, kf: int, interpret: bool,
                         approx_ok: bool = False):
    s_pad, c, rot_dim = a_grouped.shape
    w = ppf * page_rows

    if n_sub > 1:
        grid = (s_pad, n_sub)
        a_map = lambda i, j, sl, tb, ch, lv: (jnp.where(sl[i] < 0, 0, i),
                                              0, 0)
        o_map = lambda i, j, sl, tb, ch, lv: (jnp.where(sl[i] < 0, s_pad, i),
                                              0, 0)
    else:
        grid = (s_pad,)
        a_map = lambda i, sl, tb, ch, lv: (jnp.where(sl[i] < 0, 0, i), 0, 0)
        o_map = lambda i, sl, tb, ch, lv: (jnp.where(sl[i] < 0, s_pad, i),
                                           0, 0)

    out_shape, out_specs, _, _, _ = ss._candidate_io(None, 0, s_pad, kf,
                                                     o_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, rot_dim), a_map),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((w, codes.shape[-1]), codes.dtype),
            pltpu.VMEM((1, w), jnp.float32),
            pltpu.VMEM((1, w), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_bq_kernel, alpha=alpha, kf=kf, w=w,
                          n_sub=n_sub, ppf=ppf, page_rows=page_rows,
                          table_width=table_width, approx_ok=approx_ok),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(strip_list, table_flat, chain_pages, sub_live, a_grouped, codes,
      scale_pool, bias_pool)


@functools.partial(
    jax.jit,
    static_argnames=("ppf", "n_sub", "page_rows", "table_width", "alpha",
                     "kf", "approx_ok"),
)
def _paged_bq_class_jnp(strip_list, table_flat, chain_pages, sub_live,
                        a_grouped, codes, scale_pool, bias_pool, ppf: int,
                        n_sub: int, page_rows: int, table_width: int,
                        alpha: float, kf: int, approx_ok: bool = False):
    """jnp reference of the paged packed scan (shared
    :func:`_paged_bq_score_topk`; the bit-parity oracle — same skip
    predicate as the kernel for chain-exhausted or filter-dead
    sub-blocks)."""
    w = ppf * page_rows
    table2 = table_flat.reshape(-1, table_width)
    live2 = sub_live.reshape(-1, n_sub)

    def one_strip(args):
        sl, a = args
        l = jnp.maximum(sl, 0)
        chain = jnp.where(sl >= 0, chain_pages[l], 0)
        trow = table2[l]
        lrow = live2[l]

        def sub(j, carry):
            ov, oe = carry
            lw = lax.dynamic_index_in_dim(lrow, j, keepdims=False)
            pidx = jnp.maximum(
                lax.dynamic_slice_in_dim(trow, j * ppf, ppf), 0)
            blk = codes[pidx].reshape(w, codes.shape[-1])
            srow = scale_pool[pidx].reshape(1, w)
            brow = bias_pool[pidx].reshape(1, w)
            live = jnp.clip(chain - j * ppf, 0, ppf) * lw * page_rows
            bv, be = _paged_bq_score_topk(a, blk, srow, brow, live, alpha,
                                          kf, w, approx_ok)
            be = be + j * w
            if n_sub == 1:
                return bv, be
            cv = jnp.concatenate([ov, bv], axis=1)
            ce = jnp.concatenate([oe, be], axis=1)
            mv, me = ss._extract_topk(cv, ce, kf)
            first = j == 0
            dead = jnp.logical_and(jnp.logical_not(first),
                                   jnp.logical_or(j * ppf >= chain, lw == 0))
            out_v = jnp.where(first, bv, jnp.where(dead, ov, mv))
            out_e = jnp.where(first, be, jnp.where(dead, oe, me))
            return out_v, out_e

        init = (jnp.full((C, kf), jnp.inf, jnp.float32),
                jnp.zeros((C, kf), jnp.int32))
        return tuple(x.T for x in lax.fori_loop(0, n_sub, sub, init))

    return lax.map(one_strip, (strip_list, a_grouped))


def paged_bq_search_traced(queries_rot, probes, codes, scale_pool,
                           bias_pool, page_ids, table, chain_pages, k: int,
                           kf: int, alpha: float, interpret: bool,
                           pair_const=None, approx_ok: bool = False,
                           impl: str = "pallas"):
    """Sync-free paged packed strip search of ONE query tile — the
    :func:`strip_scan.paged_strip_search_traced` protocol with the packed
    B operand and the per-row RaBitQ scale pool. All operands are
    capacity-shaped (zero-recompile serving contract)."""
    page_rows = codes.shape[1]
    ppf, n_sub, w = ss.paged_plan(table.shape[1], page_rows,
                                  int(codes.shape[-1]), kf)
    if kf > w:
        raise ValueError(
            f"paged packed scan needs kf <= fetch block ({w} rows), got "
            f"{kf}")
    fn = (_paged_bq_class_call if impl == "pallas"
          else _paged_bq_class_jnp)
    kwargs = {"interpret": interpret} if impl == "pallas" else {}

    def scan(strip_list, table_flat, sub_live, a_grouped):
        return fn(strip_list, table_flat, chain_pages, sub_live, a_grouped,
                  codes, scale_pool, bias_pool, ppf, n_sub, page_rows,
                  table.shape[1], alpha, kf, approx_ok=approx_ok, **kwargs)

    return ss._paged_tile_body(queries_rot, probes, bias_pool, page_ids,
                              table, chain_pages, ppf, n_sub, scan, k, kf,
                              interpret, pair_const)


def occupancy_stats(lens, m: int, q: int, p: int, rot_dim: int,
                    workspace_bytes: int = 1 << 30, kf: int = 10,
                    bits: int = 1) -> dict:
    """Static occupancy diagnostics of one packed-scan dispatch: the strip
    planner's numbers (:func:`strip_scan.occupancy_stats`) at the scan's
    REAL planning width (the bf16 unpacked block is ``bits·rot_dim`` wide —
    the width ivf_bq's ``_ragged_plan_static`` plans with), plus the
    packed-code byte width the DMAs actually move."""
    out = ss.occupancy_stats(lens, m, q, p, dim=rot_dim * int(bits),
                             workspace_bytes=workspace_bytes, kf=kf)
    out["code_bytes_per_entry"] = multibit_width(rot_dim, bits)
    return out


def bq_dense_scan(queries_rot, probes, list_codes, scale, bias, list_ids,
                  k: int, alpha: float, pair_const=None):
    """Jittable dense packed scan — the distributed layer's off-TPU / small-
    shard path (the bq analog of _sharding.dense_local_scan): probe-tiled
    ``lax.map`` so one probe's (q, mls, rot_dim) unpacked block is the peak
    intermediate, fp32 accumulation."""
    q = queries_rot.shape[0]
    qf = queries_rot.astype(jnp.float32)

    def one_probe(j):
        lids = probes[:, j]                              # (q,)
        cand = _unpack_pm1(list_codes[lids]).astype(jnp.float32)
        ip = jnp.einsum("qd,qmd->qm", qf, cand,
                        preferred_element_type=jnp.float32)
        d = alpha * ip * scale[lids] + bias[lids]
        if pair_const is not None:
            d = d + pair_const[:, j, None]
        return d, list_ids[lids]

    p = probes.shape[1]
    d_all, ids_all = lax.map(one_probe, jnp.arange(p))   # (p, q, mls)
    d = jnp.transpose(d_all, (1, 0, 2)).reshape(q, -1)
    flat_ids = jnp.transpose(ids_all, (1, 0, 2)).reshape(q, -1)
    from raft_tpu.ops.select_k import select_k

    vals, sel = select_k(d, min(k, d.shape[1]), select_min=True)
    ids = jnp.where(jnp.isinf(vals), -1,
                    jnp.take_along_axis(flat_ids, sel, axis=1))
    if ids.shape[1] < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - ids.shape[1])),
                       constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - ids.shape[1])),
                      constant_values=-1)
    return vals, ids
