"""Strip scan — the IVF list-scan engine on TPU (round-3 rewrite).

Reference analog: the per-(query, probe) interleaved/PQ scan kernels
(neighbors/detail/ivf_flat_interleaved_scan-inl.cuh:90,
detail/ivf_pq_compute_similarity-inl.cuh) — one CTA per probed pair, early
exit at the list's real length — plus the multi-pass select pipeline
(detail/ivf_pq_search.cuh:586).

TPU redesign, round 3. Round 2's chunk-table scan (one grid step per
(list, q-chunk, 512-entry m-chunk)) measured DMA-latency-bound: ~9 µs per
512-entry chunk of pure block-fetch latency (the matmul itself is ~0.3 µs),
plus a 3-stage XLA merge whose per-pair gather/top_k dominated everything
(lax.top_k on TPU is a full sort; the qc-major gather rematerialized the
candidate set twice). The fix is to make the unit of work a **strip**: one
grid step covers one (list × ≤C-query block) pair across the ENTIRE list —
a single contiguous (L·512, dim) DMA instead of L separate 512-blocks — and
to finish the per-pair top-k INSIDE the kernel, so the host-side merge
shrinks to one gather + one small select over (q, n_probes·kf).

  * Lists are length-classed: class L ∈ {1..MAX_CLASS} (pow2) covers
    lists of up to L·512 entries (list storage is padded to a power-of-two
    number of 512-blocks, so every class divides the array). Longer lists
    keep a (MAX_CLASS·512, dim) working block and iterate sub-blocks via a
    second grid dimension, merging running top-kf across revisits — VMEM
    stays bounded no matter the list length.
  * Per strip: one MXU matmul (C, dim) × (W, dim)ᵀ → (C, W) fp32 scores
    (+ per-entry bias, +inf at padding), then a top-k on the VPU
    (_topk_block) extracts per-(query, list) top-kf values + offsets.
    A (query, probe) pair maps to exactly one strip slot, so these ARE the
    per-pair candidates — no cross-chunk reduction exists anymore.
  * The merge is one XLA gather of (q, p, kf) candidate rows followed by an
    iterative top-k over p·kf candidates (on the chip one VMEM-resident
    kernel, ops/select_k.resident_topk_min_packed; TPU top_k's sort
    measured ~10× slower at these widths) and one final (q, k)
    id-translate gather.

Work remains ∝ Σ_pairs len(list): no per-list query cap, zero candidate
drops by construction (pairs beyond one strip's query slots get their
own strip). Strip counts per class are bucketed (two buckets per octave) to
bound compiled-shape count; padding strips carry strip_list = -1 and are
skipped entirely in-kernel (round 4 — they used to scan list 0 unread).

The B operand can be fp32/bf16 (IVF-Flat raw vectors, IVF-PQ bf16 decoded
cache) or int8 (IVF-PQ's quantized decoded cache at rot_dim bytes/entry —
the fp8-LUT-compression analog, detail/ivf_pq_fp_8bit.cuh): the kernel
upcasts in VMEM, and the caller folds the dequant scale into the query
operand, so int8 costs one VPU convert and nothing else.

Round-4 changes (measured on the 1M bench shape):

  * **Sync-free fused search** — the dynamic plan's per-tile strip-count
    fetch (device→host sync mid-search) is replaced by a static
    worst-case class layout (``static_layout``); the whole search
    (coarse → device plan → kernel → merge → finalize) compiles into ONE
    dispatch (`ivf_flat._ragged_fused` / `ivf_pq._ragged_fused_pq`).
    Padding strips carry ``strip_list = -1``: the kernel skips their body
    (`pl.when`) and their block maps collapse to constants so the
    pipeline skips the re-fetches.
  * **Mantissa-packed extraction** — the in-kernel top-kf packs the
    column id into the low 12 mantissa bits of the fp32 score
    (select_k.pack_values): each pass is one min + one equality mask (2
    full-width VPU ops vs 5), which was the kernel's dominant cost.
  * Together: IVF-Flat 43K → 92K QPS, IVF-PQ 33K → 54K at unchanged
    recall (0.985), single chip, 1M × 128.

Sorted-bin top-kf (``topk_route``): each direct pass is a min, a compare
and a select over the whole (C, w) block. Where w is whole 128-lane slabs,
a compare-exchange network sorts the 128 strided lane bins once and each
pass reads only the head slab; exact and bit-identical to the direct
passes. A static rule on (kf, w) picks the route, so every strip-shaped
kernel (packed, paged, 1-bit) takes it at the same shapes; the tournament
(``approx_ok``) is unchanged.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu import obs

C = 192          # queries per strip (MXU M dim; fewer, fatter strips
                 # amortize the measured ~25 µs fixed per-strip cost;
                 # 256 measured a VMEM stack OOM at kf=40)
MC = 512         # base entry block; class-L strips read L*MC entries at once
MAX_CLASS = 8    # biggest single-fetch strip (w = 4096 entries). Round 4:
                 # the packed extraction holds ONE live score copy, so wide
                 # blocks now fit VMEM where round 3's unrolled extraction
                 # OOM'd at w=2048 — cutting grid steps for 1-4K-entry
                 # lists measured IVF-Flat 97→111K and IVF-PQ 63→92K QPS
                 # at the 1M bench shape (validated up to kf=129 in-kernel)


def _to_bf16(x):
    """In-VMEM upcast of a B block to the MXU dtype. Mosaic has no
    unsigned-to-float cast, so uint8 rows (IVF-Flat over uint8 corpora)
    go through int32 — exact for every uint8 value."""
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        x = x.astype(jnp.int32)
    return x.astype(jnp.bfloat16)


def _ceil_div(a, b):
    return -(-a // b)


def strip_eligible(m: int) -> bool:
    """True when a padded list length can feed the strip kernel: a
    power-of-two multiple of MC (every length class must divide it)."""
    return m % MC == 0 and (m // MC) & (m // MC - 1) == 0


def _next_pow2(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(int(n), 1))))


def _bucket(n: int) -> int:
    """Two buckets per octave (pow2 and 1.5·pow2): ≤ 33% padding waste while
    keeping the compiled-shape count ~2·log2(range)."""
    n = max(int(n), 8)
    p = 1 << math.floor(math.log2(n))
    if n <= p:
        return p
    if n <= p + p // 2:
        return p + p // 2
    return 2 * p


@dataclass
class StripPlan:
    """Host-built strip table for one query tile (arrays np.int32)."""

    qids: np.ndarray         # (S_pad, C) query id per strip slot, -1 pad
    strip_list: np.ndarray   # (S_pad,) list id per strip
    pair_strip: np.ndarray   # (q, p) strip of each probed pair
    pair_slot: np.ndarray    # (q, p) slot within the strip
    # static per-call layout: ((class_w_blocks, n_sub, start, count), ...)
    class_layout: Tuple[Tuple[int, int, int, int], ...]
    n_strips: int            # real strips (<= S_pad)

    @property
    def s_pad(self) -> int:
        return self.strip_list.shape[0]


def plan_strips(probes: np.ndarray, lens: np.ndarray, n_lists: int) -> StripPlan:
    """Build the strip table from a tile's probe matrix (q, p) and per-list
    entry counts. Pure numpy, ~ms per tile — the data-dependent scheduling
    the GPU does with atomics and CTA dispatch."""
    q, p = probes.shape
    flat = probes.reshape(-1).astype(np.int64)
    order = np.argsort(flat, kind="stable")
    sorted_lists = flat[order]
    qid_of = (order // p).astype(np.int32)

    r = np.bincount(flat, minlength=n_lists)             # pairs per list
    n_qc = _ceil_div(r, C)                               # strips per list
    n_mc = np.maximum(_ceil_div(np.maximum(lens, 0), MC), 1)
    cls_full = 1 << np.ceil(np.log2(n_mc)).astype(np.int64)
    cls = np.minimum(cls_full, MAX_CLASS)                # fetch-block class
    n_sub = np.maximum(cls_full // MAX_CLASS, 1)         # sub-block iterations

    # group probed lists by (cls, n_sub); fixed ascending order keeps the
    # class_layout static across tiles of the same distribution
    probed = np.nonzero(n_qc)[0]
    keys = (cls[probed] << 32) | n_sub[probed]
    uniq_keys = np.unique(keys)

    strip_base = np.zeros(n_lists, np.int64)
    strip_list_parts, layout = [], []
    start = 0
    for key in uniq_keys:
        w_blocks = int(key >> 32)
        sub = int(key & 0xFFFFFFFF)
        lists_g = probed[keys == key]
        count = int(n_qc[lists_g].sum())
        pad = _bucket(count)
        sl = np.full(pad, -1, np.int32)  # padding strips: kernel-skipped
        sl[:count] = np.repeat(lists_g.astype(np.int32), n_qc[lists_g])
        base = start + np.concatenate([[0], np.cumsum(n_qc[lists_g])[:-1]])
        strip_base[lists_g] = base
        strip_list_parts.append(sl)
        layout.append((w_blocks, sub, start, pad))
        start += pad

    s_pad = start
    strip_list = (np.concatenate(strip_list_parts) if strip_list_parts
                  else np.zeros(1, np.int32))
    if not layout:  # degenerate: no probes
        layout = [(1, 1, 0, 1)]
        s_pad = 1

    # per-pair (strip, slot): rank of the pair within its list's probe set
    pair_off = np.concatenate([[0], np.cumsum(r)]).astype(np.int64)
    rank = np.arange(q * p) - pair_off[sorted_lists]
    ps_sorted = (strip_base[sorted_lists] + rank // C).astype(np.int32)
    slot_sorted = (rank % C).astype(np.int32)
    pair_strip = np.empty(q * p, np.int32)
    pair_slot = np.empty(q * p, np.int32)
    pair_strip[order] = ps_sorted
    pair_slot[order] = slot_sorted

    # query ids per strip slot (pair arrays are in original pair order, so
    # the query of pair i is simply i // p)
    qids = np.full((s_pad, C), -1, np.int32)
    qids[pair_strip, pair_slot] = (np.arange(q * p) // p).astype(np.int32)

    return StripPlan(
        qids=qids,
        strip_list=strip_list,
        pair_strip=pair_strip.reshape(q, p),
        pair_slot=pair_slot.reshape(q, p),
        class_layout=tuple(layout),
        n_strips=int(n_qc.sum()),
    )


_PACK_BITS = 12          # low-mantissa bits carrying the column index
                         # (covers w = MAX_CLASS·MC = 4096; ≤ 2⁻¹¹ relative
                         # value perturbation — inside the bf16 contract)
_NB = 128                # lane bins of the sorted route and the tournament
                         # (strided: bin j = cols ≡ j mod _NB — a full VPU
                         # lane row, so the per-bin operations stay wide)


def _pack_scores(s, w: int, bits: int = _PACK_BITS):
    """Pack column ids into the low ``bits`` mantissa bits of fp32 scores
    (ops/select_k.pack_values — shared so the clamp/NaN/±inf invariants
    live in one place).

    A min pass over the packed values yields the winning VALUE and its
    COLUMN in one reduction — the per-pass argmin reconstruction
    (compare-to-min + one-hot sum) that dominated the round-3 kernel cost
    drops out entirely. The ≤ 2⁻¹¹ relative perturbation (12 index bits)
    sits inside this path's documented bf16 (~3 significant digits)
    ranking contract. The resident top-k (ops/select_k) packs with the
    bits ``iter_topk_min_packed`` takes for its row width instead.
    """
    assert w <= (1 << bits), w
    from raft_tpu.ops.select_k import pack_values

    return pack_values(s, bits)


def _extract_passes(state, head, drop, c: int, kf: int,
                    bits: int = _PACK_BITS):
    """kf extraction passes → ((C, kf) values, (C, kf) columns).
    ``head(state)`` is a block packed with ``bits`` index bits whose row
    minimum is the row's next winner; ``drop(state, hit)`` removes it
    (``hit`` marks it in ``head(state)``). Values at the packing clamp are
    restored to +inf: a clamped +inf sentinel (filtered/padding entry)
    must come back as inf, not as a finite ~3.4e38 hit (code-review r4)."""
    mask = (1 << bits) - 1
    kcols = lax.broadcasted_iota(jnp.int32, (c, kf), 1)

    def body(i, carry):
        state, vals, es = carry
        h = head(state)
        mn = jnp.min(h, axis=1)                       # packed winner
        mb = lax.bitcast_convert_type(mn, jnp.int32)
        e = mb & jnp.int32(mask)
        v = lax.bitcast_convert_type(mb & jnp.int32(~mask), jnp.float32)
        sel = kcols == i
        vals = jnp.where(sel, v[:, None], vals)
        es = jnp.where(sel, e[:, None], es)
        return drop(state, h == mn[:, None]), vals, es

    _, vals, es = lax.fori_loop(
        0, kf, body,
        (state, jnp.full((c, kf), jnp.inf, jnp.float32),
         jnp.zeros((c, kf), jnp.int32)),
    )
    from raft_tpu.ops.select_k import pack_clamp_for

    # pack_clamp_for's value already has zero low mantissa bits, so the
    # unpacked winner of a clamped entry equals it exactly (static python
    # float: Mosaic rejects scalar bitcast ops in-kernel)
    return jnp.where(vals >= pack_clamp_for(bits), jnp.inf, vals), es


def _extract_topk_packed(pv, kf: int, bits: int = _PACK_BITS):
    """kf min passes over packed scores (C, n) → ((C, kf) values, (C, kf)
    columns). Two full-width VPU ops per pass (min + mask) vs the generic
    _extract_topk's five — the packed trick halves-to-thirds the kernel's
    dominant cost."""
    return _extract_passes(
        pv, lambda pv: pv, lambda pv, hit: jnp.where(hit, jnp.inf, pv),
        pv.shape[0], kf, bits)


@functools.lru_cache(maxsize=None)
def _sort_pairs(n: int) -> Tuple[Tuple[int, int], ...]:
    """Compare-exchange pairs ``(i, j)``, i < j, of Batcher's odd-even
    merge sort of ``n`` keys, in order. Built for the next power of two
    with the surplus keys taken as +inf at the end: a pair that touches one
    never moves anything, so it is left out."""
    p2 = _next_pow2(n)
    pairs = []
    p = 1
    while p < p2:
        k = p
        while k >= 1:
            for j in range(k % p, p2 - k, 2 * k):
                for i in range(min(k, p2 - j - k)):
                    a, b = i + j, i + j + k
                    if a // (2 * p) == b // (2 * p) and b < n:
                        pairs.append((a, b))
            k //= 2
        p *= 2
    return tuple(pairs)


def _extract_topk_sorted(pv, kf: int, bits: int = _PACK_BITS):
    """Exact top-kf of packed scores (C, w), w = bs·_NB, from sorted lane
    bins: the same (values, columns) as :func:`_extract_topk_packed`.

    Bin j holds the columns ≡ j mod _NB, one per (C, _NB) slab. A
    compare-exchange network sorts every bin along the slab axis (plain
    elementwise min/max of slab pairs, no lane reduce) and the first
    D = min(kf, bs) levels are kept: no bin holds more than kf winners.
    Each pass then takes the lane-min of the head slab alone, counts the
    hit in the winning bin's depth and puts that bin's next level into the
    head (+inf once its D levels are spent). The levels stay put: selecting
    the next level by depth measured 1.4-1.8x faster on a v5e than
    shifting all D levels up every pass. Packed values are unique within a
    row, so the winners come out in the same ascending order as the direct
    passes, bit for bit."""
    c, w = pv.shape
    bs = w // _NB
    lv = [pv[:, i * _NB:(i + 1) * _NB] for i in range(bs)]
    for a, b in _sort_pairs(bs):
        lv[a], lv[b] = jnp.minimum(lv[a], lv[b]), jnp.maximum(lv[a], lv[b])
    lv = lv[:min(kf, bs)]

    def drop(state, hit):
        head, depth = state
        depth = depth + hit.astype(jnp.int32)
        nxt = jnp.full(head.shape, jnp.inf, jnp.float32)
        for l in range(1, len(lv)):
            nxt = jnp.where(depth == l, lv[l], nxt)
        return jnp.where(hit, nxt, head), depth

    return _extract_passes((lv[0], jnp.zeros(lv[0].shape, jnp.int32)),
                           lambda state: state[0], drop, c, kf, bits)


def _extract_topk(v, offs, kf: int):
    """kf masked-min passes over (C, n): (vals (C, kf), offsets (C, kf)).
    Offset picks use a one-hot sum — no gathers in-kernel. A fori_loop (not
    a Python unroll) keeps one live copy of the working block: the unrolled
    form held ~kf copies and blew Mosaic's 16 MB scoped-vmem stack at
    kf=40."""
    c, n = v.shape
    cols = lax.broadcasted_iota(jnp.int32, v.shape, 1)
    kcols = lax.broadcasted_iota(jnp.int32, (c, kf), 1)

    def body(i, carry):
        v, vals, es = carry
        mn = jnp.min(v, axis=1)
        am = jnp.min(jnp.where(v <= mn[:, None], cols, n), axis=1)
        hit = cols == am[:, None]
        e = jnp.sum(jnp.where(hit, offs, 0), axis=1)
        sel = kcols == i
        vals = jnp.where(sel, mn[:, None], vals)
        es = jnp.where(sel, e[:, None], es)
        return jnp.where(hit, jnp.inf, v), vals, es

    _, vals, es = lax.fori_loop(
        0, kf, body,
        (v, jnp.full((c, kf), jnp.inf, jnp.float32),
         jnp.zeros((c, kf), jnp.int32)),
    )
    return vals, es


_KEEP = 4   # per-bin survivors in the tournament pool


def topk_route(kf: int, w: int, approx_ok: bool = False) -> str:
    """Which extraction :func:`_topk_block` runs on a (C, w) block:
    ``"tournament"`` (approximate, only with ``approx_ok``), ``"sorted"``
    or ``"direct"`` (both exact, bit-identical). A pure function of the
    static shape, so telemetry counts the routes without the device."""
    bs = w // _NB
    # tournament: engage when its total work (build + pool extraction)
    # beats direct extraction (kf·w > _KEEP·w + kf·_KEEP·_NB) AND the
    # collision loss stays a tail event. The loss is governed by the
    # expected per-bin top-kf mass kf/_NB (width-independent!), so cap at
    # kf ≤ _NB/4 = 32 (mass ≤ 0.25 of the _KEEP survivors, P(loss) ~1e-4
    # per strip row); kf ≤ bs·_KEEP additionally guarantees the pool can
    # hold kf at small widths.
    wins = kf * w > _KEEP * w + kf * _KEEP * _NB
    if (approx_ok and 16 <= kf <= min(bs * _KEEP, _NB // 4) and bs >= 2
            and wins):
        return "tournament"
    # sorted bins vs direct passes, in (C, _NB) slab operations: a direct
    # pass is a min, a compare and a select over all bs slabs; the sorted
    # route pays its network (a min and a max per pair) once, then per
    # pass a compare, a depth count, a compare and a select per level
    # below the head (D = min(kf, bs) levels) and the head's select. Each
    # pass's one lane reduce is common to both and left out.
    if w % _NB or bs < 2:
        return "direct"
    sort_ops = 2 * len(_sort_pairs(bs)) + kf * (2 * min(kf, bs) + 1)
    return "sorted" if sort_ops < 3 * kf * bs else "direct"


def _topk_block(s, kf: int, w: int, approx_ok: bool,
                bits: int = _PACK_BITS):
    """Top-kf of a (C, w) score block, on the route :func:`topk_route`
    picks from the static (kf, w).

    Every route first packs each column id into its score's low ``bits``
    mantissa bits (_pack_scores), so a row minimum names its winner.

    * ``direct`` (:func:`_extract_topk_packed`): kf min passes over the
      whole block, each a min, a compare and a select over all w lanes.
    * ``sorted`` (:func:`_extract_topk_sorted`): exact and bit-identical
      to ``direct``. The _NB strided lane bins are sorted once by a
      compare-exchange network, then each pass reads only the head slab.
      It takes every shape whose w is a multiple of _NB with two or more
      slabs, where it does less work: the cells' kf = 10 and 40 at every
      list width, not kf = 1.
    * ``tournament``: keep the _KEEP smallest of each of _NB strided bins
      (built with _KEEP passes reduced along the small axis of a (C,
      w/_NB, _NB) view), then extract kf from the _KEEP·_NB pool. Exact
      unless > _KEEP of a row's true top-kf collide in one bin (entries
      land in bins by storage position, arbitrary w.r.t. distance — a
      small tail event), so it engages only for 16 ≤ kf ≤ 32 when the
      caller declares the loss acceptable via ``approx_ok`` (ADVICE r3:
      IVF-PQ over-fetches + exact-re-ranks, so it opts in; IVF-Flat's
      contract is exact-within-probes, so it never takes the lossy route
      at any k).
    """
    c = s.shape[0]
    bs = w // _NB
    route = topk_route(kf, w, approx_ok)
    pv = _pack_scores(s, w, bits)
    if route == "sorted":
        return _extract_topk_sorted(pv, kf, bits)
    if route == "direct":
        return _extract_topk_packed(pv, kf, bits)
    # tournament on packed values: the bin survivors carry their own column
    # ids in the mantissa, so the pool extraction needs no offset tables
    sv = pv.reshape(c, bs, _NB)
    pool = []
    for _ in range(_KEEP):
        mn = jnp.min(sv, axis=1)                       # (C, _NB) packed
        pool.append(mn)
        sv = jnp.where(sv == mn[:, None, :], jnp.inf, sv)
    return _extract_topk_packed(jnp.concatenate(pool, axis=1), kf, bits)


def _store_candidates(outv_ref, oute_ref, nv, ne, j, n_sub: int, kf: int):
    """Store one strip's (C, kf) top-kf lane-dense, as a (kf, C) block of
    the tile's candidate buffers (the layout :func:`merge_strip_candidates`
    reads). On a sub-block revisit (``j > 0``) the running top-kf, read
    back in (C, kf) orientation, is merged with the new one first. Shared
    by every strip-shaped kernel."""
    if n_sub == 1:
        outv_ref[0] = nv.T
        oute_ref[0] = ne.T
        return

    @pl.when(j == 0)
    def _():
        outv_ref[0] = nv.T
        oute_ref[0] = ne.T

    @pl.when(j > 0)
    def _():
        cv = jnp.concatenate([outv_ref[0].T, nv], axis=1)      # (C, 2kf)
        ce = jnp.concatenate([oute_ref[0].T, ne], axis=1)
        mv, me = _extract_topk(cv, ce, kf)
        outv_ref[0] = mv.T
        oute_ref[0] = me.T


def _store_dead(outv_ref, oute_ref, kf: int):
    """The all-dead extraction result, stored lane-dense: value ``+inf`` at
    offsets ``0..kf-1`` in column order, exactly what ``_topk_block`` /
    ``_extract_topk`` produce for an all-+inf score block."""
    c = outv_ref.shape[2]
    outv_ref[0] = jnp.full((kf, c), jnp.inf, jnp.float32)
    oute_ref[0] = lax.broadcasted_iota(jnp.int32, (kf, c), 0)


def _candidate_io(cand, n_in: int, s_tot: int, kf: int, o_map):
    """Outputs of a length-class call into the tile's shared candidate
    buffers: (s_tot + 1, kf, C) f32 values and int32 offsets, a strip's
    candidates lane-dense at its plan row, row ``s_tot`` the one trash row
    of every padding strip. With ``cand`` None the call allocates the
    buffers; otherwise it writes into ``cand`` in place
    (``input_output_aliases``), so no class's candidates are sliced,
    concatenated or copied on their way to the merge. Rows no class
    writes stay garbage the merge never reads.

    Returns (out_shape, out_specs, extra in_specs, extra operands,
    input_output_aliases); ``n_in`` counts the call's other operands,
    scalar prefetch included."""
    shape = (s_tot + 1, kf, C)
    out_shape = (jax.ShapeDtypeStruct(shape, jnp.float32),
                 jax.ShapeDtypeStruct(shape, jnp.int32))
    out_specs = [pl.BlockSpec((1, kf, C), o_map)] * 2
    if cand is None:
        return out_shape, out_specs, [], (), {}
    return (out_shape, out_specs, [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            tuple(cand), {n_in: 0, n_in + 1: 1})


def _class_maps(start: int, count: int, s_tot: int, n_sub: int):
    """Grid and block index maps of one length class of the packed strip
    kernels (fp rows here, 1-bit codes in ops/bq_scan): grid (count,) or
    (count, n_sub), step ``i`` scanning plan strip ``start + i``, with the
    strip table and the liveness words as scalar prefetch.

    Padding strips (sl = -1, kernel-skipped) get ALL their block maps
    collapsed to constants — consecutive identical block indices make
    Pallas skip the refetch, so a padding step costs only grid
    bookkeeping (~1-2 µs), not the 512 KB list DMA + output writeback.
    Outputs for padding route to the trash row (``s_tot``) so real rows
    are never clobbered by stale-buffer writebacks. Dead sub-blocks
    (liveness word 0) collapse their row maps the same way — a fully
    filtered-out list costs grid bookkeeping, not its list DMA — but keep
    their output row: the kernel writes the all-dead extraction constant
    on first visit (bit-parity with computing, see _strip_kernel).

    Returns (grid, a_map, b_map, row_map, o_map): ``b_map`` indexes
    (1, w, ·) blocks of list rows, ``row_map`` (1, 1, w) blocks of a
    per-row term (bias, scale)."""
    def parse(args):
        if n_sub > 1:
            i, j, sl, lv = args
        else:
            (i, sl, lv), j = args, 0
        pad = sl[start + i] < 0
        l = jnp.maximum(sl[start + i], 0)
        return i, j, l, pad, pad | (lv[l * n_sub + j] == 0)

    def a_map(*args):
        i, _, _, pad, _ = parse(args)
        return jnp.where(pad, 0, start + i), 0, 0

    def b_map(*args):
        _, j, l, _, dead = parse(args)
        return jnp.where(dead, 0, l), jnp.where(dead, 0, j), 0

    def row_map(*args):
        _, j, l, _, dead = parse(args)
        return jnp.where(dead, 0, l), 0, jnp.where(dead, 0, j)

    def o_map(*args):
        i, _, _, pad, _ = parse(args)
        return jnp.where(pad, s_tot, start + i), 0, 0

    grid = (count, n_sub) if n_sub > 1 else (count,)
    return grid, a_map, b_map, row_map, o_map


def _strip_kernel(sl_ref, lv_ref, a_ref, b_ref, bias_ref, *refs, alpha, kf,
                  w, n_sub, start, approx_ok):
    """One strip (× one sub-block when n_sub > 1): matmul + fused top-kf.

    Scores = alpha·(A @ Bᵀ) + bias, smaller is better; the packed top-k
    (_topk_block, on its route for (kf, w)) extracts per-row top-kf
    values and within-list entry offsets, stored lane-dense
    (_store_candidates). Sub-block revisits merge the running top-kf via
    a concat + kf passes over the 2·kf-wide block.

    Grid step ``i`` scans strip ``start + i`` of the tile's plan (the
    class's region of the whole strip table). ``refs`` ends with the two
    output refs; before them, when the call writes into buffers an earlier
    class allocated, the two aliased inputs, which the kernel never reads.

    Strips with ``strip_list == -1`` are static-layout padding (round-4
    sync-free planning, static_layout): the whole body is skipped via
    ``pl.when``, so worst-case grid padding costs only the block DMA —
    their outputs stay unwritten garbage and the merge never reads them.
    (program_id/sl_ref reads happen at kernel top level — the CPU interpret
    path rejects primitive calls inside a ``pl.when`` region.)

    ``lv_ref`` (round 19, predicate push-down) is the per-(list,
    sub-block) liveness word: 0 when the sub-block's bias lanes are ALL
    ``+inf`` (every row filtered out / tombstoned / padding). A dead
    sub-block's B/bias block maps are collapsed to block 0 (DMA skipped
    after the first fetch) and the matmul+top-k is skipped: the first
    visit writes the all-dead extraction result directly (_store_dead)
    and revisits leave the carry untouched, which is bitwise what merging
    with an all-inf block returns (ascending carry + earliest-column inf
    ties). Filtered scans therefore stay bit-identical to the
    compute-everything path while skipping dead work entirely."""
    outv_ref, oute_ref = refs[-2:]
    slv = sl_ref[start + pl.program_id(0)]
    j = pl.program_id(1) if n_sub > 1 else 0
    lvv = lv_ref[jnp.maximum(slv, 0) * n_sub + (j if n_sub > 1 else 0)]

    @pl.when((slv >= 0) & (lvv > 0))
    def _compute():
        a = a_ref[0]                                   # (C, dim) bf16
        b = _to_bf16(b_ref[0])                         # (w, dim)
        s = lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = alpha * s + bias_ref[0]                    # (C, w)
        nv, ne = _topk_block(s, kf, w, approx_ok)      # (C, kf) each
        _store_candidates(outv_ref, oute_ref, nv, ne + j * w, j, n_sub, kf)

    # dead sub-block, first visit: write the all-inf extraction constant
    # (revisits skip — the carry IS the merge result, see docstring)
    first = (j == 0) if n_sub > 1 else True

    @pl.when((slv >= 0) & (lvv == 0) & first)
    def _dead_first():
        _store_dead(outv_ref, oute_ref, kf)


@functools.partial(
    jax.jit,
    static_argnames=("w_blocks", "n_sub", "start", "count", "alpha", "kf",
                     "interpret", "approx_ok"),
)
def _strip_class_call(strip_list, a_grouped, list_data, bias3, cand,
                      w_blocks: int, n_sub: int, start: int, count: int,
                      alpha: float, kf: int, interpret: bool,
                      approx_ok: bool = False):
    """Run one length-class, the ``count`` strips of the plan from
    ``start``: grid (count,) or (count, n_sub) over (C, W) strips.
    ``strip_list`` and ``a_grouped`` are the whole tile's; ``cand`` is
    the tile's candidate buffers pair, or None for the first class
    (:func:`_candidate_io`). Returns the buffers with this class's strips
    written."""
    s_tot, c, dim = a_grouped.shape
    w = w_blocks * MC
    n_lists = bias3.shape[0]

    # Per-(list, sub-block) liveness words (round 19, predicate push-down):
    # a sub-block whose bias lanes are ALL +inf (filtered out, tombstoned,
    # or padding) contributes nothing to any top-k, so its DMAs and compute
    # are skipped. One cheap VPU pass over the bias operand — rides the
    # same jit as the scan, so mask changes re-dispatch, never recompile.
    fin = jnp.isfinite(bias3[:, 0, : n_sub * w]).reshape(n_lists, n_sub, w)
    sub_live = jnp.any(fin, axis=2).astype(jnp.int32).reshape(-1)

    grid, a_map, b_map, bias_map, o_map = _class_maps(start, count, s_tot,
                                                     n_sub)
    out_shape, out_specs, cand_specs, cand_ops, aliases = _candidate_io(
        cand, 5, s_tot, kf, o_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, dim), a_map),
            pl.BlockSpec((1, w, dim), b_map),
            pl.BlockSpec((1, 1, w), bias_map),
        ] + cand_specs,
        out_specs=out_specs,
    )
    return pl.pallas_call(
        functools.partial(_strip_kernel, alpha=alpha, kf=kf, w=w, n_sub=n_sub,
                          start=start, approx_ok=approx_ok),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(strip_list, sub_live, a_grouped, list_data, bias3, *cand_ops)


def _strip_tile_body(queries_mat, qids, strip_list, pair_strip, pair_slot,
                     list_data, bias, list_ids,
                     class_layout, k: int, kf: int, alpha: float,
                     interpret: bool, pair_const=None,
                     approx_ok: bool = False):
    """One query tile: group the query side per strip, run every length
    class, then the two-gather merge. Plain traceable function so SPMD
    callers can run it inside shard_map (distributed/ivf_*).

    ``pair_const`` (q, p): optional per-(query, probe) additive constant,
    applied AFTER the in-kernel extraction — it cannot change within-pair
    ranking, so this is exact. IVF-PQ uses it for the −2⟨q, R·c_l⟩ term so
    the int8 cache only has to carry the (much smaller) residuals."""
    n_lists, m = list_data.shape[0], list_data.shape[1]
    with jax.named_scope("strip_plan"):
        a_grouped = jnp.where(
            (qids >= 0)[:, :, None],
            queries_mat[jnp.clip(qids, 0), :],
            0,
        ).astype(jnp.bfloat16)                       # (S_pad, C, dim)
        bias3 = bias.reshape(n_lists, 1, m)

    cand = None
    with jax.named_scope("strip_scan"):
        for (w_blocks, n_sub, start, count) in class_layout:
            cand = _strip_class_call(
                strip_list, a_grouped, list_data, bias3, cand, w_blocks,
                n_sub, start, count, alpha, kf, interpret, approx_ok,
            )

    return merge_strip_candidates(*cand, strip_list, pair_strip, pair_slot,
                                  list_ids, k, kf, interpret, pair_const)


def merge_on_resident(p: int, k: int, kf: int, interpret: bool) -> bool:
    """Whether :func:`merge_strip_candidates` selects its top-k of the
    (q, p·kf) candidates with ops/select_k.resident_topk_min_packed: on
    the chip, k ≤ 64 and p·kf ≤ 2048 (≤ 11 index bits)."""
    return not interpret and min(k, p * kf) <= 64 and p * kf <= 2048


def merge_strip_candidates(cand_v, cand_e, strip_list, pair_strip, pair_slot,
                           list_ids, k: int, kf: int, interpret: bool,
                           pair_const=None):
    """The two-gather candidate merge shared by every strip-shaped engine
    (the fp B-operand kernel here, the paged kernels and the packed 1-bit
    kernels in ops/bq_scan.py — one copy, so the select/translate protocol
    cannot drift between them).

    ``cand_v`` / ``cand_e`` (S, kf, C) hold each strip's per-slot top-kf
    values and in-list offsets lane-dense, at the strip's row of the
    plan (trailing rows past the plan, such as the trash row, are never
    read): pair (query, probe) reads column ``pair_slot`` of row
    ``pair_strip``. Candidates are ordered probe-major, then by rank."""
    with jax.named_scope("merge"):
        q, p = pair_strip.shape
        cand = cand_v[pair_strip, :, pair_slot]            # (q, p, kf)
        if pair_const is not None:
            cand = cand + pair_const[:, :, None]
        cand_v = cand.reshape(q, p * kf)
        cand_e = cand_e[pair_strip, :, pair_slot].reshape(q, p * kf)
        from raft_tpu.ops.select_k import (iter_topk_min,
                                           resident_topk_min_packed)

        kk = min(k, p * kf)
        if merge_on_resident(p, k, kf, interpret):
            # packed passes (iter_topk_min_packed's, in one VMEM-resident
            # kernel that reads the candidates once): half the VPU cost of
            # iter_topk_min; ≤ 11 index bits keeps the perturbation ≤
            # 2^-12 ≈ 2.4e-4 — inside this path's bf16 score contract.
            # Wider merges (big n_probes · kf) would dilute the value
            # mantissa (code-review r4), so they take the exact iter passes.
            vals, sel = resident_topk_min_packed(cand_v, kk)
        elif kk <= 64 and not interpret:
            vals, sel = iter_topk_min(cand_v, kk)
        else:
            nv, sel = lax.top_k(-cand_v, kk)
            vals = -nv
        win_list = jnp.take_along_axis(strip_list[pair_strip], sel // kf, axis=1)
        win_off = jnp.take_along_axis(cand_e, sel, axis=1)
        out_ids = list_ids[win_list, win_off]            # (q, kk)
        if kk < k:
            vals = jnp.pad(vals, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
            out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)), constant_values=-1)
        out_ids = jnp.where(jnp.isfinite(vals), out_ids, -1)
        return vals, out_ids


def max_class_for(dim: int) -> int:
    """Largest fetch class whose (1, w, dim) fp32 B-block stays inside a
    ~6 MB double-buffered VMEM budget (review r4: MAX_CLASS=8 was only
    validated at dim=128 — a dim-768 index would request 12.6 MB blocks).
    dim=128 → 8; dim≈256 → 4; dim≈512 → 2; dim ≥ ~1024 → 1."""
    if dim <= 0:
        return MAX_CLASS
    w_max = max(MC, (6 << 20) // (dim * 4 * 2))
    cls = 1
    while cls * 2 <= MAX_CLASS and cls * 2 * MC <= w_max:
        cls *= 2
    return cls


def class_info(lens_np: np.ndarray, dim: int = 0):
    """Static per-index class table from per-list lengths: ordered distinct
    (w_blocks, n_sub) classes and each list's class ordinal. ``dim`` caps
    the fetch class so wide-row indexes keep their blocks inside VMEM."""
    max_class = min(MAX_CLASS, max_class_for(dim)) if dim else MAX_CLASS
    n_mc = np.maximum(-(-np.maximum(lens_np, 0) // MC), 1)
    cls_full = (1 << np.ceil(np.log2(n_mc)).astype(np.int64))
    w = np.minimum(cls_full, max_class)
    sub = np.maximum(cls_full // max_class, 1)
    keys = w * (1 << 20) + sub
    uniq = np.unique(keys)
    ordinal = np.searchsorted(uniq, keys).astype(np.int32)
    classes = [(int(k_ >> 20), int(k_ & ((1 << 20) - 1))) for k_ in uniq]
    return classes, ordinal


@functools.partial(
    jax.jit,
    static_argnames=("n_lists", "region_starts", "s_tot"),
)
def _plan_device(probes, cls_ord, n_lists: int,
                 region_starts: Tuple[int, ...], s_tot: int):
    """Device-side strip planning (round-3 v3): host-built plan tables (a
    few MB per tile, uploaded every search) dominated search latency. This
    builds the same tables with jnp sorts/scatters ON DEVICE; the host only
    fetches the per-class strip counts (a few ints) to fix the static grid
    sizes — or nothing at all on the static-layout path.

    Strips live in per-class regions starting at ``region_starts[c]``
    (round-4: per-class sizes — a uniform n_lists-wide stride made the
    query-side tables scale as n_classes · n_lists, which OOM'd many-list /
    few-query shapes); unused slots carry qids=-1 / strip_list=-1 and are
    never read by the merge. Returns (qids, strip_list, pair_strip,
    pair_slot, counts_per_class)."""
    with jax.named_scope("strip_plan"):
        q, p = probes.shape
        qp = q * p
        n_classes = len(region_starts)
        flat = probes.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sorted_lists = flat[order]
        # per-list pair counts from the sorted array (binary search): bincount's
        # scatter-add measured 8 ms at 320K pairs on TPU, searchsorted ~none
        bounds = jnp.searchsorted(sorted_lists,
                                  jnp.arange(n_lists + 1, dtype=jnp.int32))
        r = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
        n_qc = -(-r // C)                                  # strips per list

        # class-major list layout: lists sorted by (class, id); each list's
        # strip base = its class region start + strips of earlier lists in class
        list_order = jnp.argsort(cls_ord * n_lists
                                 + jnp.arange(n_lists, dtype=jnp.int32))
        n_qc_sorted = n_qc[list_order]
        csum = jnp.cumsum(n_qc_sorted) - n_qc_sorted       # exclusive, global
        cls_sorted = cls_ord[list_order]
        counts = jax.ops.segment_sum(n_qc_sorted, cls_sorted,
                                     num_segments=n_classes)
        class_first = jnp.cumsum(counts) - counts          # exclusive
        starts = jnp.asarray(region_starts, jnp.int32)
        base_sorted = starts[cls_sorted] + (csum - class_first[cls_sorted])
        strip_base = jnp.zeros(n_lists, jnp.int32).at[list_order].set(
            base_sorted.astype(jnp.int32))

        pair_off = jnp.cumsum(r) - r
        rank = (jnp.arange(qp, dtype=jnp.int32)
                - pair_off[sorted_lists].astype(jnp.int32))
        ps_sorted = strip_base[sorted_lists] + rank // C
        slot_sorted = rank % C
        pair_strip = jnp.zeros(qp, jnp.int32).at[order].set(ps_sorted)
        pair_slot = jnp.zeros(qp, jnp.int32).at[order].set(slot_sorted)

        # padding slots = -1: the kernel skips them entirely (round-4; with the
        # static worst-case layout the padded grid would otherwise do real work)
        strip_list = jnp.full(s_tot, -1, jnp.int32).at[ps_sorted].set(
            sorted_lists.astype(jnp.int32))
        qids = jnp.full((s_tot, C), -1, jnp.int32).at[ps_sorted, slot_sorted].set(
            (order // p).astype(jnp.int32))
        return (qids, strip_list, pair_strip.reshape(q, p),
                pair_slot.reshape(q, p), counts)


def fit_q_tile(q: int, p: int, n_lists: int, n_classes: int, kf: int,
               workspace_bytes: int, dim: int = 0,
               class_counts: Optional[Tuple[int, ...]] = None) -> int:
    """Largest query tile whose per-class region tables + kernel outputs
    stay inside the workspace budget. Per strip slot: kf fp32+int32 output
    pairs (kf·8), the qids int32 entry (4), and — the round-3 undercount
    (ADVICE) — the (S_pad, C, dim) bf16 ``a_grouped`` query-side buffer
    (2·dim bytes) built in _strip_tile_body."""
    q_tile = min(q, 16384)
    per_slot = kf * 8 + 4 + 2 * dim
    if class_counts is None:
        class_counts = tuple([n_lists] * max(n_classes, 1))

    def rows_for(qt):
        return sum(static_caps(class_counts, qt, p))

    while (rows_for(q_tile) * C * per_slot > workspace_bytes
           and q_tile > 512):
        q_tile //= 2
    return q_tile


def class_counts_of(cls_ord_np: np.ndarray, n_classes: int) -> Tuple[int, ...]:
    """Static per-class list counts (hashable, for jit static args)."""
    return tuple(int(x) for x in np.bincount(cls_ord_np, minlength=n_classes))


def static_caps(class_counts: Tuple[int, ...], qt: int, p: int):
    """Per-class worst-case strip counts for a qt-query tile: a class holds
    at most ceil(qt·p/C) full strips + one partial per list IN THAT CLASS,
    and never more strips than pairs (the qt·p bound bites at small tiles).
    """
    full = _ceil_div(qt * p, C)
    return tuple(_bucket(min(qt * p, full + int(nc)))
                 for nc in class_counts)


def static_layout(classes, class_counts: Tuple[int, ...], qt: int, p: int):
    """Host-static worst-case layout for a qt-query tile — no device fetch.

    Regions are sized PER CLASS (round-4: a uniform n_lists-wide stride
    made the query-side tables scale as n_classes · n_lists and OOM'd
    many-list shapes). With one length class (the common large-index case)
    this equals the bucketed dynamic plan's size, so the static grid costs
    nothing extra. Returns (region_starts, s_tot, layout)."""
    caps = static_caps(class_counts, qt, p)
    starts = []
    acc = 0
    for cap in caps:
        starts.append(acc)
        acc += cap
    layout = tuple(
        (classes[c][0], classes[c][1], starts[c], caps[c])
        for c in range(len(classes))
    )
    return tuple(starts), acc, layout


def strip_search_traced(queries_mat, probes, list_data, bias, list_ids,
                        cls_ord, classes, class_counts, k: int, kf: int,
                        alpha: float, interpret: bool,
                        pair_const=None, approx_ok: bool = False):
    """Sync-free strip search of ONE query tile (all of ``queries_mat``):
    fully traceable, so callers can fuse coarse quantizer + device
    planning + strip kernel + finalization into one dispatch with zero
    host round-trips. Callers split a search into tiles with
    :func:`per_tile`, one dispatch each.

    Round-4 rationale: a dynamic plan (per-class strip counts fetched to
    size the kernel grid) is a blocking device→host sync in the middle of
    every search that (a) costs a device round trip and (b) prevents
    back-to-back searches from pipelining. Here the grid is fixed at the
    static worst case (static_layout); padding strips carry
    strip_list = -1 and are skipped entirely in-kernel. (PR 21: a
    sharded tile program on the dynamic plan also halted a v5e core; it
    is gone, and every strip caller plans this way.)
    """
    q, p = probes.shape
    region_starts, s_tot, layout = static_layout(classes, class_counts, q, p)
    qids, strip_list, pair_strip, pair_slot, _ = _plan_device(
        probes, cls_ord, list_data.shape[0], region_starts, s_tot)
    return _strip_tile_body(
        queries_mat, qids, strip_list, pair_strip, pair_slot, list_data,
        bias, list_ids, layout, int(k), kf, float(alpha), bool(interpret),
        pair_const, approx_ok)


def per_tile(search, q: int, q_tile: int, span: str = "",
             counter: str = ""):
    """``search(lo, hi)`` once per query tile ``[lo, hi)`` — one dispatch
    each — and the (vals, ids) concatenated. Tiles are equal but the
    last. (PR 21: a TPU v5e core halted in a program that scanned two
    tiles, 8,192 + 1,808 queries, for IVF-PQ and IVF-Flat alike, while
    one 10k-query tile ran; so no program scans more than one.)

    With ``span`` (the caller's launch span, e.g. ``"ivf_flat::launch"``)
    each tile's call is wrapped in :func:`obs.record_launch`, which also
    adds 1 to ``counter`` per program launched."""
    qt = -(-q // -(-q // max(int(q_tile), 1)))
    outs = []
    for s in range(0, q, qt):
        with obs.record_launch(span, counter) if span else obs.NOOP_SPAN:
            outs.append(search(s, min(q, s + qt)))
    if len(outs) == 1:
        return outs[0]
    return (jnp.concatenate([o[0] for o in outs], 0),
            jnp.concatenate([o[1] for o in outs], 0))


def note_topk_routes(classes, kf: int, approx_ok: bool, q: int,
                     q_tile: int, n_lists: int, p: int, k: int,
                     probe_algo: str, interpret: bool) -> None:
    """Count the length-class kernel calls of the programs
    :func:`per_tile` launches for ``q`` queries (``strip_scan.topk.calls``)
    and those of them whose in-kernel top-kf takes the sorted route
    (``strip_scan.topk.sorted``), from the static (kf, w) alone; and their
    top-k calls on ops/select_k.resident_topk_min_packed
    (``select_k.resident.calls``): the coarse probe's select of
    ``n_lists`` (``probe_algo``, select_k's guards) and the merge
    (:func:`merge_on_resident`). No device fetch. Callers gate it on
    ``obs.enabled()``."""
    from raft_tpu.ops.select_k import resident_ok

    launches = _ceil_div(q, q_tile)
    routes = [topk_route(kf, w_blocks * MC, approx_ok)
              for w_blocks, _ in classes]
    obs.add("strip_scan.topk.calls", launches * len(routes))
    obs.add("strip_scan.topk.sorted", launches * routes.count("sorted"))
    resident = (probe_algo == "packed" and not interpret
                and resident_ok(n_lists, p))
    resident += merge_on_resident(p, k, kf, interpret)
    obs.add("select_k.resident.calls", launches * int(resident))


def occupancy_stats(lens, m: int, q: int, p: int, dim: int = 0,
                    workspace_bytes: int = 1 << 30, kf: int = 10) -> dict:
    """Static occupancy diagnostics of one strip-scan dispatch, from the
    SAME planning code the dispatch uses (class_info / fit_q_tile /
    static_layout) — "the kernel underfills the MXU" as numbers, not a
    hunch (obs/roofline, round 15):

    * ``grid`` — per length-class ``[padded_strips, n_sub, w_blocks]``
      (the compiled kernel grids);
    * ``padded_strip_fraction`` — static-layout padding strips over the
      padded total, with the REAL strip count taken at the planner's
      best case (full ``C``-slot packing, ``ceil(q·p / C)`` — the bench
      regime; skewed probe distributions only add real strips, so this
      is the floor of the padding, not an estimate of it);
    * ``tile_fill`` — real (query, probe) pairs over the slots those
      best-case strips provide (how full the MXU M-dimension runs);
    * ``padded_row_fraction`` — scan-relative row padding: real entries
      over the pow2-block-padded widths the kernel actually fetches per
      list (every probed pair pays its list's padded width);
    * ``storage_padded_fraction`` — index-relative padding against the
      global ``m``-wide list storage (what residency pays).

    ``lens`` are per-list REAL entry counts, ``m`` the padded list width,
    ``(q, p)`` the dispatch's query/probe shape. Pure numpy."""
    lens_np = np.maximum(np.asarray(lens, np.int64), 0)
    n_lists = int(lens_np.shape[0])
    classes, cls_ord = class_info(lens_np, dim=dim)
    class_counts = class_counts_of(cls_ord, len(classes))
    q_tile = fit_q_tile(q, p, n_lists, len(classes), kf, workspace_bytes,
                        dim=dim, class_counts=class_counts)
    qt = min(q_tile, q)
    tiles = _ceil_div(q, qt) if qt else 0
    _, s_tot, layout = static_layout(classes, class_counts, qt, p)
    strips_best = _ceil_div(qt * p, C)
    n_mc = np.maximum(_ceil_div(lens_np, MC), 1)
    scanned = (1 << np.ceil(np.log2(n_mc)).astype(np.int64)) * MC
    real_rows = int(lens_np.sum())
    scanned_sum = int(scanned.sum())
    return {
        "grid": [[int(cnt), int(n_sub), int(w_blocks)]
                 for (w_blocks, n_sub, _start, cnt) in layout],
        "strips_padded": int(s_tot),
        "strips_real_bestcase": int(strips_best),
        "padded_strip_fraction": round(
            max(0.0, 1.0 - strips_best / s_tot), 4) if s_tot else 0.0,
        "tile_fill": round(min(1.0, qt * p / (strips_best * C)), 4)
        if strips_best else 0.0,
        "padded_row_fraction": round(
            max(0.0, 1.0 - real_rows / scanned_sum), 4)
        if scanned_sum else 0.0,
        "storage_padded_fraction": round(
            max(0.0, 1.0 - real_rows / (n_lists * m)), 4)
        if n_lists * m else 0.0,
        "q_tile": int(qt),
        "tiles": int(tiles),
        "c": C,
        "mc": MC,
    }


# ---------------------------------------------------------------------------
# Paged strip scan (serving): the SAME strip engine over a PagedListStore's
# page chains — HBM→VMEM page DMAs instead of contiguous list blocks
# ---------------------------------------------------------------------------
#
# The Ragged Paged Attention pattern (PAPERS.md): the kernel takes the
# store's page table + chain lengths as scalar-prefetch operands and
# issues one ``make_async_copy`` per live page (the ops/cagra_hop.py
# double-semaphore machinery), so mutable paged storage is scanned IN
# PLACE at strip-kernel throughput — no gather materialization, no
# repack. Every list is planned at its CAPACITY length (table_width ×
# page_rows rows — one length class, so the compiled layout depends only
# on capacity and the zero-recompile serving contract holds), but the
# kernel only moves a chain's LIVE pages: dead sub-blocks skip both the
# DMAs and the compute, costing grid bookkeeping like padding strips.
# Tombstoned rows and tail fills self-mask through the store-maintained
# ``page_bias`` pool (+inf at dead slots — the packed kernels' trash-row
# convention); rows past the live page count are masked in-kernel by a
# lane iota against the chain length, so stale VMEM scratch never scores.
#
# Two implementations, bit-identical by construction (the ops/bq_scan.py
# precedent): ``impl="pallas"`` (the kernel; interpret-mode on CPU) and
# ``impl="jnp"`` (a lax.map reference driving the SAME per-block compute,
# :func:`_paged_score_topk`) — the parity oracle tier-1 pins.


def paged_plan(table_width: int, page_rows: int, row_bytes: int,
               kf: int) -> Tuple[int, int, int]:
    """Static fetch plan for one paged scan: ``(pages_per_fetch, n_sub,
    w)`` with ``w = pages_per_fetch · page_rows`` rows per grid step.

    The block must cover ``kf`` rows (the running per-pair top-kf can
    never recover candidates a narrower block dropped), aims for the
    packed kernel's ``MC`` granule, and stays inside the mantissa-packing
    bound (w ≤ 4096, ops/strip_scan._PACK_BITS) and a ~4 MB VMEM payload
    budget. ``table_width`` is a power of two (the store grows it
    geometrically), so ``pages_per_fetch`` always divides it."""
    W, R = int(table_width), int(page_rows)

    def _ok(p_):
        w_ = p_ * R
        return w_ <= (1 << _PACK_BITS) and w_ * max(1, row_bytes) <= (4 << 20)

    ppf = 1
    while ppf < W and ppf * R < min(max(kf, MC), 1 << _PACK_BITS):
        ppf *= 2
    while ppf < W and _ok(ppf * 2):
        ppf *= 2
    while ppf > 1 and not _ok(ppf):
        ppf //= 2
    return ppf, max(1, W // ppf), ppf * R


def paged_eligible(table_width: int, page_rows: int, row_bytes: int,
                   k: int) -> bool:
    """True when the paged Pallas engine can serve this store/k: the plan's
    block covers k (pack-bits + VMEM budget permitting) and the page
    height is sane. Callers fall back to the gather scan otherwise."""
    if page_rows < 8 or k > 512:
        return False
    _, _, w = paged_plan(table_width, page_rows, row_bytes, int(k))
    return int(k) <= min(w, table_width * page_rows, 1 << _PACK_BITS)


def _paged_score_topk(a, block, bias_row, live_rows, alpha: float, kf: int,
                      w: int, approx_ok: bool):
    """One paged block's scores + fused top-kf — THE shared compute of the
    kernel and the jnp reference (both feed it the same operands, which is
    what makes the two paths bit-identical).

    a: (C, dim) query block; block: (w, dim) payload rows (any fetch
    order-stable dtype — fp32/bf16/int8 upcast like the packed kernel);
    bias_row: (1, w) per-row additive term; live_rows: scalar — rows at
    lane >= live_rows are DEAD (absent pages / stale scratch) and masked
    to +inf AFTER the add, so garbage payload (even NaN) never ranks."""
    b = _to_bf16(block)
    s = lax.dot_general(a.astype(jnp.bfloat16), b, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = alpha * s + bias_row
    lanes = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(lanes < live_rows, s, jnp.inf)
    return _topk_block(s, kf, w, approx_ok)


def _paged_strip_kernel(sl_ref, tbl_ref, chain_ref, lv_ref, a_ref, pages_hbm,
                        bias_hbm, outv_ref, oute_ref, pay_s, bias_s,
                        psem, bsem, *, alpha, kf, w, n_sub, ppf,
                        page_rows, table_width, approx_ok):
    """One (strip × page sub-block): DMA the live pages HBM→VMEM, then the
    shared matmul + fused top-kf. Scalar prefetch carries the strip table
    (``sl``), the flattened page table, the per-list chain lengths and the
    per-(list, sub-block) filter-liveness words (``lv_ref``: 0 when every
    row the sub-block's pages hold is +inf-biased — filtered out or
    tombstoned); only live pages of live sub-blocks are copied (a
    dynamic-trip fori_loop — the Ragged Paged Attention fetch shape), dead
    sub-blocks and padding strips skip the body entirely. A filter-dead
    first sub-block still writes: ``live_rows = 0`` masks every lane, so
    the write is the all-inf extraction — bitwise what the jnp reference
    computes from the all-+inf bias lanes."""
    i = pl.program_id(0)
    slv = sl_ref[i]
    j = pl.program_id(1) if n_sub > 1 else 0
    l = jnp.maximum(slv, 0)
    chain = jnp.where(slv >= 0, chain_ref[l], 0)   # live pages in the list
    lvv = lv_ref[l * n_sub + (j if n_sub > 1 else 0)]
    base = j * ppf
    # live pages this block; a filter-dead block fetches and ranks nothing
    nv = jnp.clip(chain - base, 0, ppf) * lvv
    R = page_rows

    # issue every copy before draining any: latencies overlap; the two
    # semaphores drain exactly the issued bytes (ops/cagra_hop pattern)
    def issue(t, _):
        pid = tbl_ref[l * table_width + base + t]
        pltpu.make_async_copy(pages_hbm.at[pid],
                              pay_s.at[pl.ds(t * R, R)], psem).start()
        pltpu.make_async_copy(bias_hbm.at[pid],
                              bias_s.at[0, pl.ds(t * R, R)], bsem).start()
        return 0

    def drain(t, _):
        pid = tbl_ref[l * table_width + base + t]
        pltpu.make_async_copy(pages_hbm.at[pid],
                              pay_s.at[pl.ds(t * R, R)], psem).wait()
        pltpu.make_async_copy(bias_hbm.at[pid],
                              bias_s.at[0, pl.ds(t * R, R)], bsem).wait()
        return 0

    lax.fori_loop(0, nv, issue, 0)
    lax.fori_loop(0, nv, drain, 0)

    # j == 0 always writes (a strip's outputs must be defined even for an
    # empty list — all-+inf, which the merge translates to id -1); later
    # sub-blocks past the chain end — or filter-dead (lvv == 0) — keep the
    # running top-kf untouched
    @pl.when((slv >= 0) & ((j == 0) | ((base < chain) & (lvv > 0))))
    def _compute():
        bv, be = _paged_score_topk(a_ref[0], pay_s[...], bias_s[...],
                                   nv * R, alpha, kf, w, approx_ok)
        _store_candidates(outv_ref, oute_ref, bv, be + j * w, j, n_sub, kf)


@functools.partial(
    jax.jit,
    static_argnames=("ppf", "n_sub", "page_rows", "table_width", "alpha",
                     "kf", "interpret", "approx_ok"),
)
def _paged_class_call(strip_list, table_flat, chain_pages, sub_live,
                      a_grouped, pages, bias_pool, ppf: int, n_sub: int,
                      page_rows: int, table_width: int, alpha: float,
                      kf: int, interpret: bool, approx_ok: bool = False):
    """Run the (single) paged length class through the Pallas kernel:
    grid (S,) or (S, n_sub); pages/bias stay HBM-resident (memory_space
    ANY) and are fetched per grid step by the kernel's own DMAs.
    ``sub_live`` (n_lists·n_sub,) int32 carries the per-sub-block
    filter-liveness words (0 ⇒ the kernel issues no page DMAs and skips
    ranking for that block). Returns the (S + 1, kf, C) candidate buffers
    of :func:`merge_strip_candidates`."""
    s_pad, c, dim = a_grouped.shape
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

    out_shape, out_specs, _, _, _ = _candidate_io(None, 0, s_pad, kf, o_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, dim), a_map),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((w, pages.shape[-1]), pages.dtype),
            pltpu.VMEM((1, w), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_strip_kernel, alpha=alpha, kf=kf, w=w,
                          n_sub=n_sub, ppf=ppf, page_rows=page_rows,
                          table_width=table_width, approx_ok=approx_ok),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(strip_list, table_flat, chain_pages, sub_live, a_grouped, pages,
      bias_pool)


@functools.partial(
    jax.jit,
    static_argnames=("ppf", "n_sub", "page_rows", "table_width", "alpha",
                     "kf", "approx_ok"),
)
def _paged_class_jnp(strip_list, table_flat, chain_pages, sub_live,
                     a_grouped, pages, bias_pool, ppf: int, n_sub: int,
                     page_rows: int, table_width: int, alpha: float,
                     kf: int, approx_ok: bool = False):
    """Pure-jnp reference for the paged class: the SAME per-(strip,
    sub-block) op sequence as the kernel — shared :func:`_paged_score_topk`,
    same ``_extract_topk`` sub-block merge, same skip predicate for dead
    sub-blocks (chain-exhausted OR filter-dead ``sub_live`` word) — driven
    by a sequential ``lax.map`` over strips, each strip's candidates
    stored lane-dense (S, kf, C) like the kernel's. This IS the jnp gather
    path of the paged engine: pages are fetched with jnp advanced indexing
    and scored identically, so tier-1 pins bitwise (ids + values) parity
    against the kernel."""
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
            blk = pages[pidx].reshape(w, pages.shape[-1])
            brow = bias_pool[pidx].reshape(1, w)
            live = jnp.clip(chain - j * ppf, 0, ppf) * lw * page_rows
            bv, be = _paged_score_topk(a, blk, brow, live, alpha, kf, w,
                                       approx_ok)
            be = be + j * w
            if n_sub == 1:
                return bv, be
            cv = jnp.concatenate([ov, bv], axis=1)
            ce = jnp.concatenate([oe, be], axis=1)
            mv, me = _extract_topk(cv, ce, kf)
            # j == 0 initializes exactly like the kernel's first write;
            # dead sub-blocks keep the running top-kf (kernel skip path)
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


class PagedIds:
    """Lazy (list, in-list offset) → source-id translator with the 2-D
    advanced-indexing surface :func:`merge_strip_candidates` expects, so
    the merge is reused UNCHANGED: offset ``o`` of list ``l`` dereferences
    through the page table to ``page_ids[table[l, o // R], o % R]``; absent
    pages answer -1 (their candidates are +inf and already masked)."""

    __slots__ = ("page_ids", "table", "page_rows")

    def __init__(self, page_ids, table, page_rows: int):
        self.page_ids = page_ids
        self.table = table
        self.page_rows = int(page_rows)

    def __getitem__(self, idx):
        win_list, win_off = idx
        pg = self.table[win_list, win_off // self.page_rows]
        ids = self.page_ids[jnp.maximum(pg, 0), win_off % self.page_rows]
        return jnp.where(pg >= 0, ids, -1)


def paged_strip_search_traced(queries_mat, probes, pages, bias_pool,
                              page_ids, table, chain_pages, k: int, kf: int,
                              alpha: float, interpret: bool,
                              pair_const=None, approx_ok: bool = False,
                              impl: str = "pallas"):
    """Sync-free paged strip search of ONE query tile (all of
    ``queries_mat``) — fully traceable, so family callers fuse coarse
    quantizer + device planning + paged kernel + merge + finalize into ONE
    dispatch (the ``strip_search_traced`` protocol over page chains), and
    split a search into tiles with :func:`per_tile`.

    pages: (capacity_pages, page_rows, row_width) payload pool.
    bias_pool: (capacity_pages, page_rows) fp32 — the store-maintained
    per-row additive term, +inf at tombstones/tail fills. table:
    (n_lists, table_width) int32 page table, -1 at absent slots.
    chain_pages: (n_lists,) int32 live pages per list. Every operand is
    CAPACITY-shaped: steady-state upserts/deletes re-dispatch this same
    compiled program (the zero-recompile serving contract)."""
    ppf, n_sub, w = paged_plan(
        table.shape[1], pages.shape[1],
        int(pages.shape[-1]) * pages.dtype.itemsize, kf)
    if kf > w:
        # the running per-pair top-kf can never recover candidates a
        # narrower fetch block dropped — refuse instead of silently
        # truncating (callers route ineligible stores to the gather path)
        raise ValueError(
            f"paged strip scan needs kf <= fetch block ({w} rows), got "
            f"{kf}; use the gather backend")
    fn = _paged_class_call if impl == "pallas" else _paged_class_jnp
    kwargs = {"interpret": interpret} if impl == "pallas" else {}

    def scan(strip_list, table_flat, sub_live, a_grouped):
        return fn(strip_list, table_flat, chain_pages, sub_live, a_grouped,
                  pages, bias_pool, ppf, n_sub, pages.shape[1],
                  table.shape[1], alpha, kf, approx_ok=approx_ok, **kwargs)

    return _paged_tile_body(queries_mat, probes, bias_pool, page_ids, table,
                           chain_pages, ppf, n_sub, scan, k, kf, interpret,
                           pair_const)


def _paged_tile_body(queries_mat, probes, bias_pool, page_ids, table,
                    chain_pages, ppf: int, n_sub: int, scan, k: int, kf: int,
                    interpret: bool, pair_const=None):
    """The engine-independent part of a paged scan of one query tile:
    filter-liveness words, device strip planning over the one capacity
    length class, the grouped query side, then ``scan(strip_list,
    table_flat, sub_live, a_grouped)`` — the payload's kernel — and the
    shared merge. Shared by the fp/int8 and the packed ±1 paged engines."""
    q, p = probes.shape
    n_lists, table_width = table.shape
    page_rows = bias_pool.shape[1]

    # Per-(list, sub-block) filter-liveness words (round 19, predicate
    # push-down): a page whose bias rows are ALL +inf (every row filtered
    # out or tombstoned) holds nothing rankable; a sub-block whose live
    # chain slots all point at such pages skips its page DMAs and compute
    # in the kernel. Derived from the SAME capacity-shaped operands as the
    # scan (one cheap VPU pass), so it rides the fused jit: mask changes
    # re-dispatch, never recompile.
    span = n_sub * ppf
    page_live = jnp.any(jnp.isfinite(bias_pool), axis=1)   # (cap_pages,)
    slot_live = page_live[jnp.maximum(table, 0)] & (table >= 0)
    if span > table_width:
        slot_live = jnp.pad(slot_live, ((0, 0), (0, span - table_width)))
    elif span < table_width:
        slot_live = slot_live[:, :span]
    pos = jnp.arange(span, dtype=jnp.int32)[None, :]
    slot_live = slot_live & (pos < chain_pages[:, None])
    sub_live = jnp.any(slot_live.reshape(n_lists, n_sub, ppf),
                       axis=2).astype(jnp.int32).reshape(-1)

    # one capacity length class: the layout depends only on capacity
    region_starts, s_tot, _ = static_layout(((ppf, n_sub),), (n_lists,),
                                            q, p)
    qids, strip_list, pair_strip, pair_slot, _ = _plan_device(
        probes, jnp.zeros((n_lists,), jnp.int32), n_lists, region_starts,
        s_tot)
    a_grouped = jnp.where(
        (qids >= 0)[:, :, None], queries_mat[jnp.clip(qids, 0), :], 0,
    ).astype(jnp.bfloat16)
    ov, oe = scan(strip_list, table.reshape(-1), sub_live, a_grouped)
    return merge_strip_candidates(
        ov, oe, strip_list, pair_strip, pair_slot,
        PagedIds(page_ids, table, page_rows), k, kf, interpret, pair_const)


def paged_occupancy_stats(table_width: int, page_rows: int, chain_pages,
                          live_rows: int, tombstones: int, q: int, p: int,
                          k: int, row_bytes: int,
                          workspace_bytes: int = 1 << 30,
                          dim: int = 0) -> dict:
    """Static occupancy diagnostics of one paged-Pallas dispatch, from the
    SAME planning code the dispatch uses (:func:`paged_plan` +
    ``static_layout``) — the round-15 standing gate for new hot-path
    kernels. Beyond the strip numbers, the paged plane's own wastes:

    * ``page_fill`` — live rows over the slots of the pages actually
      chained (tail-fill waste the DMA still moves);
    * ``tombstone_fraction`` — tombstoned slots over chained-page slots
      (the waste background compaction reclaims);
    * ``chain_fill`` — chained pages over table capacity (how much of the
      capacity-planned grid the skip path prunes).

    ``chain_pages`` is the per-list live page count (numpy)."""
    chain_np = np.maximum(np.asarray(chain_pages, np.int64), 0)
    n_lists = int(chain_np.shape[0])
    kf = min(int(k), 512)
    ppf, n_sub, w = paged_plan(table_width, page_rows, row_bytes, kf)
    classes = ((ppf, n_sub),)
    class_counts = (n_lists,)
    q_tile = fit_q_tile(q, p, n_lists, 1, kf, workspace_bytes, dim=dim,
                        class_counts=class_counts)
    qt = min(q_tile, q) or 1
    _, s_tot, layout = static_layout(classes, class_counts, qt, p)
    strips_best = _ceil_div(qt * p, C)
    chained = int(chain_np.sum())
    chained_slots = chained * int(page_rows)
    cap_slots = n_lists * int(table_width) * int(page_rows)
    live = max(0, int(live_rows))
    dead = max(0, int(tombstones))
    return {
        "grid": [[int(cnt), int(ns), int(wb)]
                 for (wb, ns, _s, cnt) in layout],
        "pages_per_fetch": int(ppf),
        "n_sub": int(n_sub),
        "w": int(w),
        "strips_padded": int(s_tot),
        "strips_real_bestcase": int(strips_best),
        "padded_strip_fraction": round(
            max(0.0, 1.0 - strips_best / s_tot), 4) if s_tot else 0.0,
        "tile_fill": round(min(1.0, qt * p / (strips_best * C)), 4)
        if strips_best else 0.0,
        "page_fill": round(live / chained_slots, 4) if chained_slots
        else 0.0,
        "tombstone_fraction": round(dead / chained_slots, 4)
        if chained_slots else 0.0,
        "chain_fill": round(chained / (n_lists * table_width), 4)
        if n_lists * table_width else 0.0,
        "padded_row_fraction": round(
            max(0.0, 1.0 - live / chained_slots), 4) if chained_slots
        else 0.0,
        "capacity_slots": cap_slots,
        "q_tile": int(qt),
        "c": C,
    }


def strip_search(
    queries_mat,
    probes,
    list_data,
    list_bias,
    list_ids,
    lens,
    k: int,
    alpha: float = -2.0,
    workspace_bytes: int = 1 << 30,
    interpret: bool = False,
    pair_const=None,
    approx_ok: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Full strip scan: probes (q, p) int32 → per-query top-k over the
    probed lists' entries. Drop-in contract of round 2's ragged_search:

    queries_mat: (q, dim) query-side matrix (rotated/scaled as the caller
      needs). list_data: (n_lists, m, dim) entry matrix, fp32/bf16/int8,
      with m a power-of-two multiple of MC (512) — see _packing.pack_lists'
      pow2_chunks. list_bias: (n_lists, m) per-entry additive term (+inf at
      padding). list_ids: (n_lists, m) source row ids (-1 padding). lens:
      (n_lists,) real entry counts. Scores are ``alpha·⟨q, x⟩ + bias``,
      smaller is better; the caller adds per-query constants afterwards.

    Distances on this path accumulate the matmul in fp32 from bf16 (or
    int8-dequantized) operands: ~3 significant digits relative to the fp32
    gather oracle. The contract here is candidate RANKING (callers re-rank
    exact via neighbors/refine or consume ids); use backend="gather" where
    fp32 distances themselves are the product.
    """
    q = queries_mat.shape[0]
    lens_np = np.asarray(lens)
    n_lists, m = list_data.shape[0], list_data.shape[1]
    if m % MC or (m // MC) & (m // MC - 1):
        raise ValueError(
            f"list_data dim 1 must be a power-of-two multiple of {MC}, got {m}"
        )
    if k > MC:
        # a pair's candidates are capped at its strip's kf slots; k beyond MC
        # would silently drop in-list ranks > MC (use the gather backend)
        raise ValueError(f"strip_search supports k <= {MC}, got {k}")
    kf = min(int(k), MC)
    classes, cls_ord_np = class_info(lens_np, dim=queries_mat.shape[1])
    class_counts = class_counts_of(cls_ord_np, len(classes))
    probes_dev = jnp.asarray(probes)
    q_tile = fit_q_tile(q, probes_dev.shape[1], n_lists, len(classes), kf,
                        workspace_bytes, dim=queries_mat.shape[1],
                        class_counts=class_counts)
    cls_ord = jnp.asarray(cls_ord_np)

    def search(lo, hi):
        return _strip_search_jit(
            queries_mat[lo:hi], probes_dev[lo:hi], list_data, list_bias,
            list_ids, cls_ord, tuple(classes), class_counts, int(k), kf,
            float(alpha), bool(interpret),
            None if pair_const is None else pair_const[lo:hi], approx_ok)

    return per_tile(search, q, q_tile)


_strip_search_jit = jax.jit(
    strip_search_traced,
    static_argnames=("classes", "class_counts", "k", "kf", "alpha",
                     "interpret", "approx_ok"),
)
