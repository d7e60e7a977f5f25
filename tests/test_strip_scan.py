"""strip_scan engine vs a naive per-pair oracle (tier-1, interpret mode)."""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.ops.strip_scan import C, MC, plan_strips, strip_search


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(17)


def make_lists(rng, n_lists, dim, lens):
    chunks = max((int(max(lens)) + MC - 1) // MC, 1)
    m = MC * (1 << (chunks - 1).bit_length())  # pow2 chunks (strip_eligible)
    data = np.zeros((n_lists, m, dim), np.float32)
    bias = np.full((n_lists, m), np.inf, np.float32)
    ids = np.full((n_lists, m), -1, np.int32)
    nxt = 0
    for l in range(n_lists):
        v = rng.standard_normal((lens[l], dim)).astype(np.float32)
        data[l, : lens[l]] = v
        bias[l, : lens[l]] = (v ** 2).sum(1)
        ids[l, : lens[l]] = np.arange(nxt, nxt + lens[l])
        nxt += lens[l]
    return data, bias, ids


def oracle_l2(queries, probes, data, ids, lens, k):
    out = []
    for r in range(queries.shape[0]):
        cand = []
        for l in probes[r]:
            for j in range(lens[l]):
                cand.append((((queries[r] - data[l, j]) ** 2).sum(), int(ids[l, j])))
        cand.sort()
        row = [c[1] for c in cand[:k]] + [-1] * max(0, k - len(cand))
        out.append(row)
    return np.array(out)


class TestStripScan:
    def test_matches_oracle_l2_with_skew_and_empty_list(self, rng):
        n_lists, dim, q, k = 7, 16, 23, 5
        lens = rng.integers(0, 300, n_lists)
        lens[0] = 0  # empty list probed by everyone
        data, bias, ids = make_lists(rng, n_lists, dim, lens)
        queries = rng.standard_normal((q, dim)).astype(np.float32)
        others = np.stack([rng.choice([0, 2, 3, 4, 5, 6], 2, replace=False)
                           for _ in range(q)])
        probes = np.concatenate(
            [np.ones((q, 1), np.int64), others], axis=1).astype(np.int32)

        v, i = strip_search(
            queries, probes, jnp.asarray(data), jnp.asarray(bias),
            jnp.asarray(ids), lens, k, alpha=-2.0, interpret=True,
        )
        v = np.asarray(v) + (queries ** 2).sum(1)[:, None]
        want = oracle_l2(queries, probes, data, ids, lens, k)
        got = np.asarray(i)
        for r in range(q):
            # tie-tolerant: ids must match where distances are distinct
            if not (got[r] == want[r]).all():
                wv = sorted(
                    ((queries[r] - data[l, j]) ** 2).sum()
                    for l in probes[r] for j in range(lens[l])
                )[:k]
                # bf16 matmul: ~3 significant digits; ids may swap only
                # within that noise, so gate on the distance profile
                np.testing.assert_allclose(np.asarray(v)[r][: len(wv)], wv,
                                           rtol=5e-3, atol=5e-2)

    def test_long_list_sub_blocks_match_oracle(self, rng):
        # one list longer than MAX_CLASS*MC forces the sub-block merge path
        n_lists, dim, q, k = 3, 8, 31, 7
        lens = np.array([9000, 40, 700])
        data, bias, ids = make_lists(rng, n_lists, dim, lens)
        queries = rng.standard_normal((q, dim)).astype(np.float32)
        probes = np.tile(np.arange(3, dtype=np.int32), (q, 1))
        v, i = strip_search(
            queries, probes, jnp.asarray(data), jnp.asarray(bias),
            jnp.asarray(ids), lens, k, alpha=-2.0, interpret=True,
        )
        want = oracle_l2(queries, probes, data, ids, lens, k)
        got = np.asarray(i)
        v = np.asarray(v) + (queries ** 2).sum(1)[:, None]
        for r in range(q):
            if not (got[r] == want[r]).all():
                wv = sorted(
                    ((queries[r] - data[l, j]) ** 2).sum()
                    for l in probes[r] for j in range(lens[l])
                )[:k]
                # expanded-form bf16: |err| ~ 2·|⟨q,x⟩|·2⁻⁸, which at these
                # norms is ~0.1 absolute — ids may swap within that band
                np.testing.assert_allclose(v[r][: len(wv)], wv,
                                           rtol=2e-2, atol=2e-1)

    def test_plan_work_scales_with_load_not_cap(self, rng):
        # all queries probe one hot list: strip count must track real pairs
        n_lists, q, p = 64, 256, 4
        lens = np.full(n_lists, 100)
        probes = np.stack(
            [np.concatenate([[7], rng.choice(np.setdiff1d(np.arange(64), [7]),
                                             p - 1, replace=False)])
             for _ in range(q)]).astype(np.int32)
        plan = plan_strips(probes, lens, n_lists)
        # hot list 7: 256 pairs → ceil(256/C) strips; every other probed
        # list needs at most 1 (≤ 64 lists)
        assert plan.n_strips <= -(-q // C) + n_lists
        # single class (all lists are 1 chunk long), no sub-blocks
        assert all(w == 1 and sub == 1 for (w, sub, _, _) in plan.class_layout)

    def test_strip_search_tiling_matches_single_shot(self, rng):
        n_lists, dim, q, k = 9, 8, 600, 4
        lens = rng.integers(50, 200, n_lists)
        data, bias, ids = make_lists(rng, n_lists, dim, lens)
        queries = rng.standard_normal((q, dim)).astype(np.float32)
        probes = np.stack([rng.choice(n_lists, 3, replace=False)
                           for _ in range(q)]).astype(np.int32)
        v1, i1 = strip_search(queries, probes, jnp.asarray(data),
                              jnp.asarray(bias), jnp.asarray(ids),
                              lens, k, interpret=True)
        # tiny workspace forces multiple tiles
        v2, i2 = strip_search(queries, probes, jnp.asarray(data),
                              jnp.asarray(bias), jnp.asarray(ids),
                              lens, k, workspace_bytes=1 << 18,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_int8_cache_ranks_like_fp32(self, rng):
        # int8 B operand with the scale folded into the query side
        n_lists, dim, q, k = 5, 16, 40, 5
        lens = rng.integers(30, 200, n_lists)
        data, bias, ids = make_lists(rng, n_lists, dim, lens)
        queries = rng.standard_normal((q, dim)).astype(np.float32)
        probes = np.stack([rng.choice(n_lists, 3, replace=False)
                           for _ in range(q)]).astype(np.int32)
        scale = np.abs(data).max() / 127.0
        data_q = np.clip(np.round(data / scale), -127, 127).astype(np.int8)
        v8, i8 = strip_search(queries * scale, probes, jnp.asarray(data_q),
                              jnp.asarray(bias), jnp.asarray(ids), lens, k,
                              interpret=True)
        want = oracle_l2(queries, probes, data, ids, lens, k)
        # quantized ranking: top-k overlap must stay high
        overlap = np.mean([
            len(set(np.asarray(i8)[r]) & set(want[r])) / k for r in range(q)
        ])
        assert overlap >= 0.9

    def test_multi_class_region_remap(self, rng):
        """Regression (round 3): the merge must read each class's
        candidates at the strips' plan rows, whatever the per-class padded
        counts. Needs many lists relative to the per-class strip counts."""
        n_lists, dim, q, k = 300, 8, 200, 5
        lens = np.where(np.arange(n_lists) % 2 == 0, 100, 900)  # 2 classes
        data, bias, ids = make_lists(rng, n_lists, dim, lens)
        queries = rng.standard_normal((q, dim)).astype(np.float32)
        probes = np.stack([rng.choice(n_lists, 4, replace=False)
                           for _ in range(q)]).astype(np.int32)
        v, i = strip_search(queries, probes, jnp.asarray(data),
                            jnp.asarray(bias), jnp.asarray(ids), lens, k,
                            interpret=True)
        want = oracle_l2(queries, probes, data, ids, lens, k)
        got = np.asarray(i)
        v = np.asarray(v) + (queries ** 2).sum(1)[:, None]
        for r in range(q):
            if not (got[r] == want[r]).all():
                wv = sorted(
                    ((queries[r] - data[l, j]) ** 2).sum()
                    for l in probes[r] for j in range(lens[l])
                )[:k]
                np.testing.assert_allclose(v[r][: len(wv)], wv,
                                           rtol=2e-2, atol=2e-1)


@pytest.mark.parametrize("family", ["ivf_flat", "ivf_pq"])
def test_ragged_search_tiles_one_dispatch_each(rng, family):
    """A search larger than one query tile runs one fused dispatch per tile
    (strip_scan.per_tile) and answers exactly as one tile does."""
    from raft_tpu.core.resources import Resources
    from raft_tpu.neighbors import ivf_flat, ivf_pq

    X = rng.normal(size=(3000, 16)).astype(np.float32)
    Q = rng.normal(size=(1100, 16)).astype(np.float32)
    mod = ivf_flat if family == "ivf_flat" else ivf_pq
    params = (ivf_flat.IvfFlatParams(n_lists=8, group_size=512)
              if family == "ivf_flat" else
              ivf_pq.IvfPqParams(n_lists=8, pq_dim=8, group_size=512))
    idx = mod.build(X, params)
    one = mod.search(idx, Q, 10, n_probes=2, backend="ragged")
    tiled = mod.search(idx, Q, 10, n_probes=2, backend="ragged",
                       res=Resources(workspace_bytes=1))   # 512-query tiles
    np.testing.assert_array_equal(np.asarray(tiled[1]), np.asarray(one[1]))
    np.testing.assert_allclose(np.asarray(tiled[0]), np.asarray(one[0]),
                               rtol=1e-6)


@pytest.mark.parametrize("case", ["ivf_flat.search_paged",
                                  "ivf_pq.search_paged", "ivf_bq.search",
                                  "ivf_bq.search_paged"])
def test_strip_scan_tiles_one_program_each(rng, monkeypatch, case):
    """The paged and IVF-BQ scans launch through strip_scan.per_tile too:
    at a 64-query tile, 150 queries run ceil(150/64) = 3 programs, each
    scanning one tile, and answer as the same search in one tile does."""
    from raft_tpu import serving
    from raft_tpu.neighbors import ivf_bq, ivf_flat, ivf_pq
    from raft_tpu.ops import strip_scan as ss

    family, entry = case.split(".")
    mod = {"ivf_flat": ivf_flat, "ivf_pq": ivf_pq, "ivf_bq": ivf_bq}[family]
    # the build shapes of the test above, so the builds share its compiles
    X = rng.normal(size=(3000, 16)).astype(np.float32)
    Q = rng.normal(size=(150, 16)).astype(np.float32)
    params = {"ivf_flat": ivf_flat.IvfFlatParams(n_lists=8, group_size=512),
              "ivf_pq": ivf_pq.IvfPqParams(n_lists=8, pq_dim=8,
                                           group_size=512),
              "ivf_bq": ivf_bq.IvfBqParams(n_lists=8)}[family]
    index = mod.build(X, params)
    if entry == "search_paged":
        target = serving.PagedListStore.from_index(index, page_rows=128)
        backend = "paged_pallas"
        program = {"ivf_flat": "_paged_fused", "ivf_pq": "_paged_fused_pq",
                   "ivf_bq": "_paged_fused_bq"}[family]
    else:
        target, backend, program = index, "packed", "_bq_fused"
    search = getattr(mod, entry)
    one = search(target, Q, 10, n_probes=3, backend=backend)

    calls = []
    launch = getattr(mod, program)

    def counted(queries, *args):
        calls.append(queries.shape[0])
        return launch(queries, *args)

    monkeypatch.setattr(ss, "fit_q_tile", lambda *a, **kw: 64)
    monkeypatch.setattr(mod, program, counted)
    tiled = search(target, Q, 10, n_probes=3, backend=backend)
    assert calls == [50, 50, 50]
    np.testing.assert_array_equal(np.asarray(tiled[1]), np.asarray(one[1]))
    np.testing.assert_allclose(np.asarray(tiled[0]), np.asarray(one[0]),
                               rtol=1e-6)


def _score_block(rng, c, w):
    """Scores with the cases an extraction must survive: +inf padding
    lanes, an all-+inf row, runs of equal scores, a NaN and a -inf."""
    s = rng.standard_normal((c, w)).astype(np.float32) * 100.0
    s[1, w - w // 3:] = np.inf           # a list shorter than the block
    s[2] = np.inf                        # nothing rankable
    s[3, :50] = 1.5                      # a run of equal scores
    s[4, ::3] = -2.25                    # equal scores in every bin
    s[5, 7] = np.nan
    s[5, 9] = -np.inf
    s[6, w // 2:] = 0.75                 # half the row ties
    return s


@pytest.mark.parametrize("w,kf", [
    (w, kf) for w in (256, 512, 1024, 2048, 4096)
    for kf in (1, 2, 10, 40, 129, w) if kf <= w])
def test_sorted_topk_bit_identical_to_direct(w, kf):
    """The sorted-bin extraction returns exactly the direct passes'
    (values, columns) at every width and kf, including kf = w."""
    import jax

    from raft_tpu.ops import strip_scan as ss

    s = _score_block(np.random.default_rng(w + kf), 16, w)
    pv = ss._pack_scores(jnp.asarray(s), w)
    want = jax.jit(ss._extract_topk_packed, static_argnums=1)(pv, kf)
    got = jax.jit(ss._extract_topk_sorted, static_argnums=1)(pv, kf)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    # the all-+inf row comes back as +inf at columns 0..kf-1 on both
    assert np.isinf(np.asarray(got[0])[2]).all()


@pytest.mark.parametrize("w", [512, 1024, 2048])
def test_topk_route_rule(w):
    """The cells' shapes (kf 10 for IVF-Flat, 40 for IVF-PQ, which allows
    the tournament) take the sorted route at every list width; kf = 1
    keeps the direct passes; the tournament window is unchanged."""
    from raft_tpu.ops.strip_scan import topk_route

    for kf in (10, 40):
        for approx_ok in (False, True):
            assert topk_route(kf, w, approx_ok) == "sorted"
    assert topk_route(1, w) == "direct"
    assert topk_route(32, w) == "sorted"
    assert topk_route(32, w, approx_ok=True) == (
        "tournament" if w >= 1024 else "sorted")   # it must beat the passes
    assert topk_route(10, w + 64) == "direct"    # not whole lane slabs


def test_strip_class_call_routes_agree_with_sub_blocks(rng, monkeypatch):
    """Interpret-mode kernel at kf = 40 over lists longer than one fetch
    (n_sub = 2), so the sub-block merge runs on top of the sorted route,
    as two class calls sharing one candidate buffer: the same outputs, bit
    for bit, as the kernel forced onto the direct passes."""
    import jax

    from raft_tpu.ops import strip_scan as ss

    n_lists, dim, kf, w_blocks, n_sub = 3, 8, 40, 1, 2
    m = w_blocks * MC * n_sub
    assert ss.topk_route(kf, w_blocks * MC) == "sorted"
    data = rng.standard_normal((n_lists, m, dim)).astype(np.float32)
    bias = (data ** 2).sum(-1)
    bias[0, 700:] = np.inf                    # one list ends mid-block
    bias[1, 200:] = np.inf                    # one ends in the first block
    bias[2, 100:300] = bias[2, 0]             # ties across columns
    strip_list = np.array([0, 2, 1, -1], np.int32)
    a = rng.standard_normal((4, C, dim)).astype(np.float32)
    args = (jnp.asarray(strip_list), jnp.asarray(a, jnp.bfloat16),
            jnp.asarray(data), jnp.asarray(bias.reshape(n_lists, 1, m)))
    static = dict(w_blocks=w_blocks, n_sub=n_sub, alpha=-2.0, kf=kf,
                  interpret=True)

    def run():      # a new trace, so the route is decided again
        call = jax.jit(ss._strip_class_call.__wrapped__,
                       static_argnames=tuple(static) + ("start", "count"))
        cand = call(*args, None, start=0, count=2, **static)
        return call(*args, cand, start=2, count=2, **static)

    sv, se = run()
    monkeypatch.setattr(ss, "topk_route", lambda *a, **k: "direct")
    dv, de = run()
    assert sv.shape == (len(strip_list) + 1, kf, C)
    real = strip_list >= 0
    assert np.array_equal(np.asarray(sv)[:-1][real], np.asarray(dv)[:-1][real])
    assert np.array_equal(np.asarray(se)[:-1][real], np.asarray(de)[:-1][real])
    # list 1 holds 200 rows: its strip's 40 columns lie in the first block
    assert (np.asarray(se)[2] < 200).all()


def _multi_class_index(rng, dtype):
    """Lists in five length classes (w = 512 … 4,096 rows, one list past
    the widest fetch, so n_sub = 2), rows and bias as the IVF cells store
    them; queries and probes that leave padding strips in every class."""
    import raft_tpu.ops.strip_scan as ss

    lens = np.array([300, 900, 1800, 3000, 5000, 100, 700, 40])
    n_lists, dim, m = len(lens), 16, 16 * MC
    if dtype == "uint8":
        data = rng.integers(0, 256, (n_lists, m, dim)).astype(np.uint8)
    else:
        data = rng.integers(-127, 128, (n_lists, m, dim)).astype(np.int8)
    bias = (data.astype(np.float32) ** 2).sum(-1)
    live = np.arange(m)[None, :] < lens[:, None]
    bias[~live] = np.inf
    ids = np.where(live, np.arange(n_lists * m).reshape(n_lists, m), -1)
    classes, cls_ord = ss.class_info(lens, dim=dim)
    assert len(classes) == 5 and (8, 2) in classes
    q, p = 37, 3
    queries = rng.normal(scale=40.0, size=(q, dim)).astype(np.float32)
    probes = np.stack([rng.choice(n_lists, p, replace=False)
                       for _ in range(q)]).astype(np.int32)
    return (queries, probes, data, bias, ids.astype(np.int32), lens,
            tuple(classes), cls_ord)


def _oracle_class_scan(strip_list, a_grouped, data, bias, w, n_sub, kf,
                       alpha):
    """One class's (S_c, C, kf) candidates by a plain jnp scan: a lax.map
    over its strips of the kernel's per-block compute."""
    from jax import lax

    import raft_tpu.ops.strip_scan as ss

    def one_strip(args):
        sl, a = args
        l = jnp.maximum(sl, 0)

        def sub(j, carry):
            blk = lax.dynamic_slice_in_dim(data[l], j * w, w, axis=0)
            bi = lax.dynamic_slice_in_dim(bias[l], j * w, w)[None, :]
            s = lax.dot_general(a, ss._to_bf16(blk), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            nv, ne = ss._topk_block(alpha * s + bi, kf, w, False)
            ne = ne + j * w
            if n_sub == 1:
                return nv, ne
            mv, me = ss._extract_topk(
                jnp.concatenate([carry[0], nv], axis=1),
                jnp.concatenate([carry[1], ne], axis=1), kf)
            return jnp.where(j == 0, nv, mv), jnp.where(j == 0, ne, me)

        init = (jnp.full((C, kf), jnp.inf, jnp.float32),
                jnp.zeros((C, kf), jnp.int32))
        return lax.fori_loop(0, n_sub, sub, init)

    return lax.map(one_strip, (strip_list, a_grouped))


def _strip_search_oracle(queries, probes, data, bias, ids, classes, cls_ord,
                         k, kf, alpha):
    """The strip search with each class's (S, C, kf) candidates computed
    by a plain jnp scan (_oracle_class_scan), then sliced, concatenated
    and gathered by pair: the hand-off as it stood before the shared
    lane-dense buffer."""
    import functools

    import jax
    from jax import lax

    import raft_tpu.ops.strip_scan as ss

    q, p = probes.shape
    n_lists = data.shape[0]
    counts = ss.class_counts_of(cls_ord, len(classes))
    starts, s_tot, layout = ss.static_layout(classes, counts, q, p)
    qids, strip_list, pair_strip, pair_slot, _ = ss._plan_device(
        jnp.asarray(probes), jnp.asarray(cls_ord), n_lists, starts, s_tot)
    a_grouped = jnp.where((qids >= 0)[:, :, None],
                          jnp.asarray(queries)[jnp.clip(qids, 0)],
                          0).astype(jnp.bfloat16)
    scan = jax.jit(functools.partial(_oracle_class_scan, kf=kf, alpha=alpha),
                   static_argnames=("w", "n_sub"))
    outs = [scan(strip_list[start:start + count],
                 a_grouped[start:start + count], jnp.asarray(data),
                 jnp.asarray(bias), w=w_blocks * MC, n_sub=n_sub)
            for (w_blocks, n_sub, start, count) in layout]
    out_v = jnp.concatenate([o[0] for o in outs])     # (S, C, kf)
    out_e = jnp.concatenate([o[1] for o in outs])
    cand_v = out_v[pair_strip, pair_slot].reshape(q, p * kf)
    cand_e = out_e[pair_strip, pair_slot].reshape(q, p * kf)
    nv, sel = lax.top_k(-cand_v, k)
    vals = -nv
    win_list = jnp.take_along_axis(strip_list[pair_strip], sel // kf, axis=1)
    win_off = jnp.take_along_axis(cand_e, sel, axis=1)
    out_ids = jnp.asarray(ids)[win_list, win_off]
    return vals, jnp.where(jnp.isfinite(vals), out_ids, -1)


@pytest.mark.parametrize("dtype,kf", [("uint8", 10), ("int8", 40)])
def test_strip_search_traced_matches_oracle_bitwise(dtype, kf):
    """Five length classes (one with n_sub = 2) with padding strips, all
    writing one candidate buffer in place: (vals, ids) bit-identical to
    the plain jnp scan through the per-class concat hand-off, at
    IVF-Flat's kf and IVF-PQ's."""
    import raft_tpu.ops.strip_scan as ss

    rng = np.random.default_rng(kf)
    queries, probes, data, bias, ids, lens, classes, cls_ord = \
        _multi_class_index(rng, dtype)
    counts = ss.class_counts_of(cls_ord, len(classes))
    vals, got = ss._strip_search_jit(
        jnp.asarray(queries), jnp.asarray(probes), jnp.asarray(data),
        jnp.asarray(bias), jnp.asarray(ids), jnp.asarray(cls_ord), classes,
        counts, kf, kf, -2.0, True)
    want_v, want_i = _strip_search_oracle(queries, probes, data, bias, ids,
                                          classes, cls_ord, kf, kf, -2.0)
    assert np.array_equal(np.asarray(got), np.asarray(want_i))
    assert np.array_equal(np.asarray(vals), np.asarray(want_v))
    assert (np.asarray(got) >= 0).all()


def test_tile_body_hands_candidates_over_in_place(rng):
    """Structural guard: between the class kernels and the merge the tile
    program holds no concatenate and no slice of the grouped queries or
    of a kernel's output; the first class call allocates the lane-dense
    (S + 1, kf, C) buffers and every later one writes into them in
    place."""
    import functools

    import jax

    import raft_tpu.ops.strip_scan as ss

    queries, probes, data, bias, ids, lens, classes, cls_ord = \
        _multi_class_index(rng, "uint8")
    q, p = probes.shape
    kf = 10
    counts = ss.class_counts_of(cls_ord, len(classes))
    starts, s_tot, layout = ss.static_layout(classes, counts, q, p)
    plan = ss._plan_device(jnp.asarray(probes), jnp.asarray(cls_ord),
                           data.shape[0], starts, s_tot)[:4]
    body = functools.partial(ss._strip_tile_body, class_layout=layout, k=kf,
                             kf=kf, alpha=-2.0, interpret=True)
    jaxpr = jax.make_jaxpr(body)(jnp.asarray(queries), *plan,
                                 jnp.asarray(data), jnp.asarray(bias),
                                 jnp.asarray(ids))
    cand_shape = (s_tot + 1, kf, C)

    def eqns(jx):
        for e in jx.eqns:
            yield e
            if e.primitive.name == "pallas_call":
                continue               # the kernel body is not the hand-off
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        yield from eqns(getattr(inner, "jaxpr", inner))

    calls = []
    for e in eqns(jaxpr.jaxpr):
        name = e.primitive.name
        if name in ("concatenate", "slice", "dynamic_slice"):
            # strip-shaped arrays (grouped queries, candidates) hold C;
            # the gathers' index arrays, which jnp concatenates, do not
            assert not any(C in v.aval.shape for v in e.invars
                           if hasattr(v, "aval")), e
        if name == "pallas_call":
            calls.append(e)
    assert len(calls) == len(layout)
    for n, e in enumerate(calls):
        assert [v.aval.shape for v in e.outvars] == [cand_shape] * 2
        assert bool(e.params["input_output_aliases"]) == (n > 0)


@pytest.mark.parametrize("kf,with_const", [(10, False), (40, True)])
def test_merge_resident_route_matches_jnp_route(rng, monkeypatch, kf,
                                                with_const):
    """merge_strip_candidates on its chip route (``interpret=False``):
    the top-k of the (q, p·kf) candidates through the resident kernel
    (run here in interpret mode) gives the same values and ids, bit for
    bit, as through iter_topk_min_packed, the jnp route it replaces."""
    import raft_tpu.ops.select_k as sk
    import raft_tpu.ops.strip_scan as ss

    q, p, s_tot, n_lists, m = 45, 4, 7, 3, 256
    k = kf
    assert ss.merge_on_resident(p, k, kf, interpret=False)
    cand_v = rng.standard_normal((s_tot + 1, kf, C)).astype(np.float32)
    cand_v[1, kf // 2:] = np.inf               # a list shorter than kf
    cand_v[2, :, :20] = 0.5                    # ties across pairs
    cand_e = rng.integers(0, m, (s_tot + 1, kf, C)).astype(np.int32)
    strip_list = np.array([0, 1, 2, 0, 1, 2, -1], np.int32)
    pair_strip = rng.integers(0, s_tot - 1, (q, p)).astype(np.int32)
    pair_slot = rng.integers(0, C, (q, p)).astype(np.int32)
    list_ids = np.arange(n_lists * m, dtype=np.int32).reshape(n_lists, m)
    const = (jnp.asarray(rng.standard_normal((q, p)), jnp.float32)
             if with_const else None)
    args = [jnp.asarray(a) for a in (cand_v, cand_e, strip_list,
                                     pair_strip, pair_slot, list_ids)]

    def merge(topk):
        monkeypatch.setattr(sk, "resident_topk_min_packed", topk)
        return ss.merge_strip_candidates(*args, k, kf, False, const)

    resident, shapes = sk.resident_topk_min_packed, []
    got = merge(lambda v, kk: shapes.append(v.shape)
                or resident(v, kk, interpret=True))
    want = merge(sk.iter_topk_min_packed)
    assert shapes == [(q, p * kf)]       # one lane-dense candidate array
    assert np.array_equal(np.asarray(got[0]).view(np.int32),
                          np.asarray(want[0]).view(np.int32))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert (np.asarray(got[1]) >= 0).any()


@pytest.mark.parametrize("kind,kf,n_sub", [
    (kind, kf, n_sub) for kind in ("fp", "bq") for kf in (10, 40)
    for n_sub in (1, 2)])
def test_paged_class_kernel_matches_jnp_candidates(kind, kf, n_sub):
    """The paged kernels (interpret) and their jnp references store the
    same lane-dense (S, kf, C) candidates, bit for bit, on every real
    strip: chains of differing length, absent pages, tombstones, a
    filter-dead sub-block and padding strips."""
    from raft_tpu.ops import bq_scan
    from raft_tpu.ops import strip_scan as ss

    rng = np.random.default_rng(kf + n_sub)
    n_lists, page_rows, ppf, dim = 4, 32, 8, 16
    width = ppf * n_sub
    cap = n_lists * width
    chain = np.array([width, 3, 0, ppf + 2])[:n_lists].clip(0, width)
    table = np.full((n_lists, width), -1, np.int32)
    perm = rng.permutation(cap).astype(np.int32)
    for l in range(n_lists):
        table[l, :chain[l]] = perm[l * width:l * width + chain[l]]
    bias_pool = rng.normal(size=(cap, page_rows)).astype(np.float32)
    bias_pool[rng.random((cap, page_rows)) < 0.1] = np.inf   # tombstones
    sub_live = np.ones(n_lists * n_sub, np.int32)
    sub_live[-1] = 0                                         # filter-dead
    strip_list = np.array([0, 1, 3, 2, 0, -1, -1], np.int32)
    a = rng.normal(size=(len(strip_list), C, dim))
    common = (jnp.asarray(strip_list), jnp.asarray(table.reshape(-1)),
              jnp.asarray(chain.astype(np.int32)), jnp.asarray(sub_live),
              jnp.asarray(a, jnp.bfloat16))
    if kind == "fp":
        pages = rng.integers(0, 256, (cap, page_rows, dim)).astype(np.uint8)
        ops = (jnp.asarray(pages), jnp.asarray(bias_pool))
        kernel, ref = ss._paged_class_call, ss._paged_class_jnp
    else:
        codes = rng.integers(0, 256, (cap, page_rows, dim // 8))
        scale = rng.uniform(0.5, 2.0, (cap, page_rows)).astype(np.float32)
        ops = (jnp.asarray(codes.astype(np.uint8)), jnp.asarray(scale),
               jnp.asarray(bias_pool))
        kernel, ref = bq_scan._paged_bq_class_call, bq_scan._paged_bq_class_jnp
    static = dict(ppf=ppf, n_sub=n_sub, page_rows=page_rows,
                  table_width=width, alpha=-2.0, kf=kf)
    kv, ke = kernel(*common, *ops, interpret=True, **static)
    rv, re = ref(*common, *ops, **static)
    assert kv.shape == (len(strip_list) + 1, kf, C)
    assert rv.shape == (len(strip_list), kf, C)
    real = strip_list >= 0
    assert np.array_equal(np.asarray(kv)[:-1][real], np.asarray(rv)[real])
    assert np.array_equal(np.asarray(ke)[:-1][real], np.asarray(re)[real])
    assert np.isfinite(np.asarray(kv)[0]).all()
