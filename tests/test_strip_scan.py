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
        """Regression: device plans leave gaps between class regions; the
        merge must remap into the densely concatenated kernel outputs.
        Needs per-class padded counts BELOW the region size to trigger
        (n_lists large relative to per-class strip counts)."""
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
    (n_sub = 2), so the sub-block merge runs on top of the sorted route:
    the same outputs, bit for bit, as the kernel forced onto the direct
    passes."""
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

    def fresh():    # a new trace, so the route is decided again
        return jax.jit(ss._strip_class_call.__wrapped__,
                       static_argnames=tuple(static))

    sv, se = fresh()(*args, **static)
    monkeypatch.setattr(ss, "topk_route", lambda *a, **k: "direct")
    dv, de = fresh()(*args, **static)
    real = strip_list >= 0
    assert np.array_equal(np.asarray(sv)[real], np.asarray(dv)[real])
    assert np.array_equal(np.asarray(se)[real], np.asarray(de)[real])
    # list 1 holds 200 rows: its strip's 40 columns lie in the first block
    assert (np.asarray(se)[2] < 200).all()
