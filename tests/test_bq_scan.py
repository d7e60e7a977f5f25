"""ops/bq_scan engine: packed Pallas kernel (interpret mode) vs the
pure-jnp reference path — BIT parity (ids AND distances), property-tested
over ragged list layouts including empty and single-row lists, plus the
pack/unpack bit-layout round-trip and a brute-force score oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.ops import strip_scan as ss
from raft_tpu.ops.bq_scan import (
    bq_dense_scan,
    bq_strip_search_traced,
    pack_sign_bits,
    packed_width,
    unpack_sign_bits,
)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(23)


def make_bq_lists(rng, n_lists, rot_dim, lens):
    """Packed code lists + scale/bias planes at a strip-eligible padded
    size (pow2 multiples of MC), mirroring ivf_bq's pack."""
    chunks = max((int(max(lens)) + ss.MC - 1) // ss.MC, 1)
    m = ss.MC * (1 << (chunks - 1).bit_length())
    nb = packed_width(rot_dim)
    codes = np.zeros((n_lists, m, nb), np.uint8)
    scale = np.zeros((n_lists, m), np.float32)
    bias = np.full((n_lists, m), np.inf, np.float32)
    ids = np.full((n_lists, m), -1, np.int32)
    signs_all = {}
    nxt = 0
    for l in range(n_lists):
        if lens[l] == 0:
            continue
        signs = rng.choice([-1, 1], size=(lens[l], rot_dim)).astype(np.int8)
        signs_all[l] = signs
        codes[l, : lens[l]] = np.asarray(pack_sign_bits(jnp.asarray(signs)))
        scale[l, : lens[l]] = rng.uniform(0.5, 2.0, lens[l]).astype(np.float32)
        bias[l, : lens[l]] = rng.normal(size=lens[l]).astype(np.float32)
        ids[l, : lens[l]] = np.arange(nxt, nxt + lens[l])
        nxt += lens[l]
    return codes, scale, bias, ids, signs_all


def run_both(queries, probes, codes, scale, bias, ids, lens, k,
             alpha=-2.0, pair_const=None):
    """The packed kernel (interpret) and the jnp reference on identical
    plan inputs — the planning is shared, only the per-strip engine
    differs. Beside each (vals, ids), ``outs["cand"][impl]`` keeps the
    (strip_list, values, offsets) candidate buffers the merge read."""
    from unittest import mock

    lens_np = np.asarray(lens)
    classes, cls_ord_np = ss.class_info(lens_np, dim=queries.shape[1])
    class_counts = ss.class_counts_of(cls_ord_np, len(classes))
    outs = {"cand": {}}
    merge = ss.merge_strip_candidates
    for impl in ("pallas", "jnp"):
        def spy(cand_v, cand_e, strip_list, *rest, impl=impl):
            outs["cand"][impl] = (strip_list, cand_v, cand_e)
            return merge(cand_v, cand_e, strip_list, *rest)

        with mock.patch.object(ss, "merge_strip_candidates", spy):
            outs[impl] = bq_strip_search_traced(
                jnp.asarray(queries), jnp.asarray(probes),
                jnp.asarray(codes), jnp.asarray(scale), jnp.asarray(bias),
                jnp.asarray(ids), jnp.asarray(cls_ord_np), tuple(classes),
                class_counts, int(k), int(k), float(alpha), True,
                None if pair_const is None else jnp.asarray(pair_const),
                False, impl)
    return outs


def assert_bit_parity(outs):
    (v1, i1), (v2, i2) = outs["pallas"], outs["jnp"]
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # BIT-identical distances: same dtypes, same op sequence, same order
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    # and the lane-dense (S + 1, kf, C) candidates of every real strip
    (sl, kv, ke), (_, rv, re) = outs["cand"]["pallas"], outs["cand"]["jnp"]
    real = np.asarray(sl) >= 0
    assert kv.shape == rv.shape == (len(real) + 1, kv.shape[1], ss.C)
    np.testing.assert_array_equal(np.asarray(kv)[:-1][real],
                                  np.asarray(rv)[:-1][real])
    np.testing.assert_array_equal(np.asarray(ke)[:-1][real],
                                  np.asarray(re)[:-1][real])


class TestPackLayout:
    def test_roundtrip(self, rng):
        for rot_dim in (8, 32, 64, 128):
            signs = rng.choice([-1, 1], size=(17, rot_dim)).astype(np.int8)
            packed = pack_sign_bits(jnp.asarray(signs))
            assert packed.shape == (17, rot_dim // 8)
            back = unpack_sign_bits(packed, rot_dim)
            np.testing.assert_array_equal(np.asarray(back), signs)

    def test_zero_maps_to_minus_one(self):
        # the bit is (sign > 0): an all-zero "sign" row unpacks to all -1 —
        # callers must canonicalize sign(0) := +1 BEFORE packing (ivf_bq's
        # _encode_chunk does)
        z = jnp.zeros((1, 16), jnp.int8)
        back = unpack_sign_bits(pack_sign_bits(z), 16)
        assert (np.asarray(back) == -1).all()

    def test_rejects_unaligned(self):
        with pytest.raises(ValueError):
            packed_width(12)


class TestBitParity:
    def test_ragged_layout_property(self, rng):
        """Random ragged layouts — empty lists, single-row lists, skewed
        fills — must produce bit-identical (ids, distances) from the two
        implementations."""
        rot_dim = 32
        for trial in range(4):
            n_lists = int(rng.integers(3, 9))
            lens = rng.integers(0, 600, n_lists)
            lens[rng.integers(0, n_lists)] = 0   # empty list, always probed
            lens[rng.integers(0, n_lists)] = 1   # single-row list
            if lens.max() == 0:
                lens[0] = 7
            codes, scale, bias, ids, _ = make_bq_lists(
                rng, n_lists, rot_dim, lens)
            q, p = int(rng.integers(3, 30)), min(3, n_lists)
            queries = rng.standard_normal((q, rot_dim)).astype(np.float32)
            probes = np.stack([
                rng.choice(n_lists, p, replace=False) for _ in range(q)
            ]).astype(np.int32)
            outs = run_both(queries, probes, codes, scale, bias, ids,
                            lens, k=5)
            assert_bit_parity(outs)

    def test_all_lists_empty(self, rng):
        rot_dim = 16
        lens = np.zeros(4, np.int64)
        codes, scale, bias, ids, _ = make_bq_lists(rng, 4, rot_dim, lens)
        queries = rng.standard_normal((5, rot_dim)).astype(np.float32)
        probes = np.tile(np.arange(3, dtype=np.int32), (5, 1))
        outs = run_both(queries, probes, codes, scale, bias, ids, lens, k=3)
        assert_bit_parity(outs)
        v, i = outs["pallas"]
        assert (np.asarray(i) == -1).all()
        assert np.isinf(np.asarray(v)).all()

    def test_pair_const_and_multi_class(self, rng):
        """Two length classes (one list spilling past a single 512-block)
        plus a per-pair additive constant — the full merge remap path."""
        rot_dim = 24
        lens = np.array([1500, 30, 700, 4])
        codes, scale, bias, ids, _ = make_bq_lists(rng, 4, rot_dim, lens)
        q = 11
        queries = rng.standard_normal((q, rot_dim)).astype(np.float32)
        probes = np.stack([rng.choice(4, 3, replace=False)
                           for _ in range(q)]).astype(np.int32)
        pair_const = rng.standard_normal((q, 3)).astype(np.float32)
        outs = run_both(queries, probes, codes, scale, bias, ids, lens,
                        k=7, pair_const=pair_const)
        assert_bit_parity(outs)

    @pytest.mark.slow
    def test_sub_block_revisits(self, rng):
        """A list longer than MAX_CLASS·MC forces the n_sub > 1 running
        top-kf merge — kernel output-ref accumulation vs the reference's
        fori must stay bit-identical."""
        rot_dim = 16
        lens = np.array([ss.MAX_CLASS * ss.MC + 700, 50])
        codes, scale, bias, ids, _ = make_bq_lists(rng, 2, rot_dim, lens)
        queries = rng.standard_normal((6, rot_dim)).astype(np.float32)
        probes = np.tile(np.arange(2, dtype=np.int32), (6, 1))
        outs = run_both(queries, probes, codes, scale, bias, ids, lens, k=9)
        assert_bit_parity(outs)


class TestScoreOracle:
    def test_matches_dense_oracle(self, rng):
        """The strip engines' candidate set must match a numpy oracle of
        the same score formula (rank-level; values allclose at bf16
        contract precision)."""
        rot_dim = 32
        n_lists = 5
        lens = rng.integers(1, 400, n_lists)
        codes, scale, bias, ids, signs_all = make_bq_lists(
            rng, n_lists, rot_dim, lens)
        q, p, k = 9, 3, 5
        queries = rng.standard_normal((q, rot_dim)).astype(np.float32)
        probes = np.stack([rng.choice(n_lists, p, replace=False)
                           for _ in range(q)]).astype(np.int32)
        outs = run_both(queries, probes, codes, scale, bias, ids, lens, k)
        got_v, got_i = (np.asarray(x) for x in outs["pallas"])

        for r in range(q):
            cand = []
            for l in probes[r]:
                for j in range(lens[l]):
                    ip = float(signs_all[l][j] @ queries[r])
                    cand.append((-2.0 * ip * scale[l, j] + bias[l, j],
                                 int(ids[l, j])))
            cand.sort()
            want = [c[1] for c in cand[:k]]
            if list(got_i[r][: len(want)]) != want:
                # bf16 contraction: ids may swap within score ties — gate
                # on the distance profile instead (strip_scan test style)
                np.testing.assert_allclose(
                    got_v[r][: len(want)], [c[0] for c in cand[:k]],
                    rtol=5e-3, atol=5e-2)

    def test_dense_scan_agrees_at_fp32(self, rng):
        """bq_dense_scan (the distributed off-TPU path) ranks like the
        oracle exactly — its einsum is fp32."""
        rot_dim = 16
        n_lists = 4
        lens = rng.integers(1, 100, n_lists)
        codes, scale, bias, ids, signs_all = make_bq_lists(
            rng, n_lists, rot_dim, lens)
        q, p, k = 6, 2, 4
        queries = rng.standard_normal((q, rot_dim)).astype(np.float32)
        probes = np.stack([rng.choice(n_lists, p, replace=False)
                           for _ in range(q)]).astype(np.int32)
        v, i = bq_dense_scan(
            jnp.asarray(queries), jnp.asarray(probes), jnp.asarray(codes),
            jnp.asarray(scale), jnp.asarray(bias), jnp.asarray(ids),
            k, -2.0)
        got_i = np.asarray(i)
        for r in range(q):
            cand = []
            for l in probes[r]:
                for j in range(lens[l]):
                    ip = float(signs_all[l][j] @ queries[r])
                    cand.append((-2.0 * ip * scale[l, j] + bias[l, j],
                                 int(ids[l, j])))
            cand.sort()
            want = [c[1] for c in cand[:k]] + [-1] * max(0, k - len(cand))
            assert list(got_i[r]) == want


class TestMultiBitPlanes:
    """Round-17 extended codes: stacked bit-planes scanned by the SAME
    kernels at a wider byte width, the level weighting riding the query
    operand (ops/bq_scan module docstring)."""

    def test_pack_unpack_levels_roundtrip(self, rng):
        from raft_tpu.ops.bq_scan import (multibit_width, pack_code_planes,
                                          unpack_code_levels)

        for bits in (1, 2, 3, 4):
            for rot_dim in (8, 32, 64):
                codes = rng.integers(0, 1 << bits, (9, rot_dim)) \
                    .astype(np.uint8)
                packed = pack_code_planes(jnp.asarray(codes), bits)
                assert packed.shape == (9, multibit_width(rot_dim, bits))
                lv = np.asarray(unpack_code_levels(packed, rot_dim, bits))
                np.testing.assert_array_equal(
                    lv, 2 * codes.astype(np.int32) - ((1 << bits) - 1))

    def test_bits1_is_the_legacy_sign_layout(self, rng):
        from raft_tpu.ops.bq_scan import pack_code_planes

        codes = rng.integers(0, 2, (7, 32)).astype(np.uint8)
        signs = np.where(codes > 0, 1, -1).astype(np.int8)
        np.testing.assert_array_equal(
            np.asarray(pack_code_planes(jnp.asarray(codes), 1)),
            np.asarray(pack_sign_bits(jnp.asarray(signs))))

    def test_query_extension_contraction_identity(self, rng):
        """⟨ext(q), unpack_pm1(planes)⟩ == ⟨q, levels⟩ EXACTLY — the
        identity that lets the ±1 kernels scan multi-bit codes without a
        single kernel change."""
        from raft_tpu.ops.bq_scan import (_unpack_pm1, extend_query_planes,
                                          pack_code_planes)

        rot_dim = 32
        for bits in (2, 3, 4):
            codes = rng.integers(0, 1 << bits, (11, rot_dim)) \
                .astype(np.uint8)
            packed = pack_code_planes(jnp.asarray(codes), bits)
            q = rng.standard_normal((5, rot_dim)).astype(np.float32)
            qe = np.asarray(extend_query_planes(jnp.asarray(q), bits))
            assert qe.shape == (5, bits * rot_dim)
            pm1 = np.asarray(_unpack_pm1(packed)).astype(np.float32)
            levels = (2 * codes.astype(np.float32) - ((1 << bits) - 1))
            np.testing.assert_allclose(qe @ pm1.T, q @ levels.T,
                                       rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("bits", [2, 4])
    def test_ragged_bit_parity_multibit(self, rng, bits):
        """Kernel vs jnp reference over a ragged layout at bits > 1: ids
        AND distances bit-identical (the acceptance-criteria contract at
        the engine level)."""
        from raft_tpu.ops.bq_scan import (extend_query_planes,
                                          pack_code_planes)

        rot_dim, n_lists = 16, 6
        lens = [0, 3, ss.MC, 17, 1, ss.MC + 5]
        chunks = max((max(lens) + ss.MC - 1) // ss.MC, 1)
        m = ss.MC * (1 << (chunks - 1).bit_length())
        codes = np.zeros((n_lists, m, bits * rot_dim // 8), np.uint8)
        scale = np.zeros((n_lists, m), np.float32)
        bias = np.full((n_lists, m), np.inf, np.float32)
        ids = np.full((n_lists, m), -1, np.int32)
        nxt = 0
        for l in range(n_lists):
            if lens[l] == 0:
                continue
            cl = rng.integers(0, 1 << bits, (lens[l], rot_dim)) \
                .astype(np.uint8)
            codes[l, :lens[l]] = np.asarray(
                pack_code_planes(jnp.asarray(cl), bits))
            scale[l, :lens[l]] = rng.uniform(0.5, 2.0, lens[l]) \
                .astype(np.float32)
            bias[l, :lens[l]] = rng.normal(size=lens[l]).astype(np.float32)
            ids[l, :lens[l]] = np.arange(nxt, nxt + lens[l])
            nxt += lens[l]
        q = 4
        qr = rng.standard_normal((q, rot_dim)).astype(np.float32)
        qe = np.asarray(extend_query_planes(jnp.asarray(qr), bits))
        probes = np.stack([rng.permutation(n_lists)[:3] for _ in range(q)])
        outs = run_both(qe, probes, codes, scale, bias, ids, lens, k=5)
        assert_bit_parity(outs)
        # and against a dense oracle: score = α·⟨q, L⟩·scale + bias
        vals, got_ids = outs["jnp"]
        from raft_tpu.ops.bq_scan import unpack_code_levels

        levels = np.asarray(unpack_code_levels(
            jnp.asarray(codes), rot_dim, bits)).astype(np.float32)
        for qi in range(q):
            best = []
            for l in probes[qi]:
                for j in range(lens[l]):
                    s = -2.0 * float(qr[qi] @ levels[l, j]) * scale[l, j] \
                        + bias[l, j]
                    best.append((s, ids[l, j]))
            best.sort(key=lambda t: t[0])
            want_ids = [b[1] for b in best[:5]]
            got = [i for i in np.asarray(got_ids)[qi] if i >= 0]
            # rank parity at fp32-vs-bf16 resolution: top-1 must agree
            assert got[0] == want_ids[0] or abs(
                best[0][0] - best[1][0]) < 1e-2
