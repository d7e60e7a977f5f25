"""CAGRA + NN-descent tests: recall-gated vs the exact oracle (tier-3,
SURVEY.md §4.3 — mirrors cpp/test/neighbors/ann_cagra recall thresholds)."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core.bitset import Bitset
from raft_tpu import stats
from raft_tpu.neighbors import brute_force, cagra, nn_descent


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    n, dim, q = 1500, 24, 64
    X = rng.standard_normal((n, dim)).astype(np.float32)
    Q = rng.standard_normal((q, dim)).astype(np.float32)
    return X, Q


def _recall(got, want):
    k = want.shape[1]
    return np.mean(
        [len(set(got[i]) & set(want[i])) / k for i in range(want.shape[0])]
    )


class TestNNDescent:
    def test_graph_recall(self, data):
        X, _ = data
        n = X.shape[0]
        k = 16
        ids = nn_descent.build(
            X,
            nn_descent.NNDescentParams(
                graph_degree=k, intermediate_graph_degree=32,
                max_iterations=12, sample_size=8,
            ),
        )
        ids = np.asarray(ids)
        _, exact = brute_force.knn(X, X, k + 1)
        exact = np.asarray(exact)[:, 1:]  # drop self
        assert _recall(ids, exact) >= 0.9

    def test_distances_match_ids(self, data):
        X, _ = data
        ids, d = nn_descent.build(
            X,
            nn_descent.NNDescentParams(
                graph_degree=8, intermediate_graph_degree=16,
                max_iterations=6, sample_size=8,
            ),
            return_distances=True,
        )
        ids, d = np.asarray(ids), np.asarray(d)
        i = 17
        expect = ((X[i] - X[ids[i]]) ** 2).sum(axis=1)
        np.testing.assert_allclose(d[i], expect, rtol=1e-4, atol=1e-4)
        # sorted ascending, no self, no dups
        assert (np.diff(d[i]) >= -1e-6).all()
        assert i not in ids[i]
        assert len(np.unique(ids[i])) == len(ids[i])

    def test_params_validation(self):
        with pytest.raises(ValueError, match="graph_degree"):
            nn_descent.NNDescentParams(graph_degree=64, intermediate_graph_degree=32)
        with pytest.raises(ValueError, match="sample_size"):
            nn_descent.NNDescentParams(sample_size=0)
        with pytest.raises(ValueError, match="at least 2 rows"):
            nn_descent.build(np.zeros((1, 4), np.float32))


class TestCagraBuild:
    def test_optimize_degree_and_no_self(self, data):
        X, _ = data
        idx = cagra.build(X, cagra.CagraParams(graph_degree=16, intermediate_graph_degree=32))
        g = np.asarray(idx.graph)
        assert g.shape == (X.shape[0], 16)
        assert (g != np.arange(X.shape[0])[:, None]).all()
        # rows fully populated (connected graph region) and deduped
        assert (g >= 0).all()
        for r in [0, 100, 777]:
            assert len(np.unique(g[r])) == 16

    def test_detour_pruning_prefers_diverse_edges(self):
        # a tight cluster + far point: pruning must keep the far point
        # reachable (reverse edges guarantee in-edges to every node)
        rng = np.random.default_rng(0)
        X = np.concatenate(
            [rng.standard_normal((200, 8)).astype(np.float32),
             np.full((1, 8), 50.0, np.float32)]
        )
        idx = cagra.build(X, cagra.CagraParams(graph_degree=8, intermediate_graph_degree=16))
        g = np.asarray(idx.graph)
        assert (g == 200).any(), "far point must appear as someone's neighbor"

    def test_build_from_graph_roundtrip(self, data, tmp_path):
        X, Q = data
        idx = cagra.build(X, cagra.CagraParams(graph_degree=16, intermediate_graph_degree=32))
        p = tmp_path / "cagra.bin"
        idx.save(p)
        idx2 = cagra.CagraIndex.load(p)
        np.testing.assert_array_equal(np.asarray(idx.graph), np.asarray(idx2.graph))
        vd1, vi1 = cagra.search(idx, Q, 5)
        vd2, vi2 = cagra.search(idx2, Q, 5)
        np.testing.assert_array_equal(np.asarray(vi1), np.asarray(vi2))

    def test_load_wrong_kind(self, tmp_path, data):
        X, _ = data
        bf_idx = brute_force.build(X)
        p = tmp_path / "bf.bin"
        bf_idx.save(p)
        with pytest.raises(ValueError, match="not a cagra index"):
            cagra.CagraIndex.load(p)


class TestCagraSearch:
    @pytest.fixture(scope="class")
    def index(self, data):
        X, _ = data
        return cagra.build(
            X, cagra.CagraParams(graph_degree=16, intermediate_graph_degree=32)
        )

    def test_recall_vs_exact(self, data, index):
        X, Q = data
        k = 10
        _, vi = cagra.search(index, Q, k, cagra.CagraSearchParams(itopk_size=64))
        _, ei = brute_force.knn(Q, X, k)
        assert _recall(np.asarray(vi), np.asarray(ei)) >= 0.9

    def test_recall_improves_with_itopk(self, data, index):
        X, Q = data
        k = 10
        _, ei = brute_force.knn(Q, X, k)
        ei = np.asarray(ei)
        _, vi_small = cagra.search(index, Q, k, cagra.CagraSearchParams(itopk_size=16))
        _, vi_big = cagra.search(index, Q, k, cagra.CagraSearchParams(itopk_size=128))
        assert _recall(np.asarray(vi_big), ei) >= _recall(np.asarray(vi_small), ei)
        assert _recall(np.asarray(vi_big), ei) >= 0.95

    def test_filter(self, data, index):
        X, Q = data
        n = X.shape[0]
        keep = np.zeros(n, bool)
        keep[: n // 2] = True
        filt = Bitset.from_mask(keep)
        _, vi = cagra.search(index, Q, 5, filter=filt)
        got = np.asarray(vi)
        assert ((got < n // 2) | (got == -1)).all()
        # oracle on the allowed half
        _, ei = brute_force.search(brute_force.build(X), Q, 5, filter=filt)
        assert _recall(got, np.asarray(ei)) >= 0.85

    def test_search_width_batching(self, data, index):
        X, Q = data
        _, vi = cagra.search(
            index, Q, 10,
            cagra.CagraSearchParams(itopk_size=64, search_width=4),
        )
        _, ei = brute_force.knn(Q, X, 10)
        assert _recall(np.asarray(vi), np.asarray(ei)) >= 0.9

    def test_validation(self, data, index):
        X, Q = data
        with pytest.raises(ValueError, match="must be in"):
            cagra.search(index, Q, 100, cagra.CagraSearchParams(itopk_size=32))
        with pytest.raises(ValueError, match="queries must be"):
            cagra.search(index, Q[:, :3], 5)
        with pytest.raises(ValueError, match="filter covers"):
            cagra.search(index, Q, 5, filter=Bitset.create(10))
        with pytest.raises(ValueError, match="unknown build_algo"):
            cagra.CagraParams(build_algo="hnsw")

class TestCagraCompressed:
    """Round-5 compressed traversal (inlined int8 neighbor codes) — payload
    build, recall vs exact traversal, serialize round-trips (VERDICT r4 #8),
    integer-dataset indexes."""

    @pytest.fixture(scope="class")
    def cidx(self, data):
        X, _ = data
        return cagra.build(X, cagra.CagraParams(
            graph_degree=16, intermediate_graph_degree=32,
            compress="on"))

    def test_payload_shapes(self, data, cidx):
        X, _ = data
        n, dim = X.shape
        assert cidx.nbr_codes.shape == (n, 16, dim)  # p = min(64, dim)
        assert cidx.nbr_codes.dtype == jnp.int8
        assert cidx.proj.shape == (dim, dim)
        # projection is orthonormal
        R = np.asarray(cidx.proj)
        np.testing.assert_allclose(R.T @ R, np.eye(dim), atol=1e-5)

    def test_compressed_recall_matches_exact(self, data, cidx):
        X, Q = data
        k = 10
        _, ei = brute_force.knn(Q, X, k)
        ei = np.asarray(ei)
        sp = cagra.CagraSearchParams(itopk_size=64)
        _, vi_c = cagra.search(cidx, Q, k, sp)
        assert _recall(np.asarray(vi_c), ei) >= 0.9
        sp_e = cagra.CagraSearchParams(itopk_size=64, traversal="exact")
        _, vi_e = cagra.search(cidx, Q, k, sp_e)
        # compressed traversal + exact re-rank stays within a few points of
        # the full-precision loop
        assert _recall(np.asarray(vi_c), ei) >= _recall(
            np.asarray(vi_e), ei) - 0.05

    def test_refine_topk_validation(self, data, cidx):
        _, Q = data
        with pytest.raises(ValueError, match="refine_topk"):
            cagra.search(cidx, Q, 10, cagra.CagraSearchParams(
                itopk_size=64, refine_topk=5))

    def test_compressed_requires_payload(self, data):
        X, Q = data
        plain = cagra.build(X, cagra.CagraParams(
            graph_degree=16, intermediate_graph_degree=32, compress="off"))
        assert plain.nbr_codes is None
        with pytest.raises(ValueError, match="compression payload"):
            cagra.search(plain, Q, 5, cagra.CagraSearchParams(
                traversal="compressed"))

    def test_serialize_roundtrip_with_payload(self, data, cidx, tmp_path):
        X, Q = data
        p = tmp_path / "compressed.bin"
        cidx.save(p)
        idx2 = cagra.CagraIndex.load(p)
        assert idx2.nbr_codes is not None
        np.testing.assert_array_equal(np.asarray(cidx.nbr_codes),
                                      np.asarray(idx2.nbr_codes))
        _, vi1 = cagra.search(cidx, Q, 5)
        _, vi2 = cagra.search(idx2, Q, 5)
        np.testing.assert_array_equal(np.asarray(vi1), np.asarray(vi2))

    def test_int_dataset_roundtrip(self, tmp_path):
        """VERDICT r4 #8: an integer-dataset index must round-trip its
        dtype through save/load and search identically after."""
        rng = np.random.default_rng(11)
        Xu = rng.integers(0, 256, (1200, 16)).astype(np.uint8)
        idx = cagra.build(Xu, cagra.CagraParams(
            graph_degree=8, intermediate_graph_degree=16, compress="on"))
        assert idx.dataset.dtype == jnp.uint8
        p = tmp_path / "u8.bin"
        idx.save(p)
        idx2 = cagra.CagraIndex.load(p)
        assert idx2.dataset.dtype == jnp.uint8
        Q = Xu[:40].astype(np.float32)
        _, v1 = cagra.search(idx, Q, 5)
        _, v2 = cagra.search(idx2, Q, 5)
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        _, gt = brute_force.knn(Q, Xu.astype(np.float32), 5)
        assert _recall(np.asarray(v1), np.asarray(gt)) >= 0.9


class TestCagraFused:
    """Round-6 fused Pallas traversal (one-kernel hop, ops/cagra_hop.py):
    interpret-mode parity vs the unfused compressed loop, the 10k-point
    recall gate, and mode resolution."""

    @pytest.fixture(scope="class")
    def cidx(self, data):
        X, _ = data
        return cagra.build(X, cagra.CagraParams(
            graph_degree=16, intermediate_graph_degree=32, compress="on"))

    def test_fused_parity_with_unfused_reference(self, data, cidx):
        """The ISSUE acceptance criterion: the fused hop — interpret=True
        on CPU — is bit-identical (ids) / allclose (distances) to the
        unfused _search_impl_compressed reference. q is a q-block multiple
        so both paths draw identical random seeds."""
        X, Q = data
        k = 10
        for itopk, w in ((64, 4), (32, 1)):
            sp_f = cagra.CagraSearchParams(itopk_size=itopk, search_width=w,
                                           traversal="fused")
            sp_c = cagra.CagraSearchParams(itopk_size=itopk, search_width=w,
                                           traversal="compressed")
            vf, i_f = cagra.search(cidx, Q, k, sp_f)
            vc, i_c = cagra.search(cidx, Q, k, sp_c)
            np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_c))
            np.testing.assert_allclose(np.asarray(vf), np.asarray(vc),
                                       rtol=1e-6, atol=1e-6)

    def test_fused_parity_with_filter(self, data, cidx):
        X, Q = data
        n = X.shape[0]
        keep = np.zeros(n, bool)
        keep[: n // 2] = True
        filt = Bitset.from_mask(keep)
        sp_f = cagra.CagraSearchParams(itopk_size=64, traversal="fused")
        sp_c = cagra.CagraSearchParams(itopk_size=64, traversal="compressed")
        _, i_f = cagra.search(cidx, Q, 5, sp_f, filter=filt)
        _, i_c = cagra.search(cidx, Q, 5, sp_c, filter=filt)
        np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_c))
        got = np.asarray(i_f)
        assert ((got < n // 2) | (got == -1)).all()

    def test_fused_padded_query_batch(self, data, cidx):
        """q not a multiple of the kernel's query block: padded rows must
        be sliced off and the real rows keep fused-vs-itself determinism."""
        X, Q = data
        sp = cagra.CagraSearchParams(itopk_size=32, traversal="fused")
        v1, i1 = cagra.search(cidx, Q[:41], 5, sp)
        v2, i2 = cagra.search(cidx, Q[:41], 5, sp)
        assert i1.shape == (41, 5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_fused_recall_gate_10k(self):
        """Recall gate on the synthetic 10k dataset (ISSUE 6): the fused
        traversal holds >= 0.95 recall vs the exact oracle."""
        from raft_tpu.bench.datasets import sift_like

        data_u8, queries_u8 = sift_like(10_000, 32, 64, seed=3)
        X = data_u8.astype(np.float32)
        Q = queries_u8.astype(np.float32)
        idx = cagra.build(X, cagra.CagraParams(
            graph_degree=32, intermediate_graph_degree=64, compress="on"))
        _, gt = brute_force.knn(Q, X, 10)
        _, vi = cagra.search(idx, Q, 10, cagra.CagraSearchParams(
            itopk_size=64, search_width=4, traversal="fused"))
        rec = _recall(np.asarray(vi), np.asarray(gt))
        assert rec >= 0.95, rec

    def test_fused_requires_payload(self, data):
        X, Q = data
        plain = cagra.build(X, cagra.CagraParams(
            graph_degree=16, intermediate_graph_degree=32, compress="off"))
        with pytest.raises(ValueError, match="compression payload"):
            cagra.search(plain, Q, 5,
                         cagra.CagraSearchParams(traversal="fused"))
        with pytest.raises(ValueError, match="unknown traversal"):
            cagra.CagraSearchParams(traversal="pallas")

    def test_resolve_traversal_modes(self):
        """auto → compressed with the payload present (the fused hop
        does not compile for the chip yet); explicit fused downgrades to
        compressed when the caller disallows the kernel (distributed shard
        bodies)."""
        sp = cagra.CagraSearchParams()
        mode, _ = cagra._resolve_traversal(sp, True, 5, 64, size=1000, b=64)
        assert mode == "compressed"
        mode, _ = cagra._resolve_traversal(sp, False, 5, 64, size=1000, b=64)
        assert mode == "exact"
        sp_f = cagra.CagraSearchParams(traversal="fused")
        mode, rt = cagra._resolve_traversal(sp_f, True, 5, 64, size=1000,
                                            b=64)
        assert mode == "fused" and rt == 64
        mode, _ = cagra._resolve_traversal(sp_f, True, 5, 64, size=1000,
                                           allow_fused=False, b=64)
        assert mode == "compressed"
        mode, _ = cagra._resolve_traversal(sp_f, True, 5, 64,
                                           size=cagra.MAX_FUSED_ROWS + 1,
                                           b=64)
        assert mode == "compressed"
        # wide candidate sets (b past the exact-dedup limit) downgrade:
        # the unfused merge would switch to slack+re-select dedup there,
        # so fused could not stay bit-identical to it
        mode, _ = cagra._resolve_traversal(sp_f, True, 5, 64, size=1000,
                                           b=cagra._CAGRA_DEDUP_LIMIT + 1)
        assert mode == "compressed"

    def test_fused_hops_counter(self, data, cidx, monkeypatch):
        from raft_tpu import obs

        X, Q = data
        obs.enable()
        obs.reset()
        # hop counting is opt-in on top of telemetry (the fetch blocks on
        # the tile's last chunk; back-to-back QPS loops must stay async)
        monkeypatch.setenv("RAFT_TPU_CAGRA_COUNT_HOPS", "1")
        sp = cagra.CagraSearchParams(itopk_size=32, traversal="fused")
        cagra.search(cidx, Q, 5, sp)
        c = obs.snapshot()["counters"]
        obs.disable()
        assert c.get("cagra.search.traversal.fused") == 1
        assert c.get("cagra.search.hops", 0) >= 1


class TestRefineKnnGraph:
    """Device-resident NN-descent sweep (cagra.refine_knn_graph)."""

    @pytest.fixture(scope="class")
    def graph_case(self):
        from raft_tpu.core.resources import current_resources

        rng = np.random.default_rng(0)
        n, dim, ideg = 1500, 16, 16
        X = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
        _, nn = brute_force.search(brute_force.build(X), X, ideg + 1,
                                   select_algo="exact")
        exact = cagra._drop_self(nn, 0, ideg)
        return rng, X, exact, ideg, n, current_resources()

    def test_preserves_exact_graph_and_degree(self, graph_case):
        rng, X, exact, ideg, n, res = graph_case
        out = cagra.refine_knn_graph(X, exact, 1, 64, 0, res)
        # an already-exact graph must survive a sweep (the round-4 dup
        # collapse bug halved the degree here)
        assert float(jnp.mean(jnp.sum(out >= 0, axis=1))) == ideg
        rec = float(stats.neighborhood_recall(out, exact))
        assert rec > 0.95, rec

    def test_improves_random_graph(self, graph_case):
        rng, X, exact, ideg, n, res = graph_case
        bad = jnp.asarray(rng.integers(0, n, (n, ideg)).astype(np.int32))
        before = float(stats.neighborhood_recall(bad, exact))
        out = cagra.refine_knn_graph(X, bad, 3, 64, 0, res)
        after = float(stats.neighborhood_recall(out, exact))
        assert after > before + 0.1, (before, after)


def test_empty_query_batch(data):
    """A filtered-to-empty query batch returns empty results instead of
    crashing in the tiling math (code-review r5)."""
    X, _ = data
    idx = cagra.build(X, cagra.CagraParams(graph_degree=8,
                                           intermediate_graph_degree=16))
    v, i = cagra.search(idx, np.zeros((0, X.shape[1]), np.float32), 5)
    assert v.shape == (0, 5) and i.shape == (0, 5)


def test_wide_merge_slack_path(data):
    """width*deg beyond the exact-dedup limit takes the slack+re-select
    merge in BOTH traversals without recall collapse (shared
    _merge_candidates wide branch)."""
    X, Q = data
    idx = cagra.build(X, cagra.CagraParams(
        graph_degree=16, intermediate_graph_degree=32, compress="on"))
    _, ei = brute_force.knn(Q, X, 10)
    ei = np.asarray(ei)
    for trav in ("compressed", "exact"):
        _, vi = cagra.search(idx, Q, 10, cagra.CagraSearchParams(
            itopk_size=64, search_width=40, traversal=trav))
        assert _recall(np.asarray(vi), ei) >= 0.9, trav
