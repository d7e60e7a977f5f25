"""MNMG algorithm tests on the 8-virtual-device mesh: sharded results must
match the single-device library path (tier-1 oracle, SURVEY.md §4.3 — the
LocalCUDACluster-analog fixture is the conftest virtual CPU mesh)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import resilience
from raft_tpu.cluster import kmeans as kmeans_sd
from raft_tpu.comms import Comms, local_mesh
from raft_tpu.core.bitset import Bitset
from raft_tpu.distributed import brute_force as dbf
from raft_tpu.distributed import kmeans as dkm
from raft_tpu.neighbors import brute_force as bf


@pytest.fixture(scope="module")
def comms():
    return Comms(local_mesh(8))


@pytest.fixture
def clean_resilience():
    """Disarmed faults + a fresh shard-health registry around each
    degraded-mode test (LOST is sticky by design — it must not leak)."""
    resilience.clear_faults()
    resilience.reset_shard_health()
    resilience.clear_events()
    yield
    resilience.clear_faults()
    resilience.reset_shard_health()


def _data(rng, n=500, dim=16, q=20):
    X = rng.standard_normal((n, dim)).astype(np.float32)
    Q = rng.standard_normal((q, dim)).astype(np.float32)
    return X, Q


class TestShardedBruteForce:
    def test_matches_single_device(self, rng, comms):
        X, Q = _data(rng)
        idx_s = dbf.build(X, comms=comms)
        vd, vi = dbf.search(idx_s, Q, 10)
        ed, ei = bf.search(bf.build(X), Q, 10)
        np.testing.assert_allclose(np.asarray(vd), np.asarray(ed), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(vi), np.asarray(ei))

    def test_unpadded_rows_never_returned(self, rng, comms):
        # n not divisible by 8 → padding rows at the global tail must not
        # appear even though pad rows are all-zeros (nearest to the origin)
        X, Q = _data(rng, n=501)
        origin_query = np.zeros((1, X.shape[1]), np.float32)
        idx_s = dbf.build(X, comms=comms)
        _, vi = dbf.search(idx_s, origin_query, 10)
        assert np.asarray(vi).max() < 501
        ed, ei = bf.search(bf.build(X), origin_query, 10)
        np.testing.assert_array_equal(np.asarray(vi), np.asarray(ei))

    def test_inner_product_metric(self, rng, comms):
        X, Q = _data(rng)
        idx_s = dbf.build(X, metric="inner_product", comms=comms)
        vd, vi = dbf.search(idx_s, Q, 5)
        ed, ei = bf.search(bf.build(X, metric="inner_product"), Q, 5)
        np.testing.assert_array_equal(np.asarray(vi), np.asarray(ei))

    def test_filter(self, rng, comms):
        X, Q = _data(rng, n=256)
        keep = np.zeros(256, bool)
        keep[::2] = True  # only even ids allowed
        filt = Bitset.from_mask(keep)
        idx_s = dbf.build(X, comms=comms)
        _, vi = dbf.search(idx_s, Q, 8, filter=filt)
        got = np.asarray(vi)
        assert (got % 2 == 0).all() and (got >= 0).all()
        _, ei = bf.search(bf.build(X), Q, 8, filter=filt)
        np.testing.assert_array_equal(got, np.asarray(ei))

    def test_validation(self, rng, comms):
        X, Q = _data(rng)
        idx_s = dbf.build(X, comms=comms)
        with pytest.raises(ValueError, match="out of range"):
            dbf.search(idx_s, Q, 0)
        with pytest.raises(ValueError, match="query dim"):
            dbf.search(idx_s, Q[:, :3], 5)
        with pytest.raises(ValueError, match="filter covers"):
            dbf.search(idx_s, Q, 5, filter=Bitset.create(7))


class TestDistributedKMeans:
    def test_converges_on_blobs(self, rng, comms):
        # well-separated blobs: distributed fit must recover the centers
        centers_true = np.array(
            [[10.0, 0.0, 0.0, 0.0], [0.0, 10.0, 0.0, 0.0],
             [0.0, 0.0, 10.0, 0.0], [0.0, 0.0, 0.0, 10.0]], np.float32
        )
        X = np.concatenate(
            [c + 0.1 * rng.standard_normal((100, 4)).astype(np.float32)
             for c in centers_true]
        )
        params = kmeans_sd.KMeansParams(n_clusters=4, max_iter=50)
        out, labels = dkm.fit(X, params, comms=comms)
        got = np.asarray(out.centroids)
        # match centers up to permutation
        d = np.linalg.norm(got[:, None, :] - centers_true[None], axis=-1)
        assert (d.min(axis=1) < 0.5).all()
        assert labels.shape == (400,)
        # all members of one blob share a label
        lab = np.asarray(labels)
        for b in range(4):
            assert len(np.unique(lab[b * 100:(b + 1) * 100])) == 1

    def test_matches_single_device_inertia(self, rng, comms):
        X, _ = _data(rng, n=512, dim=8)
        params = kmeans_sd.KMeansParams(n_clusters=8, max_iter=100, init="random", seed=3)
        out_d, labels = dkm.fit(X, params, comms=comms)
        out_s = kmeans_sd.fit(X, params)
        # different inits → different local minima; inertias must be in the
        # same ballpark and labels consistent with returned centers
        assert float(out_d.inertia) <= float(out_s.inertia) * 1.3
        d = np.linalg.norm(
            X[:, None, :] - np.asarray(out_d.centroids)[None], axis=-1
        )
        np.testing.assert_array_equal(np.asarray(labels), d.argmin(axis=1))

    def test_weighted_and_padding(self, rng, comms):
        # n=333 not divisible by 8; zero-weight rows must not attract centers
        X = np.concatenate(
            [np.full((300, 2), 5.0, np.float32),
             rng.standard_normal((33, 2)).astype(np.float32) + 100.0]
        )
        w = np.concatenate([np.ones(300, np.float32), np.zeros(33, np.float32)])
        params = kmeans_sd.KMeansParams(n_clusters=1, max_iter=20)
        out, _ = dkm.fit(X, params, sample_weight=w, comms=comms)
        np.testing.assert_allclose(
            np.asarray(out.centroids)[0], [5.0, 5.0], atol=1e-3
        )

    def test_seed_reproducible(self, rng, comms):
        X, _ = _data(rng, n=200, dim=4)
        params = kmeans_sd.KMeansParams(n_clusters=5, max_iter=10, seed=7)
        out_a, _ = dkm.fit(X, params, comms=comms)
        out_b, _ = dkm.fit(X, params, comms=comms)
        np.testing.assert_array_equal(
            np.asarray(out_a.centroids), np.asarray(out_b.centroids)
        )

    def test_array_init(self, rng, comms):
        X, _ = _data(rng, n=100, dim=4)
        c0 = X[:3]
        params = kmeans_sd.KMeansParams(n_clusters=3, max_iter=10, init="array")
        out, _ = dkm.fit(X, params, centroids=c0, comms=comms)
        assert out.centroids.shape == (3, 4)
        with pytest.raises(ValueError, match="requires centroids"):
            dkm.fit(X, params, comms=comms)

    def test_validation(self, comms):
        with pytest.raises(ValueError, match="n_clusters"):
            dkm.fit(np.zeros((4, 2), np.float32),
                    kmeans_sd.KMeansParams(n_clusters=10), comms=comms)


@pytest.mark.parametrize("family", ["brute_force", "ivf_flat"])
def test_query_tiles_match_one_call(rng, comms, family):
    """A workspace too small for one call splits the queries into equal
    tiles (distributed brute force: the (tile, shard rows) distance block;
    the dense IVF scan: dense_q_tile) and changes no answer."""
    from raft_tpu import obs
    from raft_tpu.core.resources import Resources, use_resources
    from raft_tpu.distributed import ivf_flat as divf

    X, Q = _data(rng, n=800, q=20)
    if family == "brute_force":
        idx = dbf.build(X, comms=comms)
        search = lambda: dbf.search(idx, Q, 10)
        tiny = 4 * 100 * 7          # 100 rows per shard: 7-query tiles
    else:
        idx = divf.build(X, divf.IvfFlatParams(n_lists=4), comms=comms)
        search = lambda: divf.search(idx, Q, 10, n_probes=4)
        tiny = idx.max_list_size * (4 * 16 + 16 * 4) * 7
    v0, i0 = search()
    obs.enable()
    try:
        before = obs.snapshot()["counters"].get("distributed.search.tiles", 0)
        with use_resources(Resources(workspace_bytes=tiny)):
            v1, i1 = search()
        tiles = (obs.snapshot()["counters"].get("distributed.search.tiles", 0)
                 - before)
    finally:
        obs.disable()
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-6)
    if family == "ivf_flat":
        assert tiles == 3, tiles    # 20 queries in tiles of 7, 7, 6


def test_sharded_strip_tiles_match_dense_scan(rng, comms):
    """The TPU engine of the sharded search — strip tables planned inside
    each shard_map tile on the static layout, the Pallas kernel in
    interpret mode here — returns the dense XLA scan's answers."""
    from raft_tpu.distributed import ivf_flat as divf
    from raft_tpu.distributed._sharding import tiled_search
    from raft_tpu.neighbors.ivf_flat import _coarse_probes

    X, Q = _data(rng, n=4000, dim=16, q=24)
    idx = divf.build(X, divf.IvfFlatParams(n_lists=4, group_size=512),
                     comms=comms)
    assert idx.max_list_size == 512      # strip-eligible
    probes = _coarse_probes(jnp.asarray(Q), idx.centers, 3, "sqeuclidean",
                            "exact", jnp.float32)
    out = {}
    for dense in (True, False):
        v, i, _ = tiled_search(
            jnp.asarray(Q), probes, idx.lens_max, idx.n_lists, 10, comms,
            -2.0, dense=dense, interpret=True, data=idx.list_data,
            ids_arr=idx.list_ids, bias=idx.bias, algo="ivf_flat",
            n_total=idx.n_total)
        out[dense] = (np.asarray(v), np.asarray(i))
    # the strip kernel scores in bf16 (its documented contract): near-ties
    # may swap, so compare as sets, and the distances loosely
    from raft_tpu import stats

    overlap = float(stats.neighborhood_recall(out[False][1], out[True][1]))
    assert overlap >= 0.9, overlap
    np.testing.assert_allclose(np.sort(out[False][0], 1)[:, 0],
                               np.sort(out[True][0], 1)[:, 0], rtol=0.05,
                               atol=0.05)


class TestShardedIvfFlat:
    def test_build_search_matches_single_device(self):
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_flat as divf
        from raft_tpu.neighbors import brute_force
        from raft_tpu import stats

        rng = np.random.default_rng(13)
        X = rng.standard_normal((4000, 16)).astype(np.float32)
        Q = rng.standard_normal((64, 16)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = divf.build(X, divf.IvfFlatParams(n_lists=16), comms=comms)
        assert idx.list_data.shape[0] == 8 and idx.n_total == 4000
        v, i = divf.search(idx, Q, 10, n_probes=16)  # exhaustive probes
        _, gt = brute_force.search(brute_force.build(X), Q, 10)
        recall = float(stats.neighborhood_recall(i, gt))
        assert recall >= 0.99, recall
        # global row ids: all shard offsets represented
        ids = np.asarray(i)
        assert ids.max() >= 3500 and ids.min() >= 0

    def test_validation(self):
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_flat as divf

        comms = Comms(local_mesh(8))
        X = np.random.default_rng(0).standard_normal((60, 4)).astype(np.float32)
        with pytest.raises(ValueError):
            divf.build(X, divf.IvfFlatParams(n_lists=16), comms=comms)


class TestShardedIvfPq:
    def test_build_search_refine_matches_ground_truth(self):
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_pq as dpq
        from raft_tpu.neighbors import brute_force, ivf_pq, refine
        from raft_tpu import stats

        rng = np.random.default_rng(5)
        X = rng.standard_normal((4000, 32)).astype(np.float32)
        Q = rng.standard_normal((64, 32)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = dpq.build(X, ivf_pq.IvfPqParams(n_lists=16, pq_dim=16),
                        comms=comms)
        assert idx.list_codes.shape[0] == 8 and idx.n_total == 4000
        # exhaustive probes + over-fetch + exact refine: recall gate
        _, cand = dpq.search(idx, Q, 40, n_probes=16)
        v, i = refine.refine(X, Q, cand, 10)
        _, gt = brute_force.search(brute_force.build(X), Q, 10)
        recall = float(stats.neighborhood_recall(i, gt))
        assert recall >= 0.95, recall
        ids = np.asarray(i)
        assert ids.max() >= 3500 and ids.min() >= 0

    def test_metric_cosine_runs(self):
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_pq as dpq
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(7)
        X = rng.standard_normal((2000, 16)).astype(np.float32)
        Q = rng.standard_normal((16, 16)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = dpq.build(X, ivf_pq.IvfPqParams(n_lists=8, pq_dim=8,
                                              metric="cosine"), comms=comms)
        v, i = dpq.search(idx, Q, 5, n_probes=8)
        assert v.shape == (16, 5) and int(np.asarray(i).min()) >= 0

    def test_cluster_codebooks_match_recall(self):
        """codebook_kind='cluster' sharded build+search (ivf_pq_types.hpp:36
        PER_CLUSTER; round-4 — this path used to raise NotImplementedError).
        Gate: exhaustive probes + exact refine reaches the recall the
        single-device cluster path reaches on the same data."""
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_pq as dpq
        from raft_tpu.neighbors import brute_force, ivf_pq, refine
        from raft_tpu import stats

        rng = np.random.default_rng(11)
        X = rng.standard_normal((4000, 32)).astype(np.float32)
        Q = rng.standard_normal((64, 32)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = dpq.build(X, ivf_pq.IvfPqParams(
            n_lists=16, pq_dim=16, codebook_kind="cluster"), comms=comms)
        assert idx.codebooks.shape[0] == 16  # one codebook per list
        _, cand = dpq.search(idx, Q, 40, n_probes=16)
        v, i = refine.refine(X, Q, cand, 10)
        _, gt = brute_force.search(brute_force.build(X), Q, 10)
        recall = float(stats.neighborhood_recall(i, gt))
        assert recall >= 0.95, recall


class TestShardedIvfBq:
    def test_build_search_refine_matches_ground_truth(self):
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_bq as dbq
        from raft_tpu.neighbors import brute_force, ivf_bq, refine
        from raft_tpu import stats

        rng = np.random.default_rng(5)
        X = rng.standard_normal((4000, 32)).astype(np.float32)
        Q = rng.standard_normal((64, 32)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = dbq.build(X, ivf_bq.IvfBqParams(n_lists=16), comms=comms)
        assert idx.list_codes.shape[0] == 8 and idx.n_total == 4000
        assert idx.list_codes.shape[-1] == 4  # 32 bits packed to 4 bytes
        # exhaustive probes + wide over-fetch + exact refine: 1-bit codes
        # on WHITE data are the estimator's noise floor — the candidate
        # set must still carry the true neighbors at this fetch width
        _, cand = dbq.search(idx, Q, 256, n_probes=16)
        v, i = refine.refine(X, Q, cand, 10)
        _, gt = brute_force.search(brute_force.build(X), Q, 10)
        recall = float(stats.neighborhood_recall(i, gt))
        assert recall >= 0.93, recall
        ids = np.asarray(i)
        assert ids.max() >= 3500 and ids.min() >= 0

    def test_matches_single_host_scalars(self):
        """Shard-encoded correction scalars equal the single-host
        _encode_chunk on the same rows (same centers/rotation seed path is
        NOT guaranteed — distributed kmeans differs — so compare through
        a shared quantizer instead)."""
        import jax.numpy as jnp
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_bq as dbq
        from raft_tpu.neighbors import ivf_bq

        rng = np.random.default_rng(9)
        X = rng.standard_normal((2048, 16)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = dbq.build(X, ivf_bq.IvfBqParams(n_lists=8), comms=comms)
        # every valid entry's scalars must reproduce from its source row
        # through the same encode definition
        from raft_tpu.ops import distance as dist_mod

        rc = ivf_bq._pad_rot(idx.centers, idx.rot_dim) @ idx.rotation.T
        c2 = dist_mod.sqnorm(idx.centers)
        ids = np.asarray(idx.list_ids)
        scale = np.asarray(idx.list_scale)
        checked = 0
        for w in range(ids.shape[0]):
            for l in range(ids.shape[1]):
                fill = int((ids[w, l] >= 0).sum())
                if not fill:
                    continue
                rows = jnp.asarray(X[ids[w, l, :fill]])
                labels = jnp.full((fill,), l, jnp.int32)
                _, want_scale, _ = ivf_bq._encode_chunk(
                    rows, labels, idx.centers, idx.rotation, rc, c2, True)
                np.testing.assert_allclose(scale[w, l, :fill],
                                           np.asarray(want_scale),
                                           rtol=1e-5)
                checked += fill
                break  # one list per shard keeps the test fast
        assert checked > 0


class TestShardedCagra:
    def test_matches_single_device_recall(self):
        """Shard-local graphs + all-gather merge (raft-dask MNMG pattern,
        comms.py:40): merged recall must track the single-device CAGRA
        searching the same rows."""
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import cagra as dcagra
        from raft_tpu.neighbors import brute_force, cagra
        from raft_tpu import stats

        rng = np.random.default_rng(3)
        X = rng.standard_normal((4003, 32)).astype(np.float32)  # padding case
        Q = rng.standard_normal((40, 32)).astype(np.float32)
        comms = Comms(local_mesh(8))
        params = cagra.CagraParams(intermediate_graph_degree=32,
                                   graph_degree=16, build_algo="brute")
        idx = dcagra.build(X, params, comms=comms)
        v, i = dcagra.search(idx, Q, 10,
                             cagra.CagraSearchParams(itopk_size=64))
        _, gt = brute_force.search(brute_force.build(X), Q, 10)
        rec = float(stats.neighborhood_recall(i, gt))
        assert rec >= 0.9, rec
        ids = np.asarray(i)
        assert ids.max() < 4003 and ids.min() >= -1

    def test_validation(self):
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import cagra as dcagra
        from raft_tpu.neighbors import cagra
        import pytest as _pt

        rng = np.random.default_rng(0)
        X = rng.standard_normal((400, 16)).astype(np.float32)
        comms = Comms(local_mesh(8))
        with _pt.raises(ValueError, match="graph_degree"):
            dcagra.build(X, cagra.CagraParams(
                intermediate_graph_degree=64, graph_degree=64,
                build_algo="brute"), comms=comms)


class TestDistributedCagraCompressed:
    def test_compressed_shards_search(self, comms):
        """Shards built with the compression payload search through the
        compressed loop (round 5) and still match the exact oracle at a
        scale where every shard walks all its rows."""
        from raft_tpu.distributed import cagra as dcagra
        from raft_tpu.neighbors import brute_force as bf
        from raft_tpu.neighbors import cagra as slcagra

        rng = np.random.default_rng(4)
        n, dim, q, k = 1600, 16, 16, 5
        X = rng.standard_normal((n, dim)).astype(np.float32)
        Q = rng.standard_normal((q, dim)).astype(np.float32)
        idx = dcagra.build(X, slcagra.CagraParams(
            intermediate_graph_degree=16, graph_degree=8,
            build_algo="brute", compress="on"), comms=comms)
        assert idx.nbr_codes is not None
        cv, ci = dcagra.search(idx, Q, k, slcagra.CagraSearchParams(
            itopk_size=32))
        _, ei = bf.search(bf.build(X), Q, k)
        ei = np.asarray(ei)
        overlap = np.mean([
            len(set(np.asarray(ci)[r]) & set(ei[r])) / k for r in range(q)])
        assert overlap >= 0.8, overlap
        # exact traversal still selectable on a payload-carrying index
        _, ce = dcagra.search(idx, Q, k, slcagra.CagraSearchParams(
            itopk_size=32, traversal="exact"))
        overlap_e = np.mean([
            len(set(np.asarray(ce)[r]) & set(ei[r])) / k for r in range(q)])
        assert overlap_e >= 0.8, overlap_e


# ---------------------------------------------------------------------------
# Degraded-mode search (ISSUE 7): a lost shard costs coverage, not the query
# ---------------------------------------------------------------------------


def _surviving_reference(X, Q, k, lost_shards, world=8):
    """Exact top-k restricted to the rows the SURVIVING shards hold, mapped
    to global ids (the acceptance oracle: partial merge must be exact over
    the survivors)."""
    rows_per = -(-X.shape[0] // world)
    keep = np.ones(X.shape[0], bool)
    for r in lost_shards:
        keep[r * rows_per:(r + 1) * rows_per] = False
    gid = np.arange(X.shape[0])[keep]
    vd, vi = bf.search(bf.build(X[keep]), Q, k)
    return np.asarray(vd), gid[np.asarray(vi)]


class TestDegradedSearch:
    def test_brute_force_shard_loss(self, rng, comms, clean_resilience):
        X, Q = _data(rng, n=501)
        idx = dbf.build(X, comms=comms)
        resilience.arm_faults("distributed.brute_force.search.shard=fatal:1")
        res = dbf.search(idx, Q, 10)
        vd, vi = res  # SearchResult unpacks like the plain pair
        assert res.degraded and res.coverage < 1.0
        assert res.lost_shards == (0,)
        ed, ei = _surviving_reference(X, Q, 10, res.lost_shards)
        np.testing.assert_array_equal(np.asarray(vi), ei)
        np.testing.assert_allclose(np.asarray(vd), ed, rtol=1e-5, atol=1e-5)
        # every incident is observable
        events = [e["event"] for e in resilience.recent_events()]
        assert "shard_lost" in events and "partial_merge" in events
        # a FATAL loss is sticky: the next dispatch skips the shard without
        # re-probing and stays honestly degraded
        res2 = dbf.search(idx, Q, 10)
        assert res2.degraded and res2.lost_shards == (0,)
        assert resilience.shard_health().state(0) == resilience.LOST

    def test_brute_force_healthy_is_full_coverage(self, rng, comms,
                                                  clean_resilience):
        X, Q = _data(rng)
        idx = dbf.build(X, comms=comms)
        res = dbf.search(idx, Q, 10)
        assert res.coverage == 1.0 and not res.degraded
        assert res.lost_shards == ()

    def test_ivf_flat_shard_loss_exact_over_survivors(self, comms,
                                                      clean_resilience):
        from raft_tpu.distributed import ivf_flat as divf

        rng = np.random.default_rng(13)
        X = rng.standard_normal((2000, 16)).astype(np.float32)
        Q = rng.standard_normal((16, 16)).astype(np.float32)
        idx = divf.build(X, divf.IvfFlatParams(n_lists=8), comms=comms)
        resilience.arm_faults("distributed.ivf_flat.search.shard=fatal:1")
        res = divf.search(idx, Q, 10, n_probes=8)  # exhaustive probes
        assert res.degraded and res.coverage < 1.0
        _, ei = _surviving_reference(X, Q, 10, res.lost_shards)
        np.testing.assert_array_equal(np.asarray(res.indices), ei)

    def test_ivf_pq_shard_loss(self, comms, clean_resilience):
        from raft_tpu import stats
        from raft_tpu.distributed import ivf_pq as dpq
        from raft_tpu.neighbors import ivf_pq, refine

        rng = np.random.default_rng(7)
        X = rng.standard_normal((2000, 32)).astype(np.float32)
        Q = rng.standard_normal((16, 32)).astype(np.float32)
        idx = dpq.build(X, ivf_pq.IvfPqParams(n_lists=8, pq_dim=16),
                        comms=comms)
        resilience.arm_faults("distributed.ivf_pq.search.shard=fatal:1")
        res = dpq.search(idx, Q, 40, n_probes=8)  # exhaustive + over-fetch
        assert res.degraded and res.coverage < 1.0
        ids = np.asarray(res.indices)
        rows_per = -(-2000 // 8)
        assert (ids[ids >= 0] >= rows_per).all()  # no lost-shard rows
        # exact refine of the degraded candidates must hit the recall gate
        # against the reference restricted to the SURVIVING shards
        _, i_ref = refine.refine(X, Q, res.indices, 10)
        _, gt = _surviving_reference(X, Q, 10, res.lost_shards)
        assert float(stats.neighborhood_recall(i_ref, gt)) >= 0.95

    def test_ivf_bq_shard_loss(self, comms, clean_resilience):
        from raft_tpu import stats
        from raft_tpu.distributed import ivf_bq as dbq
        from raft_tpu.neighbors import ivf_bq, refine

        rng = np.random.default_rng(7)
        X = rng.standard_normal((2000, 32)).astype(np.float32)
        Q = rng.standard_normal((16, 32)).astype(np.float32)
        idx = dbq.build(X, ivf_bq.IvfBqParams(n_lists=8), comms=comms)
        resilience.arm_faults("distributed.ivf_bq.search.shard=fatal:1")
        res = dbq.search(idx, Q, 256, n_probes=8)  # exhaustive + over-fetch
        assert res.degraded and res.coverage < 1.0
        ids = np.asarray(res.indices)
        rows_per = -(-2000 // 8)
        assert (ids[ids >= 0] >= rows_per).all()  # no lost-shard rows
        # exact refine of the degraded candidates vs the reference
        # restricted to the SURVIVING shards
        _, i_ref = refine.refine(X, Q, res.indices, 10)
        _, gt = _surviving_reference(X, Q, 10, res.lost_shards)
        assert float(stats.neighborhood_recall(i_ref, gt)) >= 0.9

    def test_cagra_shard_loss(self, comms, clean_resilience):
        from raft_tpu.distributed import cagra as dcagra
        from raft_tpu.neighbors import cagra as slcagra

        rng = np.random.default_rng(3)
        X = rng.standard_normal((1600, 16)).astype(np.float32)
        Q = rng.standard_normal((16, 16)).astype(np.float32)
        idx = dcagra.build(X, slcagra.CagraParams(
            intermediate_graph_degree=16, graph_degree=8,
            build_algo="brute"), comms=comms)
        resilience.arm_faults("distributed.cagra.search.shard=fatal:1")
        res = dcagra.search(idx, Q, 5,
                            slcagra.CagraSearchParams(itopk_size=32))
        assert res.degraded and res.coverage < 1.0
        ids = np.asarray(res.indices)
        rows_per = -(-1600 // 8)
        assert (ids[ids >= 0] >= rows_per).all()
        # merged top-k tracks the exact reference over the surviving shards
        # (each small shard walks essentially all its rows at itopk=32)
        _, gt = _surviving_reference(X, Q, 5, res.lost_shards)
        overlap = np.mean([len(set(ids[r]) & set(gt[r])) / 5
                           for r in range(Q.shape[0])])
        assert overlap >= 0.8, overlap

    def test_transient_shard_heals(self, rng, comms, clean_resilience):
        """A TRANSIENT verdict marks the shard SUSPECT (one degraded
        dispatch); the next clean probe reinstates it — full coverage."""
        X, Q = _data(rng)
        idx = dbf.build(X, comms=comms)
        resilience.arm_faults(
            "distributed.brute_force.search.shard=transient:1")
        res = dbf.search(idx, Q, 10)
        assert res.degraded and res.lost_shards == (0,)
        assert resilience.shard_health().state(0) == resilience.SUSPECT
        res2 = dbf.search(idx, Q, 10)
        assert not res2.degraded and res2.coverage == 1.0
        assert resilience.shard_health().state(0) == resilience.HEALTHY

    def test_quorum_loss_raises_classified(self, rng, comms,
                                           clean_resilience):
        """Below the minimum-coverage quorum a degraded result would be
        noise: the dispatch fails with a classified FATAL instead."""
        X, Q = _data(rng)
        idx = dbf.build(X, comms=comms)
        resilience.arm_faults("distributed.brute_force.search.shard=fatal:5")
        with pytest.raises(resilience.ShardQuorumError) as ei:
            dbf.search(idx, Q, 10)
        assert resilience.classify(ei.value) == resilience.FATAL

    def test_deadline_slices_budget_over_shards(self, rng, comms,
                                                clean_resilience):
        """A shard that hangs burns its SLICE of the query deadline, not
        the whole budget: the query returns degraded well inside it."""
        X, Q = _data(rng)
        idx = dbf.build(X, comms=comms)
        resilience.arm_faults(
            "distributed.brute_force.search.shard=hang:1:60")
        t0 = time.monotonic()
        with resilience.Deadline(5.0, label="query") as dl:
            res = dbf.search(idx, Q, 10)
        assert time.monotonic() - t0 < 5.0
        assert res.degraded and res.lost_shards == (0,)
        assert not dl.reached()  # survivors answered inside the budget


# ---------------------------------------------------------------------------
# Sharded snapshots (ISSUE 7): LOST recovery = reload, not rebuild
# ---------------------------------------------------------------------------


class TestShardedSnapshot:
    def test_manifest_and_roundtrip(self, comms, tmp_path,
                                    clean_resilience):
        import json
        import os

        from raft_tpu.distributed import ivf_flat as divf, snapshot

        rng = np.random.default_rng(23)
        X = rng.standard_normal((2000, 16)).astype(np.float32)
        Q = rng.standard_normal((16, 16)).astype(np.float32)
        idx = divf.build(X, divf.IvfFlatParams(n_lists=8), comms=comms)
        d = str(tmp_path / "snap")
        mpath = snapshot.save(idx, d)
        manifest = json.load(open(mpath))
        assert manifest["kind"] == "ivf_flat" and manifest["world"] == 8
        assert len(manifest["shards"]) == 8
        for f in [manifest["common"]] + manifest["shards"]:
            assert os.path.exists(os.path.join(d, f))
        idx2 = snapshot.load(d, comms=comms)
        v0, i0 = divf.search(idx, Q, 10, n_probes=8)
        v1, i1 = divf.search(idx2, Q, 10, n_probes=8)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))

    def test_lost_shard_recovers_from_snapshot(self, rng, comms, tmp_path,
                                               clean_resilience):
        from raft_tpu.distributed import snapshot

        X, Q = _data(rng)
        idx = dbf.build(X, comms=comms)
        full = dbf.search(idx, Q, 10)
        d = str(tmp_path / "snap")
        snapshot.save(idx, d)
        resilience.arm_faults("distributed.brute_force.search.shard=fatal:1")
        degraded = dbf.search(idx, Q, 10)
        assert degraded.degraded and \
            resilience.shard_health().lost() == (0,)
        # the recovery action the shard_lost event advertises
        idx2, recovered = snapshot.recover(idx, d)
        assert recovered == (0,)
        assert resilience.shard_health().state(0) == resilience.HEALTHY
        healed = dbf.search(idx2, Q, 10)
        assert healed.coverage == 1.0 and not healed.degraded
        np.testing.assert_array_equal(np.asarray(healed.indices),
                                      np.asarray(full.indices))

    def test_wrong_world_rejected(self, comms, tmp_path, clean_resilience):
        from raft_tpu.distributed import snapshot

        rng = np.random.default_rng(2)
        X = rng.standard_normal((256, 8)).astype(np.float32)
        idx = dbf.build(X, comms=comms)
        d = str(tmp_path / "snap")
        snapshot.save(idx, d)
        with pytest.raises(ValueError, match="world"):
            snapshot.load(d, comms=Comms(local_mesh(4)))

    def test_restore_shard_load_faultpoint(self, rng, comms, tmp_path,
                                           clean_resilience):
        """Round-18 satellite: the restore path rides the new
        ``serialize.load.read`` faultpoint — an armed oom on the shard
        reload lands classified (OOM), the index is untouched, and the
        disarmed retry restores bit-identically."""
        from raft_tpu.distributed import snapshot

        X, Q = _data(rng)
        idx = dbf.build(X, comms=comms)
        full = dbf.search(idx, Q, 10)
        d = str(tmp_path / "snap")
        snapshot.save(idx, d)
        # count=2: the manifest read is plain json; the first container
        # read (common or shard file) fires
        resilience.arm_faults("serialize.load.read=oom:1")
        with pytest.raises(resilience.FaultInjected) as exc_info:
            snapshot.restore_shard(idx, d, 0)
        assert resilience.classify(exc_info.value) == resilience.OOM
        resilience.clear_faults()
        idx2 = snapshot.restore_shard(idx, d, 0)
        healed = dbf.search(idx2, Q, 10)
        np.testing.assert_array_equal(np.asarray(healed.indices),
                                      np.asarray(full.indices))


class TestDistributedBalancedKMeans:
    """Round 17: the distributed coarse trainer (shard-mapped assign +
    psum centroid reduce) behind the shard-health fit gate."""

    def test_fit_balanced_balances(self, clean_resilience):
        import numpy as np
        from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import kmeans as dkm

        rng = np.random.default_rng(11)
        X = rng.standard_normal((2048, 16)).astype(np.float32)
        comms = Comms(local_mesh(8))
        centers, labels, rep = dkm.fit_balanced(
            X, 16, KMeansBalancedParams(n_iters=10, seed=0), comms=comms)
        assert rep.coverage == 1.0 and not rep.degraded
        assert centers.shape == (16, 16) and labels.shape == (2048,)
        sizes = np.bincount(np.asarray(labels), minlength=16)
        # the balancing reseed's whole job: no starved clusters
        assert sizes.min() > 0.25 * sizes.mean()

    def test_fit_balanced_inner_product_normalizes(self, clean_resilience):
        import numpy as np
        from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import kmeans as dkm

        rng = np.random.default_rng(3)
        X = rng.standard_normal((1024, 12)).astype(np.float32)
        comms = Comms(local_mesh(8))
        centers, _, _ = dkm.fit_balanced(
            X, 8, KMeansBalancedParams(n_iters=8, metric="inner_product",
                                       seed=0), comms=comms)
        norms = np.linalg.norm(np.asarray(centers), axis=1)
        np.testing.assert_allclose(norms, 1.0, rtol=1e-4)

    def test_fit_balanced_shard_loss_degrades(self, clean_resilience):
        """An armed per-shard fit fault costs coverage, never the fit:
        training completes over the survivors, classified (the round-7 +
        shard-health gates, applied to the BUILD side)."""
        import numpy as np
        from raft_tpu import resilience
        from raft_tpu.cluster.kmeans_balanced import KMeansBalancedParams
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import kmeans as dkm

        rng = np.random.default_rng(5)
        X = rng.standard_normal((2048, 16)).astype(np.float32)
        comms = Comms(local_mesh(8))
        resilience.arm_faults("distributed.kmeans.fit.shard=fatal:1")
        try:
            centers, labels, rep = dkm.fit_balanced(
                X, 16, KMeansBalancedParams(n_iters=8, seed=0),
                comms=comms)
        finally:
            resilience.clear_faults()
        assert rep.degraded and rep.coverage < 1.0
        assert 0 in rep.dropped
        assert np.isfinite(np.asarray(centers)).all()
        sizes = np.bincount(np.asarray(labels), minlength=16)
        assert sizes.sum() == 2048


class TestShardedIvfBqMultiBit:
    def test_multibit_hadamard_build_search(self, clean_resilience):
        """The distributed build at bits=4 / Hadamard rotation: codes at
        the extended width, search recall through the no-refine estimate
        comparable to the single-host index on the same data."""
        import numpy as np
        from raft_tpu.comms import local_mesh
        from raft_tpu.comms.comms import Comms
        from raft_tpu.distributed import ivf_bq as dbq
        from raft_tpu.neighbors import brute_force, ivf_bq

        rng = np.random.default_rng(9)
        X = rng.standard_normal((2048, 24)).astype(np.float32)
        Q = rng.standard_normal((16, 24)).astype(np.float32)
        comms = Comms(local_mesh(8))
        idx = dbq.build(X, ivf_bq.IvfBqParams(
            n_lists=8, seed=0, bits=4, rotation_kind="hadamard"),
            comms=comms)
        assert idx.bits == 4 and idx.rotation_kind == "hadamard"
        rot_dim = idx.rot_dim
        assert idx.list_codes.shape[-1] == 4 * rot_dim // 8
        res = dbq.search(idx, Q, 5, n_probes=8)
        assert res.coverage == 1.0
        _, exact = brute_force.knn(Q, X, 5)
        got = np.asarray(res.indices)
        ex = np.asarray(exact)
        r = np.mean([len(set(got[i]) & set(ex[i])) / 5
                     for i in range(len(got))])
        # full-probe no-refine at 4 bits: the estimate itself must rank
        assert r >= 0.75
