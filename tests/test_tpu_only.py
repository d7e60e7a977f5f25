"""On-chip kernel validation (round-2 VERDICT Weak#6: every Pallas kernel
ran in interpret mode in CI; the round-1 VMEM fault, the qpl_cap drop bug
and the round-3 region-remap bug were all compiled-only failures).

Run on a machine with a TPU attached, through the chip tool:

    chiprun -- env RAFT_TPU_TEST_PLATFORM=tpu \
        python -m pytest tests/test_tpu_only.py -q

Skipped automatically everywhere else (conftest forces CPU by default).
"""

import numpy as np
import pytest

import jax

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs a TPU (set RAFT_TPU_TEST_PLATFORM=tpu on a TPU machine)",
)


def _overlap(a, b, k):
    a, b = np.asarray(a), np.asarray(b)
    return np.mean([len(set(a[r]) & set(b[r])) / k for r in range(a.shape[0])])


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    centers = rng.normal(scale=4.0, size=(128, 64)).astype(np.float32)
    assign = rng.integers(0, 128, 60_000)
    ds = centers[assign] + rng.normal(scale=1.0, size=(60_000, 64)).astype(np.float32)
    qs = centers[rng.integers(0, 128, 256)] + rng.normal(
        scale=1.0, size=(256, 64)).astype(np.float32)
    return ds, qs


class TestCompiledStrip:
    def test_flat_strip_matches_gather_multi_class(self, data):
        """Compiled kernel + device plan vs the fp32 gather oracle, with a
        skewed length distribution that exercises several length classes
        and the sub-block revisit path."""
        from raft_tpu.neighbors import ivf_flat

        ds, qs = data
        idx = ivf_flat.build(ds, ivf_flat.IvfFlatParams(
            n_lists=64, group_size=512))
        # bf16 MXU scores swap ids whose distances sit within ~0.4% of each
        # other at the k-boundary, so gate on CONTAINMENT in the oracle's
        # top-(k+5) instead of exact top-k set equality
        vg, ig = ivf_flat.search(idx, qs, 15, n_probes=16, backend="gather")
        vr, ir = ivf_flat.search(idx, qs, 10, n_probes=16, backend="ragged")
        contained = _overlap(ir, ig, 10)  # ir top-10 within ig top-15
        assert contained >= 0.98, contained

    def test_pq_strip_recall_on_chip(self, data):
        from raft_tpu import stats
        from raft_tpu.neighbors import brute_force, ivf_pq, refine

        ds, qs = data
        _, gt = brute_force.search(brute_force.build(ds), qs, 10)
        idx = ivf_pq.build(ds, ivf_pq.IvfPqParams(
            n_lists=64, pq_dim=32, group_size=512))
        # 32-wide fetch engages the tournament top-k path on chip (its
        # engagement window is 16 <= kf <= bs*_KEEP)
        _, cand = ivf_pq.search(idx, qs, 32, n_probes=16, backend="ragged")
        _, ids = refine.refine(ds, qs, cand, 10)
        assert float(stats.neighborhood_recall(ids, gt)) >= 0.9

    def test_big_k_boundary(self, data):
        """k near the strip cap (512) exercises the widest kernel outputs
        on the exact direct-extraction path (k=256 is above the tournament
        cap by design — exact searches must never take the lossy route);
        the tournament regime itself is covered by the PQ test's kf=32."""
        from raft_tpu.neighbors import ivf_flat

        ds, qs = data
        idx = ivf_flat.build(ds, ivf_flat.IvfFlatParams(
            n_lists=64, group_size=512))
        vg, ig = ivf_flat.search(idx, qs[:32], 256, n_probes=32,
                                 backend="gather")
        vr, ir = ivf_flat.search(idx, qs[:32], 256, n_probes=32,
                                 backend="ragged")
        assert _overlap(ig, ir, 256) >= 0.97

    def test_probe_skew_every_query_same_list(self, data):
        """Adversarial probe skew: identical queries force every pair onto
        one list — many strips for a single list, the q-chunk split path."""
        from raft_tpu.neighbors import ivf_flat

        ds, qs = data
        idx = ivf_flat.build(ds, ivf_flat.IvfFlatParams(
            n_lists=64, group_size=512))
        one = np.tile(qs[:1], (512, 1))
        vg, ig = ivf_flat.search(idx, one, 10, n_probes=4, backend="gather")
        vr, ir = ivf_flat.search(idx, one, 10, n_probes=4, backend="ragged")
        assert _overlap(ig, ir, 10) >= 0.98

    def test_pallas_lut_backend_on_chip(self, data):
        from raft_tpu.neighbors import ivf_pq

        ds, qs = data
        idx = ivf_pq.build(ds, ivf_pq.IvfPqParams(
            n_lists=64, pq_dim=32, group_size=128))
        vg, ig = ivf_pq.search(idx, qs, 10, n_probes=16, backend="gather")
        vp, ip = ivf_pq.search(idx, qs, 10, n_probes=16, backend="pallas")
        assert _overlap(ig, ip, 10) >= 0.95


@pytest.mark.parametrize("dtype,kf", [("uint8", 10), ("int8", 40)])
def test_strip_kernel_topk_routes_bit_identical(monkeypatch, dtype, kf):
    """The compiled strip kernel at a width both SIFT-1M cells scan
    (1024-row lists, dim 128), as two length-class calls writing one
    shared lane-dense candidate buffer: IVF-Flat's kf = 10 over uint8 rows
    and IVF-PQ's kf = 40 over int8 rows give the same values and columns,
    bit for bit, on the sorted top-kf route and forced onto the direct
    passes."""
    import jax.numpy as jnp

    from raft_tpu.ops import strip_scan as ss

    rng = np.random.default_rng(kf)
    n_lists, m, dim, s = 64, 1024, 128, 96
    assert ss.topk_route(kf, m) == "sorted"
    if dtype == "uint8":
        data = rng.integers(0, 256, (n_lists, m, dim)).astype(np.uint8)
    else:
        data = rng.integers(-127, 128, (n_lists, m, dim)).astype(np.int8)
    bias = (data.astype(np.float32) ** 2).sum(-1)
    lens = rng.integers(300, m + 1, n_lists)
    bias[np.arange(m)[None, :] >= lens[:, None]] = np.inf
    strip_list = rng.integers(0, n_lists, s).astype(np.int32)
    strip_list[s // 2 - 5:s // 2] = -1       # static-layout padding strips
    strip_list[-5:] = -1
    a = rng.normal(scale=8.0, size=(s, ss.C, dim))
    args = (jnp.asarray(strip_list), jnp.asarray(a, jnp.bfloat16),
            jnp.asarray(data), jnp.asarray(bias.reshape(n_lists, 1, m)))
    static = dict(w_blocks=m // ss.MC, n_sub=1, alpha=-2.0, kf=kf,
                  interpret=False)

    def run():      # a new trace, so the route is decided again
        call = jax.jit(ss._strip_class_call.__wrapped__,
                       static_argnames=tuple(static) + ("start", "count"))
        cand = call(*args, None, start=0, count=s // 2, **static)
        return call(*args, cand, start=s // 2, count=s // 2, **static)

    sv, se = run()
    monkeypatch.setattr(ss, "topk_route", lambda *a, **k: "direct")
    dv, de = run()
    assert sv.shape == (s + 1, kf, ss.C)
    real = strip_list >= 0
    sv, se = np.asarray(sv)[:-1][real], np.asarray(se)[:-1][real]
    assert np.array_equal(sv, np.asarray(dv)[:-1][real])
    assert np.array_equal(se, np.asarray(de)[:-1][real])
    assert np.isfinite(sv).all()
    assert ((se >= 0) & (se < lens[strip_list[real], None, None])).all()


@pytest.mark.parametrize("rows,n,k", [
    (10_000, 640, 40),     # IVF-PQ's merge
    (10_000, 160, 10),     # IVF-Flat's merge
    (10_000, 1024, 16),    # the cells' coarse top-16
])
def test_resident_topk_bit_identical_on_chip(rows, n, k):
    """The compiled resident top-k kernel at the SIFT-1M cells' shapes
    returns exactly iter_topk_min_packed's XLA passes' values and indices,
    bit for bit, with rows of +inf, -inf, NaN and exact ties."""
    import jax.numpy as jnp

    from raft_tpu.ops.select_k import (iter_topk_min_packed,
                                       resident_topk_min_packed)

    x = np.random.default_rng(n + k).normal(scale=100.0, size=(rows, n))
    x = x.astype(np.float32)
    x[0] = np.inf
    x[1, ::2] = -np.inf
    x[2, 3:40:3] = np.nan
    x[3, : n // 2] = 1.5
    x[4, ::3] = -2.25
    x[5, -k:] = np.inf
    x[-1, :7] = -3.5                     # ties in the last, partial block
    x = jnp.asarray(x)
    want = jax.jit(iter_topk_min_packed, static_argnums=1)(x, k)
    got = resident_topk_min_packed(x, k)
    assert np.array_equal(np.asarray(got[0]).view(np.int32),
                          np.asarray(want[0]).view(np.int32))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
