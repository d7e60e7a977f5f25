"""Filtered & hybrid search: push-down, widening, zero-recompile, fusion.

Round-19 acceptance gates (ISSUE 19):

* ``Bitset.popcount``/``pass_rate`` exact against numpy over random masks
  (tail-bit handling included — ``create(default=True)`` sets tail bits).
* Filtered search equals the post-filter reference at equal over-fetch,
  property-tested over random masks INCLUDING all-pass, all-fail and
  per-list-dead (an entire probed list masked out — the sub-block skip
  path); paged pallas(interpret) and jnp backends bit-identical under
  filters.
* Selectivity-aware widening recovers recall at ~1% selectivity without
  the caller touching ``n_probes``.
* Filter-mask mutation at fixed length causes ZERO retraces
  (``serving.scan_trace_count()`` deltas).
* The three ``ivf_*.search.filter`` faultpoints classify when armed and
  recover clean (the faultpoint-contract arming side for the new sites).
* Hybrid dense+sparse fusion: hashed projection parity (CSR vs dense),
  fused self-recall, filter pass-through, metric guard.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import resilience, serving
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import _filtering, hybrid, ivf_bq, ivf_flat, ivf_pq


@pytest.fixture(autouse=True)
def _disarm():
    resilience.clear_faults()
    yield
    resilience.clear_faults()


def _data(rng, n=600, dim=16, q=8):
    return (rng.normal(size=(n, dim)).astype(np.float32),
            rng.normal(size=(q, dim)).astype(np.float32))


#: the six IVF search entries, which share one prologue
#: (neighbors/_entry.prepare)
ENTRIES = ["ivf_flat.search", "ivf_flat.search_paged", "ivf_pq.search",
           "ivf_pq.search_paged", "ivf_bq.search", "ivf_bq.search_paged"]
_FAMILY = {"ivf_flat": ivf_flat, "ivf_pq": ivf_pq, "ivf_bq": ivf_bq}


def _entry(rng, name, metric="sqeuclidean"):
    """``(search, target, X, Q)`` for one of :data:`ENTRIES`: ``search(
    target, queries, k, ...)`` over a small index, or over the paged store
    wrapping it."""
    family, entry = name.split(".")
    params = {"ivf_flat": ivf_flat.IvfFlatParams(n_lists=8, metric=metric),
              "ivf_pq": ivf_pq.IvfPqParams(n_lists=8, pq_dim=8,
                                           metric=metric),
              "ivf_bq": ivf_bq.IvfBqParams(n_lists=8, metric=metric)}[family]
    # the shapes of _data's other callers here, so builds share compiles
    X, Q = _data(rng)
    target = _FAMILY[family].build(X, params)
    if entry == "search_paged":
        target = serving.PagedListStore.from_index(target)
    return getattr(_FAMILY[family], entry), target, X, Q


# ---------------------------------------------------------------------------
# bitset: popcount / pass_rate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_bits", [1, 31, 32, 33, 64, 257, 4096])
def test_popcount_matches_numpy(rng, n_bits):
    mask = rng.random(n_bits) < 0.37
    b = Bitset.from_mask(mask)
    assert int(b.popcount()) == int(mask.sum())
    assert b.pass_rate() == pytest.approx(float(mask.mean()))


def test_popcount_tail_bits_create_default_true():
    """create(default=True) fills the last word's unused tail bits;
    popcount must not count them."""
    for n_bits in (1, 33, 95, 129):
        assert int(Bitset.create(n_bits, default=True).popcount()) == n_bits
        assert Bitset.create(n_bits, default=True).pass_rate() == 1.0
        assert int(Bitset.create(n_bits, default=False).popcount()) == 0


def test_pass_rate_cached_per_instance(rng):
    b = Bitset.from_mask(rng.random(1000) < 0.5)
    r1 = b.pass_rate()
    assert b.pass_rate() == r1  # second call hits the host cache
    assert getattr(b, "_pass_rate_cache") == r1


# ---------------------------------------------------------------------------
# widen_plan
# ---------------------------------------------------------------------------

def test_widen_plan_identity_without_filter():
    assert _filtering.widen_plan(None, 10, 64) == (10, None, 1.0, 1.0)
    np_eff, kf_eff, rate, widen = _filtering.widen_plan(
        None, 10, 64, k_fetch=40, k_cap=512)
    assert (np_eff, kf_eff, rate, widen) == (10, 40, 1.0, 1.0)


def test_widen_plan_scales_and_clamps(rng):
    # 10% pass rate -> ~10x widen, capped at max_widen
    b = Bitset.from_mask(np.arange(1000) < 100)
    np_eff, kf_eff, rate, widen = _filtering.widen_plan(
        b, 8, 64, k_fetch=40, k_cap=512, max_widen=8.0)
    assert rate == pytest.approx(0.1)
    assert widen == pytest.approx(8.0)  # 1/0.1 = 10 capped at 8
    assert np_eff == 64  # ceil(8*8)=64 == n_lists clamp
    assert kf_eff == min(512, int(np.ceil(40 * 8.0)))
    # all-fail mask: widen hits the cap, never 1/0
    empty = Bitset.from_mask(np.zeros(100, bool))
    np_eff, _, rate, widen = _filtering.widen_plan(empty, 4, 16,
                                                   max_widen=6.0)
    assert rate == 0.0 and widen == 6.0 and np_eff == 16
    # all-pass mask: identity plan
    full = Bitset.from_mask(np.ones(100, bool))
    assert _filtering.widen_plan(full, 4, 16)[0] == 4


def test_widen_plan_env_cap(monkeypatch):
    b = Bitset.from_mask(np.arange(1000) < 10)  # 1% pass
    monkeypatch.setenv(_filtering.FILTER_MAX_WIDEN_ENV, "3")
    assert _filtering.widen_plan(b, 4, 1024)[3] == pytest.approx(3.0)
    monkeypatch.delenv(_filtering.FILTER_MAX_WIDEN_ENV)
    assert _filtering.widen_plan(b, 4, 1024)[3] == pytest.approx(8.0)


def test_apply_filter_bias_rules(rng):
    b = Bitset.from_mask(np.array([True, False, True, False]))
    ids = jnp.asarray([0, 1, 2, 3, -1, 7], jnp.int32)
    bias = jnp.asarray([1.0, 2.0, 3.0, 4.0, np.inf, 5.0], jnp.float32)
    out = np.asarray(_filtering.apply_filter_bias(bias, ids, b))
    np.testing.assert_array_equal(
        out, [1.0, np.inf, 3.0, np.inf, np.inf, np.inf])
    # id 7 is beyond the mask -> excluded; padding (-1) stays dead
    assert _filtering.apply_filter_bias(bias, ids, None) is bias


# ---------------------------------------------------------------------------
# filtered == post-filter reference at equal over-fetch
# ---------------------------------------------------------------------------

def _post_filter_reference(index_search, idx, Q, k, n_probes, mask):
    """The two-pass baseline: unfiltered scan at the SAME effective
    over-fetch, drop failing ids on the host, truncate to k."""
    kf = min(int(np.asarray(mask).sum()) + 1, 512)
    kf = max(kf, k)
    v, i = index_search(idx, Q, kf, n_probes=n_probes)
    v, i = np.asarray(v), np.asarray(i)
    out_v = np.full((Q.shape[0], k), np.inf, np.float32)
    out_i = np.full((Q.shape[0], k), -1, np.int64)
    for r in range(Q.shape[0]):
        keep = [(v[r, c], i[r, c]) for c in range(kf)
                if i[r, c] >= 0 and np.isfinite(v[r, c])
                and mask[i[r, c]]]
        for c, (vv, ii) in enumerate(keep[:k]):
            out_v[r, c], out_i[r, c] = vv, ii
    return out_v, out_i


def _masks(rng, n, n_dead_list_rows=None):
    cases = {
        "random50": rng.random(n) < 0.5,
        "all_pass": np.ones(n, bool),
        "all_fail": np.zeros(n, bool),
    }
    if n_dead_list_rows is not None:
        m = np.ones(n, bool)
        m[n_dead_list_rows] = False
        cases["list_dead"] = m
    return cases


@pytest.mark.parametrize("family,params", [
    (ivf_flat, ivf_flat.IvfFlatParams(n_lists=8)),
    (ivf_pq, ivf_pq.IvfPqParams(n_lists=8, pq_dim=8)),
    (ivf_bq, ivf_bq.IvfBqParams(n_lists=8)),
])
def test_filtered_matches_post_filter_reference(rng, family, params):
    """Exhaustive probing (n_probes=n_lists) + equal over-fetch: the
    kernel-filtered scan must return exactly what post-filtering the
    unfiltered scan returns — including the all-pass and all-fail
    extremes and a fully-dead list (the sub-block skip path)."""
    X, Q = _data(rng, n=400)
    idx = family.build(X, params)
    # kill every row of one list -> at least one fully-dead probed list
    ids0 = np.asarray(idx.list_ids[0])
    dead_rows = ids0[ids0 >= 0]
    k = 10
    for name, mask in _masks(rng, X.shape[0], dead_rows).items():
        ref_v, ref_i = _post_filter_reference(
            family.search, idx, Q, k, idx.n_lists, mask)
        v, i = family.search(idx, Q, k, n_probes=idx.n_lists,
                             filter=Bitset.from_mask(mask))
        v, i = np.asarray(v), np.asarray(i)
        fin = np.isfinite(ref_v)
        np.testing.assert_array_equal(i[fin], ref_i[fin], err_msg=name)
        np.testing.assert_allclose(v[fin], ref_v[fin], rtol=1e-5,
                                   err_msg=name)
        assert not np.isfinite(v[~fin]).any(), name
        if name == "all_fail":
            assert not np.isfinite(v).any()


def test_filtered_paged_backends_bit_identical(rng):
    """paged_pallas (interpret on CPU) vs paged_jnp under every mask
    class — the sub_live DMA-skip machinery must not change a single
    bit relative to the reference backend."""
    X, Q = _data(rng, n=512)
    idx = ivf_flat.build(X, ivf_flat.IvfFlatParams(n_lists=4))
    store = serving.PagedListStore.from_index(idx)
    ids0 = np.asarray(idx.list_ids[0])
    for name, mask in _masks(rng, X.shape[0], ids0[ids0 >= 0]).items():
        f = Bitset.from_mask(mask)
        vj, ij = ivf_flat.search_paged(store, Q, 8, n_probes=4,
                                       filter=f, backend="paged_jnp")
        vp, ip = ivf_flat.search_paged(store, Q, 8, n_probes=4,
                                       filter=f, backend="paged_pallas")
        np.testing.assert_array_equal(np.asarray(ij), np.asarray(ip),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(vj), np.asarray(vp),
                                      err_msg=name)


def test_filtered_paged_bq_backends_bit_identical(rng):
    X, Q = _data(rng, n=512)
    idx = ivf_bq.build(X, ivf_bq.IvfBqParams(n_lists=4))
    store = serving.PagedListStore.from_index(idx)
    mask = rng.random(X.shape[0]) < 0.3
    f = Bitset.from_mask(mask)
    vj, ij = ivf_bq.search_paged(store, Q, 8, n_probes=4, filter=f,
                                 backend="paged_jnp")
    vp, ip = ivf_bq.search_paged(store, Q, 8, n_probes=4, filter=f,
                                 backend="paged_pallas")
    np.testing.assert_array_equal(np.asarray(ij), np.asarray(ip))
    np.testing.assert_array_equal(np.asarray(vj), np.asarray(vp))


# ---------------------------------------------------------------------------
# selectivity-aware widening: recall at ~1% selectivity
# ---------------------------------------------------------------------------

def test_widening_recovers_selective_recall(rng):
    """At ~2% selectivity with default n_probes, the un-widened plan
    would probe too few lists to return k survivors; the automatic
    widening must hold recall >= 0.95 against brute force over the
    surviving rows — without the caller touching n_probes."""
    X, Q = _data(rng, n=2000, dim=16, q=16)
    idx = ivf_flat.build(X, ivf_flat.IvfFlatParams(n_lists=32))
    mask = rng.random(X.shape[0]) < 0.02
    mask[:5] = True  # ensure >= k survivors
    k = 5
    surv = np.flatnonzero(mask)
    d2 = ((Q[:, None, :] - X[surv][None, :, :]) ** 2).sum(-1)
    gt = surv[np.argsort(d2, axis=1)[:, :k]]
    v, i = ivf_flat.search(idx, Q, k, n_probes=4,
                           filter=Bitset.from_mask(mask))
    i = np.asarray(i)
    recall = np.mean([len(set(i[r]) & set(gt[r])) / k
                      for r in range(Q.shape[0])])
    assert recall >= 0.95, recall
    assert mask[i[np.isfinite(np.asarray(v))]].all()


@pytest.mark.parametrize("entry", ENTRIES)
def test_widening_stamped_on_span(rng, entry):
    from raft_tpu import obs

    search, target, X, Q = _entry(rng, entry)
    f = Bitset.from_mask(rng.random(X.shape[0]) < 0.25)
    obs.reset()
    obs.clear_spans()
    obs.enable()
    try:
        search(target, Q, 5, n_probes=4, filter=f)
        spans = [s for s in obs.spans()
                 if "filter_pass_rate" in (s.get("attrs") or {})]
        assert spans, "no span carried the filter plan"
        a = spans[-1]["attrs"]
        assert a["filter_pass_rate"] == pytest.approx(0.25, abs=0.1)
        assert a["filter_widen_x"] > 1.0
        assert a["filter_n_probes"] >= 4
    finally:
        obs.disable()
        obs.reset()
        obs.clear_spans()


def test_estimate_search_models_widening(rng):
    from raft_tpu.obs import costmodel

    X, _ = _data(rng, n=400)
    idx = ivf_flat.build(X, ivf_flat.IvfFlatParams(n_lists=16))
    f = Bitset.from_mask(rng.random(X.shape[0]) < 0.05)
    base = costmodel.estimate_search(idx, q=8, k=5, n_probes=2)
    wide = costmodel.estimate_search(idx, q=8, k=5, n_probes=2, filter=f)
    assert wide["workspace_bytes"] > base["workspace_bytes"]


def test_search_refined_widens_k_fetch(rng):
    """ivf_bq.search_refined at low selectivity: the widened over-fetch
    must keep refined recall against brute force over survivors."""
    X, Q = _data(rng, n=1500, q=8)
    idx = ivf_bq.build(X, ivf_bq.IvfBqParams(n_lists=8))
    mask = rng.random(X.shape[0]) < 0.05
    mask[:5] = True
    k = 5
    surv = np.flatnonzero(mask)
    d2 = ((Q[:, None, :] - X[surv][None, :, :]) ** 2).sum(-1)
    gt = surv[np.argsort(d2, axis=1)[:, :k]]
    v, i = ivf_bq.search_refined(idx, X, Q, k, n_probes=8, refine_ratio=2,
                                 filter=Bitset.from_mask(mask))
    i = np.asarray(i)
    recall = np.mean([len(set(i[r]) & set(gt[r])) / k
                      for r in range(Q.shape[0])])
    assert recall >= 0.9, recall


# ---------------------------------------------------------------------------
# store.set_filter + zero-recompile contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", [e for e in ENTRIES if "paged" in e])
def test_store_set_filter_zero_recompile(rng, entry):
    _, store, X, Q = _entry(rng, entry)
    serving.search(store, Q, 5, n_probes=8)  # warm the unfiltered program
    t0 = serving.scan_trace_count()
    store.set_filter(np.arange(X.shape[0]) % 3 == 0)
    v, i = serving.search(store, Q, 5, n_probes=8)
    assert (np.asarray(i)[np.isfinite(np.asarray(v))] % 3 == 0).all()
    t1 = serving.scan_trace_count()  # None -> Bitset: one retrace allowed
    for r in (1, 2):
        store.set_filter(np.arange(X.shape[0]) % 3 == r)
        v, i = serving.search(store, Q, 5, n_probes=8)
        assert (np.asarray(i)[np.isfinite(np.asarray(v))] % 3 == r).all()
    assert serving.scan_trace_count() == t1, \
        "mask-content mutation recompiled the scan"
    assert t1 - t0 <= 1
    # per-call filter takes precedence over the standing one
    f = Bitset.from_mask(np.arange(X.shape[0]) % 3 == 2)
    v, i = serving.search(store, Q, 5, n_probes=8, filter=f)
    assert (np.asarray(i)[np.isfinite(np.asarray(v))] % 3 == 2).all()
    # clearing restores unfiltered behavior
    store.set_filter(None)
    v, i = serving.search(store, Q, 5, n_probes=8)
    fin = np.isfinite(np.asarray(v))
    assert not (np.asarray(i)[fin] % 3 == 0).all()


# ---------------------------------------------------------------------------
# the shared prologue: validation and cosine normalisation, all six entries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", ENTRIES)
def test_entry_rejects_bad_queries_and_k(rng, entry):
    search, target, _, Q = _entry(rng, entry)
    with pytest.raises(ValueError, match="queries must be"):
        search(target, Q[:, :5], 5)
    with pytest.raises(ValueError, match="queries must be"):
        search(target, Q[0], 5)
    for k in (0, 10 ** 6):
        with pytest.raises(ValueError, match=f"k={k} out of range"):
            search(target, Q, k, n_probes=4)


@pytest.mark.parametrize("entry", ENTRIES)
def test_entry_normalises_cosine_queries(rng, entry):
    """A cosine index answers a query and its double alike: the prologue
    scales every query to unit norm (by powers of two, exactly)."""
    search, target, _, Q = _entry(rng, entry, metric="cosine")
    v1, i1 = search(target, Q, 5, n_probes=4)
    v2, i2 = search(target, 2.0 * Q, 5, n_probes=4)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


# ---------------------------------------------------------------------------
# faultpoints: ivf_*.search.filter armed + recovered (tier-1 contract)
# ---------------------------------------------------------------------------

def _filter_faultpoint(rng, family, params):
    # caller arms the literal ``ivf_<fam>.search.filter`` site first
    # (literal so the faultpoint-contract rule resolves the pairing)
    X, Q = _data(rng)
    idx = family.build(X, params)
    f = Bitset.from_mask(rng.random(X.shape[0]) < 0.5)
    with pytest.raises(Exception) as ei:
        family.search(idx, Q, 5, n_probes=4, filter=f)
    assert resilience.classify(ei.value) == resilience.TRANSIENT
    resilience.clear_faults()
    v, i = family.search(idx, Q, 5, n_probes=4, filter=f)
    assert np.asarray(i).shape == (Q.shape[0], 5)
    # the site only fires on the filtered plan path
    family.search(idx, Q, 5, n_probes=4)


def test_ivf_flat_search_filter_faultpoint(rng):
    resilience.arm_faults("ivf_flat.search.filter=transient:1")
    _filter_faultpoint(rng, ivf_flat, ivf_flat.IvfFlatParams(n_lists=8))


def test_ivf_pq_search_filter_faultpoint(rng):
    resilience.arm_faults("ivf_pq.search.filter=transient:1")
    _filter_faultpoint(rng, ivf_pq,
                       ivf_pq.IvfPqParams(n_lists=8, pq_dim=8))


def test_ivf_bq_search_filter_faultpoint(rng):
    resilience.arm_faults("ivf_bq.search.filter=transient:1")
    _filter_faultpoint(rng, ivf_bq, ivf_bq.IvfBqParams(n_lists=8))


# ---------------------------------------------------------------------------
# hybrid dense+sparse fusion
# ---------------------------------------------------------------------------

def _hybrid_data(rng, n=1200, dim=24, vocab=400, q=8):
    dense = rng.normal(size=(n, dim)).astype(np.float32)
    sp = ((rng.random((n, vocab)) < 0.02)
          * rng.random((n, vocab))).astype(np.float32)
    return dense, sp, dense[:q].copy(), sp[:q].copy()


def test_hybrid_projection_csr_dense_parity(rng):
    from raft_tpu.sparse.types import csr_from_dense

    _, sp, _, _ = _hybrid_data(rng, n=60)
    p_dense = hybrid.project_sparse(sp, 128)
    p_csr = hybrid.project_sparse(csr_from_dense(sp), 128)
    np.testing.assert_array_equal(np.asarray(p_dense), np.asarray(p_csr))
    assert p_dense.shape == (60, 128)


def test_hybrid_projection_preserves_inner_product(rng):
    _, sp, _, _ = _hybrid_data(rng, n=150)
    p = np.asarray(hybrid.project_sparse(sp, 256))
    est, exact = p @ p.T, sp @ sp.T
    corr = np.corrcoef(est.ravel(), exact.ravel())[0, 1]
    assert corr > 0.6, corr  # unbiased up to collision noise


def test_hybrid_build_search_self_recall(rng):
    dense, sp, qd, qs = _hybrid_data(rng)
    h = hybrid.build(dense, sp,
                     ivf_bq.IvfBqParams(n_lists=16,
                                        metric="inner_product"),
                     sparse_dim=128)
    assert h.dim == dense.shape[1] + 128
    v, i = hybrid.search(h, qd, qs, k=5, n_probes=16)
    assert (np.asarray(i)[:, 0] == np.arange(qd.shape[0])).mean() >= 0.9


def test_hybrid_filter_passthrough(rng):
    dense, sp, qd, qs = _hybrid_data(rng)
    h = hybrid.build(dense, sp, sparse_dim=64)
    mask = np.arange(dense.shape[0]) % 2 == 0
    v, i = hybrid.search(h, qd, qs, k=5, n_probes=16,
                         filter=Bitset.from_mask(mask))
    assert (np.asarray(i)[np.isfinite(np.asarray(v))] % 2 == 0).all()


def test_hybrid_rejects_non_inner_product(rng):
    dense, sp, _, _ = _hybrid_data(rng, n=200)
    with pytest.raises(ValueError, match="inner_product"):
        hybrid.build(dense, sp,
                     ivf_bq.IvfBqParams(n_lists=8, metric="sqeuclidean"))


def test_hybrid_serving_store_roundtrip(rng):
    dense, sp, qd, qs = _hybrid_data(rng)
    h = hybrid.build(dense, sp, sparse_dim=64)
    store = hybrid.to_store(h)
    fused_q = hybrid.fuse_queries(h, qd, qs)
    vs, is_ = serving.search(store, fused_q, 5, n_probes=16)
    vp, ip = hybrid.search(h, qd, qs, k=5, n_probes=16)
    # paged store over the packed rows: same top-1 (scan parity contract)
    assert (np.asarray(is_)[:, 0] == np.asarray(ip)[:, 0]).mean() >= 0.9
