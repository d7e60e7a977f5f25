"""select_k tests — tier-1 oracle: exact match vs numpy sort (reference
cpp/test/matrix/ select_k algo×shape sweeps)."""

import numpy as np
import pytest

from raft_tpu.ops.select_k import merge_topk, select_k


@pytest.mark.parametrize("shape", [(1, 10), (7, 100), (32, 1000)])
@pytest.mark.parametrize("k", [1, 5, 10])
@pytest.mark.parametrize("select_min", [True, False])
def test_select_k_exact(shape, k, select_min, rng):
    x = rng.random(shape).astype(np.float32)
    vals, idx = select_k(x, k, select_min=select_min)
    order = np.argsort(x if select_min else -x, axis=1)[:, :k]
    want = np.take_along_axis(x, order, axis=1)
    np.testing.assert_allclose(np.sort(np.asarray(vals)), np.sort(want), rtol=1e-6)
    # selected values must match gathered indices
    np.testing.assert_allclose(
        np.asarray(vals), np.take_along_axis(x, np.asarray(idx), axis=1), rtol=1e-6
    )


def test_select_k_with_indices(rng):
    x = rng.random((4, 50)).astype(np.float32)
    ids = rng.integers(0, 10_000, (4, 50)).astype(np.int32)
    vals, idx = select_k(x, 3, indices=ids)
    pos = np.argsort(x, axis=1)[:, :3]
    np.testing.assert_array_equal(np.asarray(idx), np.take_along_axis(ids, pos, axis=1))


def test_select_k_1d(rng):
    x = rng.random(100).astype(np.float32)
    vals, idx = select_k(x, 5)
    assert vals.shape == (5,)
    np.testing.assert_allclose(np.asarray(vals), np.sort(x)[:5], rtol=1e-6)


def test_select_k_approx_recall(rng):
    # approx backend must hit its recall target on average
    x = rng.random((64, 2048)).astype(np.float32)
    vals, idx = select_k(x, 32, algo="approx", recall_target=0.9)
    true = np.argsort(x, axis=1)[:, :32]
    got = np.asarray(idx)
    recall = np.mean([len(set(got[i]) & set(true[i])) / 32 for i in range(64)])
    assert recall >= 0.85


def test_merge_topk(rng):
    a = rng.random((5, 4)).astype(np.float32)
    b = rng.random((5, 4)).astype(np.float32)
    ia = np.arange(4, dtype=np.int32)[None].repeat(5, 0)
    ib = (4 + np.arange(4, dtype=np.int32))[None].repeat(5, 0)
    vals, idx = merge_topk(a, ia, b, ib)
    cat = np.concatenate([a, b], axis=1)
    want = np.sort(cat, axis=1)[:, :4]
    np.testing.assert_allclose(np.asarray(vals), want, rtol=1e-6)


def test_select_k_errors():
    x = np.zeros((2, 5), np.float32)
    with pytest.raises(ValueError):
        select_k(x, 6)
    with pytest.raises(ValueError):
        select_k(x, 2, algo="bogus")


def _resident_case(n, k):
    """A (rows, n) input whose row count the kernel's row block does not
    divide, with rows of +inf, -inf, NaN and exact ties."""
    from raft_tpu.ops import select_k as sk
    from raft_tpu.ops.strip_scan import topk_route

    w = -(-n // 128) * 128
    block = sk._resident_rows(1 << 20, w if topk_route(k, w) == "sorted" else n)
    rows = block + block // 2 + 3
    assert rows % block
    x = np.random.default_rng(n * 100 + k).standard_normal((rows, n))
    x = (x * 100.0).astype(np.float32)
    x[0] = np.inf                        # nothing rankable
    x[1, ::2] = -np.inf
    x[2, 3] = np.nan
    x[2, 5:9] = np.nan
    x[3, : n // 2] = 1.5                 # half the row ties
    x[4, ::3] = -2.25                    # equal values in every lane bin
    x[5, -k:] = np.inf                   # +inf inside the top k
    x[6] = np.nan
    x[7] = -np.inf
    x[-1, :7] = -3.5                     # ties in the last, partial block
    return x


@pytest.mark.parametrize("n,k", [(n, k) for n in (160, 640, 1024, 8192)
                                 for k in (1, 10, 16, 40, 64)])
def test_resident_topk_bit_identical_to_packed_passes(n, k):
    """The VMEM-resident kernel (interpret mode) returns exactly
    iter_topk_min_packed's values and indices, bit for bit, at the widths
    of the SIFT-1M cells' merges (160, 640), their coarse select (1024)
    and the packed route's widest row (8192)."""
    import jax

    from raft_tpu.ops.select_k import (iter_topk_min_packed,
                                       resident_topk_min_packed)

    x = _resident_case(n, k)
    want = jax.jit(iter_topk_min_packed, static_argnums=1)(x, k)
    got = resident_topk_min_packed(x, k, interpret=True)
    assert np.array_equal(np.asarray(got[0]).view(np.int32),
                          np.asarray(want[0]).view(np.int32))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("n,k,resident", [
    (1024, 16, True), (100, 64, True), (1024, 65, False), (8193, 10, False)])
def test_packed_select_routes_to_resident_kernel_on_tpu(monkeypatch, n, k,
                                                        resident):
    """On TPU, ``select_k(algo="packed")`` runs the resident kernel where
    it fits (k ≤ 64, n ≤ 8192) and the XLA passes elsewhere, with the
    same answers as the CPU's route."""
    import jax

    from raft_tpu.ops import select_k as sk

    x = np.random.default_rng(n + k).standard_normal((20, n))
    want = select_k(x.astype(np.float32), k, algo="packed")
    calls = []
    real = sk.resident_topk_min_packed
    monkeypatch.setattr(sk, "resident_topk_min_packed", lambda v, kk: (
        calls.append(v.shape) or real(v, kk, interpret=True)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sk._select_k_impl.clear_cache()
    got = select_k(x.astype(np.float32), k, algo="packed")
    sk._select_k_impl.clear_cache()
    assert calls == ([(20, n)] if resident else [])
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
