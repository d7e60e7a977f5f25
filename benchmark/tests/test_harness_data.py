"""The on-device SIFT-shaped generator: seeded, shaped as configured, and
no easier for IVF than the host recipe it copies."""

import numpy as np
import pytest

import run

data = run.load_module("data/sift_like.py")
ref = run.load_module("reference/exact_l2.py")
# 4 of 256 lists: the share of lists the cells probe (16 of 1,024)
N, NQ, N_LISTS, N_PROBES = 30000, 300, 256, 4


def test_seeded_and_shaped():
    a, qa = data.make(2 ** 33 + 1, 2000, 128, 50)
    b, _ = data.make(2 ** 33 + 1, 2000, 128, 50)
    c, _ = data.make(2 ** 33 + 2, 2000, 128, 50)
    assert a.shape == (2000, 128) and qa.shape == (50, 128)
    assert str(a.dtype) == "uint8"
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        data.make(-1, 10, 4, 2)


def _ivf_recall(corpus, queries):
    from raft_tpu.neighbors import ivf_flat

    idx = ivf_flat.build(corpus, ivf_flat.IvfFlatParams(n_lists=N_LISTS,
                                                        seed=0))
    _, ids = ivf_flat.search(idx, queries, 10, n_probes=N_PROBES)
    _, gt = ref.knn(corpus, queries, 10)
    ids = np.asarray(ids)
    return np.mean([len(set(ids[i]) & set(gt[i])) / 10
                    for i in range(ids.shape[0])])


def test_recall_regime_matches_the_host_recipe(tmp_path, monkeypatch):
    import jax.numpy as jnp

    from raft_tpu.bench import datasets

    monkeypatch.setenv("RAFT_TPU_DATA_DIR", str(tmp_path))
    host = _ivf_recall(*(jnp.asarray(x) for x in
                         datasets.sift_like(N, 128, NQ, seed=3)))
    dev = _ivf_recall(*data.make(3, N, 128, NQ))
    # the same regime: neither generator makes IVF's job much easier
    assert abs(dev - host) < 0.08, (dev, host)
    assert dev < 0.99
