"""Plain exact k-nearest-neighbour search under squared L2.

Straightforward ``jax.numpy`` at ``precision=HIGHEST``, in blocks of
queries and of corpus rows so that it fits beside nothing else on one chip.
It imports nothing of the program under test and takes nothing that the
program made: only the corpus and the queries, which the benchmark makes
from the seed.

Selection per corpus block is exact. The block's distances are cut into
groups of ``GROUP`` rows; the ``k`` groups with the smallest minimum hold
every one of the block's ``k`` nearest rows (a group whose minimum is below
the k-th distance holds one of the nearer rows, and there are fewer than
``k`` of those), so a top-k over those groups' rows gives the block's k
smallest distances. Ties at the k-th distance may name another row at the
same distance, which the comparison in ``check.py`` allows for by comparing
distances, not ids.

``input_dtype`` rounds the corpus and the queries to a lower precision
before the distances are taken (the control: ``float8_e4m3fn``); the
distances returned are then those of the rounded vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 128
HIGHEST = jax.lax.Precision.HIGHEST


def _rounded(x, input_dtype):
    x = x.astype(jnp.float32)
    if input_dtype is not None:
        x = x.astype(input_dtype).astype(jnp.float32)
    return x


@functools.partial(jax.jit, static_argnames=("k", "q_block", "n_block",
                                             "input_dtype"))
def _knn(corpus, queries, k, q_block, n_block, input_dtype):
    n, dim = corpus.shape
    q = queries.shape[0]
    n_pad = -(-n // n_block) * n_block
    q_pad = -(-q // q_block) * q_block
    x = jnp.pad(_rounded(corpus, input_dtype), ((0, n_pad - n), (0, 0)))
    xn = jnp.sum(x * x, axis=1)
    xn = jnp.where(jnp.arange(n_pad) < n, xn, jnp.inf)
    x = x.reshape(n_pad // n_block, n_block, dim)
    xn = xn.reshape(n_pad // n_block, n_block)
    qs = jnp.pad(_rounded(queries, input_dtype), ((0, q_pad - q), (0, 0)))
    qs = qs.reshape(q_pad // q_block, q_block, dim)
    n_groups = n_block // GROUP
    rows = jnp.arange(q_block)[:, None]

    def one_query_block(qb):
        qn = jnp.sum(qb * qb, axis=1)

        def one_corpus_block(carry, blk):
            best_d, best_i = carry
            xb, xnb, base = blk
            ip = jnp.matmul(qb, xb.T, precision=HIGHEST)
            d = jnp.maximum(qn[:, None] + xnb[None, :] - 2.0 * ip, 0.0)
            g = d.reshape(q_block, n_groups, GROUP)
            _, gi = jax.lax.top_k(-jnp.min(g, axis=2), k)        # (qb, k)
            cand = g[rows, gi].reshape(q_block, k * GROUP)
            cand_i = (gi[:, :, None] * GROUP
                      + jnp.arange(GROUP)[None, None, :]).reshape(
                          q_block, k * GROUP) + base
            nd, sel = jax.lax.top_k(-cand, k)
            ni = jnp.take_along_axis(cand_i, sel, axis=1)
            all_d = jnp.concatenate([best_d, -nd], axis=1)
            all_i = jnp.concatenate([best_i, ni], axis=1)
            md, msel = jax.lax.top_k(-all_d, k)
            return (-md, jnp.take_along_axis(all_i, msel, axis=1)), None

        init = (jnp.full((q_block, k), jnp.inf, jnp.float32),
                jnp.full((q_block, k), -1, jnp.int32))
        bases = jnp.arange(x.shape[0], dtype=jnp.int32) * n_block
        (d, i), _ = jax.lax.scan(one_corpus_block, init, (x, xn, bases))
        return d, i

    d, i = jax.lax.map(one_query_block, qs)
    return d.reshape(q_pad, k)[:q], i.reshape(q_pad, k)[:q]


def knn(corpus, queries, k: int, *, q_block: int = 512, n_block: int = 65536,
        input_dtype=None):
    """(distances (q, k) float32 ascending, ids (q, k) int32) of the exact
    k nearest corpus rows of each query, as numpy arrays."""
    n = corpus.shape[0]
    n_block = min(n_block, -(-n // GROUP) * GROUP)
    q_block = min(q_block, queries.shape[0])
    d, i = _knn(corpus, queries, int(k), int(q_block), int(n_block),
                input_dtype)
    return np.asarray(d), np.asarray(i)


@jax.jit
def _pair_dist(corpus, queries, qidx, ids):
    """Exact squared L2 of query ``qidx[r]`` to corpus rows ``ids[r, :]``
    (integer-valued inputs: every partial sum is exact in float32); ids
    outside the corpus read as inf."""
    n = corpus.shape[0]
    ok = (ids >= 0) & (ids < n)
    x = corpus[jnp.clip(ids, 0, n - 1)].astype(jnp.float32)  # (b, k, dim)
    qv = queries[qidx].astype(jnp.float32)[:, None, :]
    return jnp.where(ok, jnp.sum((x - qv) ** 2, axis=2), jnp.inf)


def pair_dist(corpus, queries, qidx, ids, *, block: int = 16384):
    """Exact squared L2 between query ``qidx[r]`` and each of ``ids[r]``
    (numpy (m, k) float32; inf for an id outside the corpus), in blocks of
    one shape."""
    qidx = np.asarray(qidx, np.int32)
    ids = np.asarray(ids, np.int32)
    m = ids.shape[0]
    out = np.empty(ids.shape, np.float32)
    for lo in range(0, m, block):
        hi = min(lo + block, m)
        qb = np.zeros(block, np.int32)
        ib = np.full((block, ids.shape[1]), -1, np.int32)
        qb[:hi - lo], ib[:hi - lo] = qidx[lo:hi], ids[lo:hi]
        out[lo:hi] = np.asarray(_pair_dist(corpus, queries, qb, ib))[:hi - lo]
    return out
