"""SIFT-shaped vectors, made on the device in one jitted call from a seed.

The recipe is that of ``raft_tpu.bench.datasets.sift_like`` (host numpy,
11 s for 1M rows), kept here so that the yardstick does not move with the
program:

  * a two-level mixture: Zipf-weighted coarse clusters (weight ~ rank^-0.7)
    with per-cluster, per-dimension spread, so cells overlap unevenly and
    populations are skewed like real descriptor data;
  * correlated dimensions: a random orthogonal basis with a decaying
    spectrum;
  * non-negative uint8 marginals: half-wave rectified, scaled so that the
    99.5th percentile lands at 110, clipped to 255.

Queries come from the same mixture. The one departure: the 99.5th
percentile is taken over the first ``PERCENTILE_ROWS`` rows rather than
over all of them (a sort of 129M floats buys nothing at this sample size).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PERCENTILE_ROWS = 65536


def key_of(seed: int):
    """A PRNG key for any non-negative seed, including those above 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "dim", "n_queries"))
def _make(key, n: int, dim: int, n_queries: int):
    total = n + n_queries
    n_coarse = max(64, min(4096, n // 256))
    k_assign, k_centers, k_spread, k_noise, k_basis = jax.random.split(key, 5)
    w = 1.0 / jnp.arange(1, n_coarse + 1, dtype=jnp.float32) ** 0.7
    assign = jax.random.categorical(k_assign, jnp.log(w), shape=(total,))
    centers = jax.random.normal(k_centers, (n_coarse, dim), jnp.float32) * 2.0
    spread = 0.5 + jax.random.uniform(k_spread, (n_coarse, dim),
                                      jnp.float32) * 1.5
    x = centers[assign] + jax.random.normal(
        k_noise, (total, dim), jnp.float32) * spread[assign]
    basis = jnp.linalg.qr(jax.random.normal(k_basis, (dim, dim),
                                            jnp.float32))[0]
    spectrum = 1.0 / jnp.sqrt(1.0 + jnp.arange(dim, dtype=jnp.float32) / 8.0)
    x = jnp.matmul(x, basis * spectrum[None, :],
                   precision=jax.lax.Precision.HIGHEST)
    x = jnp.maximum(x, 0.0)
    p = jnp.percentile(x[:min(total, PERCENTILE_ROWS)], 99.5)
    x = jnp.clip(x * (110.0 / jnp.maximum(p, 1e-6)), 0, 255).astype(jnp.uint8)
    return x[:n], x[n:]


def make(seed: int, n_rows: int, dim: int, n_queries: int):
    """(corpus uint8 (n_rows, dim), queries uint8 (n_queries, dim)) on the
    default device; the same seed gives the same arrays."""
    return _make(key_of(seed), int(n_rows), int(dim), int(n_queries))
