"""Pairwise distances — the hottest primitive in the framework.

Reference surface: cpp/include/raft/distance/distance_types.hpp:26-66 enumerates
the metrics; public entry points distance()/pairwise_distance() at
distance/distance-inl.cuh:67,238; tile/arch dispatch in
distance/detail/pairwise_matrix/dispatch-inl.cuh:69 (CUTLASS tensor cores on
SM80+); fusedL2NN (distance + per-row argmin, the k-means inner loop) at
distance/fused_l2_nn-inl.cuh:76.

TPU design — two regimes instead of one CUDA tile kernel family:

  * **Expanded (MXU) metrics** — anything expressible as f(x@y.T, row stats):
    sqeuclidean/euclidean, cosine, inner product, correlation, hellinger,
    jaccard (Tanimoto), dice, russellrao. One big gemm (bf16-in/fp32-accum
    optional via Resources.compute_dtype) + rank-1 corrections. This is the
    CUTLASS-path analog and where the FLOPs live.
  * **Elementwise (VPU) metrics** — l1, chebyshev, minkowski, canberra,
    braycurtis, hamming, jensenshannon, kl_divergence: tiled broadcast
    (tile_m, 1, k) vs (1, n, k) reductions, with the row-tile size picked from
    the Resources workspace budget (the chooseTileSize analog,
    neighbors/detail/knn_brute_force.cuh:78-91).

All functions are jit-compatible (static shapes, no Python branching on values).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu.core.resources import Resources, current_resources
from raft_tpu.utils.tiling import pad_and_tile

# Canonical metric names + aliases (mirrors DistanceType,
# distance/distance_types.hpp:26-66 and pylibraft's DISTANCE_TYPES table).
_ALIASES = {
    "l2": "sqeuclidean",
    "l2_expanded": "sqeuclidean",
    "l2_unexpanded": "sqeuclidean",
    "euclidean_expanded": "euclidean",
    "l2sqrt": "euclidean",
    "l2sqrtexpanded": "euclidean",
    "cityblock": "l1",
    "manhattan": "l1",
    "taxicab": "l1",
    "linf": "chebyshev",
    "lp": "minkowski",
    "ip": "inner_product",
    "dot": "inner_product",
    "kl": "kl_divergence",
    "kldivergence": "kl_divergence",
    "jensen-shannon": "jensenshannon",
}

EXPANDED_METRICS = frozenset(
    {
        "sqeuclidean",
        "euclidean",
        "cosine",
        "inner_product",
        "correlation",
        "hellinger",
        "jaccard",
        "dice",
        "russellrao",
    }
)
ELEMENTWISE_METRICS = frozenset(
    {
        "l1",
        "chebyshev",
        "minkowski",
        "canberra",
        "braycurtis",
        "hamming",
        "jensenshannon",
        "kl_divergence",
    }
)
ALL_METRICS = EXPANDED_METRICS | ELEMENTWISE_METRICS | {"haversine"}


def canonical_metric(metric: str) -> str:
    m = metric.lower()
    m = _ALIASES.get(m, m)
    if m not in ALL_METRICS:
        raise ValueError(f"unknown metric {metric!r}; supported: {sorted(ALL_METRICS)}")
    return m


@functools.partial(jax.jit, static_argnames=("axis",))
def sqnorm(x: jax.Array, axis: int = 1) -> jax.Array:
    """Row squared-L2 norms, squaring in fp32: fp16 inputs overflow and int8
    inputs wrap if squared in their own dtype before the fp32 accumulation.
    One fused program, so the fp32 copy is never materialized (eagerly, a
    4M-row uint8 list block's copy alone is 8 GB — PR 21's 4-chip run)."""
    xf = jnp.asarray(x).astype(jnp.float32)
    return jnp.sum(xf * xf, axis=axis)


def matmul_t(x: jax.Array, y: jax.Array, compute_dtype=None, precision=None) -> jax.Array:
    """x @ y.T with fp32 accumulation; optionally bf16 MXU inputs.

    The gemm every expanded metric rides on (CUTLASS-dispatch analog,
    distance/detail/pairwise_matrix/dispatch-inl.cuh:104). ``precision``
    follows jax.lax conventions: on TPU, fp32 inputs at "default" precision run
    single-pass bf16 on the MXU (fast, ~3 significant digits); "highest" runs
    the multi-pass fp32-accurate scheme. Primitive APIs (pairwise_distance,
    fused_l2_nn) default to "highest" — their contract is numerical accuracy;
    ANN search paths default to "default" — their contract is recall.
    """
    if jnp.issubdtype(x.dtype, jnp.integer) or jnp.issubdtype(y.dtype, jnp.integer):
        # integer datasets (uint8/int8 big-ann formats) against float
        # queries: upcast the integer operand — bf16 is exact for |v| <= 256
        target = compute_dtype or jnp.float32
        x = x.astype(target)
        y = y.astype(target)
        precision = None if compute_dtype is not None else precision
    elif compute_dtype is not None and x.dtype == jnp.float32 and compute_dtype != jnp.float32:
        x = x.astype(compute_dtype)
        y = y.astype(compute_dtype)
        precision = None
    return lax.dot_general(
        x,
        y,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision,
    )


# ---------------------------------------------------------------------------
# Expanded (gemm-based) metrics
# ---------------------------------------------------------------------------


def _expanded_distance(x, y, metric, compute_dtype, precision="highest"):
    ip = matmul_t(x, y, compute_dtype, precision)  # (m, n) fp32 accumulation
    if metric == "inner_product":
        return ip
    if metric in ("sqeuclidean", "euclidean"):
        xn = sqnorm(x)
        yn = sqnorm(y)
        d2 = xn[:, None] + yn[None, :] - 2.0 * ip
        d2 = jnp.maximum(d2, 0.0)
        return jnp.sqrt(d2) if metric == "euclidean" else d2
    if metric == "cosine":
        xn = jnp.sqrt(sqnorm(x))
        yn = jnp.sqrt(sqnorm(y))
        denom = jnp.maximum(xn[:, None] * yn[None, :], 1e-30)
        return 1.0 - ip / denom
    if metric == "correlation":
        xc = x - jnp.mean(x, axis=1, keepdims=True)
        yc = y - jnp.mean(y, axis=1, keepdims=True)
        return _expanded_distance(xc, yc, "cosine", compute_dtype, precision)
    if metric == "hellinger":
        # d = sqrt(1 - sum_i sqrt(x_i * y_i)) via gemm of sqrt-ed inputs
        # (reference hellinger is the "expanded" form too).
        sq_ip = matmul_t(jnp.sqrt(jnp.maximum(x, 0.0)), jnp.sqrt(jnp.maximum(y, 0.0)), compute_dtype, precision)
        return jnp.sqrt(jnp.maximum(1.0 - sq_ip, 0.0))
    if metric == "jaccard":
        # Generalized (Tanimoto): 1 - <x,y> / (|x|^2 + |y|^2 - <x,y>)
        xn = sqnorm(x)
        yn = sqnorm(y)
        denom = xn[:, None] + yn[None, :] - ip
        return 1.0 - jnp.where(denom > 0, ip / jnp.maximum(denom, 1e-30), 1.0)
    if metric == "dice":
        xs = jnp.sum(x, axis=1, dtype=jnp.float32)
        ys = jnp.sum(y, axis=1, dtype=jnp.float32)
        denom = xs[:, None] + ys[None, :]
        return 1.0 - jnp.where(denom > 0, 2.0 * ip / jnp.maximum(denom, 1e-30), 1.0)
    if metric == "russellrao":
        k = x.shape[1]
        return (k - ip) / k
    raise AssertionError(metric)


# ---------------------------------------------------------------------------
# Elementwise (tiled broadcast) metrics
# ---------------------------------------------------------------------------


def _elementwise_tile(xt, y, metric, p):
    """Distance of a row tile (tm,k) against all of y (n,k) → (tm,n)."""
    xt_ = xt[:, None, :]
    y_ = y[None, :, :]
    if metric == "l1":
        return jnp.sum(jnp.abs(xt_ - y_), axis=-1)
    if metric == "chebyshev":
        return jnp.max(jnp.abs(xt_ - y_), axis=-1)
    if metric == "minkowski":
        return jnp.sum(jnp.abs(xt_ - y_) ** p, axis=-1) ** (1.0 / p)
    if metric == "canberra":
        num = jnp.abs(xt_ - y_)
        den = jnp.abs(xt_) + jnp.abs(y_)
        return jnp.sum(jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0), axis=-1)
    if metric == "braycurtis":
        num = jnp.sum(jnp.abs(xt_ - y_), axis=-1)
        den = jnp.sum(jnp.abs(xt_ + y_), axis=-1)
        return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)
    if metric == "hamming":
        return jnp.mean((xt_ != y_).astype(jnp.float32), axis=-1)
    if metric == "jensenshannon":
        m = 0.5 * (xt_ + y_)
        safe = lambda a, b: jnp.where(a > 0, a * jnp.log(jnp.maximum(a, 1e-30) / jnp.maximum(b, 1e-30)), 0.0)
        js = 0.5 * jnp.sum(safe(xt_, m) + safe(y_, m), axis=-1)
        return jnp.sqrt(jnp.maximum(js, 0.0))
    if metric == "kl_divergence":
        safe = jnp.where(xt_ > 0, xt_ * jnp.log(jnp.maximum(xt_, 1e-30) / jnp.maximum(y_, 1e-30)), 0.0)
        return jnp.sum(safe, axis=-1)
    raise AssertionError(metric)


def _row_tile_size(n: int, k: int, workspace_bytes: int) -> int:
    """Pick a row-tile so tile_m*n*k fp32 intermediates fit the workspace budget
    (chooseTileSize analog, neighbors/detail/knn_brute_force.cuh:84)."""
    per_row = max(1, n * k * 4)
    tm = max(1, workspace_bytes // per_row)
    return min(tm, 4096)


def _tiled_elementwise(x, y, metric, p, workspace_bytes):
    m, k = x.shape
    n = y.shape[0]
    tm = _row_tile_size(n, k, workspace_bytes)
    if tm >= m:
        return _elementwise_tile(x, y, metric, p)
    tiles, n_tiles = pad_and_tile(x, tm)
    out = lax.map(lambda xt: _elementwise_tile(xt, y, metric, p), tiles)
    return out.reshape(n_tiles * tm, n)[:m]


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def haversine(x: jax.Array, y: jax.Array) -> jax.Array:
    """Great-circle distance between (lat, lon) radian pairs (reference
    spatial/knn/detail/haversine_distance.cuh)."""
    if x.shape[1] != 2 or y.shape[1] != 2:
        raise ValueError("haversine requires 2-d (lat, lon) inputs")
    lat1, lon1 = x[:, 0][:, None], x[:, 1][:, None]
    lat2, lon2 = y[:, 0][None, :], y[:, 1][None, :]
    sin_dlat = jnp.sin(0.5 * (lat2 - lat1))
    sin_dlon = jnp.sin(0.5 * (lon2 - lon1))
    a = sin_dlat**2 + jnp.cos(lat1) * jnp.cos(lat2) * sin_dlon**2
    return 2.0 * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


@functools.partial(jax.jit, static_argnames=("metric", "p", "workspace_bytes", "compute_dtype"))
def _pairwise_distance_impl(x, y, metric, p, workspace_bytes, compute_dtype):
    if metric == "haversine":
        return haversine(x, y)
    if metric in EXPANDED_METRICS:
        return _expanded_distance(x, y, metric, compute_dtype)
    return _tiled_elementwise(x, y, metric, p, workspace_bytes)


def pairwise_distance(
    x,
    y,
    metric: str = "sqeuclidean",
    p: float = 2.0,
    res: Optional[Resources] = None,
) -> jax.Array:
    """All-pairs distance matrix (m, n) between rows of x (m,k) and y (n,k).

    API analog of raft::distance::pairwise_distance
    (distance/distance-inl.cuh:238). ``metric`` accepts the canonical names in
    :data:`ALL_METRICS` plus common aliases ("l2", "cityblock", ...).
    """
    res = res or current_resources()
    metric = canonical_metric(metric)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if x.ndim != 2 or y.ndim != 2 or x.shape[1] != y.shape[1]:
        raise ValueError(f"shape mismatch: {x.shape} vs {y.shape}")
    compute_dtype = res.compute_dtype if metric in EXPANDED_METRICS else None
    return _pairwise_distance_impl(
        x, y, metric, float(p), int(res.workspace_bytes), compute_dtype
    )


@functools.partial(jax.jit, static_argnames=("sqrt", "tile_m", "precision"))
def _fused_l2_nn_impl(x, y, sqrt, tile_m, precision):
    m, k = x.shape
    yn = sqnorm(y)

    def one_tile(xt):
        ip = matmul_t(xt, y, precision=precision)
        xn = sqnorm(xt)
        d2 = jnp.maximum(xn[:, None] + yn[None, :] - 2.0 * ip, 0.0)
        idx = jnp.argmin(d2, axis=1).astype(jnp.int32)
        val = jnp.min(d2, axis=1)
        return val, idx

    if tile_m >= m:
        val, idx = one_tile(x)
    else:
        tiles, _ = pad_and_tile(x, tile_m)
        val, idx = lax.map(one_tile, tiles)
        val = val.reshape(-1)[:m]
        idx = idx.reshape(-1)[:m]
    if sqrt:
        val = jnp.sqrt(val)
    return val, idx


def fused_l2_nn_argmin(
    x,
    y,
    sqrt: bool = False,
    precision: str = "highest",
    res: Optional[Resources] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row nearest neighbor under L2: (min_dist, argmin) of shape (m,).

    Analog of fusedL2NN (distance/fused_l2_nn-inl.cuh:76,181) — the k-means
    assignment inner loop. The fusion here is XLA's: gemm + rank-1 correction +
    row argmin in one compiled program, tiled over query rows.
    """
    res = res or current_resources()
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    n, k = y.shape
    tm = max(1, min(int(res.workspace_bytes) // max(1, n * 4 * 4), 8192))
    return _fused_l2_nn_impl(x, y, bool(sqrt), tm, precision)
