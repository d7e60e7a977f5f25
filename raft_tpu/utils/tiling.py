"""Row-tiling helpers — the one place the pad/reshape pattern lives.

Every tiled algorithm (elementwise distances, fused L2 argmin, brute-force
search) pads its row dimension to a tile multiple and reshapes to
(n_tiles, tile, ...); centralized so budget fixes propagate (the memory-aware
tiling role of reference neighbors/detail/knn_brute_force.cuh:78-91).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_rows(x: jax.Array, multiple: int, fill=0) -> jax.Array:
    """Pad axis 0 up to the next multiple (no-op if already aligned)."""
    m = x.shape[0]
    pad = ceil_div(m, multiple) * multiple - m
    if pad == 0:
        return x
    pad_shape = (pad,) + x.shape[1:]
    return jnp.concatenate([x, jnp.full(pad_shape, fill, x.dtype)])


def pad_and_tile(x: jax.Array, tile: int, fill=0) -> Tuple[jax.Array, int]:
    """Pad axis 0 to a multiple of ``tile`` and reshape to
    (n_tiles, tile, *rest). Returns (tiles, n_tiles)."""
    xp = pad_rows(x, tile, fill)
    n_tiles = xp.shape[0] // tile
    return xp.reshape((n_tiles, tile) + x.shape[1:]), n_tiles


def map_row_tiles(fn, args: Tuple, tile: int, fills: Tuple = None,
                  min_tile: int = 128):
    """Run ``fn`` over row tiles of several same-leading-dim arrays and
    restitch the row dimension.

    ``fn`` takes a tuple of (tile, ...) blocks and returns an array or tuple
    of arrays with leading dim ``tile``. If the row count fits one tile, fn is
    called directly (no pad/reshape). ``fills`` optionally gives the padding
    value per arg (default 0 — searches that must ignore padded rows should
    pass sentinel fills, e.g. -1 for id arrays).

    When called EAGERLY (no argument is a tracer), the tile size is
    OOM-adaptive (ISSUE 3): a ``RESOURCE_EXHAUSTED`` dispatch retries at
    half the tile down to ``min_tile`` via ``resilience.degrade_on_oom``
    (the result is forced inside the attempt so the failure surfaces where
    it can be recovered). Under jit tracing the original single-dispatch
    path runs unchanged — recovery then belongs to the caller's host
    wrapper.
    """
    n = args[0].shape[0]
    if tile >= n:
        return fn(args)
    fills = fills or (0,) * len(args)

    def run(tile):
        n_tiles = ceil_div(n, tile)
        tiled = tuple(
            pad_and_tile(a, tile, fill)[0] for a, fill in zip(args, fills)
        )
        out = jax.lax.map(fn, tiled)
        def unstitch(o):
            return o.reshape((n_tiles * tile,) + o.shape[2:])[:n]
        return jax.tree.map(unstitch, out)

    if any(isinstance(a, jax.core.Tracer) for a in args):
        return run(tile)
    from raft_tpu.resilience import degrade_on_oom, force_completion

    def attempt(t):
        # wait for the device inside the executor: an async OOM that
        # surfaced later would escape its recovery scope
        return force_completion(run(t))

    return degrade_on_oom(attempt, tile,
                          floor=min(int(tile), max(1, int(min_tile))),
                          site="tiling.map_row_tiles")
