"""Version shims for jax API moves.

The installed jax (0.9 series, pinned in pyproject.toml) has every API in
its current home; these one-line wrappers keep the call sites' spelling in
one place.
"""

from __future__ import annotations

import jax
from jax import lax


def axis_size(axis: str) -> int:
    return lax.axis_size(axis)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
