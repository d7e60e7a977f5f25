"""Compile the IVF-Flat batch cell's window program for a described TPU
v5e, with no chip: what Mosaic or XLA would refuse at the cell's shapes
shows here for free.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_v5e.py

The program is ``ivf_flat._ragged_fused`` (coarse GEMM, device strip
planning, strip kernel, merge, finalize) for one 10k-query tile at 16
probes over 1,024 lists of up to 4,096 uint8 rows of 128. Its length
classes come from a skewed list-length draw with the configuration's mean
(1M / 1,024 rows); a real build's classes differ in their counts only.
Prints the compile time and ``memory_analysis()``.
"""

from __future__ import annotations

import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main() -> None:
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from raft_tpu.neighbors import ivf_flat
    from raft_tpu.ops import strip_scan as ss

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    n_rows, n_lists, dim, q, p, k, mls = 1_000_000, 1024, 128, 10_000, 16, \
        10, 4096
    rng = np.random.default_rng(0)
    lens = rng.gamma(2.0, n_rows / n_lists / 2.0, n_lists)
    lens = np.minimum(np.round(lens * n_rows / lens.sum()), mls).astype(int)
    classes, cls_ord = ss.class_info(lens, dim=dim)
    classes = tuple(classes)
    counts = ss.class_counts_of(cls_ord, len(classes))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    t = time.perf_counter()
    compiled = ivf_flat._ragged_fused.lower(
        arg((q, dim), jnp.float32), arg((n_lists, dim), jnp.float32),
        arg((n_lists, mls, dim), jnp.uint8), arg((n_lists, mls), jnp.float32),
        arg((n_lists, mls), jnp.int32), arg((n_lists,), jnp.int32),
        k=k, n_probes=p, metric="sqeuclidean", select_algo="exact",
        compute_dtype=None, classes=classes, class_counts=counts,
        interpret=False).compile()
    print(f"ivf_flat._ragged_fused q={q} p={p} classes={classes} "
          f"counts={counts}: compile_s={time.perf_counter() - t:.1f} "
          f"kernel={'tpu_custom_call' in compiled.as_text()}")
    print(compiled.memory_analysis())


if __name__ == "__main__":
    main()
