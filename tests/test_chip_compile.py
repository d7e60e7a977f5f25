"""Compile the main-path Pallas kernels for a described TPU v5e, no chip.

Interpret mode (every other kernel test here) cannot see what Mosaic
refuses on the chip: unaligned slices, blocks over the fast-memory limit.
These tests compile each kernel with ``interpret=False`` at real widths for
one chip of a described ``v5e:2x2`` topology, from shapes alone, and check
the program holds the kernel (``tpu_custom_call``). The kernel functions
are compiled directly: the public searches pick interpret mode from
``jax.default_backend()``, which is the CPU here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and a worker that
described it at import would change what every other worker collects.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from raft_tpu.ops import bq_scan, cagra_hop, pq_scan, select_k
from raft_tpu.ops import strip_scan as ss


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compile cannot be read back from the persistent
    # cache without the chip: keep this module's compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardings)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


N_LISTS, DIM, S = 1024, 128, 96   # strips per call: a bucketed class count


@pytest.mark.parametrize("dtype,w_blocks,n_sub,kf", [
    (jnp.uint8, 8, 1, 10),    # IVF-Flat over uint8 rows, 4096-row lists
    (jnp.uint8, 8, 2, 10),    # lists past the widest fetch: sub-blocks
    (jnp.int8, 1, 1, 40),     # IVF-PQ int8 decoded cache, 512-row lists
    # the sorted top-kf route at every list width the SIFT-1M cells have
    (jnp.uint8, 1, 1, 10),
    (jnp.uint8, 2, 1, 10),
    (jnp.uint8, 4, 1, 10),
    (jnp.int8, 2, 1, 40),
    (jnp.int8, 4, 1, 40),
    (jnp.int8, 8, 1, 40),     # 32 slabs, 32 levels: the largest network
])
def test_strip_kernel(one_chip, dtype, w_blocks, n_sub, kf):
    """Two classes of one tile: the first call allocates the lane-dense
    candidate buffers, the second writes its strips into them in place."""
    assert ss.topk_route(kf, w_blocks * ss.MC) == "sorted"
    m = w_blocks * ss.MC * n_sub
    call = functools.partial(ss._strip_class_call, w_blocks=w_blocks,
                             n_sub=n_sub, alpha=-2.0, kf=kf, interpret=False)

    def two_classes(strip_list, a_grouped, list_data, bias3):
        cand = call(strip_list, a_grouped, list_data, bias3, None, start=0,
                    count=S // 2)
        return call(strip_list, a_grouped, list_data, bias3, cand,
                    start=S // 2, count=S // 2)

    compiled = _compile(two_classes, one_chip,
                        ((S,), jnp.int32), ((S, ss.C, DIM), jnp.bfloat16),
                        ((N_LISTS, m, DIM), dtype),
                        ((N_LISTS, 1, m), jnp.float32))
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_paged_kernel(one_chip):
    page_rows, width, cap_pages, kf = 128, 64, 16384, 10
    ppf, n_sub, _ = ss.paged_plan(width, page_rows, DIM, kf)
    fn = functools.partial(ss._paged_class_call, ppf=ppf, n_sub=n_sub,
                           page_rows=page_rows, table_width=width,
                           alpha=-2.0, kf=kf, interpret=False)
    _compile(fn, one_chip,
             ((S,), jnp.int32), ((N_LISTS * width,), jnp.int32),
             ((N_LISTS,), jnp.int32), ((N_LISTS * n_sub,), jnp.int32),
             ((S, ss.C, DIM), jnp.bfloat16),
             ((cap_pages, page_rows, DIM), jnp.uint8),
             ((cap_pages, page_rows), jnp.float32))


def test_bq_kernel(one_chip):
    m, kf, nb = 4096, 40, DIM // 8
    fn = functools.partial(bq_scan._bq_class_call, cand=None, w_blocks=8,
                           n_sub=1, start=0, count=S, alpha=-2.0, kf=kf,
                           interpret=False)
    _compile(fn, one_chip,
             ((S,), jnp.int32), ((S, ss.C, DIM), jnp.bfloat16),
             ((N_LISTS, m, nb), jnp.uint8), ((N_LISTS, 1, m), jnp.float32),
             ((N_LISTS, 1, m), jnp.float32))


@pytest.mark.xfail(strict=True, reason="Mosaic refuses the code-page DMA: "
                   "a (page_rows, rot_dim/8 = 16) uint8 slice is not "
                   "aligned to the 128-lane tiling; paged_backend_auto "
                   "routes BQ stores to paged_jnp")
def test_paged_bq_kernel(one_chip):
    page_rows, width, cap_pages, kf, nb = 128, 64, 16384, 40, DIM // 8
    ppf, n_sub, _ = ss.paged_plan(width, page_rows, nb, kf)
    fn = functools.partial(bq_scan._paged_bq_class_call, ppf=ppf,
                           n_sub=n_sub, page_rows=page_rows,
                           table_width=width, alpha=-2.0, kf=kf,
                           interpret=False)
    _compile(fn, one_chip,
             ((S,), jnp.int32), ((N_LISTS * width,), jnp.int32),
             ((N_LISTS,), jnp.int32), ((N_LISTS * n_sub,), jnp.int32),
             ((S, ss.C, DIM), jnp.bfloat16),
             ((cap_pages, page_rows, nb), jnp.uint8),
             ((cap_pages, page_rows), jnp.float32),
             ((cap_pages, page_rows), jnp.float32))


@pytest.mark.parametrize("rows,n,k", [
    (10_000, 160, 10),     # IVF-Flat's merge: 16 probes × kf 10
    (10_000, 640, 40),     # IVF-PQ's merge: 16 probes × kf 40
    (10_000, 1024, 16),    # both cells' coarse top-16 of 1,024 lists
    (1000, 8192, 64),      # the packed route's widest row, largest k
])
def test_resident_topk_kernel(one_chip, rows, n, k):
    fn = functools.partial(select_k.resident_topk_min_packed, k=k,
                           interpret=False)
    _compile(fn, one_chip, ((rows, n), jnp.float32))


def test_lut_kernel(one_chip):
    lists, qpl, pq_dim, nc, m = 64, 64, 64, 256, 4096
    fn = functools.partial(pq_scan.pq_scan, nc=nc, interpret=False)
    _compile(fn, one_chip,
             ((lists, qpl, pq_dim * nc), jnp.bfloat16),
             ((lists, pq_dim, m), jnp.uint8), ((lists, m), jnp.float32))


@pytest.mark.xfail(strict=True, reason="Mosaic refuses the hop's batched "
                   "int8 contraction and its (q_block*w, deg) -> "
                   "(q_block, w*deg) relayout; CAGRA auto traversal "
                   "stays on the compressed loop")
def test_cagra_hop(one_chip):
    q, itopk, w, n, deg, p = 2048, 64, 4, 1_000_000, 64, 64
    fn = functools.partial(cagra_hop.fused_hop, q_block=32, interpret=False)
    _compile(fn, one_chip,
             ((q, itopk), jnp.int32), ((q, itopk), jnp.float32),
             ((q, itopk), jnp.float32), ((q, w), jnp.int32),
             ((q, p), jnp.float32), ((n, deg), jnp.int32),
             ((n, deg, p), jnp.int8))
