"""Microbench: where does a CAGRA search iteration spend time on this TPU?

Measures, per op, amortized wall-clock over back-to-back dispatches:
  - row gather (q, m) rows from (n, dim), fp32 vs int8
  - batched einsum distance on the gathered block
  - merge_topk_dedup at the search shapes
  - a full _search_impl call at several (width, itopk) points
"""
import time

import jax
import jax.numpy as jnp

from raft_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache()


def timeit(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    n, dim, q = 1_000_000, 128, 2000
    key = jax.random.key(0)
    k1, k2, k3 = jax.random.split(key, 3)
    X = jax.random.normal(k1, (n, dim), jnp.float32)
    X8 = (X * 10).astype(jnp.int8)
    Q = jax.random.normal(k2, (q, dim), jnp.float32)
    norms = jnp.sum(X * X, axis=1)

    for m in (64, 256, 1024):
        ids = jax.random.randint(k3, (q, m), 0, n, dtype=jnp.int32)

        @jax.jit
        def gather_f32(ids):
            return X[ids]

        @jax.jit
        def gather_i8(ids):
            return X8[ids]

        @jax.jit
        def gather_dist(ids):
            xv = X[ids]
            ip = jnp.einsum("qmd,qd->qm", xv, Q)
            return norms[ids] - 2 * ip

        @jax.jit
        def gather_dist_i8(ids):
            xv = X8[ids].astype(jnp.bfloat16)
            ip = jnp.einsum("qmd,qd->qm", xv, Q.astype(jnp.bfloat16))
            return norms[ids] - 2 * ip.astype(jnp.float32)

        @jax.jit
        def onehot_dist(ids):
            # no-gather variant: distances via flat take on X reshaped? same gather.
            return None

        print(f"m={m:5d} gather_f32 {timeit(gather_f32, ids)*1e3:8.2f} ms", flush=True)
        print(f"m={m:5d} gather_i8  {timeit(gather_i8, ids)*1e3:8.2f} ms", flush=True)
        print(f"m={m:5d} gath+dist  {timeit(gather_dist, ids)*1e3:8.2f} ms", flush=True)
        print(f"m={m:5d} gath+d_i8  {timeit(gather_dist_i8, ids)*1e3:8.2f} ms", flush=True)

    # merge at search shapes
    from raft_tpu.ops.segment import merge_topk_dedup

    itopk = 64
    for b in (64, 256):
        ids0 = jax.random.randint(k1, (q, itopk), 0, n, dtype=jnp.int32)
        d0 = jax.random.uniform(k1, (q, itopk))
        cids = jax.random.randint(k2, (q, b), 0, n, dtype=jnp.int32)
        cd = jax.random.uniform(k2, (q, b))

        @jax.jit
        def merge(ids0, d0, cids, cd):
            return merge_topk_dedup(ids0, d0, cids, cd, itopk,
                                    payload=jnp.zeros((q, itopk), jnp.bool_),
                                    cand_payload=jnp.zeros(cids.shape, jnp.bool_))

        print(f"b={b:5d} merge      {timeit(merge, ids0, d0, cids, cd)*1e3:8.2f} ms", flush=True)

    # full search at 100k (bench shape) and 1M
    from raft_tpu.neighbors import cagra

    for nn in (100_000,):
        Xs = X[:nn]
        # cheap graph: random (bench measures search speed, recall irrelevant here)
        g = jax.random.randint(k3, (nn, 32), 0, nn, dtype=jnp.int32)
        idx = cagra.CagraIndex(Xs, g, jnp.sum(Xs * Xs, axis=1))
        for width, itopk in ((1, 64), (4, 64), (8, 64)):
            p = cagra.CagraSearchParams(itopk_size=itopk, search_width=width)
            dt = timeit(lambda: cagra.search(idx, Q, 10, p), reps=5)
            print(f"n={nn} w={width} itopk={itopk} search {dt*1e3:8.2f} ms "
                  f"({q/dt:,.0f} QPS)", flush=True)


if __name__ == "__main__":
    main()
