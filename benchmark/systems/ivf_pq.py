"""IVF-PQ with exact re-ranking through the library's public entry points:
``neighbors.ivf_pq.search`` for ``k_fetch`` candidates, then
``neighbors.refine.refine`` against the raw corpus down to ``k``."""

from __future__ import annotations

import jax

ENGINE_COUNTERS = ("ivf_pq.search.backend.",)


def build(corpus, cfg: dict, seed: int):
    from raft_tpu.neighbors import ivf_pq

    params = ivf_pq.IvfPqParams(n_lists=cfg["n_lists"], pq_dim=cfg["pq_dim"],
                                pq_bits=cfg["pq_bits"], metric=cfg["metric"],
                                group_size=cfg["group_size"],
                                seed=seed % (1 << 31))
    return jax.block_until_ready(ivf_pq.build(corpus, params))


def batch_search(index, corpus, cfg: dict):
    from raft_tpu.neighbors import ivf_pq, refine

    def search(queries):
        _, cand = ivf_pq.search(index, queries, cfg["k_fetch"],
                                n_probes=cfg["n_probes"])
        return refine.refine(corpus, queries, cand, cfg["k"],
                             metric=cfg["metric"])

    return search
