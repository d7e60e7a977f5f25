"""IVF-Flat through the library's public entry points.

Batch: ``neighbors.ivf_flat.search``. Served: a ``serving.PagedListStore``
made from the index, behind a ``serving.QueryQueue`` over
``serving.searcher``; every queue bucket's program is warmed up before the
queue is handed over.
"""

from __future__ import annotations

import jax

ENGINE_COUNTERS = ("ivf_flat.search.backend.", "ivf_flat.search_paged.backend.")


def build(corpus, cfg: dict, seed: int):
    from raft_tpu.neighbors import ivf_flat

    params = ivf_flat.IvfFlatParams(n_lists=cfg["n_lists"],
                                    metric=cfg["metric"],
                                    group_size=cfg["group_size"],
                                    seed=seed % (1 << 31))
    return jax.block_until_ready(ivf_flat.build(corpus, params))


def batch_search(index, corpus, cfg: dict):
    from raft_tpu.neighbors import ivf_flat

    def search(queries):
        return ivf_flat.search(index, queries, cfg["k"],
                               n_probes=cfg["n_probes"])

    return search


def serve(index, queries_np, cfg: dict):
    """A started-ready QueryQueue over a paged store of ``index``."""
    from raft_tpu import serving

    s = cfg["serving"]
    store = serving.PagedListStore.from_index(index, page_rows=s["page_rows"])
    queue = serving.QueryQueue(
        serving.searcher(store, cfg["k"], n_probes=cfg["n_probes"]),
        max_batch=s["max_batch"], slo_s=s["slo_s"])
    rows = queries_np.astype("float32")
    for b in queue.buckets:
        jax.block_until_ready(serving.search(store, rows[:b], cfg["k"],
                                             n_probes=cfg["n_probes"]))
    return queue
