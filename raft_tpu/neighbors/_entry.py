"""The one prologue of the IVF search entries.

``ivf_flat``, ``ivf_pq`` and ``ivf_bq`` each have a packed ``search`` and a
paged ``search_paged``. All six do the same host-side steps before they
launch a scan, in this order (:func:`prepare`):

1. cast the queries to float32 and check their ``(q, dim)`` shape;
2. clamp ``n_probes`` to ``n_lists``;
3. take the store's standing filter when the caller passes none;
4. with a filter: the ``<family>.search.filter`` faultpoint, then
   ``_filtering.widen_plan``, whose numbers become the ``filter_*`` attrs;
5. check ``k`` against the probes' capacity;
6. L2-normalise the queries of a cosine index;
7. under ``obs.enabled()``: the ``<entry>.queries`` / ``.probes`` /
   ``.rows_scanned`` / ``.backend.<engine>`` counters and the scan-span
   attrs.

What differs between the families — the engine, the capacity per probe,
the roofline note and its occupancy — is passed in as data or done by the
caller after the call. The memoised occupancy lookups the roofline notes
share are here too (:func:`list_occupancy`, :func:`page_occupancy`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp

from raft_tpu import obs
from raft_tpu.neighbors import _filtering
from raft_tpu.obs import roofline as obs_roofline


class Prepared(NamedTuple):
    """What :func:`prepare` hands back to the entry."""

    queries: object          # (q, dim) float32, normalised for cosine
    n_probes: int            # clamped, then widened by the filter plan
    filter: object           # the caller's filter, else the standing one
    attrs: Optional[dict]    # scan-span attrs; None with telemetry off


def prepare(entry: str, queries, k: int, n_probes: int, filter, backend: str,
            *, dim: int, n_lists: int, metric: str, rows: int,
            k_cap: Optional[int] = None, k_error: str = "k={k} out of range",
            count_rows: bool = False, standing_filter=None,
            attrs: Optional[dict] = None) -> Prepared:
    """The prologue of one search call (module docstring, steps 1-7).

    ``entry`` is the counter prefix (``"ivf_pq.search_paged"``); its
    first component names the family's filter faultpoint. ``rows`` is the
    capacity of one probe (``max_list_size``, or ``table_width ·
    page_rows``): ``k`` must lie in ``1..n_probes·rows``, capped at
    ``k_cap`` when given, else ``ValueError(k_error)`` formatted with
    ``k``, ``n_probes`` and ``rows``. ``count_rows`` adds
    ``<entry>.rows_scanned`` (the padded upper bound ``q·n_probes·rows``).
    ``attrs`` are extra scan-span attrs, placed before the filter's."""
    queries = jnp.asarray(queries).astype(jnp.float32)
    if queries.ndim != 2 or queries.shape[1] != dim:
        raise ValueError(f"queries must be (q, {dim}), got {queries.shape}")
    n_probes = int(min(n_probes, n_lists))
    if filter is None:
        # a standing store-level filter (PagedListStore.set_filter) applies
        # when the caller passes none — per-call filters take precedence
        filter = standing_filter
    filter_attrs = None
    if filter is not None:
        from raft_tpu.resilience import faultpoint

        site = f"{entry.partition('.')[0]}.search.filter"
        faultpoint(site)
        # selectivity-aware widening: over-probe by ~1/pass_rate (capped)
        # so k SURVIVORS come back at selective filters — the effective
        # n_probes flows into validation, telemetry and the roofline model
        n_probes, _, f_rate, f_widen = _filtering.widen_plan(
            filter, n_probes, n_lists)
        filter_attrs = {"filter_pass_rate": round(f_rate, 6),
                        "filter_widen_x": round(f_widen, 4),
                        "filter_n_probes": n_probes}
    cap = n_probes * rows if k_cap is None else min(n_probes * rows, k_cap)
    if not 0 < k <= cap:
        raise ValueError(k_error.format(k=k, n_probes=n_probes, rows=rows))
    if metric == "cosine":
        queries = queries / jnp.maximum(
            jnp.linalg.norm(queries, axis=1, keepdims=True), 1e-30)
    scan_attrs = None
    if obs.enabled():
        q = int(queries.shape[0])
        obs.add(f"{entry}.queries", q)
        obs.add(f"{entry}.probes", q * n_probes)
        if count_rows:
            # padded upper bound on candidate rows visited (the strip
            # engines' actual work is ∝ real list fills; telemetry only)
            obs.add(f"{entry}.rows_scanned", q * n_probes * rows)
        obs.add(f"{entry}.backend.{backend}", 1)
        scan_attrs = {"backend": backend, "queries": q,
                      "probes": n_probes, "k": int(k), **(attrs or {})}
        if filter_attrs:
            scan_attrs.update(filter_attrs)
    return Prepared(queries, n_probes, filter, scan_attrs)


def list_occupancy(index, q: int, n_probes: int, k: int, res, stats):
    """The strip planner's occupancy of a packed dispatch, memoised on the
    index, or None while the host holds no per-list lengths (telemetry
    must never force a device sync to get them). ``stats(lens, m, q, p,
    workspace_bytes=, kf=)`` is the family's occupancy function."""
    lens = getattr(index, "_lens_np_cache", None)
    if lens is None or lens.shape[0] != index.n_lists:
        return None
    kf = min(int(k), 512)
    return obs_roofline.memo_occupancy(
        index, (id(lens), q, n_probes, kf, res.workspace_bytes),
        lambda: stats(lens, index.max_list_size, q, n_probes,
                      workspace_bytes=res.workspace_bytes, kf=kf))


def page_occupancy(store, width: int, q: int, n_probes: int, k: int, res,
                   row_bytes: int, dim: int):
    """The paged planner's occupancy of a paged-strip dispatch, from host
    state the store already holds, memoised until its layout or fill
    moves."""
    from raft_tpu.ops.strip_scan import paged_occupancy_stats

    return obs_roofline.memo_occupancy(
        store,
        (store.pages_used, store.size, store.tombstones, width, q,
         n_probes, int(k), res.workspace_bytes),
        lambda: paged_occupancy_stats(
            width, store.page_rows, store._list_pages, store.size,
            store.tombstones, q, n_probes, int(k), row_bytes,
            workspace_bytes=res.workspace_bytes, dim=dim))
