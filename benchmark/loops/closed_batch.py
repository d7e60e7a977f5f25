"""Closed loop, one caller: whole query batches back to back.

Traffic parameters (``traffic/<mix>.json``): ``batch``, the queries in one
call, and ``orders``, how many seeded orders of the query set the batches
cycle through (every order holds the same queries, so each batch does the
same work).

Each batch ends in ``block_until_ready`` and a fetch of its distances and
ids to the host before the next one is called. ``qps`` is every query
answered over the whole window: from the first call to the last fetch.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def prepare(system, queries_np, traffic: dict, seed: int,
            seconds: float) -> dict:
    """Place the batches on the device and warm up the batch shape."""
    batch, n_orders = int(traffic["batch"]), int(traffic.get("orders", 1))
    nq = queries_np.shape[0]
    if batch > nq:
        raise ValueError(f"batch {batch} is larger than the query set {nq}")
    rng = np.random.default_rng(seed)
    orders = [rng.permutation(nq)[:batch].astype(np.int32)
              for _ in range(n_orders)]
    qf = queries_np.astype(np.float32)
    batches = [jax.device_put(qf[o]) for o in orders]
    search = system["batch_search"]
    for b in batches[:1]:
        jax.block_until_ready(search(b))
    return {"search": search, "orders": orders, "batches": batches}


def window(state: dict, seconds: float, tracer) -> dict:
    """Run batches until ``seconds`` have passed; returns the record."""
    search, batches = state["search"], state["batches"]
    calls, answers = [], []
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        tracer.tick(now - t0, len(calls))
        j = i % len(batches)
        with jax.profiler.TraceAnnotation("bench::dispatch"):
            out = search(batches[j])
        t_ret = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench::wait"):
            d, ids = jax.block_until_ready(out)
            d, ids = np.asarray(d), np.asarray(ids)
        t_done = time.perf_counter()
        calls.append((now, t_ret, t_done))
        answers.append((j, d, ids))
        i += 1
    tracer.tick(float("inf"), len(calls))
    elapsed = calls[-1][2] - t0 if calls else seconds
    return {"t0": t0, "elapsed_s": elapsed, "calls": calls,
            "answers": answers, "batch": batches[0].shape[0]}


def answers(state: dict, record: dict):
    """(query index (m,), distances (m, k), ids (m, k), missing count)."""
    qidx, ds, ids, missing = [], [], [], 0
    for j, d, i in record["answers"]:
        order = state["orders"][j]
        rows = min(len(order), d.shape[0], i.shape[0])
        missing += len(order) - rows
        qidx.append(order[:rows])
        ds.append(d[:rows])
        ids.append(i[:rows])
    if not qidx:
        return (np.zeros(0, np.int32), np.zeros((0, 1), np.float32),
                np.zeros((0, 1), np.int32), 0)
    return (np.concatenate(qidx), np.concatenate(ds), np.concatenate(ids),
            missing)


def end_to_end(record: dict) -> dict:
    n = len(record["calls"]) * record["batch"]
    return {"qps": n / record["elapsed_s"], "attempted": n, "failed": 0}
