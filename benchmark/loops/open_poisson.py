"""Open loop: single-query requests with Poisson arrivals at a fixed rate.

Traffic parameters (``traffic/<mix>.json``): ``rate_per_s``, the offered
rate, and ``wait_after_s``, how long past the window's close the loop
waits for answers still due.

Every seed gets the same number of requests and the same multiset of gaps
between arrivals (drawn once from a fixed generator, scaled to the
window), in an order drawn from the seed, so the seed changes the order of
the work and never its amount. Requests take distinct queries, a seeded
permutation of the query set.

A generator thread submits each request at its due time and hands its
handle to a collector thread, which waits on the handles in the order they
were sent and stamps each one's completion on its own clock when
``result()`` returns. A request's latency runs from its due time, not from
when it was sent, to that stamp: a generator that falls behind shows as
latency, and its lateness is reported on its own line. Answers go into
arrays allocated before the window and each handle is dropped once read, so
the loop keeps no growing heap and the collector stays on.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time

import numpy as np

GAPS_SEED = 0x5EED


def schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of the requests of one run."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(GAPS_SEED).exponential(1.0 / rate, n)
    gaps *= seconds / (gaps.sum() + gaps.mean())
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))


def prepare(system, queries_np, traffic: dict, seed: int,
            seconds: float) -> dict:
    """Build and warm the served path, and fix the run's requests."""
    due = schedule(float(traffic["rate_per_s"]), seconds, seed)
    nq = queries_np.shape[0]
    perm = np.random.default_rng(seed + 1).permutation(nq)
    qidx = np.resize(perm, due.shape[0]).astype(np.int32)
    queue = system["serve"]()
    queue.start()
    # the queue's own first dispatches, full and single, before the window
    for n in (queue.max_batch, 1):
        for h in [queue.submit(queries_np[i % nq]) for i in range(n)]:
            _, ids = h.result(timeout=60.0)
    return {"queue": queue, "due": due, "qidx": qidx, "k": np.size(ids),
            "rows": queries_np.astype(np.float32)[qidx],
            "wait_after_s": float(traffic.get("wait_after_s", 60.0))}


def window(state: dict, seconds: float, tracer) -> dict:
    queue, due, rows, k = state["queue"], state["due"], state["rows"], \
        state["k"]
    n = due.shape[0]
    t_sub = np.zeros(n)
    ok = np.zeros(n, bool)
    dist = np.zeros((n, k), np.float32)
    ids = np.full((n, k), -1, np.int32)
    sent = queue_mod.SimpleQueue()
    t0 = time.perf_counter() + 0.01
    t_close = t0 + seconds + state["wait_after_s"]
    t_done = np.full(n, t_close)  # a request never answered: the whole wait

    def generate():
        try:
            for i in range(n):
                delay = t0 + due[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                t_sub[i] = time.perf_counter()
                sent.put((i, queue.submit(rows[i])))
        finally:
            sent.put(None)

    def collect():
        while (item := sent.get()) is not None:
            i, h = item
            wait = max(0.0, t_close - time.perf_counter())
            try:
                d, r = h.result(timeout=wait)
            except Exception:  # a failed or lost request: counted
                continue
            t_done[i] = time.perf_counter()
            dist[i], ids[i], ok[i] = np.ravel(d), np.ravel(r), True

    gen = threading.Thread(target=generate, name="bench-generator")
    col = threading.Thread(target=collect, name="bench-collector")
    depth = []
    col.start()
    gen.start()
    while gen.is_alive():
        now = time.perf_counter() - t0
        tracer.tick(now, None)
        depth.append((now, queue.depth))
        gen.join(0.05)
    tracer.tick(float("inf"), None)
    col.join()
    queue.stop()
    return {"lat_s": t_done - (t0 + due), "ok": ok, "dist": dist, "ids": ids,
            "due_s": due, "late_s": t_sub - (t0 + due), "depth": depth,
            "n": n}


def answers(state: dict, record: dict):
    ok = record["ok"]
    return (state["qidx"][ok], record["dist"][ok], record["ids"][ok],
            int(record["n"] - ok.sum()))


def end_to_end(record: dict) -> dict:
    """p50/p99 over every request of the window. One that failed or never
    came counts as missing every limit: its latency is the whole time the
    loop waited for it."""
    lat = record["lat_s"]
    failed = int(record["n"] - record["ok"].sum())
    return {"p50_ms": 1e3 * float(np.percentile(lat, 50, method="higher")),
            "p99_ms": 1e3 * float(np.percentile(lat, 99, method="higher")),
            "attempted": int(record["n"]), "failed": failed}
