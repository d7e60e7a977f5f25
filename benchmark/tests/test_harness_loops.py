"""Each traffic loop on the CPU at a tiny size, through its own functions,
over a stand-in search whose answers are known."""

import jax.numpy as jnp
import numpy as np
import pytest

import run

closed_batch = run.load_module("loops/closed_batch.py")
open_poisson = run.load_module("loops/open_poisson.py")
K = 3


class NoTrace:
    def __init__(self):
        self.ticks = []

    def tick(self, elapsed, n_done):
        self.ticks.append((elapsed, n_done))


def fake_search(queries):
    """Row i answers ids derived from its first coordinate."""
    q = jnp.asarray(queries)
    base = q[:, 0].astype(jnp.int32)
    ids = base[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]
    return ids.astype(jnp.float32), ids


@pytest.fixture
def queries():
    return np.arange(50, dtype=np.float32)[:, None].repeat(4, axis=1) * 10


def test_closed_batch(queries):
    state = closed_batch.prepare({"batch_search": fake_search}, queries,
                                 {"batch": 20, "orders": 3}, seed=9,
                                 seconds=0.3)
    tracer = NoTrace()
    rec = closed_batch.window(state, 0.3, tracer)
    assert len(rec["calls"]) >= 2 and tracer.ticks[-1][1] == len(rec["calls"])
    qidx, d, ids, missing = closed_batch.answers(state, rec)
    assert missing == 0 and qidx.shape == (20 * len(rec["calls"]),)
    # each answer row belongs to its own query
    np.testing.assert_array_equal(ids[:, 0], (queries[qidx, 0]).astype(int))
    e2e = closed_batch.end_to_end(rec)
    assert e2e["attempted"] == qidx.shape[0]
    assert e2e["qps"] == pytest.approx(qidx.shape[0] / rec["elapsed_s"])


def test_closed_batch_counts_rows_left_out(queries):
    def half(q):
        d, i = fake_search(q)
        return d[: q.shape[0] // 2], i[: q.shape[0] // 2]

    state = closed_batch.prepare({"batch_search": half}, queries,
                                 {"batch": 20}, seed=1, seconds=0.1)
    rec = closed_batch.window(state, 0.1, NoTrace())
    _, _, _, missing = closed_batch.answers(state, rec)
    assert missing == 10 * len(rec["calls"])


def test_schedule_same_work_for_every_seed():
    a = open_poisson.schedule(200.0, 2.0, seed=1)
    b = open_poisson.schedule(200.0, 2.0, seed=2 ** 33 + 7)
    assert a.shape == b.shape == (400,)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)))
    assert a[-1] < 2.0 and not np.array_equal(a, b)


def test_open_poisson(queries):
    from raft_tpu import serving

    def serve():
        return serving.QueryQueue(fake_search, max_batch=8, slo_s=0.02)

    state = open_poisson.prepare({"serve": serve}, queries,
                                 {"rate_per_s": 100, "wait_after_s": 5},
                                 seed=3, seconds=0.5)
    rec = open_poisson.window(state, 0.5, NoTrace())
    qidx, d, ids, missing = open_poisson.answers(state, rec)
    assert missing == 0 and qidx.shape == (50,)
    np.testing.assert_array_equal(ids[:, 0], (queries[qidx, 0]).astype(int))
    e2e = open_poisson.end_to_end(rec)
    assert e2e["attempted"] == 50 and e2e["failed"] == 0
    assert 0 < e2e["p50_ms"] <= e2e["p99_ms"] < 5e3
