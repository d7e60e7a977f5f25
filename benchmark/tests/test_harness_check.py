"""``correct`` comes out false for the control and for a broken timed
path, and true for a sound run, at a size a CPU holds. The limits are the
cells' own."""

import jax.numpy as jnp
import numpy as np
import pytest

import check
import run
from conftest import served_cell, tiny_cell

data = run.load_module("data/sift_like.py")
ref = run.load_module("reference/exact_l2.py")
SEED = 2 ** 33 + 17


@pytest.fixture(scope="module")
def corpus_queries():
    return data.make(SEED, 20000, 128, 300)


def _compare(corpus, queries, d, ids, limits, missing=0, with_recall=False):
    ref_d, _ = ref.knn(corpus, queries, 10)
    numbers, recall = check.compare(
        np.arange(queries.shape[0], dtype=np.int32), d, ids, missing, ref_d,
        lambda qi, ii: ref.pair_dist(corpus, queries, qi, ii), limits)
    return (numbers, recall) if with_recall else numbers


def test_reference_selection_is_exact(corpus_queries):
    corpus, queries = corpus_queries
    d, _ = ref.knn(corpus, queries, 10, n_block=4096)
    x = np.asarray(corpus, np.float64)
    q = np.asarray(queries, np.float64)[:40]
    full = (q * q).sum(1)[:, None] + (x * x).sum(1)[None] - 2 * q @ x.T
    np.testing.assert_array_equal(d[:40], np.sort(full, axis=1)[:, :10])


@pytest.mark.parametrize("cell", ["sift1m-ivf_flat.batch10k",
                                  "sift1m-ivf_pq.batch10k"])
def test_control_fails_and_reference_passes(corpus_queries, cell):
    corpus, queries = corpus_queries
    limits = run.cell_spec(cell)["limits"]
    d, ids = ref.knn(corpus, queries, 10)
    assert check.passed(_compare(corpus, queries, d, ids, limits))
    d, ids = ref.knn(corpus, queries, 10, input_dtype=jnp.float8_e4m3fn)
    numbers = _compare(corpus, queries, d, ids, limits)
    assert not check.passed(numbers), numbers
    # chosen in float8, returned with exact distances: only the choice is
    # off, and ``incomplete`` alone sees it
    exact = ref.pair_dist(corpus, queries,
                          np.arange(queries.shape[0], dtype=np.int32), ids)
    numbers = _compare(corpus, queries, exact, ids, limits)
    assert numbers["dist_err"]["value"] == 0.0
    assert numbers["incomplete"]["value"] > limits["incomplete"], numbers


def test_served_limits_are_the_batch_cells():
    assert served_cell()["limits"] == run.cell_spec(
        "sift1m-ivf_flat.batch10k")["limits"]


def test_each_number_catches_its_fault(corpus_queries):
    corpus, queries = corpus_queries
    limits = run.cell_spec("sift1m-ivf_flat.batch10k")["limits"]
    d, ids = ref.knn(corpus, queries, 10)
    wrong = ids.copy()
    wrong[5, 0] = (wrong[5, 0] + 1) % corpus.shape[0]
    assert _compare(corpus, queries, d, wrong, limits)["dist_err"]["value"] \
        > limits["dist_err"]
    twice = ids.copy()
    twice[7, 1] = twice[7, 0]
    assert _compare(corpus, queries, d, twice, limits)["bad_ids"]["value"] == 1
    out = ids.copy()
    out[9, 2] = -1
    assert _compare(corpus, queries, d, out, limits)["bad_ids"]["value"] == 1
    # the nearest row of every third answer swapped for the 11th
    d11, ids11 = ref.knn(corpus, queries, 11)
    short = ids.copy()
    short[::3, 0] = ids11[::3, 10]
    numbers = _compare(corpus, queries, d, short, limits)
    assert numbers["incomplete"]["value"] == pytest.approx(
        len(short[::3]) / len(short))
    half, recall = _compare(corpus, queries[:150], d[:150], ids[:150],
                            limits, missing=150, with_recall=True)
    assert half["missing"]["value"] == 150
    assert recall == pytest.approx(0.5)


def _break_batch(fault):
    def adapt(system):
        search = system["batch_search"]

        def broken(queries):
            d, ids = search(queries)
            if fault == "altered":
                ids = ids.at[:, 0].set((ids[:, 0] + 1) % 20000)
            else:  # half of the batch left out
                d, ids = d[: d.shape[0] // 2], ids[: ids.shape[0] // 2]
            return d, ids

        return {**system, "batch_search": broken}

    return adapt


def _break_served(system):
    serve = system["serve"]

    def broken_serve():
        queue = serve()
        search = queue._search_fn

        def rotated(q):  # each request gets its neighbour's answer
            d, ids = search(q)
            return jnp.roll(d, 1, axis=0), jnp.roll(ids, 1, axis=0)

        queue._search_fn = rotated
        return queue

    return {**system, "serve": broken_serve}


@pytest.mark.parametrize("name,adapt", [
    ("sift1m-ivf_flat.batch10k", None),
    ("sift1m-ivf_flat.batch10k", _break_batch("altered")),
    ("sift1m-ivf_pq.batch10k", _break_batch("half")),
    ("sift1m-ivf_flat.served", None),
    ("sift1m-ivf_flat.served", _break_served),
])
def test_whole_run(cpu_devices, name, adapt):
    """A whole run past the chip check, the timed path sound or broken."""
    res = run.run_cell(tiny_cell(name), SEED, 1.0, False, cpu_devices,
                       adapt_system=adapt)
    assert res["correct"] is (adapt is None), res["check"]
    assert list(res["check"]) == list(check.ORDER)
    assert res["metrics"]["setup_s"]["value"] > 0
