"""The benchmark's own tests: CPU only, small sizes.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import run  # noqa: E402

# small enough for a CPU in seconds, large enough to leave most lists unprobed
TINY = {"n_rows": 20000, "n_queries": 400, "n_lists": 64, "group_size": 64}


def served_cell() -> dict:
    """The served open loop over ``sift1m-ivf_flat``, held to the batch
    cell's limits: no cell of ``BENCHMARK.json`` serves yet."""
    spec = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return run.assemble("sift1m-ivf_flat.served", "sift1m-ivf_flat",
                        "served_poisson", 1, spec,
                        limits="sift1m-ivf_flat.batch10k")


def tiny_cell(name: str) -> dict:
    cell = served_cell() if name.endswith(".served") else run.cell_spec(name)
    cell["config"].update(TINY)
    if cell["traffic"]["loop"] == "closed_batch":
        cell["traffic"].update(batch=400, orders=2)
    else:
        cell["traffic"].update(rate_per_s=60, wait_after_s=20)
        cell["config"]["serving"].update(max_batch=8)
    return cell


@pytest.fixture
def cpu_devices():
    import jax

    return jax.devices()
