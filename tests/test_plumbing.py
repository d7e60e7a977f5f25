"""Plumbing: trace ranges, pooled resources manager, bench harness runner."""

import numpy as np
import pytest

import jax

from raft_tpu.bench import run_benchmark
from raft_tpu.core.resources_manager import clear_pool, get_resources, set_resource_defaults
from raft_tpu.core.trace import trace_range, traced


class TestTrace:
    def test_traced_preserves_result(self):
        @traced("test::fn")
        def f(x):
            return x + 1

        assert f(41) == 42

    def test_range_context(self):
        with trace_range("test::block"):
            out = jax.numpy.sum(jax.numpy.ones(8))
        assert float(out) == 8.0


class TestResourcesManager:
    def test_pooled_identity_and_defaults(self):
        clear_pool()
        set_resource_defaults(workspace_bytes=123456)
        r1 = get_resources()
        r2 = get_resources()
        assert r1 is r2
        assert r1.workspace_bytes == 123456
        clear_pool()
        set_resource_defaults(workspace_bytes=1 << 30)
        r3 = get_resources()
        assert r3 is not r1

    def test_per_device_entries(self):
        clear_pool()
        devs = jax.devices()
        if len(devs) >= 2:
            assert get_resources(devs[0]) is not get_resources(devs[1])


class TestBenchRunner:
    def test_sweep_records(self):
        cfg = {
            "dataset": {"kind": "blobs", "n": 3000, "dim": 16,
                        "n_queries": 50, "n_clusters": 32},
            "k": 5,
            "algos": [
                {"name": "brute_force", "build": {}, "search": [{}]},
                {"name": "ivf_flat", "build": {"n_lists": 16},
                 "search": [{"n_probes": 4}, {"n_probes": 16}]},
            ],
        }
        records = run_benchmark(cfg, reps=1)
        assert len(records) == 3
        bf = [r for r in records if r["algo"] == "brute_force"][0]
        assert bf["recall"] == 1.0 and bf["qps"] > 0
        flat = [r for r in records if r["algo"] == "ivf_flat"]
        # nprobe=16 == n_lists: exhaustive, recall 1.0
        assert max(f["recall"] for f in flat) == 1.0
        assert all(f["build_s"] >= 0 for f in flat)

    def test_files_dataset_and_unknown_algo(self, tmp_path):
        rng = np.random.default_rng(0)
        X = rng.standard_normal((500, 8)).astype(np.float32)
        Q = rng.standard_normal((20, 8)).astype(np.float32)
        np.save(tmp_path / "b.npy", X)
        np.save(tmp_path / "q.npy", Q)
        cfg = {
            "dataset": {"kind": "files", "base": str(tmp_path / "b.npy"),
                        "queries": str(tmp_path / "q.npy")},
            "k": 3,
            "algos": [{"name": "brute_force", "build": {}, "search": [{}]}],
        }
        assert run_benchmark(cfg, reps=1)[0]["recall"] == 1.0
        cfg["algos"] = [{"name": "bogus"}]
        with pytest.raises(ValueError):
            run_benchmark(cfg, reps=1)


class TestCompileCache:
    """utils.compile_cache: the environment's directory wins; otherwise one
    fixed directory inside the checkout."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_dir_is_left_alone(self, monkeypatch, tmp_path):
        from raft_tpu.utils import compile_cache

        d = str(tmp_path / "xla")
        monkeypatch.setenv(compile_cache.ENV, d)
        # what JAX does with the variable at import
        jax.config.update("jax_compilation_cache_dir", d)
        assert compile_cache.enable_persistent_cache() == d
        assert jax.config.jax_compilation_cache_dir == d

    def test_default_is_fixed_in_checkout(self, monkeypatch):
        import os

        from raft_tpu.utils import compile_cache

        monkeypatch.delenv(compile_cache.ENV, raising=False)
        first = compile_cache.enable_persistent_cache()
        assert compile_cache.enable_persistent_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
