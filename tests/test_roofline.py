"""Roofline plane (ISSUE 12): per-dispatch FLOP/byte model, utilization
gauges, kernel occupancy.

Tier-1 contracts:

* ``estimate_flops`` — EXACT (zero tolerance) against brute-force
  counting oracles on random tiny shapes for every registered entry: the
  oracles count op-by-op with python loops, independently of the closed
  forms, so an algebra slip in either side fails loudly;
* XLA cross-check — where the backend's ``cost_analysis()`` reports
  ``flops``, the static matmul model agrees within the documented 2×
  band (the compiler may fold constants / fuse the bias adds), and the
  analysis lowering fabricates no unexplained retrace;
* occupancy — exact values for a hand-built ragged layout through the
  kernels' own planning code (strip_scan / bq_scan / cagra_hop);
* peaks — a TPU whose ``device_kind`` the table does not list is an
  error; ``summary()`` reports the static model and never a measured time
  (device time comes from a profiler trace);
* NOOP gate — telemetry off ⇒ zero roofline work on the hot path;
* report — ``obs.report.collect()`` carries a validating ``roofline``
  section; malformed records are flagged.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import obs
from raft_tpu.neighbors import brute_force, ivf_flat
from raft_tpu.obs import compile as obs_compile
from raft_tpu.obs import report as obs_report
from raft_tpu.obs import roofline
from raft_tpu.ops import bq_scan, cagra_hop, strip_scan


@pytest.fixture
def telemetry():
    obs.reset()
    obs.tracing.clear_spans()
    roofline.reset()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.reset()
        obs.tracing.clear_spans()
        roofline.reset()


@pytest.fixture
def peaks_env(monkeypatch):
    """A known synthetic peak pair (1 TFLOP/s, 100 GB/s) via the env
    override knobs — the unlisted-platform/CPU-preview route."""
    monkeypatch.setenv(roofline.PEAK_FLOPS_ENV, "1e12")
    monkeypatch.setenv(roofline.PEAK_BW_ENV, "1e11")
    yield


# ---------------------------------------------------------------------------
# FLOP/byte oracles: brute-force counting, independent of the closed forms
# ---------------------------------------------------------------------------


def _loop_matmul_flops(m, n, kdim):
    """2 FLOPs per MAC, counted one output element at a time."""
    total = 0
    for _ in range(m):
        for _ in range(n):
            total += 2 * kdim
    return total


class TestFlopOracles:
    @pytest.mark.parametrize("draw", range(3))
    def test_brute_force(self, rng, draw):
        q, n, dim, k = (int(rng.integers(1, 7)) for _ in range(4))
        est = roofline.estimate_flops("brute_force.search", q=q, n=n,
                                      dim=dim, k=k, dtype="float32")
        flops = _loop_matmul_flops(q, n, dim)
        for _ in range(q):
            for _ in range(n):
                flops += 1                      # norm/bias add
        assert est["flops"] == flops
        assert est["bytes_read"] == q * dim * 4 + n * dim * 4 + n * 4
        assert est["bytes_written"] == q * k * 8

    @pytest.mark.parametrize("draw", range(3))
    def test_ivf_flat(self, rng, draw):
        q = int(rng.integers(1, 6))
        dim = int(rng.integers(2, 9))
        n_lists, mls = int(rng.integers(2, 5)), int(rng.integers(2, 9))
        p, k = int(rng.integers(1, 4)), int(rng.integers(1, 4))
        est = roofline.estimate_flops(
            "ivf_flat.search", q=q, dim=dim, n_lists=n_lists,
            max_list_size=mls, n_probes=p, k=k, dtype="float32")
        flops = _loop_matmul_flops(q, n_lists, dim)      # coarse
        for _ in range(q):
            for _ in range(p):
                for _ in range(mls):
                    flops += 2 * dim + 1                 # score + bias
        assert est["flops"] == flops
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (q * dim * 4 + n_lists * dim * 4
                                     + strips * mls * (dim * 4 + 8))
        assert est["bytes_written"] == q * k * 8

    @pytest.mark.parametrize("draw", range(3))
    def test_ivf_pq(self, rng, draw):
        q, dim = int(rng.integers(1, 5)), int(rng.integers(4, 9))
        pq_dim = int(rng.choice([2, 4]))
        n_lists, mls = 3, int(rng.integers(2, 7))
        p, k = 2, 3
        rd = pq_dim * math.ceil(dim / pq_dim)
        est = roofline.estimate_flops(
            "ivf_pq.search", q=q, dim=dim, n_lists=n_lists,
            max_list_size=mls, pq_dim=pq_dim, n_probes=p, k=k)
        flops = _loop_matmul_flops(q, n_lists, dim)      # coarse
        flops += _loop_matmul_flops(q, rd, dim)          # rotation
        for _ in range(q):
            for _ in range(p):
                for _ in range(mls):
                    flops += 2 * rd + 1                  # int8 strip scan
        assert est["flops"] == flops
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (q * dim * 4 + n_lists * dim * 4
                                     + rd * rd * 4
                                     + strips * mls * (rd + 8))

    @pytest.mark.parametrize("draw", range(3))
    def test_ivf_bq(self, rng, draw):
        q, dim = int(rng.integers(1, 5)), int(rng.integers(4, 20))
        n_lists, mls, p, k = 3, int(rng.integers(2, 7)), 2, 3
        rd = math.ceil(dim / 8) * 8
        est = roofline.estimate_flops(
            "ivf_bq.search", q=q, dim=dim, n_lists=n_lists,
            max_list_size=mls, n_probes=p, k=k)
        flops = _loop_matmul_flops(q, n_lists, dim)
        flops += _loop_matmul_flops(q, rd, dim)
        for _ in range(q):
            for _ in range(p):
                for _ in range(mls):
                    flops += 2 * rd + 2             # ±1 scan + scale + bias
        assert est["flops"] == flops
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (q * dim * 4 + n_lists * dim * 4
                                     + rd * rd * 4
                                     + strips * mls * (rd // 8 + 12))

    @pytest.mark.parametrize("draw", range(3))
    def test_ivf_bq_multibit_hadamard(self, rng, draw):
        """The round-17 extended-code scan: every extra bit-plane widens
        the per-entry contraction to bits·rot_dim, the strip stream to
        bits·rot_dim/8 code bytes; the SRHT rotation counts the sign
        multiply + log2(rd) butterfly stages + the 1/√d scale per row
        with only a (rd,) operand."""
        q, dim = int(rng.integers(1, 5)), int(rng.integers(3, 9))
        n_lists, mls = 3, int(rng.integers(2, 7))
        p, k = 2, 3
        bits = int(rng.integers(2, 5))
        rd = 1 << math.ceil(math.log2(max(dim, 8)))     # hadamard width
        est = roofline.estimate_flops(
            "ivf_bq.search", q=q, dim=dim, n_lists=n_lists,
            max_list_size=mls, n_probes=p, k=k, rot_dim=rd, bits=bits,
            rotation_kind="hadamard")
        flops = _loop_matmul_flops(q, n_lists, dim)      # coarse
        for _ in range(q):                               # SRHT butterfly
            flops += rd * (int(math.log2(rd)) + 2)
        for _ in range(q):
            for _ in range(p):
                for _ in range(mls):
                    flops += 2 * rd * bits + 2           # wide scan + s/b
        assert est["flops"] == flops
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (q * dim * 4 + n_lists * dim * 4
                                     + rd * 4             # sign diagonal
                                     + strips * mls * (bits * rd // 8 + 12))

    def test_srht_apply_oracle(self):
        n, rd = 5, 64
        est = roofline.estimate_flops("linalg.srht_apply", n=n, rot_dim=rd)
        # per row: rd sign multiplies + log2(rd) add/sub stages of rd
        # butterflies + rd scale multiplies
        assert est["flops"] == n * rd * (6 + 2)
        assert est["bytes_read"] == n * rd * 4 + rd * 4
        assert est["bytes_written"] == n * rd * 4
        # the O(d log d) vs O(d²) claim as numbers: dense apply of the
        # same rows costs 2·n·d·d
        dense = 2 * n * rd * rd
        assert est["flops"] < dense / 10

    def test_build_model_oracles(self):
        """Hand-counted build models (round-17 satellite: the bench's
        flat/pq/bq build phases stamp these)."""
        n, dim, nl, tr, it = 10, 4, 3, 6, 2
        est = roofline.estimate_flops(
            "ivf_flat.build", n=n, dim=dim, n_lists=nl, kmeans_iters=it,
            train_rows=tr)
        want = it * 4 * tr * nl * dim + 2 * n * nl * dim + 2 * n * dim
        assert est["flops"] == want
        pq_dim, cb_it, cbr = 2, 3, 5
        rd = pq_dim * math.ceil(dim / pq_dim)
        est = roofline.estimate_flops(
            "ivf_pq.build", n=n, dim=dim, n_lists=nl, pq_dim=pq_dim,
            kmeans_iters=it, codebook_iters=cb_it, train_rows=tr,
            cb_rows=cbr)
        want = (it * 4 * tr * nl * dim + 2 * n * nl * dim
                + cb_it * 4 * cbr * 256 * rd + 2 * n * dim * rd
                + 2 * n * 256 * rd)
        assert est["flops"] == want
        rdb = 8
        for bits, rkind, rot_f in (
                (1, "dense", 2 * n * dim * rdb),
                (3, "hadamard", n * rdb * (3 + 2))):
            est = roofline.estimate_flops(
                "ivf_bq.build", n=n, dim=dim, n_lists=nl, kmeans_iters=it,
                train_rows=tr, rot_dim=rdb, bits=bits,
                rotation_kind=rkind)
            want = (it * 4 * tr * nl * dim + 2 * n * nl * dim + rot_f
                    + n * rdb * (2 * bits + 4))
            assert est["flops"] == want, (bits, rkind)

    @pytest.mark.parametrize("draw", range(2))
    def test_paged_flat(self, rng, draw):
        q, dim, n_lists = int(rng.integers(1, 5)), 4, 3
        pr, tw, p, k = int(rng.integers(1, 4)), int(rng.integers(1, 4)), 2, 3
        est = roofline.estimate_flops(
            "ivf_flat.paged_scan", q=q, dim=dim, n_lists=n_lists,
            page_rows=pr, table_width=tw, n_probes=p, k=k, dtype="float32")
        flops = _loop_matmul_flops(q, n_lists, dim)
        for _ in range(q):
            for _ in range(p * tw * pr):
                flops += 2 * dim + 1
        assert est["flops"] == flops
        # gather path: every query pays its own chain fetch
        assert est["bytes_read"] == (q * dim * 4 + n_lists * dim * 4
                                     + q * p * tw * pr * (dim * 4 + 8))

    @pytest.mark.parametrize("draw", range(2))
    def test_paged_pq(self, rng, draw):
        q, dim, pq_dim = int(rng.integers(1, 4)), 8, 4
        n_lists, pr, tw, p, k = 3, 2, int(rng.integers(1, 4)), 2, 3
        rd = pq_dim * math.ceil(dim / pq_dim)
        est = roofline.estimate_flops(
            "ivf_pq.paged_scan", q=q, dim=dim, n_lists=n_lists,
            page_rows=pr, table_width=tw, pq_dim=pq_dim, n_probes=p, k=k)
        flops = _loop_matmul_flops(q, n_lists, dim)
        flops += _loop_matmul_flops(q, rd, dim)
        flops += _loop_matmul_flops(q, 256, rd)          # LUT build
        for _ in range(q):
            for _ in range(p * tw * pr):
                flops += 2 * pq_dim                      # lookup + add
        assert est["flops"] == flops
        code_w = (pq_dim * 8 + 7) // 8
        assert est["bytes_read"] == (
            q * dim * 4 + n_lists * dim * 4 + rd * rd * 4
            + pq_dim * 256 * (rd // pq_dim) * 4
            + q * p * tw * pr * (code_w + 8))

    @pytest.mark.parametrize("draw", range(2))
    def test_paged_pallas_flat(self, rng, draw):
        q, dim, n_lists = int(rng.integers(1, 5)), 4, 3
        pr, tw, p, k = int(rng.integers(1, 4)), int(rng.integers(1, 4)), 2, 3
        est = roofline.estimate_flops(
            "ivf_flat.paged_pallas", q=q, dim=dim, n_lists=n_lists,
            page_rows=pr, table_width=tw, n_probes=p, k=k, dtype="float32")
        flops = _loop_matmul_flops(q, n_lists, dim)
        for _ in range(q):
            for _ in range(p * tw * pr):
                flops += 2 * dim + 1                     # contraction + bias
        assert est["flops"] == flops
        # strip-shared page streams: one chain fetch (payload + bias pool
        # rows) per STRIP, not per query — the win over the gather path
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (q * dim * 4 + n_lists * dim * 4
                                     + strips * tw * pr * (dim * 4 + 4))
        assert est["bytes_written"] == q * k * 8

    @pytest.mark.parametrize("draw", range(2))
    def test_paged_pallas_pq(self, rng, draw):
        q, dim, pq_dim = int(rng.integers(1, 4)), 8, 4
        n_lists, pr, tw, p, k = 3, 2, int(rng.integers(1, 4)), 2, 3
        rd = pq_dim * math.ceil(dim / pq_dim)
        est = roofline.estimate_flops(
            "ivf_pq.paged_pallas", q=q, dim=dim, n_lists=n_lists,
            page_rows=pr, table_width=tw, pq_dim=pq_dim, n_probes=p, k=k)
        flops = _loop_matmul_flops(q, n_lists, dim)
        flops += _loop_matmul_flops(q, rd, dim)          # query rotation
        for _ in range(q):
            for _ in range(p * tw * pr):
                flops += 2 * rd + 1        # int8-cache contraction + bias
        assert est["flops"] == flops
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (
            q * dim * 4 + n_lists * dim * 4 + rd * rd * 4
            + strips * tw * pr * (rd + 4))               # int8 cache + bias
        assert est["bytes_written"] == q * k * 8

    @pytest.mark.parametrize("draw", range(2))
    def test_paged_pallas_bq(self, rng, draw):
        q, dim = int(rng.integers(1, 4)), 16
        n_lists, pr, tw, p, k = 3, 2, int(rng.integers(1, 4)), 2, 3
        rd = math.ceil(dim / 8) * 8
        est = roofline.estimate_flops(
            "ivf_bq.paged_pallas", q=q, dim=dim, n_lists=n_lists,
            page_rows=pr, table_width=tw, n_probes=p, k=k)
        flops = _loop_matmul_flops(q, n_lists, dim)
        flops += _loop_matmul_flops(q, rd, dim)          # query rotation
        for _ in range(q):
            for _ in range(p * tw * pr):
                flops += 2 * rd + 2          # ±1 contraction + scale + bias
        assert est["flops"] == flops
        strips = math.ceil(q * p / roofline.STRIP_C)
        assert est["bytes_read"] == (
            q * dim * 4 + n_lists * dim * 4 + rd * rd * 4
            + strips * tw * pr * (rd // 8 + 4 + 4))  # codes + scale + bias
        assert est["bytes_written"] == q * k * 8

    @pytest.mark.parametrize("draw", range(2))
    def test_cagra_fused_hop(self, rng, draw):
        q, w, deg = int(rng.integers(1, 5)), 2, int(rng.integers(2, 5))
        pdim, itopk, hops = int(rng.integers(2, 6)), 4, int(rng.integers(1, 3))
        est = roofline.estimate_flops(
            "cagra.fused_hop", q=q, width=w, degree=deg, proj_dim=pdim,
            itopk=itopk, hops=hops)
        b = w * deg
        flops = 0
        for _ in range(hops):
            flops += _loop_matmul_flops(q, b, pdim)       # ip
            flops += _loop_matmul_flops(q, b, pdim)       # norm
            flops += 2 * _loop_matmul_flops(q, itopk, itopk + b)  # one-hots
        assert est["flops"] == flops
        assert est["bytes_read"] == hops * (
            q * b * 4 + q * b * pdim + q * pdim * 4 + 3 * q * itopk * 4)
        assert est["bytes_written"] == hops * 3 * q * itopk * 4

    def test_serving_scatter(self):
        est = roofline.estimate_flops(
            "serving.scatter", n_rows=5, dim=16, payload_width=16,
            payload_dtype="float32")
        assert est["flops"] == 0
        assert est["bytes_read"] == 5 * 16 * 4
        # pow2 bucket × (payload + id + aux + scan bias)
        assert est["bytes_written"] == 8 * (16 * 4 + 12)
        # kind-specific extra pool row (PQ decoded cache / BQ scale)
        est = roofline.estimate_flops(
            "serving.scatter", n_rows=5, dim=16, payload_width=16,
            payload_dtype="uint8", extra_row_bytes=24)
        assert est["bytes_written"] == 8 * (16 + 12 + 24)

    def test_unknown_entry_raises(self):
        with pytest.raises(ValueError, match="unknown roofline entry"):
            roofline.estimate_flops("hnsw.search", q=1)

    def test_strip_c_pins_kernel_constant(self):
        # the strip byte models share their fetch across STRIP_C query
        # slots — mirrored as a plain constant so the model stays
        # importable in jax-free parents. This pin (against the kernel's
        # OWN tuned constant, not a copy) is what catches a retune.
        assert roofline.STRIP_C == strip_scan.C


# ---------------------------------------------------------------------------
# peaks + bound
# ---------------------------------------------------------------------------


class TestPeaksAndBound:
    def test_env_override_wins(self, peaks_env):
        peaks = roofline.platform_peaks()
        assert peaks["source"] == "env"
        assert peaks["peak_flops"] == 1e12 and peaks["peak_bw"] == 1e11

    def test_partial_env_override_is_ignored(self, monkeypatch):
        # regression (review r15): one knob set without the other must
        # not fold a synthetic peak into the table/unknown branch — the
        # provenance field would certify a half-made-up denominator
        monkeypatch.setenv(roofline.PEAK_FLOPS_ENV, "1e12")
        monkeypatch.delenv(roofline.PEAK_BW_ENV, raising=False)
        peaks = roofline.platform_peaks()
        assert peaks["source"] in ("table", "unknown")
        if peaks["source"] == "unknown":
            assert peaks["peak_flops"] == 0.0 and peaks["peak_bw"] == 0.0
        else:
            row = next(r for r in roofline._PEAK_TABLE
                       if r[0] in peaks["device_kind"].lower())
            assert (peaks["peak_flops"], peaks["peak_bw"]) == row[1:]

    def test_unknown_peaks_are_honest(self, monkeypatch):
        monkeypatch.delenv(roofline.PEAK_FLOPS_ENV, raising=False)
        monkeypatch.delenv(roofline.PEAK_BW_ENV, raising=False)
        # CPU device_kind matches no table row
        util = roofline.utilization(
            "brute_force.search", measured_s=0.01, q=4, n=100, dim=8, k=3)
        assert util["bound"] == roofline.BOUND_UNKNOWN
        assert util.get("peaks_unknown") is True
        assert util["mxu_utilization"] is None
        assert util["hbm_bw_utilization"] is None
        # achieved throughput needs no denominator — still reported
        assert util["achieved_gflops"] > 0

    def test_unlisted_tpu_kind_is_an_error(self, monkeypatch):
        monkeypatch.delenv(roofline.PEAK_FLOPS_ENV, raising=False)
        monkeypatch.delenv(roofline.PEAK_BW_ENV, raising=False)
        monkeypatch.setattr(roofline, "_device",
                            lambda: ("tpu", "TPU v99 experimental"))
        with pytest.raises(ValueError, match="TPU v99 experimental"):
            roofline.platform_peaks()
        # a listed kind still reads the table
        monkeypatch.setattr(roofline, "_device", lambda: ("tpu", "TPU v5 lite"))
        assert roofline.platform_peaks()["source"] == "table"

    def test_bound_verdicts(self, peaks_env):
        # compute-heavy: huge dim → intensity far above the 10 flop/byte
        # ridge of the synthetic peaks
        cu = roofline.utilization("brute_force.search", q=64, n=4096,
                                  dim=4096, k=4)
        assert cu["bound"] == roofline.BOUND_COMPUTE
        assert cu["predicted_bound_s"] == pytest.approx(
            cu["flops"] / 1e12)
        # memory-only: the scatter has zero flops
        mu = roofline.utilization("serving.scatter", n_rows=8, dim=16,
                                  payload_width=16)
        assert mu["bound"] == roofline.BOUND_MEMORY
        assert mu["predicted_bound_s"] == pytest.approx(mu["bytes"] / 1e11)

    def test_utilization_measured_fold(self, peaks_env):
        est = roofline.estimate_flops("brute_force.search", q=8, n=512,
                                      dim=32, k=4)
        util = roofline.utilization("brute_force.search", measured_s=1e-3,
                                    q=8, n=512, dim=32, k=4)
        assert util["achieved_gflops"] == pytest.approx(
            est["flops"] / 1e-3 / 1e9, rel=1e-3)
        assert util["mxu_utilization"] == pytest.approx(
            est["flops"] / 1e-3 / 1e12, rel=1e-3)
        assert util["hbm_bw_utilization"] == pytest.approx(
            est["bytes"] / 1e-3 / 1e11, rel=1e-3)
        assert 0 < util["model_to_measured"] <= 1.0 + 1e-9

    def test_peak_table_selects_generation(self):
        # the table itself: a v5e-kind string resolves to the v5e row,
        # and the lite variant outranks the base v5 row
        for pat, pf, pb in roofline._PEAK_TABLE:
            if pat == "v5e":
                assert (pf, pb) == (197e12, 819e9)
        low = "tpu v5 lite".lower()
        hit = next((row for row in roofline._PEAK_TABLE if row[0] in low))
        assert hit[1] == 197e12


# ---------------------------------------------------------------------------
# occupancy: exact values for hand-built ragged layouts
# ---------------------------------------------------------------------------


class TestOccupancy:
    def test_strip_occupancy_hand_layout(self):
        # lens [700, 100, 512, 0], m=1024: pow2 block widths are
        # [1024, 512, 512, 512] → two classes ((1,1) ×3 lists, (2,1) ×1);
        # q=4, p=2 → 8 pairs → 1 best-case strip; static caps bucket to 8
        # per class → 16 padded strips.
        occ = strip_scan.occupancy_stats([700, 100, 512, 0], 1024, 4, 2)
        assert occ["grid"] == [[8, 1, 1], [8, 1, 2]]
        assert occ["strips_padded"] == 16
        assert occ["strips_real_bestcase"] == 1
        assert occ["padded_strip_fraction"] == pytest.approx(
            1 - 1 / 16, abs=1e-4)
        assert occ["tile_fill"] == pytest.approx(8 / 192, abs=1e-4)
        # scanned rows: 2·512 + 512 + 512 + 512 = 2560; real 1312
        assert occ["padded_row_fraction"] == pytest.approx(
            1 - 1312 / 2560, abs=1e-4)
        assert occ["storage_padded_fraction"] == pytest.approx(
            1 - 1312 / 4096, abs=1e-4)
        assert occ["q_tile"] == 4 and occ["tiles"] == 1

    def test_strip_occupancy_full_lists_no_row_padding(self):
        occ = strip_scan.occupancy_stats([512, 512], 512, 192, 1)
        assert occ["padded_row_fraction"] == 0.0
        assert occ["storage_padded_fraction"] == 0.0
        # 192 pairs = exactly one full strip
        assert occ["strips_real_bestcase"] == 1
        assert occ["tile_fill"] == 1.0

    def test_bq_occupancy_delegates_with_code_width(self):
        occ = bq_scan.occupancy_stats([700, 100, 512, 0], 1024, 4, 2,
                                      rot_dim=64)
        base = strip_scan.occupancy_stats([700, 100, 512, 0], 1024, 4, 2,
                                          dim=64)
        assert occ["code_bytes_per_entry"] == 8
        assert occ["padded_row_fraction"] == base["padded_row_fraction"]
        assert occ["grid"] == base["grid"]

    def test_paged_occupancy_hand_layout(self):
        """Hand-counted paged planner stats: 4 lists at W=4, R=32, chains
        [2, 1, 0, 4], 150 live rows, 30 tombstones."""
        occ = strip_scan.paged_occupancy_stats(
            table_width=4, page_rows=32, chain_pages=[2, 1, 0, 4],
            live_rows=150, tombstones=30, q=4, p=2, k=3, row_bytes=64)
        # plan: kf=3 < MC ⇒ ppf grows to cover min(MC, 4096) or W: ppf=4,
        # n_sub=1, w=128
        assert occ["pages_per_fetch"] == 4 and occ["n_sub"] == 1
        assert occ["w"] == 128
        chained_slots = (2 + 1 + 0 + 4) * 32
        assert occ["page_fill"] == pytest.approx(150 / chained_slots,
                                                 abs=1e-4)
        assert occ["tombstone_fraction"] == pytest.approx(
            30 / chained_slots, abs=1e-4)
        assert occ["chain_fill"] == pytest.approx(7 / 16, abs=1e-4)
        assert occ["capacity_slots"] == 4 * 4 * 32
        # all 4·2 pairs fit one strip (C=192): best case 1 real strip
        assert occ["strips_real_bestcase"] == 1
        assert 0.0 <= occ["padded_strip_fraction"] < 1.0

    def test_cagra_occupancy(self):
        occ = cagra_hop.occupancy_stats(100, 32, 4, 16, 32, 64)
        assert occ["q_pad"] == 128 and occ["grid"] == [4]
        assert occ["padded_row_fraction"] == pytest.approx(28 / 128,
                                                           abs=1e-4)
        assert occ["tile_fill"] == pytest.approx(100 / 128, abs=1e-4)
        assert occ["block"] == [32, 64, 32]
        assert occ["mxu_m_fill"] == pytest.approx(0.25)
        # block-multiple q: zero padding
        occ = cagra_hop.occupancy_stats(128, 32, 4, 16, 32, 64)
        assert occ["padded_row_fraction"] == 0.0


# ---------------------------------------------------------------------------
# XLA cost_analysis cross-check
# ---------------------------------------------------------------------------


class TestXlaCrossCheck:
    def test_matmul_flops_within_band(self):
        m, n, kdim = 64, 16, 32

        @jax.jit
        def f(a, b):
            return a @ b

        a = jnp.zeros((m, kdim), jnp.float32)
        b = jnp.zeros((kdim, n), jnp.float32)
        u0 = obs_compile.unexplained_retraces()
        cost = roofline.xla_cost_analysis(f, a, b)
        # the analysis lowering must never fabricate an unexplained
        # retrace (it rides suppress_analysis)
        assert obs_compile.unexplained_retraces() == u0
        if cost is None:
            pytest.skip("backend provides no cost_analysis flops")
        model = 2 * m * n * kdim
        # documented band: 2× — the compiler may count FMA as one flop,
        # fold constants, or fuse neighbors; grosser disagreement means
        # the model (or the reading) is wrong
        assert model / 2 <= cost["flops"] <= model * 2, (cost, model)

    def test_unavailable_backend_degrades_to_none(self):
        class NotJitted:
            def lower(self, *a, **k):
                raise RuntimeError("no lowering here")

        assert roofline.xla_cost_analysis(NotJitted()) is None


# ---------------------------------------------------------------------------
# summary + report
# ---------------------------------------------------------------------------


def _tiny_flat(rng, n=600, dim=16, n_lists=4):
    X = rng.standard_normal((n, dim)).astype(np.float32)
    return X, ivf_flat.build(X, ivf_flat.IvfFlatParams(
        n_lists=n_lists, list_size_cap=0))


class TestSummary:
    def test_summary_has_no_measured_leg(self, telemetry, rng, peaks_env):
        X, idx = _tiny_flat(rng)
        ivf_flat.search(idx, X[:4], 3, n_probes=2)
        row = roofline.summary()["entries"]["ivf_flat.search"]
        assert row["measured_s"] is None
        assert row["bound"] in (roofline.BOUND_COMPUTE,
                                roofline.BOUND_MEMORY)
        assert row["dispatches"] >= 1
        assert not any(k.startswith("dispatch.")
                       for k in obs.snapshot()["histograms"])


class TestNoopGate:
    def test_telemetry_off_means_zero_roofline_work(self, rng):
        obs.disable()
        obs.reset()
        roofline.reset()
        X, idx = _tiny_flat(rng)
        ivf_flat.search(idx, X[:4], 3, n_probes=2)
        bf = brute_force.build(X)
        brute_force.search(bf, X[:4], 3)
        assert roofline.entries() == {}
        assert not any(k.startswith("roofline.")
                       for k in obs.snapshot()["gauges"])
        # a stray direct call is one branch, no state
        roofline.note_dispatch("brute_force.search",
                               {"q": 1, "n": 1, "dim": 1, "k": 1})
        assert roofline.entries() == {}


class TestReportSection:
    def test_collect_carries_validating_roofline(self, telemetry, rng):
        X, idx = _tiny_flat(rng)
        ivf_flat.search(idx, X[:4], 3, n_probes=2)
        rep = obs_report.collect()
        roof = rep["roofline"]
        assert roof and "ivf_flat.search" in roof["entries"]
        problems = obs_report.validate(rep, require_classes=())
        assert not [p for p in problems if "roofline" in p], problems

    def test_validate_flags_malformed_records(self):
        bad = {"roofline": {
            "peaks": {"source": "made-up"},
            "entries": {"x.search": {"flops": float("nan"), "bytes": 0,
                                     "bound": "sideways"}}}}
        problems = obs_report.validate(bad, require_classes=())
        text = "\n".join(problems)
        assert "provenance" in text
        assert "flops" in text and "bytes" in text and "bound" in text

    def test_validate_rejects_bound_claims_without_peaks(self):
        bad = {"roofline": {
            "peaks": {"source": "unknown"},
            "entries": {"x.search": {"flops": 1.0, "bytes": 1.0,
                                     "bound": "compute"}}}}
        problems = obs_report.validate(bad, require_classes=())
        assert any("unknown peaks" in p for p in problems)

    def test_lenient_on_absent_section(self):
        assert not [p for p in obs_report.validate({}, require_classes=())
                    if "roofline" in p]


class TestSearchConveniences:
    def test_utilization_search_and_note_search(self, telemetry, rng):
        X, idx = _tiny_flat(rng)
        util = roofline.utilization_search(idx, q=8, k=3, n_probes=2)
        direct = roofline.estimate_flops(
            "ivf_flat.search", q=8, k=3, n_probes=2, dim=idx.dim,
            n_lists=idx.n_lists, max_list_size=idx.max_list_size,
            dtype=str(idx.list_data.dtype))
        assert util["flops"] == direct["flops"]
        assert util["bytes"] == direct["bytes"]
        roofline.note_search(idx, q=8, k=3, n_probes=2)
        assert roofline.entries()["ivf_flat.search"]["est"]["flops"] == \
            direct["flops"]
        # regression (review r15): note_search must project the layout
        # onto the model's keyword surface — a raw index_layout dict
        # (norms/plan_cache keys) would make summary() raise for the
        # entry and poison the whole report section
        row = roofline.summary()["entries"]["ivf_flat.search"]
        assert row["flops"] == direct["flops"]
        assert row["bound"] in ("compute", "memory", "unknown")

    def test_summary_means_over_mixed_shapes(self, telemetry, rng,
                                             peaks_env):
        # regression (review r15): a window with MIXED dispatch shapes
        # (the serving bucket ramp) must fold to per-dispatch means —
        # not the LAST shape's model against the mean of ALL durations
        X, idx = _tiny_flat(rng)
        roofline.note_search(idx, q=1, k=3, n_probes=2)
        roofline.note_search(idx, q=63, k=3, n_probes=2)
        f1 = roofline.estimate_search_flops(idx, q=1, k=3, n_probes=2)
        f63 = roofline.estimate_search_flops(idx, q=63, k=3, n_probes=2)
        row = roofline.summary()["entries"]["ivf_flat.search"]
        assert row["dispatches"] == 2
        assert row["flops"] == pytest.approx(
            (f1["flops"] + f63["flops"]) / 2)
        assert row["bytes"] == pytest.approx(
            (f1["bytes"] + f63["bytes"]) / 2)
        assert row["last_shapes"]["q"] == 63

    def test_entry_wiring_notes_search_dispatches(self, telemetry, rng):
        X, idx = _tiny_flat(rng)
        ivf_flat.search(idx, X[:4], 3, n_probes=2)
        bf = brute_force.build(X)
        brute_force.search(bf, X[:4], 3)
        ents = roofline.entries()
        assert "ivf_flat.search" in ents
        assert "brute_force.search" in ents
        assert ents["brute_force.search"]["shapes"]["n"] == X.shape[0]
