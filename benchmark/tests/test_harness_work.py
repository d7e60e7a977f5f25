"""The work counts and the peak table against hand counts."""

import json

import pytest

import roofline
import run


def test_scan_work_sift1m_batch():
    cfg = run.cell_spec("sift1m-ivf_flat.batch10k")["config"]
    flops, nbytes = roofline.scan_work(cfg, 10_000)
    # 10,000 queries x 16 probes x 1e6/1024 rows x 128 dims x 2
    assert flops == pytest.approx(2 * 10_000 * 16 * (1e6 / 1024) * 128)
    # every list is probed: the whole uint8 corpus once, plus the queries
    assert nbytes == pytest.approx(1e6 * 128 * 1 + 10_000 * 128 * 4)


def test_scan_work_one_served_dispatch():
    cfg = run.cell_spec("sift1m-ivf_flat.batch10k")["config"]
    flops, nbytes = roofline.scan_work(cfg, 8)
    rows = 8 * 16 * 1e6 / 1024           # 125,000 probed rows
    assert flops == pytest.approx(2 * rows * 128)
    assert nbytes == pytest.approx(rows * 128 + 8 * 128 * 4)


def test_roofline_share_and_bound():
    peaks = roofline.peaks_for("TPU v5 lite")
    share, bound = roofline.roofline_share(393e12, 819e9 * 2, 4.0, peaks)
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = roofline.roofline_share(393e12 * 3, 0.0, 3.0, peaks)
    assert (share, bound) == (pytest.approx(100.0), "compute")


def test_unknown_device_is_an_error(tmp_path):
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v99")
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"x": {}}))
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu", str(path))
