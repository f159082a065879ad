"""The peak table and the search's roofline count at the cells' shapes."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import pytest

import peaks
import work


def test_missing_device_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99")


def test_v5e_peaks_and_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_whype_step_is_compute_bound():
    ops, nbytes = work.search_work(slots=2, trials_per_slot=512,
                                   classes_on_chip=102_400, cores_on_chip=1024,
                                   dim=2048)
    assert ops == 2 * 1024 * 102_400 * 2048          # 4.29e11
    assert nbytes == 1024 * 256 + 2 * 102_400 * 256 + 1024 * 1024 * 8
    t, bound = work.least_time(ops, nbytes, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(1.0926e-3, rel=1e-3)


def test_table1_step():
    ops, nbytes = work.search_work(slots=32, trials_per_slot=64,
                                   classes_on_chip=6400, cores_on_chip=64,
                                   dim=512)
    assert ops == 2 * 2048 * 6400 * 512
    t, bound = work.least_time(ops, nbytes, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(ops / 393e12)


def test_four_chip_step_per_chip():
    ops, _ = work.search_work(slots=8, trials_per_slot=512,
                              classes_on_chip=25_600, cores_on_chip=256,
                              dim=2048)
    assert ops == 2 * 4096 * 25_600 * 2048
