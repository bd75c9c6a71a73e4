"""Work counts and the peaks table against hand counts."""

import pytest

from bench import work


def test_flops_per_minor_by_hand():
    # ⅔·27 + 2 = 20 at m = 3; ⅔·1728 + 2 = 1154 at m = 12
    assert work.flops_per_minor(3) == pytest.approx(20.0)
    assert work.flops_per_minor(12) == pytest.approx(1154.0)


@pytest.mark.parametrize("m,n,mats,want", [
    (3, 5, 1, 10 * 20.0),              # C(5, 3) = 10 minors
    (3, 5, 4, 4 * 10 * 20.0),          # real matrices only
    (12, 32, 1, 225_792_840 * 1154.0),
    (6, 5, 3, 0.0),                    # m > n: no minors
])
def test_det_flops_by_hand(m, n, mats, want):
    assert work.det_flops(m, n, mats) == pytest.approx(want)


def test_det_bytes_reads_each_matrix_once():
    assert work.det_bytes(12, 32, 2) == 2 * 4 * (12 * 32 + 1)


def test_peaks_known_and_unknown_devices():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks("_source")


def test_roofline_names_its_bound():
    t, bound = work.roofline_seconds(197e12, 1.0, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.roofline_seconds(1.0, 819e9, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(1.0)
