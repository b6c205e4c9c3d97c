"""The sweep's work count on hand-worked shapes, and the peaks table."""
import pytest

from bench import peaks, roofline


def test_side_work_hand_worked():
    # n=2 rows, 3 other rows, 4 ratings, K=2, hyperprior drawn
    f, b = roofline.side_work(2, 3, 4, 2, fixed_prior=False)
    per_rating = 2 * 4 + 2 * 2                     # 2K^2 + 2K
    per_row = 4 + 2 + 8 / 3 + 3 * 4                # K^2 + K + K^3/3 + 3K^2
    hyper = 2 * 2 * 4 + 2 * 8                      # 2nK^2 + 2K^3
    assert f == pytest.approx(4 * per_rating + 2 * per_row + hyper)
    assert b == 8 * 4 + 4 * 2 * 3 + 2 * 4 * 2 * 2  # CSR, other, noise+out
    f2, b2 = roofline.side_work(2, 3, 4, 2, fixed_prior=True)
    assert f2 == pytest.approx(f - hyper)
    assert b2 == b + 4 * (4 + 2) * 2               # per-row prior


def test_sweep_work_kept_share_and_bound():
    f0, b0 = roofline.sweep_work(10, 5, 20, 7, 3, True, 0.0)
    f1, b1 = roofline.sweep_work(10, 5, 20, 7, 3, True, 1.0)
    assert f1 - f0 == pytest.approx(2 * 3 * 7)
    assert b1 - b0 == pytest.approx(2 * 4 * (9 + 3) * 15 + 12 * 7)
    pk = peaks.peaks("TPU v5 lite")
    t, bound = roofline.least_time(1.0, 819e9, pk)
    assert (t, bound) == (pytest.approx(1.0), "bytes")
    t, bound = roofline.least_time(197e12, 1.0, pk)
    assert (t, bound) == (pytest.approx(1.0), "flops")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
