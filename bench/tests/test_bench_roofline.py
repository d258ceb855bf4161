import pytest

from bench import roofline


def test_counts_by_hand():
    # 3 modes (10, 20, 30), nnz 100, R 4.
    assert roofline.mode_flops(100, 4, 3) == 1200
    # COO 100 * (4 + 12) = 1600; mode 0 reads factors 1, 2 (50 rows) and
    # writes 10 rows: 60 * 4 * 4 = 960.
    assert roofline.mode_bytes(100, 4, (10, 20, 30), 0) == 2560
    assert roofline.mode_bytes(100, 4, (10, 20, 30), 2) == 2560
    flops, nbytes = roofline.sweep_counts(100, 4, (10, 20, 30))
    assert (flops, nbytes) == (3600, 3 * 2560)


def test_vast_sweep_is_memory_bound_on_v5e():
    dims = (165400, 11400, 2, 100, 89)
    flops, nbytes = roofline.sweep_counts(18_932_249, 32, dims)
    # 5 * 18.9M * 32 * 5 operations against 5 * 18.9M * 24 bytes of COO.
    assert flops == 5 * 18_932_249 * 32 * 5
    assert nbytes > 5 * 18_932_249 * 24
    s = roofline.roofline_seconds(flops, nbytes, "TPU v5 lite")
    assert s == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_is_refused():
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        roofline.roofline_seconds(1, 1, "cpu")
