"""The peaks table."""
import pytest

from bench.peaks import peaks


def test_peaks_of_v5e():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")
