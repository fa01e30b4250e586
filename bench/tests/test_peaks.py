"""The peaks table: keyed by device kind, an unknown chip is an error."""

import pytest

from harness.peaks import PEAKS, SOURCE, peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.int8_ops == 394e12
    assert p.hbm_bytes_per_s == 819e9
    assert "v5e" in SOURCE


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
    assert "cpu" not in PEAKS
