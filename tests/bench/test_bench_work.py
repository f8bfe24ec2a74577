"""Work counts and the table of peaks, against hand-computed values."""
import pytest

from _benchcells import ROOT  # noqa: F401  (puts the repository on the path)
from bench import work as W


def test_min_hbm_bytes_268m_grid():
    # 3 fields, read once and written once, 4096*1024*64 f32 cells each
    assert W.min_hbm_bytes(4096, 1024, 64) == 2 * 3 * 4096 * 1024 * 64 * 4
    assert W.min_hbm_bytes(4096, 1024, 64) == 6_442_450_944


def test_min_hbm_bytes_counts_published_z_not_lane_padding():
    # Z=64 sits in 128 lanes on the chip; the work counts 64
    assert W.min_hbm_bytes(1, 1, 64) == 6 * 64 * 4
    assert W.field_bytes(64, 256, 64) == 64 * 256 * 64 * 4


def test_cell_substeps():
    assert W.cell_substeps(4096, 1024, 64, 12) == 268_435_456 * 12
    assert W.cell_substeps(8, 32, 64, 4 * 16) == 8 * 32 * 64 * 64


def test_least_seconds_at_v5e_peak():
    assert W.least_seconds(819_000_000_000, "TPU v5 lite") == pytest.approx(1.0)
    assert W.least_seconds(6_442_450_944, "TPU v5 lite", n_chips=4) \
        == pytest.approx(6_442_450_944 / (4 * 819e9))


def test_peaks_name_their_source():
    p = W.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5 lite "])
def test_peaks_refuse_an_unknown_device(kind):
    with pytest.raises(KeyError, match="no peaks"):
        W.peaks(kind)
