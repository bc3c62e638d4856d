import pytest

from benchmark import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.for_device("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    assert p["int32_vpu_ops_per_s"] is None  # unpublished: no share of it


@pytest.mark.parametrize("kind", ["TPU v9 imaginary", "cpu", "_about", ""])
def test_an_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError):
        peaks.for_device(kind)
