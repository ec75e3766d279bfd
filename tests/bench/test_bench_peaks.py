"""The table of peaks: keyed by JAX's device kind, with its source; a kind
that is not there is an error, never a default."""
import pytest

from bench import harness


def test_v5e_peaks():
    p = harness.peak_of("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_kind_raises(kind):
    with pytest.raises(KeyError):
        harness.peak_of(kind)
