"""The chip's peaks, which every architecture's counts are read against.
The counts themselves are each architecture module's
(``test_harness_seam.py`` checks the dense one's)."""
import pytest

import counts


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peak_for("TPU v99")
    assert counts.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
