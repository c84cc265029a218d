"""The peaks table names its source and refuses a device it does not know."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import pytest

from chipbench.peaks import PEAKS, UnknownDevice, peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert (p.flops_per_s, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)


def test_every_entry_states_its_source():
    assert all(p.source for p in PEAKS.values())
