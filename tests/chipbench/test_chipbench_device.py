"""A device the peaks table does not know, a CPU, or too few chips are
refused before any work."""
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..", "..")))

import pytest  # noqa: E402

from chipbench import device  # noqa: E402


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind

    def memory_stats(self):
        return {"peak_bytes_in_use": 123}


def test_v5e_is_known_with_its_published_peaks():
    p = device.check([_Dev("tpu", "TPU v5 lite")], 1)
    assert (p.flops_per_s, p.bytes_per_s) == (197e12, 819e9)


@pytest.mark.parametrize("devs,chips", [
    ([_Dev("tpu", "TPU v9 imaginary")], 1),   # kind not in the table
    ([_Dev("cpu", "cpu")], 1),                 # no accelerator
    ([_Dev("gpu", "TPU v5 lite")], 1),         # platform disagrees
    ([_Dev("tpu", "TPU v5 lite")], 4),         # too few chips
    ([], 1),
])
def test_refused(devs, chips):
    with pytest.raises(device.NoChip):
        device.check(devs, chips)


def test_describe_reports_the_fullest_chip():
    d = device.describe([_Dev("tpu", "TPU v5 lite")] * 2, 1)
    assert d == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                 "memory_peak_bytes": 123}
