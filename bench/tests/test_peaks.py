"""The peak table: known devices resolve, unknown ones are an error."""
import json
import os

import pytest

from bench import flops

PEAKS = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                    "peaks.json")))


def test_v5e_peaks():
    assert flops.peak(PEAKS, "TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert flops.peak(PEAKS, "TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert PEAKS["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak(PEAKS, "cpu", "bf16_flops_per_s")
