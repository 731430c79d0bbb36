"""bench/flops.py against the MAC counts of paper Table I."""
import json
import os

import pytest

from bench import flops

ARCH = json.load(open(os.path.join(os.path.dirname(__file__), "..", "configs",
                                   "cronet-large.json")))["cronet"]

# Table I, as configs/cronet.py's docstring gives it: small / medium / large
TABLE_I = {
    "trunk/conv3d1": ("294K", "562K", "1.1M"),
    "trunk/conv3d2": ("12.6M", "24M", "47.2M"),
    "branch/conv2d1": ("432K", "864K", "1.7M"),
    "branch/conv2d2": ("13.8M", "27.6M", "55.3M"),
    "branch/rnn": ("61.4K", "61.4K", "61.4K"),
}
MESHES = ((30, 10), (30, 20), (60, 20))


def _value(text):
    return float(text[:-1]) * {"K": 1e3, "M": 1e6}[text[-1]]


def test_forward_macs_match_table_i():
    for i, (nx, ny) in enumerate(MESHES):
        macs = flops.forward_macs(dict(ARCH, nelx=nx, nely=ny))
        for layer, printed in TABLE_I.items():
            # Table I prints two or three significant digits, cut off
            assert macs[layer] == pytest.approx(_value(printed[i]), rel=0.02), \
                (layer, nx, macs[layer])


def test_forward_macs_match_the_program_count():
    from repro.configs.cronet import get_cronet_config
    from repro.core import cronet

    for size, (nx, ny) in zip(("small", "medium", "large"), MESHES):
        prog = cronet.count_macs(get_cronet_config(size))
        ours = flops.forward_macs(dict(ARCH, nelx=nx, nely=ny))
        assert prog == ours


def test_forward_flops_large():
    # about 212 MFLOP per slot at 60x20, 55 MFLOP at 30x10
    assert flops.forward_flops(dict(ARCH, nelx=60, nely=20)) == 211_628_736
    assert flops.forward_flops(dict(ARCH, nelx=30, nely=10)) == 55_164_096
