"""Operation counts of the CRONet forward, from the layer shapes of paper
Table I, and the peak table lookup.

MACs are counted as Table I counts them: the trunk's first 3-D conv at
its three depth-valid positions, its second at all four depths; the
branch's convs over every one of the ``hist_len`` frames; the RNN's ten
unrolled steps; the four fully connected layers once. One MAC is two
operations."""
from __future__ import annotations


def forward_macs(c: dict) -> dict:
    """Per-layer MACs of one forward for one slot; ``c`` holds the widths
    and ``nelx``/``nely``."""
    h, w = c["nely"] + 1, c["nelx"] + 1
    t_feat = c["t_pool"][0] * c["t_pool"][1] * c["t_pool"][2] * c["t_c2"]
    b_feat = c["b_pool"][0] * c["b_pool"][1] * c["b_c2"]
    frames = c["hist_len"] * c["nely"] * c["nelx"]
    macs = {
        "trunk/conv3d1": (c["t_depth"] - 1) * h * w * (2 * 3 * 3 * c["t_c1"]),
        "trunk/conv3d2": c["t_depth"] * h * w * (3 * 3 * c["t_c1"] * c["t_c2"]),
        "trunk/fc1": t_feat * c["mid"],
        "trunk/fc2": c["mid"] * c["p"],
        "branch/conv2d1": frames * 3 * 3 * c["b_c1"],
        "branch/conv2d2": frames * 3 * 3 * c["b_c1"] * c["b_c2"],
        "branch/rnn": c["hist_len"] * c["rnn_hidden"] * (b_feat
                                                         + c["rnn_hidden"]),
        "branch/fc1": c["rnn_hidden"] * c["mid"],
        "branch/fc2": c["mid"] * c["p"],
    }
    macs["total"] = sum(macs.values())
    return macs


def forward_flops(c: dict) -> int:
    return 2 * forward_macs(c)["total"]


def peak(peaks: dict, device_kind: str, key: str) -> float:
    """A peak of ``bench/peaks.json`` for this device; an unknown device is
    an error, never a default."""
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(peaks['devices'])})")
    return float(peaks["devices"][device_kind][key])
