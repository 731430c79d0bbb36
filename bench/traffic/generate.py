"""The one traffic generator: reads a mix's parameters and makes its requests.

A mix (``bench/traffic/<name>.json``) is either

- ``"loop": "closed"``: ``outstanding`` requests in flight at all times,
  each completion followed at once by the next submission; or
- ``"loop": "open"``: Poisson arrivals at ``rate_per_s``, each submitted
  when due whatever the system's state.

Every request is a top-edge point load on the MBB supports of the cell's
mesh: load node x in [0, nelx - 1), vertical magnitude -(0.5 ... 1.5), the
seeded point-load sampler of the serving benchmarks. ``n_iter`` is fixed
or ranges over ``[n_iter_min, n_iter_max]``.

Steadiness: each seed gets the same multiset of load nodes, iteration
counts and inter-arrival gaps, in its own order. Load nodes and iteration
counts cycle through seeded permutations of every admissible value; gaps
are the exponential distribution's quantiles at (k + 1/2) / 64, permuted
in blocks of 64. So the work of a run depends on the seed only through
the order of the requests and the load magnitudes, which scale a linear
problem and change no design.
"""
from __future__ import annotations

import math

import numpy as np

GAP_BLOCK = 64


def _cycled(rng, values, n: int) -> np.ndarray:
    values = np.asarray(values)
    reps = -(-n // len(values))
    return np.concatenate([rng.permutation(values) for _ in range(reps)])[:n]


def requests(mix: dict, nelx: int, n: int, seed: int) -> list:
    """The first ``n`` requests of the mix for this seed.

    Each is a dict with ``load_x`` (int), ``fy`` (float), ``n_iter`` (int)
    and, for an open loop, ``due_s``: seconds after the window opens."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 12])
    xs = _cycled(rng, np.arange(nelx - 1), n)
    fys = -(0.5 + rng.random(n))
    if "n_iter" in mix:
        iters = np.full(n, int(mix["n_iter"]))
    else:
        iters = _cycled(rng, np.arange(mix["n_iter_min"],
                                       mix["n_iter_max"] + 1), n)
    out = [{"load_x": int(x), "fy": float(fy), "n_iter": int(it)}
           for x, fy, it in zip(xs, fys, iters)]
    if mix["loop"] == "open":
        q = (np.arange(GAP_BLOCK) + 0.5) / GAP_BLOCK
        gaps = _cycled(rng, -np.log1p(-q) / float(mix["rate_per_s"]), n)
        for r, due in zip(out, np.cumsum(gaps) - gaps[0]):
            r["due_s"] = float(due)
    return out


def expected_count(mix: dict, seconds: float) -> int:
    """Requests an open loop makes due within ``seconds``, with margin."""
    return int(math.ceil(float(mix["rate_per_s"]) * seconds * 1.5)) + GAP_BLOCK
