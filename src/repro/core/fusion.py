"""Fusion strategy configuration (paper §IV-C) and the execution paths it
selects for CRONet inference.

Execution paths, in increasing fusion level:
  none : layer-by-layer, every intermediate forced through HBM — the
         conventional-accelerator baseline the paper compares against
         (each op is its own jit; device_get/put between layers makes the
         DRAM round-trips real, not just conceptual).
  l1   : per-op kernels with activations fused (SiLU inside conv/GEMM).
  l2l3 : the single megakernel (kernels/cronet_pipeline.py) — everything
         on-chip, scratch staging for reshaped intermediates.

benchmarks/scaling.py measures all three; the dry-run HLO of l2l3 proves
the two-touch HBM contract (one input DMA in, one output store).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.cronet import CRONetConfig
from repro.core import cronet
from repro.kernels import conv as kconv
from repro.kernels import gemm as kgemm
from repro.kernels import pool as kpool
from repro.kernels import cronet_pipeline


@dataclasses.dataclass(frozen=True)
class FusionConfig:
    l1: bool = True     # element-wise ops fused into compute kernels
    l2: bool = True     # layer->layer streaming (no HBM between subgraphs)
    l3: bool = True     # oversized/reshaped intermediates staged on-chip

    @property
    def path(self) -> str:
        if self.l2 and self.l3:
            return "l2l3"
        if self.l1:
            return "l1"
        return "none"


def infer(cfg: CRONetConfig, params: Dict, load_vol, hist,
          fusion: FusionConfig = FusionConfig(),
          interpret: Optional[bool] = None):
    """CRONet inference under a fusion config. load_vol: (4,H,W,1);
    hist: (T,ny,nx,1); returns (p,). ``interpret=None`` lets every kernel
    pick the Pallas interpreter on a CPU backend only
    (``repro.kernels.resolve_interpret``)."""
    if fusion.path == "l2l3":
        return cronet_pipeline.cronet_fused(cfg, params, load_vol, hist,
                                            interpret=interpret)
    return _layerwise(cfg, params, load_vol, hist, l1=fusion.l1,
                      interpret=interpret)


def _layerwise(cfg, params, load_vol, hist, l1: bool,
               interpret: Optional[bool]):
    """Per-op kernel execution; with l1=False each activation is a separate
    pass over the tensor (the unfused baseline)."""
    tr, br = params["trunk"], params["branch"]
    act = (lambda x: x) if l1 else jax.nn.silu

    def maybe(x):        # activation handling: fused vs separate pass
        return x if l1 else jax.nn.silu(x)

    # Trunk
    t = kconv.conv3d(load_vol[None], tr["conv1"], depth_padding="causal_same",
                     fuse_silu=l1, interpret=interpret)
    if not l1:
        t = jax.nn.silu(t)
    t = kconv.conv3d(t, tr["conv2"], depth_padding="same", fuse_silu=l1,
                     interpret=interpret)
    if not l1:
        t = jax.nn.silu(t)
    t = kpool.adaptive_avg_pool3d(t, cfg.t_pool, interpret=interpret)
    tf = t.reshape(1, -1)
    tf = kgemm.gemm(tf, tr["fc1"], activation="silu" if l1 else None,
                    interpret=interpret)
    if not l1:
        tf = jax.nn.silu(tf)
    trunk_out = kgemm.gemm(tf, tr["fc2"], interpret=interpret)

    # Branch (time-distributed)
    T = cfg.hist_len
    x = hist  # (T, ny, nx, 1) — T rides the kernel batch grid
    x = kconv.conv2d(x, br["conv1"], fuse_silu=l1, interpret=interpret)
    if not l1:
        x = jax.nn.silu(x)
    x = kconv.conv2d(x, br["conv2"], fuse_silu=l1, interpret=interpret)
    if not l1:
        x = jax.nn.silu(x)
    x = kpool.maxpool2d(x, 2, interpret=interpret)
    x = kpool.adaptive_avg_pool2d(x, cfg.b_pool, interpret=interpret)
    feats = x.reshape(T, -1)                       # (T, 32)

    h = jnp.zeros((1, cfg.rnn_hidden), feats.dtype)
    for i in range(T):                              # RNN on GEMM (paper §IV-D3)
        xh = kgemm.gemm(feats[i:i + 1], br["rnn_wx"], interpret=interpret)
        hh = kgemm.gemm(h, br["rnn_wh"], interpret=interpret)
        h = jnp.tanh(xh + hh)
    bf = kgemm.gemm(h, br["fc1"], activation="silu" if l1 else None,
                    interpret=interpret)
    if not l1:
        bf = jax.nn.silu(bf)
    branch_out = kgemm.gemm(bf, br["fc2"], interpret=interpret)

    return (branch_out * trunk_out)[0]
