"""The fully on-chip CRONet inference megakernel — the paper's headline
contribution ("first end-to-end network fully realized on the AIE array")
in TPU form: ONE pallas_call executes the entire network with every weight
and every intermediate activation resident in VMEM. HBM is touched exactly
twice: the input DMA at kernel entry and the output store at exit — the
TPU equivalent of the paper's GMIO-only DRAM contract.

Layout. Every activation is a lane-dense 2-D tile: channels on sublanes,
the image flattened row-major on lanes with one zero column after each
row and a zero tail (``_Geometry``). A 3x3 SAME convolution is then nine
lane rotations of its input, each feeding one 2-D matmul
``(Cout, Cin) @ (Cin, L)`` (an outer product when Cin == 1); the zero
column and tail supply the padding, and a valid-lane mask re-zeroes them
between layers. Pooling layers are matmuls against constant pooling
matrices, and the FC/RNN layers are ``(1, K) @ (K, N)`` row products.
Nothing inside the kernel reshapes across the two tiled dims. The wrapper
flattens the inputs and re-lays the weights; under jit those are a few
small XLA ops per call.

Fusion mapping (paper §IV-C -> this kernel):
  L1: SiLU/Tanh applied in-register right after each conv/GEMM
      accumulation (no separate activation pass).
  L2: adjacent layers consume each other's values directly — inside one
      kernel there is no inter-layer buffer traffic to schedule.
  L3: the trunk's largest intermediate (conv2 output, four depth slices
      that the AAP3D windows re-read) is staged in an explicit VMEM
      scratch buffer — the Memory-Tile analogue.

VMEM at CRONet-large (60x20): the f32 trunk stage is 4 x 64 x 1408 x 4 B
= 1.4 MB; the re-laid weights, pooling constants and their double
buffers add about 5 MB. That is inside Mosaic's default scoped-VMEM
limit on v5e (16 MiB), which is the budget a kernel gets unless it asks
for more (``vmem_limit_bytes``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import pad_to_multiple
from repro.configs.cronet import CRONetConfig
from repro.core.cronet import _adaptive_bounds
from repro.kernels import resolve_interpret

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """Flattened image layout: row y, column x -> lane y*stride + x."""
    rows: int
    cols: int

    @property
    def stride(self) -> int:
        return self.cols + 1                 # one zero column per row

    @property
    def lanes(self) -> int:
        # a rotation by up to stride+1 lanes must wrap into zeros
        return pad_to_multiple(self.rows * self.stride + self.stride + 1,
                               128)

    def taps(self):
        """Lane offset of each 3x3 tap (i, j), row-major."""
        return [(i - 1) * self.stride + (j - 1)
                for i in range(3) for j in range(3)]

    def flatten(self, img):
        """(..., rows, cols) -> (..., lanes) f32."""
        lead = [(0, 0)] * (img.ndim - 2)
        x = jnp.pad(img.astype(_F32), lead + [(0, 0), (0, 1)])
        x = x.reshape(*img.shape[:-2], self.rows * self.stride)
        return jnp.pad(x, lead + [(0, self.lanes - x.shape[-1])])

    def lane_of(self, y, x) -> int:
        return y * self.stride + x

    def valid_mask(self) -> np.ndarray:
        m = np.zeros((1, self.lanes), np.float32)
        for y in range(self.rows):
            m[0, self.lane_of(y, 0):self.lane_of(y, self.cols)] = 1.0
        return m


@functools.lru_cache(maxsize=16)
def _constants(cfg: CRONetConfig):
    """Per-configuration constant tiles: valid-lane masks, the AAP3D
    spatial pooling matrix, the branch maxpool+AAP(1,1) weights, and the
    block-diagonal mask / group-sum matrix that turn the trunk FC1 over
    the flattened pooled features into 2-D matmuls."""
    trunk, branch = _Geometry(*cfg.nodes), _Geometry(cfg.nely, cfg.nelx)
    _, ph, pw = cfg.t_pool
    hs, he = _adaptive_bounds(trunk.rows, ph)
    ws, we = _adaptive_bounds(trunk.cols, pw)
    pool = np.zeros((ph * pw, trunk.lanes), np.float32)
    for i in range(ph):
        for j in range(pw):
            n = (he[i] - hs[i]) * (we[j] - ws[j])
            for y in range(hs[i], he[i]):
                a = trunk.lane_of(y, ws[j])
                pool[i * pw + j, a:a + we[j] - ws[j]] = 1.0 / n
    hh, ww = cfg.nely // 2, cfg.nelx // 2
    anchors = np.zeros((1, branch.lanes), np.float32)
    for i in range(hh):
        for j in range(ww):
            anchors[0, branch.lane_of(2 * i, 2 * j)] = 1.0 / (hh * ww)
    lane = np.arange(ph * pw * cfg.mid)
    diag = (lane[None, :] // cfg.mid
            == np.arange(ph * pw)[:, None]).astype(np.float32)
    group = (lane[None, :] % cfg.mid
             == np.arange(cfg.mid)[:, None]).astype(np.float32)
    return (trunk.valid_mask(), branch.valid_mask(), pool, anchors, diag,
            group)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _dot_nt(a, b):
    """a (M, K) x b (N, K) -> (M, N): contract the lane dims."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _make_kernel(cfg: CRONetConfig):
    T = cfg.hist_len
    trunk, branch = _Geometry(*cfg.nodes), _Geometry(cfg.nely, cfg.nelx)
    depth = cfg.t_depth
    ds, de = _adaptive_bounds(depth, cfg.t_pool[0])

    def gather(x, off, lanes):          # out[q] = x[q + off]
        return x if off == 0 else pltpu.roll(x, (-off) % lanes, 1)

    def conv_cin1(rows, w_ref, geo):
        """Cin == 1 conv as outer products: rows are (1, L) input planes,
        w_ref (9 * len(rows), Cout, 1) their taps -> (Cout, L)."""
        acc = None
        for r, row in enumerate(rows):
            for t, off in enumerate(geo.taps()):
                term = (w_ref[9 * r + t].astype(_F32)
                        * gather(row, off, geo.lanes))
                acc = term if acc is None else acc + term
        return acc

    def conv(x, w_ref, geo):
        """3x3 SAME conv: x (Cin, L), w_ref (9, Cout, Cin) -> (Cout, L)."""
        acc = None
        for t, off in enumerate(geo.taps()):
            term = _dot(w_ref[t].astype(_F32), gather(x, off, geo.lanes))
            acc = term if acc is None else acc + term
        return acc

    def kernel(load_ref, hist_ref, tc1_ref, tc2_ref, tf1_ref, tf2_ref,
               bc1_ref, bc2_ref, rwx_ref, rwh_ref, bf1_ref, bf2_ref,
               tmask_ref, bmask_ref, pool_ref, anchor_ref, diag_ref,
               group_ref, out_ref, trunk_stage):
        # One grid step == one batch slot: load/hist/out blocks carry a
        # leading block dim of 1; weights are the same full block at every
        # step (they stay VMEM-resident across the whole batch — the
        # serving amortization the paper's GMIO contract enables).
        # ---------------- TrunkNet ----------------
        tmask = tmask_ref[...]

        def trunk_depth(d, carry):
            # conv3d-1 k=(2,3,3), causal-same depth: planes d and d+1 (the
            # wrapper appends the zero plane past the end)
            planes = [load_ref[0, pl.ds(d + r, 1), :] for r in range(2)]
            t1 = jax.nn.silu(conv_cin1(planes, tc1_ref, trunk)) * tmask
            # conv3d-2 k=(1,3,3): per-depth 2D conv; L3: stage to VMEM
            trunk_stage[d] = jax.nn.silu(conv(t1, tc2_ref, trunk))
            return carry

        jax.lax.fori_loop(0, depth, trunk_depth, 0)

        # AAP3D: depth windows averaged, then the spatial (5,5) windows as
        # one pooling matmul per window; FC1 over the (depth, i, j, c)
        # flattening as block-diagonal matmuls (no in-kernel flatten)
        fc1 = None
        for k in range(cfg.t_pool[0]):
            sl = trunk_stage[ds[k]]
            for d in range(ds[k] + 1, de[k]):
                sl = sl + trunk_stage[d]
            sl = sl * (1.0 / (de[k] - ds[k]))            # (64, Lt)
            pooled = _dot_nt(pool_ref[...], sl)           # (25, 64)
            part = _dot(pooled, tf1_ref[k].astype(_F32)) * diag_ref[...]
            fc1 = part if fc1 is None else fc1 + part     # (25, 25*mid)
        fc1 = jnp.sum(fc1, axis=0, keepdims=True)         # (1, 25*mid)
        tmid = jax.nn.silu(_dot_nt(fc1, group_ref[...]))  # (1, mid)
        trunk_out = _dot(tmid, tf2_ref[...].astype(_F32))     # (1, p)

        # ---------------- BranchNet ----------------
        # time-distributed CNN -> MaxPool2 -> AAP(1,1), each step feeding
        # the fully unrolled RNN (L2; paper maps the RNN onto GEMM)
        bmask = bmask_ref[...]
        rwx = rwx_ref[...].astype(_F32)
        rwh = rwh_ref[...].astype(_F32)

        def frame(t, h):
            img = hist_ref[0, pl.ds(t, 1), :]             # (1, Lb)
            c1 = jax.nn.silu(conv_cin1([img], bc1_ref, branch)) * bmask
            c2 = jax.nn.silu(conv(c1, bc2_ref, branch))   # (32, Lb)
            s = branch.stride
            mp = jnp.maximum(
                jnp.maximum(c2, gather(c2, 1, branch.lanes)),
                jnp.maximum(gather(c2, s, branch.lanes),
                            gather(c2, s + 1, branch.lanes)))
            feat = _dot_nt(anchor_ref[...], mp)           # (1, 32)
            return jnp.tanh(_dot(feat, rwx) + _dot(h, rwh))   # L1: tanh

        h = jax.lax.fori_loop(0, T, frame,
                              jnp.zeros((1, cfg.rnn_hidden), _F32))

        bmid = jax.nn.silu(_dot(h, bf1_ref[...].astype(_F32)))
        branch_out = _dot(bmid, bf2_ref[...].astype(_F32))    # (1, p)

        # ---------------- combine (Mul node -> GMIO out) ----------------
        out_ref[0] = (branch_out * trunk_out).astype(out_ref.dtype)

    return kernel


def cronet_fused(cfg: CRONetConfig, params: Dict, load_vol: jax.Array,
                 hist: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    """Fully-fused CRONet inference, batched over the Pallas grid.

    load_vol: (B, 4, ny+1, nx+1, 1); hist: (B, T, ny, nx, 1) -> (B, p) in
    ``load_vol``'s dtype; the kernel computes in f32 from weights of any
    float dtype. One grid step serves one batch slot; the serving
    engine's B problems share a single kernel launch with weights loaded
    once. Unbatched (4, ny+1, nx+1, 1)/(T, ny, nx, 1) inputs return (p,).
    """
    if cfg.b_pool != (1, 1):
        raise ValueError(f"cronet_fused pools the branch to (1, 1); "
                         f"got b_pool={cfg.b_pool}")
    squeeze = load_vol.ndim == 4
    if squeeze:
        load_vol, hist = load_vol[None], hist[None]
    B = load_vol.shape[0]
    trunk, branch = _Geometry(*cfg.nodes), _Geometry(cfg.nely, cfg.nelx)
    tr, br = params["trunk"], params["branch"]
    kd, ph, pw = cfg.t_pool

    def taps(w):                        # (..., Cin, Cout) -> (n, Cout, Cin)
        return jnp.swapaxes(w.reshape(-1, *w.shape[-2:]), 1, 2)

    # the load volume gets one zero depth plane past the end: conv3d-1's
    # causal-same depth padding
    planes = jnp.pad(load_vol[..., 0], ((0, 0), (0, 1), (0, 0), (0, 0)))
    batched = [trunk.flatten(planes), branch.flatten(hist[..., 0])]
    fc1 = tr["fc1"].reshape(kd, ph * pw, cfg.t_c2, cfg.mid)
    weights = [
        taps(tr["conv1"]),                                  # (18, 16, 1)
        taps(tr["conv2"]),                                  # (9, 64, 16)
        jnp.swapaxes(fc1, 1, 2).reshape(kd, cfg.t_c2, -1),  # (3, 64, 25*mid)
        tr["fc2"],
        taps(br["conv1"]),                                  # (9, 16, 1)
        taps(br["conv2"]),                                  # (9, 32, 16)
        br["rnn_wx"], br["rnn_wh"], br["fc1"], br["fc2"],
    ] + [jnp.asarray(c) for c in _constants(cfg)]
    out = pl.pallas_call(
        _make_kernel(cfg),
        grid=(B,),
        in_specs=[pl.BlockSpec((1,) + a.shape[1:], lambda b: (b, 0, 0))
                  for a in batched]
                 + [pl.BlockSpec(a.shape, lambda b, nd=a.ndim: (0,) * nd)
                    for a in weights],
        out_specs=pl.BlockSpec((1, 1, cfg.p), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, cfg.p), load_vol.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.t_depth, cfg.t_c2, trunk.lanes),
                                   _F32)],                 # trunk L3 stage
        interpret=resolve_interpret(interpret),
        name="cronet_fused",
    )(*batched, *weights)
    out = out[:, 0]
    return out[0] if squeeze else out
