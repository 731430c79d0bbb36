"""Hybrid NN-FEA topology optimization (paper §VI-A, Table III).

Workflow: `hist_len` FEA warm-up iterations seed the CRONet recurrent
context; afterwards each iteration runs CRONet and accepts the prediction
iff the physics residual ||K u_pred - f|| / ||f|| is below a threshold —
otherwise FEA is invoked for that iteration (the paper's dynamic
selection). Reports CRONet invocation count + solution accuracy vs the
pure-FEA reference, reproducing Table III for fp32/bf16/int8 weights.

The loop is implemented as a pure, batch-first step function over stacked
problem state (density, history ring-buffer, displacement, per-slot gate
bookkeeping): ONE compiled ``hybrid_step`` drives both the classic
single-problem ``run_hybrid`` (B=1) and the slot-batched serving engine
(serve/topo_service.py, B=slots). All constituent ops are bitwise
batch-invariant on CPU, so slot b of a batched run reproduces a standalone
run exactly — the property the serving benchmark asserts.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.cronet import CRONetConfig
from repro.core import cronet
from repro.fea import fea2d, simp
from repro.obs import metrics as obs_metrics
from repro.optim.compress import dequantize_int8, quantize_int8

_INPUT_DTYPE = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.float32}


def cast_params(params, precision: str):
    """fp32 | bf16 | int8 (fake-quant weights, per-tensor symmetric)."""
    if precision == "fp32":
        return jax.tree.map(lambda p: p.astype(jnp.float32), params)
    if precision == "bf16":
        return jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    if precision == "int8":
        def q(p):
            qq, s = quantize_int8(p)
            return dequantize_int8(qq, s).astype(jnp.float32)
        return jax.tree.map(q, params)
    raise ValueError(precision)


class HybridState(NamedTuple):
    """Stacked per-slot optimization state (leading axis B)."""
    x: jnp.ndarray          # (B, nely, nelx) densities
    hist: jnp.ndarray       # (B, T, nely, nelx) density ring buffer, oldest first
    it: jnp.ndarray         # (B,) int32 per-slot iteration counter
    err: jnp.ndarray        # (B,) last measured CRONet relative L2 error
    n_cronet: jnp.ndarray   # (B,) int32 accepted-surrogate iterations
    n_fea: jnp.ndarray      # (B,) int32 FEA iterations
    compliance: jnp.ndarray  # (B,) compliance of the last iteration
    cg_iters: jnp.ndarray   # (B,) int32 cumulative CG iterations the
    #                         slot's FEA fallbacks burned (the masked CG
    #                         already counts them per slot; surfacing
    #                         them here is what lets the serving engine
    #                         report "where the fallback budget went"
    #                         without any extra device work)
    cg_breakdowns: jnp.ndarray  # (B,) int32 FEA fallbacks whose CG stopped
    #                             at a breakdown (fea2d.solve_b) instead of
    #                             converging or reaching max_iter


def init_state(cfg: CRONetConfig, bp: fea2d.BatchProblem) -> HybridState:
    """Fresh state for every slot: uniform volfrac density, cold history.
    On a shape-padded batch the passive border starts (and stays) at 0."""
    B = bp.batch
    x0 = jnp.broadcast_to(bp.volfrac[:, None, None],
                          (B, bp.nely, bp.nelx)).astype(jnp.float32)
    if bp.elem_mask is not None:
        x0 = x0 * bp.elem_mask
    # each field gets its own buffer: the jitted step donates the state, and
    # aliased leaves would be donated twice
    return HybridState(
        x=x0,
        hist=jnp.zeros((B, cfg.hist_len, bp.nely, bp.nelx), jnp.float32),
        it=jnp.zeros((B,), jnp.int32),
        err=jnp.full((B,), jnp.inf, jnp.float32),
        n_cronet=jnp.zeros((B,), jnp.int32),
        n_fea=jnp.zeros((B,), jnp.int32),
        compliance=jnp.zeros((B,), jnp.float32),
        cg_iters=jnp.zeros((B,), jnp.int32),
        cg_breakdowns=jnp.zeros((B,), jnp.int32),
    )


def reset_slot(cfg: CRONetConfig, state: HybridState, i: int,
               volfrac: float, elem_mask=None) -> HybridState:
    """Re-initialize slot i in place (serving refill after completion).
    ``elem_mask`` (nely, nelx) zeroes the passive shape-class border."""
    x0 = jnp.full(state.x.shape[1:], volfrac)
    if elem_mask is not None:
        x0 = x0 * elem_mask
    return HybridState(
        x=state.x.at[i].set(x0),
        hist=state.hist.at[i].set(0.0),
        it=state.it.at[i].set(0),
        err=state.err.at[i].set(jnp.inf),
        n_cronet=state.n_cronet.at[i].set(0),
        n_fea=state.n_fea.at[i].set(0),
        compliance=state.compliance.at[i].set(0.0),
        cg_iters=state.cg_iters.at[i].set(0),
        cg_breakdowns=state.cg_breakdowns.at[i].set(0),
    )


def reset_lanes(state: HybridState, reset, volfrac,
                elem_mask=None) -> HybridState:
    """Re-initialize every lane flagged in ``reset`` ((B,) bool) in one
    pass, leaving the others untouched: lane for lane the values
    ``reset_slot`` writes, as a ``jnp.where`` over the flags, so it traces
    into a single program. ``volfrac`` is (B,); ``elem_mask`` (B, nely,
    nelx) zeroes the passive shape-class border."""
    x0 = jnp.broadcast_to(volfrac[:, None, None], state.x.shape)
    if elem_mask is not None:
        x0 = x0 * elem_mask

    def where(leaf, fresh):
        flags = reset.reshape(reset.shape + (1,) * (leaf.ndim - 1))
        return jnp.where(flags, jnp.asarray(fresh, leaf.dtype), leaf)

    return HybridState(
        x=where(state.x, x0), hist=where(state.hist, 0.0),
        it=where(state.it, 0), err=where(state.err, jnp.inf),
        n_cronet=where(state.n_cronet, 0), n_fea=where(state.n_fea, 0),
        compliance=where(state.compliance, 0.0),
        cg_iters=where(state.cg_iters, 0),
        cg_breakdowns=where(state.cg_breakdowns, 0))


def park_slot(state: HybridState, i: int) -> HybridState:
    """Gather lane i to host numpy (preemption parking).

    The parked tuple is a complete per-slot optimization snapshot
    (density, history ring, gate bookkeeping); host-side so
    it can be re-admitted on any shard/device. Restoring it with
    ``restore_slot`` and stepping resumes the trajectory bitwise — every
    op in the batched step is slot-invariant, and gather/scatter of a
    lane is exact.
    """
    return HybridState(*[np.asarray(leaf[i]) for leaf in state])


def restore_slot(state: HybridState, i: int,
                 parked: HybridState) -> HybridState:
    """Scatter a parked lane snapshot back into slot i (re-admission)."""
    return HybridState(*[leaf.at[i].set(jnp.asarray(v))
                         for leaf, v in zip(state, parked)])


def move_slot(state: HybridState, src: int, dst: int) -> HybridState:
    """Copy lane src's snapshot over lane dst (ladder compaction before a
    width shrink). Same exactness argument as park/restore: a lane
    gather/scatter is bitwise, and every batched op is slot-invariant, so
    the moved trajectory continues exactly. src's old lane is left behind
    as garbage — the caller reseeds or slices it away."""
    return HybridState(*[leaf.at[dst].set(leaf[src]) for leaf in state])


def resize_state(state: HybridState, new_b: int) -> HybridState:
    """Re-width the stacked state to ``new_b`` lanes (per-tick ladder rung
    change). Shrinking slices off the tail — callers compact live lanes
    below ``new_b`` first (move_slot). Growing appends idle lanes shaped
    like ``init_state`` output (x=0.5, cold history, err=inf); they are
    reseeded via reset/restore before any request lands on them."""
    B = state.x.shape[0]
    if new_b == B:
        return state
    if new_b < B:
        return HybridState(*[leaf[:new_b] for leaf in state])
    n = new_b - B

    def pad(leaf, fill):
        extra = jnp.full((n,) + leaf.shape[1:], fill, leaf.dtype)
        return jnp.concatenate([leaf, extra], axis=0)

    return HybridState(
        x=pad(state.x, 0.5), hist=pad(state.hist, 0.0),
        it=pad(state.it, 0), err=pad(state.err, jnp.inf),
        n_cronet=pad(state.n_cronet, 0), n_fea=pad(state.n_fea, 0),
        compliance=pad(state.compliance, 0.0),
        cg_iters=pad(state.cg_iters, 0),
        cg_breakdowns=pad(state.cg_breakdowns, 0))


def _oracle_forward(cfg: CRONetConfig):
    def fwd(params, load_vol, hist):
        return cronet.forward(cfg, params, load_vol, hist)
    return fwd


def _megakernel_forward(cfg: CRONetConfig):
    from repro.kernels import cronet_pipeline

    def fwd(params, load_vol, hist):
        # interpret auto-detects the platform (CPU -> interpreter,
        # accelerator -> real lowering); see repro.kernels.resolve_interpret
        return cronet_pipeline.cronet_fused(cfg, params, load_vol, hist)
    return fwd


@functools.lru_cache(maxsize=32)
def make_hybrid_step(cfg: CRONetConfig, u_scale: float,
                     error_threshold: float = 0.05, verify_every: int = 3,
                     rmin: float = 1.5, precision: str = "bf16",
                     backend: str = "oracle",
                     fea_backend: str = "reference") -> Callable:
    """Build the jitted batched iteration:

        step(params, bp: BatchProblem, load_vol (B,4,H,W,1), state) -> state

    Selection rule (paper §VI-A: "based on the error of the previous
    iteration's output"): whenever an FEA solve happens, CRONet's prediction
    for that same state is scored (relative L2 vs FEA); CRONet is used for
    subsequent iterations while the last measured error is under
    `error_threshold`, with a forced FEA verification every `verify_every`
    iterations — applied independently per slot. FEA runs once, batched,
    for whichever slots need it (skipped entirely when no slot does);
    accepted-surrogate slots discard the masked solve, so per-slot
    trajectories are identical to standalone runs.

    Cached per configuration so sequential B=1 callers and the B=slots
    serving engine share one compiled artifact family (jax.jit re-traces
    per batch width, not per call).

    ``fea_backend`` selects the batched-CG engine for the FEA fallback:
    ``"reference"`` (pure XLA) or ``"fused"`` (single-pallas_call
    iteration, kernels/cg_fused.py) — bitwise-identical results, so the
    choice is a pure deployment knob (fea2d.solve_b docstring).
    """
    dtype = _INPUT_DTYPE[precision]
    forward = {"oracle": _oracle_forward,
               "megakernel": _megakernel_forward}[backend](cfg)
    filt_b = simp.make_filter_b(cfg.nelx, cfg.nely, rmin)
    filt_mask_b = simp.make_filter_b(cfg.nelx, cfg.nely, rmin, masked=True)

    trace_count = [0]  # bumped per retrace; see .trace_count below

    @functools.partial(jax.jit, donate_argnums=(3,))
    def step(params, bp: fea2d.BatchProblem, load_vol,
             state: HybridState) -> HybridState:
        trace_count[0] += 1  # python body runs only when jit (re)traces
        # compile-event telemetry: this python body executes once per XLA
        # (re)trace, so the counter records exactly the compile events
        # (looked up at trace time so a swapped default registry is seen)
        obs_metrics.default_registry().counter(
            "hybrid_compiles_total",
            "XLA (re)traces of the jitted hybrid step").inc(
            backend=backend, fea_backend=fea_backend,
            width=state.x.shape[0])
        warm = state.it >= cfg.hist_len

        def predict():
            pred = forward(params, load_vol.astype(dtype),
                           state.hist[..., None].astype(dtype))  # (B, p)
            return cronet.decode_to_dofs(cfg, pred) * u_scale * bp.free_mask

        # named scopes label the step's regions in the compiled HLO and the
        # profiler trace (metadata only: the arithmetic is unchanged).
        # Pre-warm-up no slot can consume or score the prediction, so skip
        # the forward entirely (it is the whole step cost on the
        # interpret-mode megakernel backend)
        with jax.named_scope("cronet_forward"):
            u_pred = jax.lax.cond(jnp.any(warm), predict,
                                  lambda: jnp.zeros_like(bp.f))
        with jax.named_scope("gate"):
            use_cronet = (warm & (state.err < error_threshold)
                          & (state.it % verify_every != 0))
            need_fea = ~use_cronet

        # the masked CG reports per-slot iteration counts and breakdown
        # flags alongside U; carrying them through the state (zeros when
        # no slot needed FEA) costs nothing on-device and gives the
        # serving engine the CG-fallback budget per request. Each solve
        # starts from zero, never from state (solve_b docstring: why).
        with jax.named_scope("cg_solve"):
            u_fea, cg_its, cg_broke = jax.lax.cond(
                jnp.any(need_fea),
                lambda: fea2d.solve_b(bp, state.x, need=need_fea,
                                      backend=fea_backend),
                lambda: (jnp.zeros_like(bp.f),
                         jnp.zeros_like(state.cg_iters),
                         jnp.zeros_like(need_fea)))

        # batch-invariant norms: err is COMPARED against the gate threshold,
        # so it must be bitwise-identical at any batch width
        with jax.named_scope("gate"):
            un = fea2d.tree_norm(u_fea)
            err_new = (fea2d.tree_norm(u_pred - u_fea)
                       / jnp.maximum(un, 1e-30))
            err = jnp.where(need_fea & warm, err_new, state.err)
            u = jnp.where(use_cronet[:, None], u_pred, u_fea)

        with jax.named_scope("sens_filter_oc"):
            c, dc = fea2d.compliance_and_sens_b(bp, state.x, u)
            # elem_mask=None is an EMPTY pytree subtree, so this branches at
            # trace time — the unmasked path lowers to exactly the pre-ladder
            # graph (bitwise contract with historical runs)
            if bp.elem_mask is None:
                dc_f = filt_b(state.x, dc)
            else:
                dc_f = filt_mask_b(state.x, dc, bp.elem_mask)
            hist = jnp.roll(state.hist, -1, axis=1).at[:, -1].set(state.x)
            if bp.elem_mask is None:
                dv = jnp.ones_like(state.x) / (cfg.nelx * cfg.nely)
                x = simp.oc_update_b(state.x, dc_f, dv[0], bp.volfrac)
            else:
                # the mean-over-ACTIVE-elements volume constraint has uniform
                # gradient 1/active_count, which differs per slot under
                # shape-class padding — a flat 1/(nelx*nely) would hand the
                # bisection the padded mesh's gradient and shift the update
                # away from what a dedicated (unpadded) engine computes
                active = jnp.maximum(fea2d.tree_sum(
                    bp.elem_mask.reshape(state.x.shape[0], -1)), 1.0)
                dv = jnp.ones_like(state.x) / active[:, None, None]
                x = simp.oc_update_b(state.x, dc_f, dv, bp.volfrac,
                                     mask=bp.elem_mask)
        return HybridState(
            x=x, hist=hist, it=state.it + 1, err=err,
            n_cronet=state.n_cronet + use_cronet.astype(jnp.int32),
            n_fea=state.n_fea + need_fea.astype(jnp.int32), compliance=c,
            cg_iters=state.cg_iters + cg_its.astype(jnp.int32),
            cg_breakdowns=state.cg_breakdowns + cg_broke.astype(jnp.int32))

    # tracing telemetry: trace_count[0] is the number of XLA compilations
    # this step has triggered (one per distinct batch width). The serving
    # engine's streaming tests assert it stays flat across live
    # admissions — submit() must be a compiled-cache hit, never a retrace.
    step.trace_count = trace_count
    return step


@dataclasses.dataclass
class HybridResult:
    cronet_invocations: int
    fea_invocations: int
    final_compliance: float
    reference_compliance: float
    solution_accuracy: float   # 100 * (1 - |c - c_ref| / c_ref)
    design_match: float        # 100 * (1 - mean |x - x_ref|)
    compliances: np.ndarray
    density: Optional[np.ndarray] = None   # (nely, nelx) final design


def run_hybrid(cfg: CRONetConfig, params, u_scale: float,
               n_iter: int = 100, error_threshold: float = 0.05,
               verify_every: int = 3, rmin: float = 1.5,
               reference: Optional[dict] = None, precision: str = "bf16",
               problem: Optional[fea2d.Problem] = None,
               compute_metrics: bool = True, backend: str = "oracle",
               fea_backend: str = "reference"):
    """Run the hybrid loop for one problem; returns HybridResult.

    A thin B=1 driver over the batched core (make_hybrid_step) — the same
    compiled step the serving engine runs at B=slots.
    reference: optional precomputed pure-FEA history (from simp.run_simp);
    compute_metrics=False skips the pure-FEA reference run and the final
    FEA evaluation (throughput benchmarking), leaving metric fields NaN.
    """
    prob = problem if problem is not None else fea2d.mbb_problem(cfg.nelx,
                                                                 cfg.nely)
    params = cast_params(params, precision)
    # pad to B=2: XLA lowers a unit batch dim specially (squeeze + different
    # vectorization/FMA choices), so B=1 results are not bitwise-comparable
    # to B>1 slots. Widths >= 2 are mutually slot-invariant; the idle slot
    # converges instantly in the masked CG.
    bp = fea2d.stack_problems([prob, fea2d.idle_problem(cfg.nelx, cfg.nely)])
    load_vol = fea2d.load_volume_b(bp)
    step = make_hybrid_step(cfg, u_scale, error_threshold, verify_every,
                            rmin, precision, backend, fea_backend)
    state = init_state(cfg, bp)
    cs = []
    for _ in range(n_iter):
        state = step(params, bp, load_vol, state)
        cs.append(state.compliance[0])   # device scalar: no per-iter sync
    cs = [float(c) for c in cs]

    x = state.x[0]
    n_cronet = int(state.n_cronet[0])
    n_fea = int(state.n_fea[0])
    if not compute_metrics:
        return HybridResult(
            cronet_invocations=n_cronet, fea_invocations=n_fea,
            final_compliance=float("nan"), reference_compliance=float("nan"),
            solution_accuracy=float("nan"), design_match=float("nan"),
            compliances=np.asarray(cs), density=np.asarray(x))

    if reference is None:
        _, reference = simp.run_simp(prob, n_iter=n_iter, rmin=rmin)
    c_ref = float(reference["c"][-1])
    # solution quality = FEA-evaluated compliance of the FINAL DESIGN (the
    # quantity topology optimization minimizes), not the last surrogate u.
    u_fin, _, _ = fea2d.solve(prob, x)
    c_fin, _ = fea2d.compliance_and_sens(prob, x, u_fin)
    c_fin = float(c_fin)
    acc = 100.0 * max(0.0, 1.0 - abs(c_fin - c_ref) / abs(c_ref))
    dm = 100.0 * float(1.0 - np.mean(np.abs(np.asarray(x) - reference["x"][-1])))
    return HybridResult(
        cronet_invocations=n_cronet, fea_invocations=n_fea,
        final_compliance=c_fin, reference_compliance=c_ref,
        solution_accuracy=acc, design_match=dm, compliances=np.asarray(cs),
        density=np.asarray(x))
