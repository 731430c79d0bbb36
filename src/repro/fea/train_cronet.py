"""CRONet training on FEA-generated trajectories.

Dataset: sliding (hist_len)-windows over SIMP trajectories; the target is
the FEA displacement field of the *next* iteration (that is what the
surrogate replaces). Trained with AdamW in fp32, deployed in bf16
(paper §V).

Training runs over the MULTI-trajectory dataset (fea/dataset.py): load
cases sampled from the serving request distribution, mixed-trajectory
minibatches with per-window load-volume conditioning, a train/held-out
split BY TRAJECTORY, and per-load-case eval loss + surrogate-acceptance
metrics (the fraction of held-out windows whose prediction the hybrid
loop's residual gate would accept). A single-trajectory 5-tuple from the
legacy ``build_dataset`` is still accepted for compatibility.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import materialize
from repro.configs.cronet import CRONetConfig
from repro.core import cronet
from repro.fea import dataset as ds_mod
from repro.fea import fea2d, simp
from repro.optim import adamw


def build_dataset(cfg: CRONetConfig, n_iter: int = 100, rmin: float = 1.5):
    """Legacy single-MBB-trajectory dataset: returns (load_vol, hists
    (N,T,ny,nx,1), targets (N, ndof), u_scale, reference history).

    Kept (unbatched ``run_simp``) for the legacy 5-tuple's callers; new
    code should use ``fea.dataset.build_dataset`` — the multi-load-case
    path the serving stack is trained on.
    """
    prob = fea2d.mbb_problem(cfg.nelx, cfg.nely)
    _, hist = simp.run_simp(prob, n_iter=n_iter, rmin=rmin)
    windows, targets = ds_mod.window_trajectory(hist, cfg.hist_len)
    u_scale = float(np.abs(targets).max())
    load_vol = np.asarray(fea2d.load_volume(prob), np.float32)[None]
    return load_vol, windows, targets / u_scale, u_scale, hist


def _coerce_dataset(cfg: CRONetConfig, data) -> ds_mod.TrajectoryDataset:
    """Accept a TrajectoryDataset or the legacy 5-tuple."""
    if isinstance(data, ds_mod.TrajectoryDataset):
        return data
    load_vol, windows, targets, u_scale, hist = data
    n = windows.shape[0]
    return ds_mod.TrajectoryDataset(
        load_vol=np.ascontiguousarray(
            np.broadcast_to(load_vol, (n,) + load_vol.shape[1:])),
        windows=windows, targets=targets, u_scale=u_scale,
        traj_id=np.zeros((n,), np.int32),
        cases=(ds_mod.MBB_CASE,), ref=hist)


@dataclasses.dataclass
class TrainResult:
    """Everything a training run produced. Iterable as the legacy
    ``(params, u_scale, losses, ref)`` 4-tuple."""
    params: Dict
    u_scale: float
    losses: List[float]
    ref: Dict                      # trajectory-0 pure-FEA history
    eval_metrics: Dict             # heldout mse/acceptance + per-case rows
    cases: Tuple[ds_mod.LoadCase, ...]
    heldout_traj: np.ndarray       # trajectory ids held out of training

    def __iter__(self):
        return iter((self.params, self.u_scale, self.losses, self.ref))


@functools.lru_cache(maxsize=16)
def _make_eval_fn(cfg: CRONetConfig):
    """Jitted per-window (mse, relative L2 error) — cached per cfg so
    repeated evaluate() calls (per-epoch eval, threshold sweeps, the
    per-case loops in tests) hit the compile cache instead of retracing
    cronet.forward every time."""

    @jax.jit
    def rel_err(p, lv_b, hist_b, target_b):
        pred = cronet.forward(cfg, p, lv_b, hist_b, invariant=False)
        grid = cronet.decode_displacement(cfg, pred)
        u = jnp.transpose(grid, (0, 2, 1, 3)).reshape(hist_b.shape[0], -1)
        mse = jnp.mean(jnp.square(u - target_b), axis=-1)
        err = (jnp.linalg.norm(u - target_b, axis=-1)
               / jnp.maximum(jnp.linalg.norm(target_b, axis=-1), 1e-30))
        return mse, err

    return rel_err


def evaluate(cfg: CRONetConfig, params, data: ds_mod.TrajectoryDataset,
             traj: Optional[np.ndarray] = None,
             error_threshold: float = 0.05, chunk: int = 64) -> Dict:
    """Per-load-case eval over the given trajectories (default: all).

    Reports, per case and pooled: the normalized eval MSE (the training
    objective), the mean relative L2 displacement error, and the
    surrogate-acceptance rate — the fraction of windows whose prediction
    the hybrid loop's residual gate (relative error < error_threshold)
    would accept. Acceptance is the metric that decides whether the NN
    path fires in serving at all.
    """
    if traj is None:
        traj = np.arange(data.n_trajectories)
    rel_err = _make_eval_fn(cfg)

    per_case, all_mse, all_err = {}, [], []
    for t in traj:
        rows = data.rows_of(int(t))
        mses, errs = [], []
        for lo in range(0, len(rows), chunk):
            idx = rows[lo:lo + chunk]
            m, e = rel_err(params, jnp.asarray(data.load_vol[idx]),
                           jnp.asarray(data.windows[idx]),
                           jnp.asarray(data.targets[idx]))
            mses.append(np.asarray(m))
            errs.append(np.asarray(e))
        mses, errs = np.concatenate(mses), np.concatenate(errs)
        case = data.cases[int(t)]
        per_case[f"traj{int(t)}_{case.kind}"] = {
            "case": case.describe(),
            "eval_mse": float(mses.mean()),
            "mean_rel_err": float(errs.mean()),
            "acceptance": float((errs < error_threshold).mean()),
            "windows": int(len(rows)),
        }
        all_mse.append(mses)
        all_err.append(errs)
    all_mse = np.concatenate(all_mse) if all_mse else np.zeros((0,))
    all_err = np.concatenate(all_err) if all_err else np.zeros((0,))
    return {
        "eval_mse": float(all_mse.mean()) if len(all_mse) else float("nan"),
        "mean_rel_err": float(all_err.mean()) if len(all_err) else float("nan"),
        "acceptance": float((all_err < error_threshold).mean())
        if len(all_err) else 0.0,
        "error_threshold": error_threshold,
        "per_case": per_case,
    }


def train(cfg: CRONetConfig, steps: int = 400, batch: int = 16,
          seed: int = 0, lr: float = 2e-3, data=None, log_every: int = 100,
          verbose: bool = True, noise: float = 0.01,
          heldout_frac: float = 0.25, error_threshold: float = 0.05,
          ckpt_dir: Optional[str] = None,
          init_params: Optional[Dict] = None) -> TrainResult:
    """Train CRONet on the (multi-)trajectory dataset.

    Minibatches mix windows from every TRAINING trajectory; a
    ``heldout_frac`` of trajectories (split by trajectory, never by
    window) is excluded from training and scored afterwards with
    ``evaluate`` — the generalization signal the model registry records
    for every checkpoint. With ``ckpt_dir`` the run persists its final
    params + metrics through ``checkpoint/manager.py``. With
    ``init_params`` the run WARM-STARTS from an existing fp32 parameter
    tree instead of a fresh ``materialize`` — the fine-tune path
    (``finetune_from_tag``); ``steps=0`` then just evaluates it.

    Returns a ``TrainResult`` (unpacks as the legacy
    ``(params, u_scale, losses, ref)``).
    """
    if data is None:
        data = ds_mod.build_dataset(cfg)
    data = _coerce_dataset(cfg, data)
    train_traj, held_traj = ds_mod.split_by_trajectory(
        data, heldout_frac, seed)
    train_rows = np.concatenate([data.rows_of(int(t)) for t in train_traj])
    n = len(train_rows)

    if init_params is not None:
        params = init_params
    else:
        specs = cronet.param_specs(dataclasses.replace(cfg,
                                                       dtype="float32"))
        params = materialize(specs, jax.random.key(seed))
    ocfg = adamw.AdamWConfig(lr=lr, warmup_steps=20, total_steps=steps,
                             weight_decay=0.0, master_fp32=False)
    opt = adamw.init_state(ocfg, params)

    def loss_fn(p, lv_b, hist_b, target_b):
        # invariant=False: training has no bitwise batch contract; plain
        # GEMMs are ~3x faster on the FC layers
        pred = cronet.forward(cfg, p, lv_b, hist_b, invariant=False)
        grid = cronet.decode_displacement(cfg, pred)          # (B,ny,nx,2)
        u = jnp.transpose(grid, (0, 2, 1, 3)).reshape(hist_b.shape[0], -1)
        return jnp.mean(jnp.square(u - target_b))

    @jax.jit
    def step(p, opt, lv_b, hist_b, target_b):
        l, g = jax.value_and_grad(loss_fn)(p, lv_b, hist_b, target_b)
        p, opt, _ = adamw.apply_updates(ocfg, p, g, opt)
        return p, opt, l

    rng = np.random.default_rng(seed)
    losses = []
    for i in range(steps):
        idx = train_rows[rng.integers(0, n, size=min(batch, n))]
        wb = data.windows[idx]
        if noise:
            # jitter the density histories: robustness off the training
            # trajectory (the hybrid loop's designs drift from pure-FEA's)
            wb = np.clip(wb + rng.normal(0, noise, wb.shape).astype(np.float32),
                         0.001, 1.0)
        p_, o_, l = step(params, opt, jnp.asarray(data.load_vol[idx]),
                         jnp.asarray(wb), jnp.asarray(data.targets[idx]))
        params, opt = p_, o_
        losses.append(float(l))
        if verbose and i % log_every == 0:
            print(f"  cronet train step {i}: mse={losses[-1]:.5f}")

    eval_traj = held_traj if len(held_traj) else train_traj
    metrics = evaluate(cfg, params, data, traj=eval_traj,
                       error_threshold=error_threshold)
    metrics["heldout"] = bool(len(held_traj))
    metrics["train_trajectories"] = int(len(train_traj))
    metrics["final_train_mse"] = losses[-1] if losses else float("nan")
    if verbose:
        print(f"  eval ({'held-out' if metrics['heldout'] else 'train'} "
              f"trajectories {list(map(int, eval_traj))}): "
              f"mse={metrics['eval_mse']:.5f} "
              f"rel_err={metrics['mean_rel_err']:.3f} "
              f"acceptance={metrics['acceptance']:.0%}")

    result = TrainResult(params=params, u_scale=data.u_scale, losses=losses,
                         ref=data.ref, eval_metrics=metrics,
                         cases=data.cases, heldout_traj=held_traj)
    if ckpt_dir is not None:
        from repro.checkpoint import manager as ckpt
        ckpt.save(ckpt_dir, steps, {"params": params},
                  extras={"u_scale": data.u_scale,
                          "metrics": metrics,
                          "load_cases": [c.describe() for c in data.cases],
                          "cfg": dataclasses.asdict(cfg)})
    return result


def train_and_register(cfg: CRONetConfig, registry, *, tag: Optional[str]
                       = None, pin: bool = False, **train_kw):
    """Train, then persist the run as a registry version: params through
    checkpoint/manager.py plus metadata (cfg, u_scale, training load
    distribution, eval metrics). Returns (record, result)."""
    result = train(cfg, **train_kw)
    record = registry.register(
        result.params, cfg, result.u_scale, tag=tag, pin=pin,
        metrics=result.eval_metrics,
        load_cases=[c.describe() for c in result.cases])
    return record, result


def finetune_from_tag(reg, base_tag: str, mesh, harvested, *,
                      steps: int = 300, lr: float = 5e-4,
                      replay_cases: int = 4,
                      replay_n_iter: Optional[int] = None,
                      tag: Optional[str] = None, pin: bool = False,
                      seed: int = 0, heldout_frac: float = 0.25,
                      error_threshold: float = 0.05,
                      verbose: bool = False, **train_kw):
    """Fine-tune a bucket specialist from its serving checkpoint — the
    flywheel's training layer.

    Warm-starts from ``base_tag``'s fp32 master weights (never a fresh
    init: the point is to move an already-good fleet model toward the
    bucket's observed traffic, cf. FE-CNN per-discretization
    fine-tuning) and trains on ``harvested`` — the bucket's
    fell-back-to-FEA load cases regenerated as trajectories
    (``fea.dataset.harvest_dataset``) — MIXED with up to
    ``replay_cases`` trajectories replayed from the base checkpoint's
    own training distribution. The replay half is the anti-forgetting
    guard: fine-tuning on failures alone would trade the fleet
    distribution away for the bucket's tail.

    The child is registered MESH-SPECIALIZED for ``mesh`` with lineage
    metadata (``parent=base_tag``), so ``ModelResolver`` prefers it for
    its bucket only and the retention sweep can group it under its
    lineage. ``tag`` defaults to ``"<base>-ft<nelx>x<nely>"`` with a
    numeric suffix when taken. Returns ``(record, result)``.
    """
    nelx, nely = int(mesh[0]), int(mesh[1])
    base_params, base_rec = reg.load(base_tag)
    cfg = dataclasses.replace(base_rec.cfg, nelx=nelx, nely=nely)
    if harvested is None or harvested.n_windows == 0:
        raise ValueError(
            f"finetune_from_tag needs a non-empty harvested dataset for "
            f"{nelx}x{nely} (harvest_dataset returned "
            f"{'None' if harvested is None else 'no windows'})")

    data = harvested
    if replay_cases > 0 and base_rec.load_cases:
        replay = [ds_mod.LoadCase.from_dict(d)
                  for d in base_rec.load_cases[:replay_cases]]
        if replay_n_iter is None:
            # match the harvested trajectories' length so neither side
            # of the mix dominates by window count alone
            per_traj = len(harvested.rows_of(0))
            replay_n_iter = per_traj + cfg.hist_len
        replay_ds = ds_mod.build_dataset(cfg, cases=replay,
                                         n_iter=replay_n_iter)
        data = ds_mod.concat_datasets(harvested, replay_ds)

    result = train(cfg, steps=steps, lr=lr, seed=seed, data=data,
                   heldout_frac=heldout_frac,
                   error_threshold=error_threshold, verbose=verbose,
                   init_params=base_params, **train_kw)
    result.eval_metrics["finetuned_from"] = base_tag
    result.eval_metrics["harvested_trajectories"] = int(
        harvested.n_trajectories)

    if tag is None:
        base = f"{base_tag}-ft{nelx}x{nely}"
        taken = set(reg.tags())
        tag = base
        k = 2
        while tag in taken:
            tag = f"{base}.{k}"
            k += 1
    record = reg.register(
        result.params, cfg, result.u_scale, tag=tag, pin=pin,
        mesh=(nelx, nely), parent=base_tag,
        metrics=result.eval_metrics,
        load_cases=[c.describe() for c in result.cases])
    return record, result
