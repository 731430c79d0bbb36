"""Multi-load-case trajectory dataset for CRONet training.

The surrogate only generalizes across the request distribution the
serving gateway actually sees when it is trained across it (Zhang et al.
arXiv:1901.07761; Sosnovik & Oseledets arXiv:1709.09578 train over many
optimization trajectories for the same reason). This module owns that
data layer:

  * ``LoadCase`` — a declarative load configuration (position, angle,
    magnitude) that builds its ``fea2d.point_load_problem``; the
    registry stores these as the checkpoint's training distribution.
  * ``sample_load_cases`` — the sampler over the serving request space:
    random top-edge position, load angle, and magnitude, plus the
    canonical MBB case the paper benchmarks.
  * ``run_simp_b`` — SIMP trajectory generation batched through the
    PR 1 batch axis (``fea2d.BatchProblem`` / ``solve_b``): one jitted
    batch-first step advances every trajectory at once instead of a
    Python loop over per-case ``run_simp`` calls.
  * ``build_dataset`` — windows the trajectories into one stacked
    multi-trajectory ``TrajectoryDataset`` with per-window ``load_vol``
    conditioning and a single shared ``u_scale``.
  * ``harvest_dataset`` / ``concat_datasets`` — the serving-data
    flywheel's data layer: deduplicated fell-back-to-FEA load cases
    from a gateway harvest log regenerated as trajectories on the
    bucket's mesh, and the harvested + replayed-synthetic
    anti-forgetting mix the fine-tune trains on.

The single-trajectory MBB path (``train_cronet.build_dataset``) remains
as a thin compatibility wrapper over ``run_simp`` for its callers
(benchmarks/precision.py, the examples).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.cronet import CRONetConfig
from repro.fea import fea2d, simp


# ------------------------------------------------------------- load cases


@dataclasses.dataclass(frozen=True)
class LoadCase:
    """One load configuration on the (nelx, nely) MBB-style mesh.

    ``load_frac`` is the load node's x position as a FRACTION of nelx
    (mesh-independent, so a sampled distribution transfers across
    buckets); the load itself is (Fx, Fy) at that top-edge node.
    """
    load_frac: float = 0.0          # x position / nelx, in [0, 1)
    load: Tuple[float, float] = (0.0, -1.0)
    volfrac: float = 0.5
    kind: str = "point"             # "mbb" marks the canonical case

    def load_node(self, nelx: int) -> Tuple[int, int]:
        # keep loads off the right-most column: directly above the
        # bottom-right support the fp32 CG system degenerates (see
        # benchmarks/topo_serving.py)
        return (min(int(round(self.load_frac * nelx)), nelx - 1), 0)

    def problem(self, nelx: int, nely: int) -> fea2d.Problem:
        return fea2d.point_load_problem(nelx, nely,
                                        load_node=self.load_node(nelx),
                                        load=self.load,
                                        volfrac=self.volfrac)

    def describe(self) -> Dict:
        """JSON-able metadata for the model registry."""
        return {"kind": self.kind, "load_frac": self.load_frac,
                "load": list(self.load), "volfrac": self.volfrac}

    @classmethod
    def from_dict(cls, d: Dict) -> "LoadCase":
        return cls(load_frac=float(d["load_frac"]),
                   load=tuple(d["load"]), volfrac=float(d["volfrac"]),
                   kind=d.get("kind", "point"))

    @classmethod
    def from_problem(cls, prob: fea2d.Problem,
                     kind: str = "harvest") -> "LoadCase":
        """Reconstruct the load case a point-load problem was built
        from — the serving-traffic harvester's inverse of
        ``problem()``: a completed ``TopoRequest`` carries only its
        ``fea2d.Problem``, and the flywheel needs the declarative case
        back to regenerate a training trajectory for it.

        The dominant loaded node is recovered from the load vector
        (node id ``x * (nely + 1) + y``, 2 dofs per node — the 88-line
        layout ``point_load_problem`` uses). Loads the boundary
        conditions zeroed (an x-load on the fixed left edge) come back
        as the FREE component only, which is exactly the load the
        trajectory would feel anyway."""
        f = np.asarray(prob.f)
        pairs = f.reshape(-1, 2)                      # (n_nodes, 2)
        node = int(np.argmax(np.abs(pairs).sum(axis=1)))
        xn = node // (prob.nely + 1)
        return cls(load_frac=xn / max(prob.nelx, 1),
                   load=(float(pairs[node, 0]), float(pairs[node, 1])),
                   volfrac=float(prob.volfrac), kind=kind)

    def key(self, ndigits: int = 4) -> Tuple:
        """Dedup key: two requests with the same (rounded) load
        configuration regenerate the same trajectory, so the harvester
        keeps only one."""
        return (round(self.load_frac, ndigits),
                round(self.load[0], ndigits),
                round(self.load[1], ndigits),
                round(self.volfrac, ndigits))


MBB_CASE = LoadCase(load_frac=0.0, load=(0.0, -1.0), kind="mbb")


def sample_load_cases(n: int, seed: int = 0, include_mbb: bool = True,
                      max_angle_deg: float = 50.0,
                      mag_range: Tuple[float, float] = (0.5, 1.5)
                      ) -> List[LoadCase]:
    """Sample ``n`` load cases from the serving request distribution:
    uniform top-edge position, load direction within ``max_angle_deg``
    of straight-down, magnitude in ``mag_range``. With ``include_mbb``
    the first case is the canonical MBB load (the paper's benchmark),
    anchoring the distribution to the reference problem."""
    rng = np.random.default_rng(seed)
    cases: List[LoadCase] = [MBB_CASE] if include_mbb else []
    while len(cases) < n:
        frac = float(rng.uniform(0.0, 1.0))
        theta = float(np.deg2rad(rng.uniform(-max_angle_deg, max_angle_deg)))
        mag = float(rng.uniform(*mag_range))
        cases.append(LoadCase(
            load_frac=frac,
            load=(mag * np.sin(theta), -mag * np.cos(theta))))
    return cases


# ------------------------------------------------- batched SIMP trajectories


@functools.lru_cache(maxsize=16)
def _make_simp_step_b(nelx: int, nely: int, rmin: float):
    """One jitted batch-first SIMP iteration over a BatchProblem: FEA
    solve (masked batched CG), compliance + sensitivity, filter, OC
    update — the training-time twin of fea/hybrid.make_hybrid_step."""
    filt_b = simp.make_filter_b(nelx, nely, rmin)
    dv = jnp.full((nely, nelx), 1.0 / (nelx * nely))

    @jax.jit
    def step(bp: fea2d.BatchProblem, X):
        # from zero, like the serving step (make_hybrid_step: why)
        U, _, broke = fea2d.solve_b(bp, X)
        c, dc = fea2d.compliance_and_sens_b(bp, X, U)
        dc_f = filt_b(X, dc)
        X_new = simp.oc_update_b(X, dc_f, dv, bp.volfrac)
        return X_new, U, c, broke

    return step


def run_simp_b(probs: Sequence[fea2d.Problem], n_iter: int = 60,
               rmin: float = 1.5) -> List[Dict[str, np.ndarray]]:
    """Run SIMP for every problem at once through the batch axis.

    Returns one ``run_simp``-shaped history dict per problem (``x``:
    densities AFTER each OC update, ``u``: the displacement of the solve
    that produced that update, ``c``: compliance) — the same recording
    convention ``simp.run_simp`` uses, so windowing code treats both
    identically — plus ``broke``: whether that solve stopped at a CG
    breakdown (``fea2d.solve_b``).
    """
    bp = fea2d.stack_problems(probs)
    step = _make_simp_step_b(bp.nelx, bp.nely, rmin)
    B = bp.batch
    X = jnp.broadcast_to(bp.volfrac[:, None, None],
                         (B, bp.nely, bp.nelx)).astype(jnp.float32)
    xs, us, cs, bs = [], [], [], []
    for _ in range(n_iter):
        X, U, c, broke = step(bp, X)
        xs.append(X)
        us.append(U)
        cs.append(c)
        bs.append(broke)
    # one host transfer at the end instead of a per-iteration sync
    xs = np.asarray(jnp.stack(xs))          # (T, B, nely, nelx)
    us = np.asarray(jnp.stack(us))          # (T, B, ndof)
    cs = np.asarray(jnp.stack(cs))          # (T, B)
    bs = np.asarray(jnp.stack(bs))          # (T, B)
    return [{"x": xs[:, b], "u": us[:, b], "c": cs[:, b], "broke": bs[:, b]}
            for b in range(B)]


# ----------------------------------------------------------------- dataset


class TrajectoryDataset(NamedTuple):
    """Stacked sliding windows over many SIMP trajectories.

    One row = (density-history window, per-window load conditioning) ->
    next FEA displacement, normalized by ONE shared ``u_scale`` so a
    single deployed scalar serves every load case.
    """
    load_vol: np.ndarray    # (N, 4, nely+1, nelx+1, 1) TrunkNet input
    windows: np.ndarray     # (N, T, nely, nelx, 1) BranchNet input
    targets: np.ndarray     # (N, ndof) u / u_scale
    u_scale: float
    traj_id: np.ndarray     # (N,) which trajectory each window came from
    cases: Tuple[LoadCase, ...]
    ref: Dict               # trajectory-0 history (reference metrics)

    @property
    def n_windows(self) -> int:
        return self.windows.shape[0]

    @property
    def n_trajectories(self) -> int:
        return len(self.cases)

    def rows_of(self, traj: int) -> np.ndarray:
        """Window indices belonging to one trajectory."""
        return np.nonzero(self.traj_id == traj)[0]


def window_trajectory(hist: Dict[str, np.ndarray], hist_len: int):
    """Sliding (hist_len)-windows over one SIMP history; the target is
    the displacement field of the solve that follows the window — the
    exact quantity the hybrid loop asks the surrogate to replace.

    A window whose target solve stopped at a CG breakdown (``broke``,
    recorded by ``run_simp_b``) is left out: that displacement is an
    unconverged iterate, not the FEA answer."""
    xs, us = np.asarray(hist["x"]), np.asarray(hist["u"])
    broke = np.asarray(hist.get("broke", np.zeros(len(xs), bool)), bool)
    keep = np.arange(hist_len, len(xs))
    keep = keep[~broke[keep]]
    windows = xs[keep[:, None] - hist_len + np.arange(hist_len)]
    return (windows[..., None].astype(np.float32),
            us[keep].astype(np.float32))


def build_dataset(cfg: CRONetConfig,
                  cases: Optional[Sequence[LoadCase]] = None,
                  n_iter: int = 100, rmin: float = 1.5, seed: int = 0,
                  n_cases: int = 6, batch: int = 8) -> TrajectoryDataset:
    """Build the stacked multi-trajectory dataset.

    ``cases`` defaults to ``sample_load_cases(n_cases, seed)`` (MBB
    first). Trajectory generation runs through ``run_simp_b`` in chunks
    of ``batch`` stacked problems; every trajectory is then windowed and
    stacked with its own ``load_vol`` conditioning row, and ONE shared
    ``u_scale`` (max |u| over all targets) normalizes the whole set.
    Targets whose solve broke down are dropped (``window_trajectory``).
    """
    if cases is None:
        cases = sample_load_cases(n_cases, seed=seed)
    cases = tuple(cases)
    probs = [c.problem(cfg.nelx, cfg.nely) for c in cases]
    hists: List[Dict[str, np.ndarray]] = []
    for lo in range(0, len(probs), batch):
        hists.extend(run_simp_b(probs[lo:lo + batch], n_iter=n_iter,
                                rmin=rmin))
    load_vols, windows, targets, traj_id = [], [], [], []
    for t, (prob, hist) in enumerate(zip(probs, hists)):
        w, tg = window_trajectory(hist, cfg.hist_len)
        lv = np.asarray(fea2d.load_volume(prob), np.float32)
        load_vols.append(np.broadcast_to(lv[None], (len(w),) + lv.shape))
        windows.append(w)
        targets.append(tg)
        traj_id.append(np.full((len(w),), t, np.int32))
    targets = np.concatenate(targets)
    u_scale = float(np.abs(targets).max())
    return TrajectoryDataset(
        load_vol=np.ascontiguousarray(np.concatenate(load_vols)),
        windows=np.concatenate(windows),
        targets=targets / u_scale,
        u_scale=u_scale,
        traj_id=np.concatenate(traj_id),
        cases=cases,
        ref=hists[0],
    )


def concat_datasets(a: TrajectoryDataset,
                    b: TrajectoryDataset) -> TrajectoryDataset:
    """Stack two trajectory datasets (same mesh and hist_len) into one,
    renormalizing to a single shared ``u_scale`` — the anti-forgetting
    mix the flywheel fine-tune trains on (harvested serving trajectories
    + replayed synthetic ones). ``b``'s trajectory ids are shifted past
    ``a``'s, so ``split_by_trajectory`` and per-case eval keep working
    on the combined set; ``ref`` stays ``a``'s."""
    if a.windows.shape[1:] != b.windows.shape[1:]:
        raise ValueError(
            f"cannot concat datasets of different window shapes "
            f"{a.windows.shape[1:]} vs {b.windows.shape[1:]} "
            f"(mesh/hist_len must match)")
    u_scale = max(a.u_scale, b.u_scale)
    # targets are stored pre-divided by their own u_scale: rescale both
    # onto the shared one so the physical displacements stay identical
    targets = np.concatenate([a.targets * (a.u_scale / u_scale),
                              b.targets * (b.u_scale / u_scale)])
    return TrajectoryDataset(
        load_vol=np.concatenate([a.load_vol, b.load_vol]),
        windows=np.concatenate([a.windows, b.windows]),
        targets=targets.astype(np.float32),
        u_scale=u_scale,
        traj_id=np.concatenate([a.traj_id,
                                b.traj_id + a.n_trajectories]),
        cases=a.cases + b.cases,
        ref=a.ref)


def harvest_dataset(gateway_log, mesh: Tuple[int, int], *,
                    cfg: CRONetConfig, n_iter: int = 40, rmin: float = 1.5,
                    max_cases: int = 16, batch: int = 8
                    ) -> Optional[TrajectoryDataset]:
    """Convert a bucket's harvested fallback traffic into a training
    dataset: the rejected (fell-back-to-FEA) requests' load cases are
    pulled from ``gateway_log``, deduplicated, and regenerated as
    pure-FEA SIMP trajectories on the bucket's mesh through
    ``run_simp_b`` — the DAgger-style move that puts the load
    configurations serving actually failed on into the fine-tune
    distribution (FE-CNN per-discretization fine-tuning, arXiv
    2106.13652).

    ``gateway_log`` is duck-typed: anything with
    ``rejected_cases(mesh)`` (``serve.flywheel.HarvestLog``) or a plain
    sequence of ``LoadCase``s / ``describe()`` dicts. Returns ``None``
    when the log holds no cases for the mesh — the flywheel trigger
    treats that as "nothing to learn from yet"."""
    raw = (gateway_log.rejected_cases(mesh)
           if hasattr(gateway_log, "rejected_cases") else gateway_log)
    seen, cases = set(), []
    for c in raw:
        case = c if isinstance(c, LoadCase) else LoadCase.from_dict(c)
        k = case.key()
        if k in seen:
            continue
        seen.add(k)
        cases.append(case)
    if not cases:
        return None
    # newest-first truncation: under the per-bucket spool bound the
    # most recent traffic is the distribution serving is failing on NOW
    if len(cases) > max_cases:
        cases = cases[-max_cases:]
    nelx, nely = int(mesh[0]), int(mesh[1])
    cfg = dataclasses.replace(cfg, nelx=nelx, nely=nely)
    return build_dataset(cfg, cases=cases, n_iter=n_iter, rmin=rmin,
                         batch=batch)


def split_by_trajectory(ds: TrajectoryDataset, heldout_frac: float = 0.25,
                        seed: int = 0):
    """Train/held-out split BY TRAJECTORY (never by window — windows of
    one trajectory are heavily correlated, so a window-level split leaks
    the eval set into training). Returns (train_traj, held_traj) index
    arrays; at least one trajectory is held out when there are >= 2, and
    trajectory 0 (the canonical case) always stays in training."""
    n = ds.n_trajectories
    if n < 2 or heldout_frac <= 0.0:
        return np.arange(n), np.arange(0)
    n_held = min(n - 1, max(1, int(round(n * heldout_frac))))
    rng = np.random.default_rng(seed)
    held = rng.choice(np.arange(1, n), size=n_held, replace=False)
    held = np.sort(held)
    train = np.setdiff1d(np.arange(n), held)
    return train, held
