"""Run the CRONet serving path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: sharded engine vs one shard

The model is CRONet-large as published (paper Table I widths, 419,760
parameters, 60x20 mesh, hist_len=10), obtained the way
``examples/serve_topo.py --train`` obtains it: a small seeded multi-load
dataset (``dataset.build_dataset``: the MBB trajectory and seeded point
loads, a quarter of them held out), a short training run registered in
a ``ModelRegistry`` under ``experiments/results/chip_smoke/``, and a
``TopoGateway`` built from that registry. Everything comes from seed 0;
nothing is downloaded.

One chip:
  Phase A  default backends (``oracle`` forward, ``reference`` CG). Every
           request of two waves completes; each final design has finite
           densities in [0, 1], meets its volume fraction within VOL_TOL,
           and its FEA-evaluated compliance is within REF_C_TOL (pure
           FEA) or REF_C_TOL_CRONET (surrogate steps accepted) of a pure
           SIMP run of the same problem (``simp.run_simp`` with its FEA
           solver jitted, on the chip, under highest matmul precision).
           At least one request accepts the CRONet surrogate, and wave 2
           agrees with wave 1.
  Phase B  the megakernel's forward first agrees with the oracle's within
           FWD_TOL on training windows; then the same requests run on the
           Pallas kernels (``megakernel`` forward, ``fused`` CG), take
           the CRONet branch too, and agree with phase A: compliances
           within PHASE_C_TOL, mean absolute density difference within
           PHASE_X_TOL. Bitwise equality is reported, not required.
The reference run comes last, so that a phase failure costs no
reference time.
Four chips (``--chips 4``): one engine whose slot groups are pinned one
per chip (``shard_devices``) serves the same requests as a one-shard
engine; the device holding each shard's state is printed, the shards
must sit on four distinct devices, and the results must agree within
the phase tolerances.

The kernel resolution counter must show compiled kernels only. Every
line before the last is informational. The last line is one JSON object
naming the device; it is printed only when every check passed, and the
script exits non-zero otherwise (including when JAX finds no TPU).
"""
import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

SEED = 0
SIZE = "large"
TRAIN_CASES = 8         # seeded load cases: MBB + 7 point loads, 2 held out
N_ITER = 40             # SIMP iterations per trajectory and per request
TRAIN_STEPS = 6000
THRESHOLD = 0.1         # residual gate (examples/serve_topo.py default)
SLOTS = 8
LADDER = (2, 4, 8)
N_REQUESTS = 6          # per wave: the MBB anchor + seeded point loads
BIFURCATING = (2,)      # seeded loads left out: f32 rounding alone picks
#                         their local optimum (see REF_C_TOL)
RESULT_TIMEOUT_S = 900

VOL_TOL = 1e-3          # |mean density - volfrac|
REF_C_TOL = 0.005       # FEA compliance vs pure SIMP, relative, pure FEA
REF_C_TOL_CRONET = 0.05  # the same once surrogate steps were accepted
PHASE_C_TOL = 0.05      # compliance between two serving runs, relative
PHASE_X_TOL = 0.05      # mean |density difference| between two runs
FWD_TOL = 1e-3          # megakernel vs oracle forward, max |diff| / max |ref|

ROOT = os.path.dirname(os.path.abspath(__file__))


class CheckFailed(Exception):
    pass


def check(ok: bool, msg: str):
    if not ok:
        raise CheckFailed(msg)


def train_model(cfg, out_dir: str):
    """Seeded dataset -> short training run -> registry version."""
    from repro.fea import dataset as dsm
    from repro.fea import train_cronet
    from repro.serve import ModelRegistry

    shutil.rmtree(out_dir, ignore_errors=True)
    registry = ModelRegistry(os.path.join(out_dir, "registry"))
    t0 = time.perf_counter()
    data = dsm.build_dataset(
        cfg, cases=dsm.sample_load_cases(TRAIN_CASES, seed=SEED),
        n_iter=N_ITER)
    t1 = time.perf_counter()
    record, result = train_cronet.train_and_register(
        cfg, registry, tag="chip-smoke", data=data, steps=TRAIN_STEPS,
        seed=SEED, verbose=False, error_threshold=THRESHOLD)
    t2 = time.perf_counter()
    m = result.eval_metrics
    print(f"model: {cfg.name} {cfg.nelx}x{cfg.nely} hist_len={cfg.hist_len} "
          f"params={cfg.param_count()}; dataset {data.n_windows} windows "
          f"in {t1 - t0:.1f}s (u_scale {data.u_scale:.6g}); "
          f"{TRAIN_STEPS} steps in "
          f"{t2 - t1:.1f}s, mse {result.losses[0]:.5f} -> {result.losses[-1]:.6f}, "
          f"{'held-out' if m['heldout'] else 'training'} acceptance "
          f"{m['acceptance']:.2f}")
    per_case = train_cronet.evaluate(cfg, result.params, data,
                                     error_threshold=THRESHOLD)["per_case"]
    held = {f"traj{int(t)}_" for t in result.heldout_traj}
    print("model: mean relative error per trajectory (* held out): "
          + ", ".join(f"{k}{'*' if k.startswith(tuple(held)) else ''} "
                      f"{v['mean_rel_err']:.4f}"
                      for k, v in per_case.items()))
    return registry, record.tag, result.params, data


def make_problems(cfg):
    """The MBB anchor plus seeded off-distribution top-edge point loads
    (the request mix of examples/serve_topo.py), less the BIFURCATING
    ones."""
    from repro.fea import fea2d

    rng = np.random.default_rng(SEED)
    probs = [fea2d.point_load_problem(cfg.nelx, cfg.nely)]
    while len(probs) < N_REQUESTS + len(BIFURCATING):
        probs.append(fea2d.point_load_problem(
            cfg.nelx, cfg.nely,
            load_node=(int(rng.integers(0, cfg.nelx - 1)), 0),
            load=(0.0, float(-0.5 - rng.random()))))
    return [p for i, p in enumerate(probs) if i not in BIFURCATING]


def serve(label: str, registry, tag: str, probs, **engine_kwargs):
    """Two waves of the same requests through a registry-backed gateway.
    Returns (wave results, shard devices per engine)."""
    from repro.serve import TopoGateway, TopoRequest

    t0 = time.perf_counter()
    gw = TopoGateway.from_registry(
        registry, tag=tag, slots=SLOTS, ladder=LADDER, precision="fp32",
        error_threshold=THRESHOLD, max_pending=None, **engine_kwargs)
    waves, walls = [], []
    try:
        for w in range(2):
            if w:
                t0 = time.perf_counter()
            futs = [gw.submit(TopoRequest(uid=100 * w + i, problem=p,
                                          n_iter=N_ITER))
                    for i, p in enumerate(probs)]
            done = [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]
            wall = time.perf_counter() - t0
            check(all(r.done for r in done), f"{label}: a request did not "
                  f"complete")
            waves.append(done)
            walls.append(wall)
            kind = ("cold: gateway, compile and warm-up included" if w == 0
                    else "warm")
            print(f"{label}: wave {w + 1} ({kind}): {len(done)} requests "
                  f"in {wall:.2f}s")
        print(f"{label}: compile/warm-up ~{walls[0] - walls[1]:.2f}s "
              f"(wave 1 - wave 2)")
        shards = {}
        for mesh, eng in gw.engines.items():
            # a shard that got no request may still be warming its ladder
            # in its own thread; its state exists once that is done
            deadline = time.monotonic() + RESULT_TIMEOUT_S
            while (any(sh.state is None for sh in eng._shards)
                   and time.monotonic() < deadline):
                time.sleep(0.1)
            shards[mesh] = [
                (str(sh.device),
                 None if sh.state is None
                 else sorted(str(d) for d in sh.state.x.devices()))
                for sh in eng._shards]
    finally:
        gw.shutdown()
    for r in waves[-1]:
        print(f"{label}: req {r.uid % 100}: n_cronet={r.cronet_iters} "
              f"n_fea={r.fea_iters} cg_iters={r.cg_iters} "
              f"cg_breakdowns={r.cg_breakdowns} "
              f"compliance={r.compliance:.6g}")
    return waves, shards


def _fea_solver():
    """run_simp's default FEA solver (CG solve from zero, then compliance
    and sensitivity), jitted once for all problems of one mesh."""
    import jax

    from repro.fea import fea2d

    @jax.jit
    def solver(prob, x):
        u, _, _ = fea2d.solve(prob, x)
        c, dc = fea2d.compliance_and_sens(prob, x, u)
        return u, c, dc

    return solver


def fea_compliance(solver, prob, x) -> float:
    """Compliance of a final design by a full FEA solve, in f32 at the
    highest matmul precision."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return float(solver(prob, jnp.asarray(x, jnp.float32))[1])


def reference_compliances(solver, probs):
    """FEA compliance of each problem's pure-SIMP design: ``simp.run_simp``
    with the jitted solver (its eager default compiles the CG loop again
    on every iteration), at the highest matmul precision."""
    import jax

    from repro.fea import simp

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        out = [fea_compliance(solver, p, simp.run_simp(
                   p, n_iter=N_ITER, solver=lambda x, p=p: solver(p, x)
               )[0].x)
               for p in probs]
    print(f"reference: simp.run_simp x{len(probs)} at highest precision in "
          f"{time.perf_counter() - t0:.1f}s")
    return out


def check_designs(label: str, solver, done, probs, ref_c=None):
    """Finite densities in [0, 1], volume fraction met, and (with ref_c)
    FEA compliance near the pure-SIMP reference. Returns the FEA
    compliances."""
    cs = []
    for r, p in zip(done, probs):
        x = np.asarray(r.density)
        check(bool(np.isfinite(x).all()), f"{label}: req {r.uid}: non-finite "
              f"density")
        check(float(x.min()) >= 0.0 and float(x.max()) <= 1.0,
              f"{label}: req {r.uid}: density outside [0, 1]")
        vol = float(x.mean())
        check(abs(vol - p.volfrac) <= VOL_TOL, f"{label}: req {r.uid}: "
              f"volume {vol:.6f} vs volfrac {p.volfrac}")
        c = fea_compliance(solver, p, x)
        check(np.isfinite(c) and c > 0, f"{label}: req {r.uid}: compliance "
              f"{c}")
        cs.append(c)
        if ref_c is not None:
            ref = ref_c[len(cs) - 1]
            dev = abs(c - ref) / ref
            tol = REF_C_TOL_CRONET if r.cronet_iters else REF_C_TOL
            print(f"{label}: req {r.uid}: FEA compliance {c:.6g}, run_simp "
                  f"{ref:.6g} ({dev:.4%}, limit {tol:.1%})")
            check(dev <= tol, f"{label}: req {r.uid}: compliance deviates "
                  f"{dev:.4%} from run_simp")
    return cs


def compare(label: str, a, ca, b, cb):
    """Two serving runs of the same requests: bitwise report plus the
    PHASE_* tolerances."""
    bitwise = all(np.array_equal(np.asarray(x.density), np.asarray(y.density))
                  for x, y in zip(a, b))
    dx = max(float(np.mean(np.abs(np.asarray(x.density)
                                  - np.asarray(y.density))))
             for x, y in zip(a, b))
    dc = max(abs(u - v) / abs(u) for u, v in zip(ca, cb))
    print(f"{label}: bitwise-equal densities: {bitwise}; largest mean "
          f"|density diff| {dx:.3e} (limit {PHASE_X_TOL}); largest "
          f"compliance diff {dc:.4%} (limit {PHASE_C_TOL:.0%})")
    check(dx <= PHASE_X_TOL, f"{label}: densities differ by {dx:.3e}")
    check(dc <= PHASE_C_TOL, f"{label}: compliances differ by {dc:.4%}")


def kernel_resolutions():
    from repro.obs import metrics as obs_metrics

    ctr = obs_metrics.default_registry().counter("kernel_resolutions_total")
    res = {f"{m}/{s}": ctr.value(mode=m, source=s)
           for m in ("compiled", "interpret") for s in ("auto", "explicit")}
    print(f"kernel_resolutions_total: {res}")
    check(res["interpret/auto"] == 0 and res["interpret/explicit"] == 0,
          "a kernel resolved to the Pallas interpreter")
    return res


def check_forward(cfg, params, data):
    """The megakernel's CRONet forward against the oracle's on the MBB
    trajectory's first windows, both at highest precision."""
    import jax
    import jax.numpy as jnp

    from repro.core import cronet
    from repro.kernels import cronet_pipeline

    rows = data.rows_of(0)[:SLOTS]
    lv = jnp.asarray(data.load_vol[rows])
    hist = jnp.asarray(data.windows[rows])
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, a, b: cronet.forward(cfg, p, a, b))(
            params, lv, hist)
    out = jax.jit(lambda p, a, b: cronet_pipeline.cronet_fused(
        cfg, p, a, b))(params, lv, hist)
    check(out.shape == ref.shape, f"megakernel output {out.shape} vs "
          f"{ref.shape}")
    out, ref = np.asarray(out), np.asarray(ref)
    check(bool(np.isfinite(out).all()), "megakernel output not finite")
    dev = float(np.abs(out - ref).max() / np.abs(ref).max())
    print(f"megakernel vs oracle forward, {len(rows)} windows: max |diff| / "
          f"max |ref| = {dev:.3e} (limit {FWD_TOL})")
    check(dev <= FWD_TOL, f"megakernel forward deviates {dev:.3e}")


def check_cronet_taken(label: str, done):
    n_cronet = [r.cronet_iters for r in done]
    check(sum(n_cronet) > 0, f"{label}: no request took the CRONet branch")


def run_one_chip(cfg, out_dir: str):
    registry, tag, params, data = train_model(cfg, out_dir)
    probs = make_problems(cfg)
    solver = _fea_solver()
    a_waves, _ = serve("phase A", registry, tag, probs)
    check_cronet_taken("phase A", a_waves[-1])
    ca1, ca = (check_designs("phase A", solver, w, probs) for w in a_waves)
    compare("phase A wave 2 vs wave 1", a_waves[0], ca1, a_waves[1], ca)

    check_forward(cfg, params, data)
    b_waves, _ = serve("phase B", registry, tag, probs,
                       backend="megakernel", fea_backend="fused")
    check_cronet_taken("phase B", b_waves[-1])
    _, cb = (check_designs("phase B", solver, w, probs) for w in b_waves)
    compare("phase B vs A", a_waves[-1], ca, b_waves[-1], cb)
    res = kernel_resolutions()
    check(res["compiled/auto"] > 0, "phase B resolved no compiled kernel")

    ref_c = reference_compliances(solver, probs)
    check_designs("phase A", solver, a_waves[-1], probs, ref_c)


def run_four_chips(cfg, out_dir: str):
    registry, tag, _, _ = train_model(cfg, out_dir)
    probs = make_problems(cfg)
    solver = _fea_solver()
    s_waves, shards = serve("sharded", registry, tag, probs)
    for mesh, placement in shards.items():
        for i, (dev, holders) in enumerate(placement):
            print(f"sharded: engine {mesh[0]}x{mesh[1]} shard {i}: pinned "
                  f"to {dev}, state on {holders}")
        distinct = {dev for dev, _ in placement}
        check(len(placement) == 4 and len(distinct) == 4,
              f"sharded: shards on {sorted(distinct)}, expected 4 devices")
        check(all(holders == [dev] for dev, holders in placement),
              "sharded: a shard's state is not on its pinned device")
    o_waves, _ = serve("one shard", registry, tag, probs, shards=1)
    cs = check_designs("sharded", solver, s_waves[-1], probs)
    co = check_designs("one shard", solver, o_waves[-1], probs)
    compare("sharded vs one shard", o_waves[-1], co, s_waves[-1], cs)
    kernel_resolutions()


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded-engine path "
                         "and its one-shard comparison")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit("chip_smoke: the repro package (src/repro) is not next to "
                 "this script")
    sys.path.insert(0, src)
    from repro.common import use_compile_cache

    cache = use_compile_cache()
    import jax

    from repro.configs.cronet import get_cronet_config

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")

    cfg = get_cronet_config(SIZE)
    out_dir = os.path.join(ROOT, "experiments", "results", "chip_smoke")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(cfg, out_dir)
        else:
            run_one_chip(cfg, out_dir)
    except CheckFailed as e:
        sys.exit(f"chip_smoke: check failed: {e}")
    print(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
