"""Records ``data/v5e_span_sample.xplane.pb``, the trace that
``test_span_reduce.py`` reads: a TopoServingEngine on the deployed
backends (megakernel forward, fused CG) at paper Table I widths on the
30x10 mesh, 4 slots on the ladder (2, 4), seeded weights, serving a wave
of four requests of 12-15 iterations after a warm-up wave. The
``bench_window`` span opens once every lane has dispatched its ninth
step, so it holds the forward's steps (from the eleventh), each lane's
harvest and the lane resets after it, and stays under 2 MB. Run it on one
TPU chip from the checkout's root:

    python3 bench/tests/record_span_sample.py <out_dir>

It writes the trace under ``<out_dir>`` and prints what the reduction
reads from it.
"""
import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 7


def main(out_dir: str):
    import dataclasses

    import jax

    from bench import span_reduce, trace_reduce
    from repro.common import materialize
    from repro.configs.cronet import get_cronet_config
    from repro.core import cronet
    from repro.fea import fea2d
    from repro.serve import TopoRequest, TopoServingEngine

    cfg = dataclasses.replace(get_cronet_config("small"), dtype="float32")
    params = materialize(cronet.param_specs(cfg), jax.random.key(SEED))
    eng = TopoServingEngine(cfg, params, 377.622, slots=4, ladder=(2, 4),
                            precision="fp32", error_threshold=0.1,
                            backend="megakernel", fea_backend="fused")

    def submit(uid0):
        return [eng.submit(TopoRequest(
            uid=uid0 + k, n_iter=12 + k, problem=fea2d.point_load_problem(
                cfg.nelx, cfg.nely, load_node=(3 + 5 * k, 0),
                load=(0.0, -1.0)))) for k in range(4)]

    for f in submit(100):
        f.result(timeout=600)
    futs = submit(0)
    shard = eng._shards[0]
    while any(a is None or shard.slot_iters[i] < 9
              for i, a in enumerate(shard.slot_adm)):
        time.sleep(0.0005)
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for f in futs:
                f.result(timeout=600)
    finally:
        jax.profiler.stop_trace()
    eng.shutdown()
    path = max(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "v5e_span_sample.xplane.pb")
    with open(path, "rb") as src, open(out, "wb") as dst:
        dst.write(src.read())
    print(f"wrote {out}: {os.path.getsize(out)} bytes")

    from jax.profiler import ProfileData

    stats, kernels = set(), []
    for plane in ProfileData.from_file(out).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for e in line.events:
                    stats.update(k for k, _ in e.stats)
                    if span_reduce.region_of(e.name):
                        kernels.append(e.name[:160])
    print("stats on XLA Ops events:", sorted(stats))
    print("kernel events:", len(kernels), kernels[:2])
    print(json.dumps(span_reduce.reduce(trace_reduce.load(out), 1)))


if __name__ == "__main__":
    main(sys.argv[1])
