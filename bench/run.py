"""Run one benchmark cell of the CRONet serving path on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration ``bench/configs/<config>.json`` (with
the plain reference it names under ``bench/references/``), its traffic
mix ``bench/traffic/<traffic>.json`` (read by ``bench/traffic/generate.py``)
and each metric's reader ``bench/metrics/<name>.py`` (or, for a split
metric ``<name>.<part>``, ``bench/metrics/<name>.py``).

A run: JAX and the chip; weights from the seed on the device; a
``TopoGateway`` with the configuration's backends, slots and ladder; a
warm-up wave through it (set-up ends here); ``--seconds`` of the mix
through ``TopoGateway.submit`` -> ``TopoFuture.result``; then, with the
gateway shut down, the correctness check against the plain reference
(``bench/check.py``). With ``--trace 1`` a profiler trace of the middle of
the window gives the device's busy time, and the run reports its
per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.
The run exits non-zero and prints no result where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
GRACE_S = 60.0          # how long past the window's close answers are awaited
WARMUP_TIMEOUT_S = 900.0
WARM_S = 4.0            # seconds of the cell's own traffic before the window
WARM_UIDS = 10 ** 6     # warm-up requests take uids below -WARM_UIDS + n
CLOSED_LOOP_REQUESTS = 65536   # more than any closed loop completes in a run


class NoChip(Exception):
    pass


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else the
    time this module was first executed."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return min(btime + ticks / os.sysconf("SC_CLK_TCK"), T_START)
    except (OSError, ValueError, StopIteration):
        return T_START


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_file(*parts) -> str:
    return os.path.join(BENCH, *parts)


def load_cell(workload: str):
    """(benchmark, cell, configuration, traffic mix) for a cell's name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(bench_file("traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return bench, cell, cfg, mix


def metric_reader(name: str):
    for stem in (name, name.split(".")[0]):
        path = bench_file("metrics", stem + ".py")
        if os.path.exists(path):
            return load_module(path, "bench_metric_" + stem.replace(".", "_"))
    raise SystemExit(f"bench: no reader for metric {name!r}")


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def use_compile_cache():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else a fixed directory inside the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""
    _instance = None

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, name, _secs, **_kw):
        # fired for a compile and for a load from the persistent cache
        if name.endswith("backend_compile_duration"):
            self.n += 1


def devices_for(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs


def build_gateway(cfg: dict, params):
    from repro.configs.cronet import CRONetConfig
    from repro.serve import TopoGateway

    arch = dict(cfg["cronet"])
    arch["b_pool"], arch["t_pool"] = (tuple(arch["b_pool"]),
                                      tuple(arch["t_pool"]))
    ccfg = CRONetConfig(name=cfg["name"], nelx=cfg["nelx"], nely=cfg["nely"],
                        dtype="float32", **arch)
    return TopoGateway(
        ccfg, params, float(cfg["u_scale"]), slots=cfg["slots"],
        max_pending=None, ladder=tuple(cfg["ladder"]),
        precision=cfg["precision"], error_threshold=cfg["error_threshold"],
        verify_every=cfg["verify_every"], rmin=cfg["rmin"],
        backend=cfg["backend"], fea_backend=cfg["fea_backend"],
        shards=cfg["shards"])


class Problems:
    """Builds each request's point-load problem from the mesh's MBB
    template, so that a submission costs the client microseconds."""

    def __init__(self, cfg: dict):
        from repro.fea import fea2d

        self.nely = cfg["nely"]
        self.template = fea2d.point_load_problem(
            cfg["nelx"], cfg["nely"], volfrac=cfg["volfrac"])

    def __call__(self, r: dict):
        f = np.zeros(self.template.f.shape, np.float32)
        f[2 * r["load_x"] * (self.nely + 1) + 1] = r["fy"]
        return self.template._replace(f=f)


class Window:
    """Drives the mix through the gateway and stamps every request."""

    def __init__(self, gw, problems, mix, nelx, seconds, seed):
        from bench.traffic import generate

        count = (generate.expected_count(mix, seconds)
                 if mix["loop"] == "open" else CLOSED_LOOP_REQUESTS)
        self.gw, self.problems, self.mix = gw, problems, mix
        self.reqs = generate.requests(mix, nelx, count, seed)
        self.seconds = seconds
        self.cv = threading.Condition()
        self.finished = []          # (monotonic stamp, index)
        self.sent = {}              # index -> (future, when it was due)

    def _done(self, i):
        def cb(_fut):
            t = time.monotonic()
            with self.cv:
                self.finished.append((t, i))
                self.cv.notify_all()
        return cb

    def submit(self, i, uid0, due):
        from repro.serve import TopoRequest

        r = self.reqs[i]
        req = TopoRequest(uid=uid0 + i, problem=self.problems(r),
                          n_iter=r["n_iter"])
        t = time.monotonic()
        fut = self.gw.submit(req)
        self.sent[i] = (fut, t if due is None else due)
        fut.add_done_callback(self._done(i))

    def closed(self, t0, uid0):
        """``outstanding`` requests in flight until the window closes."""
        close = t0 + self.seconds
        nxt = 0
        for _ in range(int(self.mix["outstanding"])):
            self.submit(nxt, uid0, None)
            nxt += 1
        seen = 0
        while True:
            with self.cv:
                self.cv.wait_for(lambda: len(self.finished) > seen,
                                 timeout=max(close - time.monotonic(), 0.01))
                new = self.finished[seen:]
                seen = len(self.finished)
            if time.monotonic() >= close:
                break
            for _ in new:
                self.submit(nxt, uid0, None)
                nxt += 1

    def open(self, t0, uid0):
        """Each request submitted when due; returns lateness per request."""
        late = []
        for i, r in enumerate(self.reqs):
            if r["due_s"] >= self.seconds:
                break
            due = t0 + r["due_s"]
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            late.append(time.monotonic() - due)
            self.submit(i, uid0, due)
        return late

    def drive(self, t0, uid0):
        """The mix from ``t0`` until ``seconds`` later; returns how late
        each open-loop submission was (empty for a closed loop)."""
        if self.mix["loop"] == "open":
            return self.open(t0, uid0)
        self.closed(t0, uid0)
        return []

    def wait_all(self, deadline):
        for fut, _ in self.sent.values():
            try:
                fut.result(timeout=max(deadline - time.monotonic(), 0.0))
            except Exception:  # noqa: BLE001 - a failure is counted below
                pass


class Profiler(threading.Thread):
    """Traces ``span_s`` seconds in the middle of the window."""

    def __init__(self, start_at: float, span_s: float, logdir: str):
        super().__init__(name="bench-profiler", daemon=True)
        self.start_at, self.span_s, self.logdir = start_at, span_s, logdir
        self.error = None

    def run(self):
        import jax

        try:
            time.sleep(max(self.start_at - time.monotonic(), 0.0))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.logdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench_window"):
                    time.sleep(self.span_s)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - reported by the caller
            self.error = e


def warm_up(gw, problems, cfg, mix, seed, marks):
    """Before the window: one wave through the gateway, as many requests as
    the mix keeps in flight (a closed loop) or as there are slots (an open
    loop), with staggered iteration counts, which starts every shard (each
    compiles or loads its ladder as it starts); then ``WARM_S`` seconds of
    the mix itself, from another seed, awaited to the end. The window then
    meets no lane, rung or device that set-up has not served."""
    from repro.serve import TopoRequest

    from bench.traffic import generate

    n = int(mix.get("outstanding", cfg["slots"]))
    hist = cfg["cronet"]["hist_len"]
    futs = [gw.submit(TopoRequest(
        uid=-1 - i, problem=problems(r), n_iter=hist + 2 + i % 4))
        for i, r in enumerate(generate.requests(mix, cfg["nelx"], n,
                                                seed ^ 0x5EED))]
    for f in futs:
        f.result(timeout=WARMUP_TIMEOUT_S)
    marks.append(("gateway, ladder and first wave", time.time()))
    win = Window(gw, problems, mix, cfg["nelx"], WARM_S, seed ^ 0xBEEF)
    win.drive(time.monotonic(), -WARM_UIDS)
    win.wait_all(time.monotonic() + WARMUP_TIMEOUT_S)
    marks.append((f"{WARM_S:g} s of the mix", time.time()))


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             overrides: dict | None = None, mix_overrides: dict | None = None,
             allow_cpu: bool = False, faults=None, variants: bool = False,
             log=print):
    """One run of a cell. Returns the result dict (see module docstring).

    ``overrides`` and ``mix_overrides`` replace keys of the configuration
    and of the traffic mix (the control, rate sweeps and tests use them);
    ``faults`` is called with the gateway before the window, so a test can
    break the timed path underneath; ``variants`` adds the control's and
    the planted faults' readings (``bench/check.py``) under ``variants``."""
    bench, cell, cfg, mix = load_cell(workload)
    cfg = {**cfg, **(overrides or {})}
    mix = {**mix, **(mix_overrides or {})}
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoChip("the system under test (src/repro) is not in this "
                     "checkout")
    for p in (src, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    use_compile_cache()
    import jax

    from bench import check, tap as tapmod
    from repro.obs import metrics as obs_metrics

    devs = devices_for(cell["chips"], allow_cpu)
    used = devs[:cell["chips"]]
    compiles = CompileCounter.get()
    ref = load_module(bench_file("references", cfg["reference"] + ".py"),
                      "bench_reference_" + cfg["reference"])

    # ------------------------------------------------------------ set-up
    marks = [("jax and the chip", time.time())]
    dims = dict(cfg["cronet"], nelx=cfg["nelx"], nely=cfg["nely"])
    params = ref.make_params(dims, seed)
    jax.block_until_ready(params)
    marks.append(("weights", time.time()))
    gw = build_gateway(cfg, params)
    tapper = tapmod.Tap()
    try:
        problems = Problems(cfg)
        warm_up(gw, problems, cfg, mix, seed, marks)
        if faults is not None:
            faults(gw)
        for eng in gw.engines.values():
            tapper.attach(eng)
        win = Window(gw, problems, mix, cfg["nelx"], seconds, seed)
        hyb = obs_metrics.default_registry().counter("hybrid_compiles_total")
        hyb0, comp0 = hyb.total(), compiles.n
        prof = None
        if trace:
            span = min(5.0, max(1.0, 0.25 * seconds))
            logdir = tempfile.mkdtemp(prefix="bench_trace_")
        marks.append(("tap and traffic", time.time()))
        # ----------------------------------------------------- window
        t0 = time.monotonic()
        setup_s = time.time() - process_start()
        steps0 = sum(e.total_steps for e in gw.engines.values())
        if trace:
            prof = Profiler(t0 + 0.5 * (seconds - span), span, logdir)
            prof.start()
        steps_box = {}

        def read_steps_at_close():
            time.sleep(max(t0 + seconds - time.monotonic(), 0.0))
            steps_box["n"] = sum(e.total_steps for e in gw.engines.values())

        closer = threading.Thread(target=read_steps_at_close, daemon=True)
        closer.start()
        late = win.drive(t0, 0)
        closer.join()
        close = t0 + seconds
        win.wait_all(close + GRACE_S)
        if prof is not None:
            prof.join()
        hyb_in, comp_in = hyb.total() - hyb0, compiles.n - comp0
        memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in used)
        shards = sum(e.shards for e in gw.engines.values())

        # ------------------------------------------------ collect
        records, failed = [], 0
        done_at = dict((i, t) for t, i in win.finished)
        for i, (fut, due) in sorted(win.sent.items()):
            r = win.reqs[i]
            if not fut.done() or fut.exception() is not None:
                failed += 1
                continue
            q = fut.request
            records.append({
                "uid": q.uid, "load_x": r["load_x"], "fy": r["fy"],
                "n_iter": r["n_iter"], "due": due,
                "done": done_at[i], "latency_s": done_at[i] - due,
                "queue_wait_s": q.queue_wait_s, "cg_iters": q.cg_iters,
                "fea_iters": q.fea_iters, "cronet_iters": q.cronet_iters,
                "cg_breakdowns": q.cg_breakdowns, "density": q.density,
                "compliance": q.compliance})
        if mix["loop"] == "closed":
            in_window = [r for r in records if r["done"] <= close]
            span_s = (max(r["done"] for r in in_window) - t0
                      if in_window else seconds)
        else:
            in_window = records
            span_s = seconds
    finally:
        gw.shutdown()
    del gw
    tapper.release()

    trace_out = None
    if trace:
        from bench import trace_reduce

        if prof.error is not None:
            raise RuntimeError(f"profiler failed: {prof.error}")
        trace_out = trace_reduce.reduce_dir(logdir, len(used))
        shutil.rmtree(logdir, ignore_errors=True)

    # ---------------------------------------------------- earlier lines
    fea = sum(r["fea_iters"] for r in in_window)
    cro = sum(r["cronet_iters"] for r in in_window)
    log(f"device: {used[0].platform} {used[0].device_kind} x{len(devs)} "
        f"(cell uses {len(used)})")
    prev = process_start()
    parts = []
    for label, t in marks:
        parts.append(f"{label} {t - prev:.3f} s")
        prev = t
    log("set-up: " + "; ".join(parts))
    log(f"set-up {setup_s:.3f} s; window {seconds} s; programs compiled or "
        f"loaded in the window: {comp_in}; hybrid_compiles_total in the "
        f"window: {hyb_in:.0f}")
    log(f"completed in window {len(in_window)} of {len(win.sent)} submitted, "
        f"failed {failed}; surrogate accepted on {cro} of {cro + fea} "
        f"iterations ({(cro / max(cro + fea, 1)):.4f})")
    if mix["loop"] == "open" and len(in_window) >= 6:
        by_due = sorted(in_window, key=lambda r: r["due"])
        third = len(by_due) // 3
        log("open loop latency p95 in the first / last third of the window: "
            + " / ".join(f"{np.percentile([r['latency_s'] for r in part], 95):.4f} s"
                         for part in (by_due[:third], by_due[-third:])))
    if late:
        log(f"open loop lateness: median {np.median(late) * 1e3:.3f} ms, "
            f"p95 {np.percentile(late, 95) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms over {len(late)} requests")
    if trace_out is not None:
        log("device busy per chip: " + ", ".join(
            f"{k} {v:.4f}s" for k, v in trace_out["busy_s"].items())
            + f" of {trace_out['window_s']:.4f}s traced")

    # ---------------------------------------------------- metrics
    flops = load_module(bench_file("flops.py"), "bench_flops")
    with open(bench_file("peaks.json")) as f:
        peaks = json.load(f)
    ctx = SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, seconds=seconds, setup_s=setup_s,
        window_s=span_s, records=in_window,
        steps=steps_box.get("n", steps0) - steps0, shards=shards,
        chips=len(used), trace=trace_out, flops=flops,
        peaks=peaks, device_kind=used[0].device_kind)
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---------------------------------------------------- correctness
    picked = check.sample(in_window, int(cfg["check"]["sample"]), seed)
    t_check = time.monotonic()
    rows = []
    ok, numbers, varied = check.run(cfg, ref, seed, picked, tapper, rows,
                                    variants)
    for row in rows:
        log("replayed " + json.dumps(row))
    log(f"reference replay of {len(picked)} requests in "
        f"{time.monotonic() - t_check:.2f} s; the tap launched "
        f"{tapper.launches} programs of its own")
    ok = ok and failed == 0 and len(in_window) > 0
    checks = {k: {"value": v, "limit": lim} for k, v, lim in numbers}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory)}
    if trace_out is not None:
        device["busy_s"] = float(np.mean(list(trace_out["busy_s"].values())))
        device["window_s"] = trace_out["window_s"]
    result = {"correct": bool(ok), "attempted": len(win.sent),
              "failed": failed, "metrics": metrics, "device": device}
    if trace_out is not None:
        result["breakdown"] = trace_out["breakdown"]
    if variants:
        result["variants"] = {
            k: {"correct": bool(v_ok),
                "checks": {n: {"value": v, "limit": lim}
                           for n, v, lim in nums}}
            for k, (v_ok, nums) in varied.items()}
    result["checks"] = checks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        sys.exit(f"bench: {e}")
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
