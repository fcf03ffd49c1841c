"""One run of one cell: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, one traffic mix or one
metric is found by name from ``BENCHMARK.json``:

- the cell's configuration file (``configs[].file``), whose ``kind``
  names the model adapter ``bench/models/<kind>.py``;
- the traffic mix ``bench/traffic/<traffic>.json`` (see ``loadgen``);
- each metric's reader ``bench/metrics/<name before the first '.'>.py``,
  a module with ``read(run) -> float | None``.

A run: the chip is checked, the weights are made on the device from the
seed (one jitted call), the payload pool is drawn from the seed, the
served model is registered with the program's ``ControlPlane`` with only
the cell's buckets, every bucket is compiled and warmed, and one round of
traffic goes through the plane.  That is set-up.  Then the window runs
for ``--seconds``: requests go ``ControlPlane.submit`` -> ``pump`` ->
``ImageBackend.launch`` -> the bucket executable.  Afterwards a sample of
the answers, drawn from the seed, is compared with the plain float32
reference, and the result line is printed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

from bench import check, loadgen, trace as tracemod
from bench.peaks import peaks_for

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class NoChip(SystemExit):
    """The machine lacks what the cell needs: no result is printed."""


# ---------------------------------------------------------------------------
# discovery: everything by name
# ---------------------------------------------------------------------------

def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file's contents
    traffic: loadgen.Traffic
    adapter: object         # bench/models/<kind>.py
    end_to_end: list[dict]  # the metric entries this cell reports
    per_layer: list[dict]
    bench_dir: pathlib.Path


def _reports(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    """A per-layer metric is read in the cells its ``workloads`` lists;
    without the key, in every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_of_cell


def find_cell(benchmark: dict, name: str, root: pathlib.Path,
              bench_dir: pathlib.Path = BENCH_DIR,
              plan_buckets=None) -> Cell:
    """Resolve workload ``name`` of ``benchmark`` (the parsed
    ``BENCHMARK.json``) to its files; ``root`` is the checkout root that
    the configuration's ``file`` is relative to."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r} (known: {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in benchmark["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    if plan_buckets is None:
        from repro.core.plan import BATCH_BUCKETS as plan_buckets
    traffic = loadgen.load_traffic(bench_dir / "traffic" / f"{w['traffic']}.json",
                                   plan_buckets)
    adapter = load_module(bench_dir / "models" / f"{conf['kind']}.py",
                          f"bench_model_{conf['kind']}")
    e2e = [m for m in benchmark["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=conf, traffic=traffic, adapter=adapter,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def metric_reader(bench_dir: pathlib.Path, metric_name: str):
    stem = metric_name.split(".", 1)[0]
    return load_module(bench_dir / "metrics" / f"{stem}.py",
                       f"bench_metric_{stem}")


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

def require_chip(chips: int):
    """The devices of the cell, or ``NoChip`` before any phase."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips; JAX found "
                     f"{len(devs)}")
    try:
        peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(f"bench: {e}") from None
    return devs[:chips]


def use_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = root / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(path)


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def seed32(seed: int) -> int:
    """A 31-bit PRNG key seed from any whole number."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
               & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    submitted: int = 0
    completed: int = 0                    # answered inside the window
    latencies: list = dataclasses.field(default_factory=list)
    lateness: list = dataclasses.field(default_factory=list)
    pump_spans: list = dataclasses.field(default_factory=list)
    launch_spans: list = dataclasses.field(default_factory=list)
    submit_spans: list = dataclasses.field(default_factory=list)


def _instrument_launch(be, win: Window, annotate: bool):
    """Record each backend launch as a host span (and a profiler
    annotation when tracing), from outside the program: the backend
    instance's ``launch`` is wrapped, its class is untouched."""
    import jax
    inner = be.launch

    def launch(payloads, bucket):
        t = time.perf_counter_ns()
        if annotate:
            with jax.profiler.TraceAnnotation("bench.launch"):
                out = inner(payloads, bucket)
        else:
            out = inner(payloads, bucket)
        win.launch_spans.append((t, time.perf_counter_ns()))
        return out

    be.launch = launch


def run_closed(cp, model: str, pool: np.ndarray, traffic, seconds: float,
               seed: int, sample: check.Reservoir, win: Window,
               payload_of: list):
    """Keep ``traffic.outstanding`` requests in flight: each answer is
    followed at once by a new request, until the window closes."""
    from repro.serving.control_plane import ServeRequest
    rng = np.random.default_rng([seed, 1])
    clock = time.perf_counter

    def submit(n):
        t = time.perf_counter_ns()
        for idx in rng.integers(0, len(pool), n):
            rid = len(payload_of)
            payload_of.append(int(idx))
            cp.submit(ServeRequest(rid=rid, model=model, payload=pool[idx]))
        win.submitted += n
        win.submit_spans.append((t, time.perf_counter_ns()))

    win.t0 = clock()
    t_end = win.t0 + seconds
    submit(traffic.outstanding)
    while True:
        t = time.perf_counter_ns()
        done = cp.pump()
        if done:
            win.pump_spans.append((t, time.perf_counter_ns()))
        for r in done:
            sample.offer(r.rid, r.out)
            win.latencies.append(r.t_done - r.t_arrival)
            r.out = None
        win.completed += len(done)
        if clock() >= t_end:
            break
        submit(len(done))
    win.t1 = clock()


def run_open(cp, model: str, pool: np.ndarray, traffic, seconds: float,
             seed: int, sample: check.Reservoir, win: Window,
             payload_of: list):
    """Submit each request at its scheduled arrival, whatever the server
    is doing; latency runs from the scheduled arrival."""
    from repro.serving.control_plane import ServeRequest
    offsets = loadgen.open_schedule(traffic, seconds, seed)
    idx = loadgen.payload_indices(len(offsets), len(pool), seed)
    payload_of.extend(int(i) for i in idx)
    clock = time.perf_counter
    n, i = len(offsets), 0
    win.t0 = clock()
    sched = win.t0 + offsets
    while i < n:
        now = clock()
        if sched[i] <= now:
            t = time.perf_counter_ns()
            while i < n and sched[i] <= now:
                cp.submit(ServeRequest(rid=i, model=model,
                                       payload=pool[idx[i]],
                                       t_arrival=float(sched[i])))
                win.lateness.append(now - sched[i])
                i += 1
            win.submit_spans.append((t, time.perf_counter_ns()))
        if cp.pending():
            t = time.perf_counter_ns()
            done = cp.pump()
            if done:
                win.pump_spans.append((t, time.perf_counter_ns()))
                _take(done, sample, win)
        elif i < n:
            wait = sched[i] - clock()
            if wait > 2e-4:
                time.sleep(wait - 1e-4)
    win.submitted = n
    win.t1 = max(clock(), win.t0 + seconds)


def _take(done, sample, win):
    for r in done:
        sample.offer(r.rid, r.out)
        win.latencies.append(r.t_done - r.t_arrival)
        r.out = None
    win.completed += len(done)


def drain(cp, sample, win: Window, limit_s: float) -> int:
    """Answer what is still queued after the window (the coalescing
    policy unchanged), for at most ``limit_s``; returns how many never
    came."""
    t_end = time.perf_counter() + limit_s
    while cp.pending() and time.perf_counter() < t_end:
        done = cp.pump()
        for r in done:
            sample.offer(r.rid, r.out)
            win.latencies.append(r.t_done - r.t_arrival)
            r.out = None
    return cp.pending()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path, t_start: float,
             chip: bool = True, program_over: Optional[dict] = None,
             swap: Optional[Callable] = None, log=None):
    """One run; returns the result line as a dict and the run's readings
    before they were reduced.  ``chip=False`` skips
    the look for a chip and the compile cache (tests on the CPU);
    ``program_over`` replaces fields of the program's model config (the
    program's own int8 path is the lower-precision control);
    ``swap(serve_fn, weights) -> serve_fn`` replaces the served function
    (the reference in the program's place, or a planted fault).  A traced
    run leaves its trace under ``.bench_out/trace`` until the next."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    import jax
    devs = require_chip(cell.chips) if chip else jax.devices()[:cell.chips]
    log(f"bench: {cell.name} seed={seed} seconds={seconds} trace={int(trace)}"
        f" devices={devs[0].platform}:{devs[0].device_kind}x{len(devs)}")
    if chip:
        log(f"bench: compile cache {use_compile_cache(root)}")
    compiles = CompileCounter()
    from repro.serving.control_plane import ControlPlane

    ad, model, conf = cell.adapter, cell.config["model"], cell.config
    cfg = ad.program_config(model, **(program_over or {}))
    ad.check_geometry(cfg, model)
    key = jax.random.PRNGKey(seed32(seed))
    k_w, k_pool = jax.random.split(key)
    make = jax.jit(lambda k: (lambda w: (w, ad.pack(w, cfg)))(
        ad.init_weights(k, model)))
    weights, params = make(k_w)
    pool = np.asarray(jax.jit(lambda k: ad.make_pool(
        k, conf["payload_pool"], model))(k_pool))
    serve = ad.serve_fn(params, cfg)
    if swap is not None:
        serve = swap(serve, weights)
    cp = ControlPlane()
    be = cp.register_image_model(
        cell.config_name, serve, np.zeros(ad.payload_shape(model), np.float32),
        buckets=cell.traffic.buckets, max_wait_ms=cell.traffic.max_wait_ms)
    t = time.perf_counter()
    be.warmup()
    # one round through the plane warms the host path as well
    from repro.serving.control_plane import ServeRequest
    cp.run([ServeRequest(rid=-1 - j, model=cell.config_name, payload=pool[j % len(pool)])
            for j in range(cell.traffic.buckets[-1])])
    log(f"bench: buckets {list(be.batcher.buckets)} compiled and warmed in "
        f"{time.perf_counter() - t:.3f} s; launch ms "
        + str({b: round(c * 1e3, 4) for b, c in
               be.batcher.bucket_cost_s.items()}))
    n_launch0 = len(be.batcher.launches)
    setup_s = time.perf_counter() - t_start

    win = Window()
    _instrument_launch(be, win, annotate=trace)
    sample = check.Reservoir(conf["check"]["sample"], seed)
    payload_of: list[int] = []
    loop = run_closed if cell.traffic.loop == "closed" else run_open
    n_comp0 = compiles.n
    trace_dir = root / ".bench_out" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        anchor = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("bench.window"):
            loop(cp, cell.config_name, pool, cell.traffic, seconds, seed,
                 sample, win, payload_of)
        jax.profiler.stop_trace()
    else:
        loop(cp, cell.config_name, pool, cell.traffic, seconds, seed,
             sample, win, payload_of)
    window_s = win.t1 - win.t0
    launches = list(be.batcher.launches[n_launch0:])
    compiles_in_window = compiles.n - n_comp0
    pending_at_close = cp.pending()
    unanswered = drain(cp, sample, win, cell.traffic.drain_s)
    st = cp.stats()
    failed = st["rejected"] + st["shed"] + unanswered
    log(f"bench: window {window_s:.4f} s, {win.submitted} requests, "
        f"{win.completed} answered in the window, {len(launches)} launches, "
        f"{compiles_in_window} compiles inside the window, "
        f"{pending_at_close} queued at the close")
    if win.lateness:
        late = np.asarray(win.lateness) * 1e3
        log(f"bench: generator lateness ms p50 {np.percentile(late, 50):.4f}"
            f" p99 {np.percentile(late, 99):.4f} max {late.max():.4f}")
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)

    # free the program before the reference runs
    del cp, be, serve, params
    gc.collect()
    jax.clear_caches()

    rids = [rid for rid, _ in sample.items]
    outs = np.stack([o for _, o in sample.items]) if rids else np.zeros(
        (0,), np.float32)
    xs = pool[[payload_of[r] for r in rids]]
    t = time.perf_counter()
    refs = reference_outputs(ad, weights, xs, model,
                             conf["check"].get("ref_block", 16),
                             precision=conf["check"]["reference_precision"])
    log(f"bench: reference over {len(rids)} sampled answers in "
        f"{time.perf_counter() - t:.3f} s")
    nums = check.numbers(outs, refs, unanswered)
    if len(rids):
        e = check.rel_errors(outs, refs)
        log(f"bench: rel_err per answer: median {np.median(e):.6g}, "
            f"p90 {np.percentile(e, 90):.6g}, max {e.max():.6g}")
    correct, shown = check.verdict(nums, conf["check"]["limits"])

    # every request's latency, answered in the window or in the drain; an
    # answer that never came waited at least the whole drain
    lat = list(win.latencies)
    lat += [cell.traffic.drain_s] * max(0, win.submitted - len(lat))
    run = SimpleNamespace(
        cell=cell, setup_s=setup_s, window_s=window_s,
        completed=win.completed, latencies_s=lat, launches=launches,
        submitted=win.submitted, failed=failed, win=win,
        peaks=peaks_for(devs[0].device_kind) if chip else None,
        model_flops=ad.model_flops(model),
        pallas_sites=pallas_sites(ad, cfg, model), trace=None,
        trace_window=None, compiles_in_window=compiles_in_window,
        pending_at_close=pending_at_close, checked=nums,
        sample_outs=outs, sample_xs=xs, weights=weights)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem_peak)}
    out = {"correct": bool(correct), "attempted": int(win.submitted),
           "failed": int(failed)}
    if trace:
        tr = tracemod.read_xplane(next(trace_dir.rglob("*.xplane.pb")))
        align_spans(tr, win, anchor)
        run.trace, run.trace_window = tr, tracemod.window(tr)
        lo, hi = run.trace_window
        device["busy_s"] = tracemod.busy(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["metrics"] = read_metrics(cell, cell.per_layer, run)
        out["device"] = device
        out["breakdown"] = {"device_ops": tracemod.top_ops(tr, lo, hi),
                            "idle_gaps": tracemod.idle_by_label(tr, lo, hi)}
    else:
        out["metrics"] = read_metrics(cell, cell.end_to_end, run)
        out["device"] = device
    out["checked"] = shown
    for k, v in shown.items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    return out, run


def reference_outputs(ad, weights, xs: np.ndarray, model: dict,
                      block: int, **kw) -> np.ndarray:
    """The reference over the sampled payloads, ``block`` rows at a time
    (one compiled program: the last block is padded)."""
    import jax
    if not len(xs):
        return np.zeros((0,), np.float32)
    f = jax.jit(lambda w, x: ad.reference(w, x, model, **kw))
    outs = []
    for i in range(0, len(xs), block):
        x = xs[i:i + block]
        pad = block - len(x)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        outs.append(np.asarray(f(weights, x))[:block - pad])
    return np.concatenate(outs)


def pallas_sites(ad, cfg, model: dict):
    """``bucket -> [Site]`` of the sites the program's plans route to the
    Pallas kernels at that bucket."""
    sites = ad.sites(model)
    plans = ad.program_plans(cfg)

    def at(bucket: int):
        return [s for s, p in zip(sites, plans)
                if p.route_for_batch(bucket).path == "pallas"]
    return at


def align_spans(tr: tracemod.Trace, win: Window, anchor_ns: int) -> None:
    """Put the host spans recorded on ``perf_counter_ns`` on the trace's
    clock, anchored at the ``bench.window`` annotation's start."""
    lo, _ = tracemod.window(tr)
    off = lo - anchor_ns
    for name, spans in (("bench.pump", win.pump_spans),
                        ("bench.submit", win.submit_spans)):
        tr.spans[name] = [(s + off, e + off) for s, e in spans]
    if "bench.launch" not in tr.spans:
        tr.spans["bench.launch"] = [(s + off, e + off)
                                    for s, e in win.launch_spans]


def read_metrics(cell: Cell, entries: list[dict], run) -> dict:
    out = {}
    for m in entries:
        v = metric_reader(cell.bench_dir, m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = pathlib.Path.cwd()
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    try:
        cell = find_cell(benchmark, args.workload, root)
        result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          root=root, t_start=t_start)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0
