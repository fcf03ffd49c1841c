"""The readings of the program's own spans and site scopes: the readers on
a seeded trace shaped like a serving window, checked against brute
counts; the guard against another run's profile; the protobuf fields the
scopes are decoded from; and the readers in whole traced runs on the
CPU."""
import json
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchkit import ROOT, tiny_gan

from bench import convcount, harness, peaks, program_trace

STEPS = program_trace.STEPS
MODEL = {"z_dim": 8, "layers": [[4, 32, 16, 5, 2], [8, 16, 3, 5, 2]]}
SKEW = 8e5          # ns the fixture's device events run ahead of the host's
NEW = ("queue_wait_ms.open", "schedule_ms_per_launch.bulk",
       "schedule_ms_per_launch.open", "h2d_ms_per_launch.bulk",
       "h2d_ms_per_launch.open", "d2h_ms_per_launch.bulk",
       "d2h_ms_per_launch.open", "heaviest_site_roofline")


def _kernel(i, site):
    return (f"%untangled_deconv.{4 + i} = f32[4,8,8,16] custom-call(...)",
            f"jit(batched)/{site}/untangled_deconv/pallas_call:")


@pytest.fixture(scope="module")
def window_trace():
    """300 launches, each a schedule span, a launch span and its five
    steps, with the device's kernels (scoped by site) and other ops inside
    the wait, all device events placed ``SKEW`` early; the window starts at
    the 10th schedule and ends at the 290th.  Returns the trace, per
    launch what the brute counts are taken from, the sites, and each
    device event with the site a brute count gives it (``None``: a kernel
    that names no site or two; ``"-"``: no kernel)."""
    rng = np.random.default_rng(13)
    gan = harness.load_module(ROOT / "bench/models/gan.py", "bench_model_gan")
    sites = [s.name for s in gan.sites(MODEL)]
    spans = {n: [] for n in ("huge2.schedule", program_trace.LAUNCH) + STEPS}
    ops, modules, truth, keyed, t = [], [], [], [], 1_000.0
    entries = [(*_kernel(i, s), s) for i, s in enumerate(sites)] + [
        ("%fusion.3 = f32[4,4,4,32] fusion(...)",
         "jit(batched)/proj/dot_general:", "-"),
        (*_kernel(2, "dc0"), "dc0"), (*_kernel(3, "x"), None),
        (*_kernel(4, "dc0/dc1"), None)]
    for seq in range(1, 301):
        bucket = int(rng.choice([1, 4, 16, 64]))
        waits = rng.uniform(0, 5e3, int(rng.integers(1, bucket + 1)))
        s1 = t + rng.uniform(5e3, 3e4)
        spans["huge2.schedule"].append((t, s1, {"seq": seq}))
        l0 = s1 + rng.uniform(1e3, 5e3)
        c, step = l0 + rng.uniform(1e3, 5e3), {}
        for n in STEPS:
            step[n] = (c, c + rng.uniform(1e4, 2e5))
            c = step[n][1] + rng.uniform(100, 2e3)
        w0, w1 = step["huge2.launch.wait"]
        m0 = d = w0 + rng.uniform(0, 0.1) * (w1 - w0)
        dev = []
        for name, scope, key in entries:
            dur = rng.uniform(0.2, 0.6) * (w1 - w0) / len(entries)
            ops.append((name, d - SKEW, d + dur - SKEW, scope))
            keyed.append(((d - SKEW, d + dur - SKEW), key))
            dev.append((d, d + dur))
            d += dur * rng.uniform(1.0, 1.2)     # kernels never overlap
        modules.append((m0 - SKEW, max(e for _, e in dev) - SKEW))
        for n, (a, b) in step.items():
            spans[n].append((a, b, {"seq": seq}))
        spans[program_trace.LAUNCH].append((l0, c, {
            "seq": seq, "model": "m", "bucket": bucket, "live": len(waits),
            "wait_us_sum": float(waits.sum()),
            "wait_us_max": float(waits.max())}))
        truth.append({"start": l0, "bucket": bucket, "waits": waits,
                      "schedule": s1 - t, "step": step, "dev": dev})
        t = c + rng.uniform(1e3, 1e5)
    lo, hi = spans["huge2.schedule"][9][0], spans["huge2.schedule"][289][0]
    pt = program_trace.ProgramTrace(window=(lo, hi), spans=spans,
                                    ops={0: sorted(ops, key=lambda o: o[1])},
                                    modules={0: modules})
    return pt, [x for x in truth if lo <= x["start"] < hi], sites, keyed


def _run_on(tmp_path, monkeypatch, pt, truth, window=None):
    trace_dir = tmp_path / ".bench_out" / "trace" / "plugins"
    trace_dir.mkdir(parents=True, exist_ok=True)
    (trace_dir / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(program_trace, "_read_cached", lambda *a: pt)
    sites = harness.load_module(ROOT / "bench/models/gan.py",
                                "bench_model_gan").sites(MODEL)
    cell = SimpleNamespace(bench_dir=tmp_path / "bench", config={
        "model": MODEL}, adapter=harness.load_module(
            ROOT / "bench/models/gan.py", "bench_model_gan"))
    return SimpleNamespace(cell=cell, trace_window=window or pt.window,
                           launches=[(x["bucket"], len(x["waits"]))
                                     for x in truth],
                           peaks=peaks.PEAKS["TPU v5 lite"],
                           pallas_sites=lambda b: sites)


def _read(name, run):
    return harness.metric_reader(ROOT / "bench", name).read(run)


def _grid(intervals, lo, hi, step=20.0):
    g = np.zeros(int((hi - lo) // step) + 1, bool)
    for s, e in intervals:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            g[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
    return g


def test_host_readers_by_brute_count(window_trace, tmp_path, monkeypatch):
    pt, truth, _, _ = window_trace
    run = _run_on(tmp_path, monkeypatch, pt, truth)
    waits = np.concatenate([x["waits"] for x in truth])
    assert _read("queue_wait_ms.open", run) == pytest.approx(
        waits.mean() / 1e3)

    def mean_ms(names):
        return np.mean([sum(x["step"][n][1] - x["step"][n][0]
                            for n in names) for x in truth]) / 1e6
    for cell in ("bulk", "open"):
        assert _read(f"schedule_ms_per_launch.{cell}", run) == pytest.approx(
            np.mean([x["schedule"] for x in truth]) / 1e6)
        assert _read(f"h2d_ms_per_launch.{cell}", run) == pytest.approx(
            mean_ms(["huge2.launch.stack", "huge2.launch.h2d"]))
        assert _read(f"d2h_ms_per_launch.{cell}", run) == pytest.approx(
            mean_ms(["huge2.launch.d2h"]))


def test_heaviest_site_by_brute_count(window_trace, tmp_path, monkeypatch):
    pt, truth, sites, keyed = window_trace
    run = _run_on(tmp_path, monkeypatch, pt, truth)
    lo, hi = pt.window
    step = 20.0
    ns = {k: _grid([iv for iv, key in keyed if key == k],
                   lo, hi, step).sum() * step for k in sites + [None]}
    heavy = max(sites, key=ns.get)
    site = next(s for s in run.pallas_sites(1) if s.name == heavy)
    bound = sum(convcount.roofline_s(site, b, run.peaks)[0]
                for b, _ in run.launches)
    got = _read("heaviest_site_roofline", run)
    assert got == pytest.approx(100 * bound / (ns[heavy] / 1e9), rel=0.01)
    by_site = program_trace.kernel_ns_by_site(pt, sites, lo, hi)
    # the proj fusion is no kernel; a kernel naming no site or two is kept
    # apart, and no time is counted twice
    assert set(by_site) == set(sites) | {None}
    assert by_site == pytest.approx(ns, rel=0.01)


def test_another_runs_profile_reads_nothing(window_trace, tmp_path,
                                            monkeypatch):
    pt, truth, _, _ = window_trace
    lo, hi = pt.window
    run = _run_on(tmp_path, monkeypatch, pt, truth, window=(lo + 1, hi))
    assert program_trace.load(run) is None
    assert all(_read(name, run) is None for name in NEW)
    run.trace_window = None
    assert program_trace.load(run) is None


def test_unnamed_kernels_read_nothing(window_trace, tmp_path, monkeypatch):
    """A program whose kernels carry no ``pallas_call`` name and no site
    scope (as before both were given) reads no site roofline."""
    pt, truth, _, _ = window_trace
    bare = program_trace.ProgramTrace(
        window=pt.window, spans=pt.spans, modules=pt.modules,
        ops={0: [(n.replace("untangled_deconv", "batched"), s, e, "")
                 for n, s, e, _ in pt.ops[0]]})
    run = _run_on(tmp_path, monkeypatch, bare, truth)
    assert _read("heaviest_site_roofline", run) is None
    assert _read("d2h_ms_per_launch.bulk", run) is not None


def test_split_and_clock_offset(window_trace):
    pt, truth, _, _ = window_trace
    lo, hi = pt.window
    least, most = program_trace.clock_offset(pt)
    assert least == pytest.approx(max(
        d[0] - m[0] for d, m in zip(pt.spans["huge2.launch.dispatch"],
                                    pt.modules[0])))
    assert most == pytest.approx(min(
        w[1] - m[1] for w, m in zip(pt.spans["huge2.launch.wait"],
                                    pt.modules[0])))
    assert least <= SKEW <= most
    raw = program_trace.split(pt, lo, hi)
    fixed = program_trace.split(pt, lo, hi, SKEW)
    assert raw["launches"] == fixed["launches"] == len(truth)
    assert raw["busy_inside_launch"] < 1.0 == fixed["busy_inside_launch"]
    span = sum(x["step"][n][1] - x["step"][n][0] for x in truth
               for n in STEPS)
    launch = sum(e - s for s, e, _ in program_trace.launches(pt, lo, hi))
    assert fixed["steps_cover_launch"] == pytest.approx(span / launch)
    busy = sum(_grid(x["dev"], min(x["dev"])[0],
                     max(e for _, e in x["dev"])).sum() * 20.0 for x in truth)
    wait_idle = fixed["steps"]["huge2.launch.wait"]["idle_ms"]
    wait = fixed["steps"]["huge2.launch.wait"]["ms"]
    assert (wait - wait_idle) * len(truth) * 1e6 == pytest.approx(
        busy, rel=0.01)
    assert fixed["steps"]["huge2.launch.d2h"]["idle_ms"] == pytest.approx(
        fixed["steps"]["huge2.launch.d2h"]["ms"])


def test_reduction_round_trips(window_trace, tmp_path):
    pt, _, _, _ = window_trace
    p = tmp_path / "p.json.gz"
    pt.to_json(p)
    back = program_trace.ProgramTrace.from_json(p)
    assert back == pt


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of ``(number, int | str | bytes)`` fields."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_scopes_are_decoded_from_the_event_metadata(tmp_path):
    deconv = "%untangled_deconv.4 = f32[64,8,8,512] custom-call(...)"
    fusion = "%fusion.3 = f32[64,4,4,1024] fusion(...)"
    scope = "jit(batched)/dc0/untangled_deconv/pallas_call:"

    def stat_meta(sid, name):
        return (5, _msg((1, sid), (2, _msg((1, sid), (2, name)))))

    def event_meta(eid, name, *stats):
        return (4, _msg((1, eid), (2, _msg(
            (1, eid), (2, name), (4, name.split(" ")[0][1:]),
            *[(5, _msg(*s)) for s in stats]))))
    device = _msg(
        (1, 7), (2, "/device:TPU:0 (pid 3)"),
        stat_meta(1, "hlo_category"), stat_meta(2, "tf_op"),
        stat_meta(3, "jit(batched)/proj/dot_general:"),
        event_meta(1, deconv, [(1, 1), (5, "custom-call")],
                   [(1, 2), (5, scope)]),
        event_meta(2, fusion, [(1, 2), (7, 3)]),
        event_meta(3, "%copy.1 = f32[4]", [(1, 1), (5, "copy")]))
    host = _msg((1, 1), (2, "/host:CPU"), stat_meta(2, "tf_op"),
                event_meta(1, "huge2.launch", [(1, 2), (5, "not a scope")]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device), (4, "a-host")))
    assert program_trace.op_scopes(path) == {
        deconv: scope, fusion: "jit(batched)/proj/dot_general:"}
    assert program_trace.is_kernel(deconv)
    assert not program_trace.is_kernel(fusion)
    assert program_trace.is_kernel("untangled_conv_tiled.2")
    assert not program_trace.is_kernel("%batched.4 = f32[64,8,8,512]")


@pytest.mark.parametrize("workload,want", [
    ("dcgan-bulk", {"schedule_ms_per_launch.bulk", "h2d_ms_per_launch.bulk",
                    "d2h_ms_per_launch.bulk"}),
    ("dcgan-trickle", {"queue_wait_ms.open", "schedule_ms_per_launch.open",
                       "h2d_ms_per_launch.open", "d2h_ms_per_launch.open",
                       "pad_fraction.open"})])
def test_a_traced_run_reads_the_program_spans(tmp_path, workload, want):
    """A whole traced run on the CPU: the host-span readers find the
    profile the run left behind.  The CPU has no device plane and no peak,
    so the device readers read nothing."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(tiny_gan()))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        c["file"] = "bench/configs/tiny.json"
    cell = harness.find_cell(bench, workload, tmp_path,
                             bench_dir=tmp_path / "bench")
    out, _ = harness.run_cell(cell, 2**31 + 17, 0.5, True, root=tmp_path,
                              t_start=time.perf_counter(), chip=False,
                              log=lambda *a: None)
    assert out["correct"], out["checked"]
    assert set(out["metrics"]) == want
    assert all(m["value"] >= 0 for m in out["metrics"].values())
