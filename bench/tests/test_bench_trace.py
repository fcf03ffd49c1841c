"""The reduction from a trace to shares: interval arithmetic on small
made-up traces, and the metrics on a seeded trace shaped like a serving
window, checked against brute counts on a fine grid."""
from types import SimpleNamespace

import numpy as np
import pytest

from benchkit import ROOT

from bench import harness, trace



def _trace(ops, spans):
    return trace.Trace(ops={0: [(n, s, e, k) for n, s, e, k in ops]},
                       spans=spans)


def test_union_gaps_and_busy():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (10, 12)])
    assert merged == [(0, 3), (5, 7), (10, 12)]
    assert trace.covered(merged, 1, 11) == 2 + 2 + 1
    assert trace.gaps(merged, 1, 11) == [(3, 5), (7, 10)]
    tr = _trace([("a", 0, 2, True), ("b", 1, 3, False), ("c", 5, 7, True)],
                {"bench.window": [(0, 10)]})
    assert trace.busy(tr, 0, 10) == 5
    assert trace.kernel_ns(tr, 0, 10) == 4
    assert trace.kernel_ns(tr, 1, 6) == 1 + 1


def test_idle_is_labelled_by_host_span():
    tr = _trace([("k", 0, 10, True), ("k", 30, 40, True)],
                {"bench.window": [(0, 50)],
                 "bench.pump": [(8, 32)], "bench.launch": [(12, 30)],
                 "bench.submit": [(40, 44)]})
    got = dict(trace.idle_by_label(tr, 0, 50))
    assert got == pytest.approx({"launch": 18e-9, "pump": 2e-9,
                                 "submit": 4e-9, "loadgen": 6e-9})
    assert sum(got.values()) == pytest.approx(
        (50 - trace.busy(tr, 0, 50)) / 1e9)


def _run_on(tr, launches=(), completed=0):
    from bench import peaks
    lo, hi = trace.window(tr)
    return SimpleNamespace(trace=tr, trace_window=(lo, hi),
                           launches=list(launches), completed=completed,
                           peaks=peaks.PEAKS["TPU v5 lite"],
                           model_flops=1.28e9, pallas_sites=lambda b: [])


def _read(name, run):
    return harness.metric_reader(ROOT / "bench", name).read(run)


def test_host_ms_subtracts_device_time():
    tr = _trace([("k", 2e6, 5e6, True)],
                {"bench.window": [(0, 10e6)], "bench.pump": [(1e6, 6e6)]})
    assert _read("host_ms_per_launch.bulk", _run_on(tr)) == pytest.approx(2.0)
    assert _read("device_idle_share.bulk", _run_on(tr)) == pytest.approx(70.0)


@pytest.fixture(scope="module")
def busy_trace():
    """A seeded trace with the shape of a serving window: overlapping
    device ops (some Pallas kernels) inside launch spans, pumps around
    the launches, submits in between."""
    rng = np.random.default_rng(7)
    ops, launches, pumps, submits, t = [], [], [], [], 1_000.0
    for _ in range(300):
        p0 = t
        l0 = p0 + rng.uniform(5e3, 2e4)
        d = l0 + rng.uniform(1e4, 5e4)
        for _ in range(int(rng.integers(1, 6))):
            dur = rng.uniform(1e3, 2e5)
            ops.append((f"op{int(rng.integers(0, 9))}", d, d + dur,
                        bool(rng.integers(0, 2))))
            d += dur * rng.uniform(0.5, 1.2)       # some ops overlap
        l1 = d + rng.uniform(1e4, 8e4)
        p1 = l1 + rng.uniform(1e3, 1e4)
        launches.append((l0, l1))
        pumps.append((p0, p1))
        s0 = p1 + rng.uniform(0, 1e4)
        submits.append((s0, s0 + rng.uniform(1e3, 3e4)))
        t = submits[-1][1] + rng.uniform(0, 1e5)
    return trace.Trace(ops={0: sorted(ops, key=lambda e: e[1])},
                       spans={"bench.window": [(0.0, t)],
                              "bench.launch": launches, "bench.pump": pumps,
                              "bench.submit": submits})


def _grid(intervals, lo, hi, step=50.0):
    g = np.zeros(int((hi - lo) // step) + 1, bool)
    for s, e in intervals:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            g[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
    return g


def test_busy_and_idle_agree_with_a_brute_count(busy_trace):
    lo, hi = trace.window(busy_trace)
    ops = busy_trace.ops[0]
    brute = _grid([(s, e) for _, s, e, _ in ops], lo, hi).sum() * 50.0
    busy = trace.busy(busy_trace, lo, hi)
    assert busy == pytest.approx(brute, rel=0.01)
    idle = _read("device_idle_share.open", _run_on(busy_trace))
    assert idle == pytest.approx(100 * (1 - busy / (hi - lo)))
    kern = sum(e - s for _, s, e, k in ops if k)
    assert trace.kernel_ns(busy_trace, lo, hi) == pytest.approx(kern)
    idle_s = sum(t for _, t in trace.idle_by_label(busy_trace, lo, hi))
    assert idle_s == pytest.approx((hi - lo - busy) / 1e9, rel=1e-9)


def test_host_time_per_launch_by_brute_count(busy_trace):
    ops = busy_trace.ops[0]
    dev = _grid([(s, e) for _, s, e, _ in ops], 0.0, trace.window(
        busy_trace)[1], step=10.0)
    want = []
    for s, e in busy_trace.spans["bench.pump"]:
        a, b = int(s // 10), int(np.ceil(e / 10))
        want.append((e - s) - dev[a:b].sum() * 10.0)
    ms = _read("host_ms_per_launch.open", _run_on(busy_trace))
    assert ms == pytest.approx(np.mean(want) / 1e6, rel=0.01)
    assert _read("host_ms_per_launch.bulk", _run_on(busy_trace)) == ms


def test_top_ops_are_ordered(busy_trace):
    lo, hi = trace.window(busy_trace)
    top = trace.top_ops(busy_trace, lo, hi)
    assert len(top) <= 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)


def test_reduced_trace_round_trips(busy_trace, tmp_path):
    p = tmp_path / "t.json.gz"
    busy_trace.to_json(p)
    back = trace.Trace.from_json(p)
    assert back.ops == busy_trace.ops
    assert back.spans == {k: [tuple(s) for s in v]
                          for k, v in busy_trace.spans.items()}


@pytest.mark.parametrize("name,stats,want", [
    ("fusion.3", "hlo_category=convolution", False),
    ("custom-call.7", "", True),
    ("kernel", 'long_name=custom_call_target="tpu_custom_call"', True),
    ("copy.1", "hlo_category=custom-call", True)])
def test_pallas_events_are_recognised(name, stats, want):
    assert trace.is_pallas(name, stats) is want
