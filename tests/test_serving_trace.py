"""The serving path's own trace, on the CPU: one ``huge2.launch`` span per
bucket launch around its start (stack, H2D, dispatch), its finish (wait,
D2H) found after it by ``seq``, also while launches overlap, a
``huge2.schedule`` span before it, the launch stamps on every request,
answers unchanged by a running profiler, and the conv sites' names in the
lowered generator."""
import json
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.models import gan
from repro.serving.control_plane import ControlPlane, ServeRequest

ROOT = pathlib.Path(__file__).resolve().parents[1]
STEPS = ("huge2.launch.stack", "huge2.launch.h2d", "huge2.launch.dispatch",
         "huge2.launch.wait", "huge2.launch.d2h")
START = STEPS[:3]                  # inside the launch span; the rest follow
WAVES = (1, 3, 20, 2)              # requests per drained wave


def plane():
    cp = ControlPlane()
    be = cp.register_image_model("m", lambda x: jax.numpy.tanh(x) * 2.0,
                                 np.zeros((8,), np.float32),
                                 buckets=(1, 4, 16))
    return cp, be


def serve(cp):
    rng = np.random.default_rng(3)
    rid = 0
    for n in WAVES:
        reqs = [ServeRequest(rid=rid + i, model="m",
                             payload=rng.standard_normal(8).astype(np.float32))
                for i in range(n)]
        rid += n
        cp.run(reqs)
    return {r.rid: r.out for r in cp.done}


def host_spans(log_dir) -> dict:
    """``huge2.*`` host events of the profile: name -> [(start, end, args)]."""
    from jax.profiler import ProfileData
    path = next(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    out = {}
    for plane_ in ProfileData.from_file(str(path)).planes:
        if not plane_.name.startswith("/host:"):
            continue
        for line in plane_.lines:
            for e in line.events:
                if e.name.startswith("huge2."):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
    return {k: sorted(v, key=lambda s: s[0]) for k, v in out.items()}


def closed_loop(cp, pumps=12):
    """Keep two of the largest bucket outstanding: each answer is followed
    at once by a new request, so every pump starts a launch while the last
    one is in flight."""
    rng = np.random.default_rng(5)
    rid = 0

    def submit(n):
        nonlocal rid
        for _ in range(n):
            cp.submit(ServeRequest(
                rid=rid, model="m",
                payload=rng.standard_normal(8).astype(np.float32)))
            rid += 1
    submit(32)
    for _ in range(pumps):
        submit(len(cp.pump()))
    cp.run()


def traced_run(log_dir, drive):
    cp, be = plane()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        answers = drive(cp)
    finally:
        jax.profiler.stop_trace()
    return cp, be, answers, host_spans(log_dir)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return traced_run(tmp_path_factory.mktemp("trace"), serve)


@pytest.fixture(scope="module")
def traced_overlap(tmp_path_factory):
    return traced_run(tmp_path_factory.mktemp("trace_overlap"), closed_loop)


def test_one_launch_span_per_launch(traced):
    cp, be, _, spans = traced
    launches = spans["huge2.launch"]
    assert len(launches) == len(be.batcher.launches) == cp.launch_seq
    assert [(a["bucket"], a["live"]) for _, _, a in launches] == \
        be.batcher.launches
    assert [a["seq"] for _, _, a in launches] == list(
        range(1, cp.launch_seq + 1))
    assert {a["model"] for _, _, a in launches} == {"m"}
    assert {b for b, _ in be.batcher.launches} == {1, 4, 16}


def assert_steps_follow_their_launch(spans):
    """Stack, H2D and dispatch lie inside their ``huge2.launch`` in order;
    wait and D2H carry the same ``seq`` and follow its dispatch in order."""
    for s, e, a in spans["huge2.launch"]:
        t = s
        for name in STEPS:
            (step,) = [sp for sp in spans[name] if sp[2]["seq"] == a["seq"]]
            end = e if name in START else float("inf")
            assert t <= step[0] <= step[1] <= end, (name, a["seq"])
            t = step[1]
        (sched,) = [sp for sp in spans["huge2.schedule"]
                    if sp[2]["seq"] == a["seq"]]
        assert sched[1] <= s


def test_steps_nest_inside_their_launch(traced):
    assert_steps_follow_their_launch(traced[3])


def test_steps_found_by_seq_when_launches_overlap(traced_overlap):
    cp, be, _, spans = traced_overlap
    launches = spans["huge2.launch"]
    assert len(launches) == len(be.batcher.launches) == cp.launch_seq
    assert {b for b, _ in be.batcher.launches} == {16}
    wait = {a["seq"]: s for s, _, a in spans["huge2.launch.wait"]}
    # launch k+1 starts before launch k's wait does: they really overlap
    overlapping = [a["seq"] for (s, _, a) in launches if s < wait.get(
        a["seq"] - 1, -1)]
    assert overlapping == list(range(2, cp.launch_seq + 1))
    assert [a["overlapped"] for _, _, a in launches] == \
        [0] + [1] * (cp.launch_seq - 1)
    assert_steps_follow_their_launch(spans)


def test_waits_match_the_request_stamps(traced):
    cp, _, _, spans = traced
    assert len(cp.done) == sum(WAVES)
    for _, _, a in spans["huge2.launch"]:
        reqs = [r for r in cp.done if r.launch_seq == a["seq"]]
        assert len(reqs) == a["live"]
        waits = [(r.t_launch - r.t_arrival) * 1e6 for r in reqs]
        assert a["wait_us_sum"] == pytest.approx(sum(waits), rel=1e-6)
        assert a["wait_us_max"] == pytest.approx(max(waits), rel=1e-6)
        assert all(r.t_arrival <= r.t_launch <= r.t_done for r in reqs)


def test_answers_are_the_same_without_a_profiler(traced):
    _, _, traced_answers, _ = traced
    cp, _ = plane()
    answers = serve(cp)
    assert answers.keys() == traced_answers.keys()
    for rid, out in answers.items():
        assert np.array_equal(out, traced_answers[rid]), rid


def test_straggler_monitor_takes_the_launch_stamps(monkeypatch):
    ticks = iter(range(1000))
    cp = ControlPlane(clock=lambda: float(next(ticks)))
    cp.register_image_model("m", lambda x: x + 1.0,
                            np.zeros((2,), np.float32), buckets=(4,))
    seen = []
    monkeypatch.setattr(cp, "_observe",
                        lambda model, bucket, dt, seq=None: seen.append(dt))
    cp.run([ServeRequest(rid=i, model="m", payload=np.ones(2, np.float32))
            for i in range(3)])
    (dt,) = seen
    assert {r.t_done - r.t_launch for r in cp.done} == {dt}
    assert dt > 0


def test_conv_sites_are_named_in_the_lowered_generator():
    from bench.models import gan as bench_gan
    conf = json.loads((ROOT / "bench/configs/dcgan-lsun64.json").read_text())
    cfg = gan.DCGAN
    params = jax.eval_shape(lambda k: gan.generator_init(k, cfg)[0],
                            jax.random.PRNGKey(0))
    z = jax.ShapeDtypeStruct((4, cfg.z_dim), np.float32)
    text = jax.jit(lambda p, x: gan.generator_apply(p, x, cfg)).lower(
        params, z).as_text(debug_info=True)
    scopes = sorted(set(re.findall(r"/(dc\d+)/", text)))
    assert scopes == [f"dc{i}" for i in range(len(cfg.layers))]
    assert scopes == [s.name for s in bench_gan.sites(conf["model"])]
    assert "/proj/" in text
