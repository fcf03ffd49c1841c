"""The control plane's launch pipeline, on the CPU: an image launch is
started and finished in two halves, and at most one started launch stays
in flight across a pump.  Answers are bit-identical to the synchronous
``DynamicImageBatcher.execute``; every request is answered once; a lone
launch is answered by its own pump; faults replay only the dead launch;
``degrade`` answers what is in flight."""
import pathlib

import jax
import numpy as np
import pytest

from repro.models import gan
from repro.models.gan import DeconvLayer, GANConfig
from repro.runtime.fault import FailureInjector, NodeFailure
from repro.serving.control_plane import ControlPlane, ServeRequest
from repro.serving.image_batcher import DynamicImageBatcher

TINY = GANConfig("tiny", (
    DeconvLayer(4, 32, 16, 5, 2),
    DeconvLayer(8, 16, 3, 5, 2),
), z_dim=16)


def echo_plane(buckets=(1, 4, 16), max_wait_ms=2.0, **kw):
    cp = ControlPlane(**kw)
    be = cp.register_image_model("echo", lambda x: x * 2.0,
                                 np.zeros((4,), np.float32), buckets=buckets,
                                 max_wait_ms=max_wait_ms)
    return cp, be


class Clients:
    """A closed loop: ``outstanding`` requests kept in flight, each answer
    followed at once by a new request, as the benchmark's bulk cell does."""

    def __init__(self, cp, model, dim, outstanding, seed=0):
        self.cp, self.model, self.dim = cp, model, dim
        self.rng = np.random.default_rng(seed)
        self.reqs: dict[int, ServeRequest] = {}
        self.answered: list[ServeRequest] = []
        self.submit(outstanding)

    @property
    def payloads(self) -> dict[int, np.ndarray]:
        return {rid: r.payload for rid, r in self.reqs.items()}

    def submit(self, n):
        for _ in range(n):
            rid = len(self.reqs)
            z = self.rng.standard_normal(self.dim).astype(np.float32)
            self.reqs[rid] = ServeRequest(rid=rid, model=self.model, payload=z)
            self.cp.submit(self.reqs[rid])

    def pump(self, **kw):
        done = self.cp.pump(**kw)
        self.answered += done
        self.submit(len(done))
        return done

    def drain(self):
        while self.cp.pending():
            self.answered += self.cp.pump(drain=True)


def launch_args(log_dir) -> list[dict]:
    """The args of every ``huge2.launch`` host event of the profile."""
    from jax.profiler import ProfileData
    path = next(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            out += [(e.start_ns, dict(e.stats)) for line in plane.lines
                    for e in line.events if e.name == "huge2.launch"]
    return [a for _, a in sorted(out, key=lambda x: x[0])]


def test_closed_loop_answers_match_execute():
    params, _ = gan.generator_init(jax.random.PRNGKey(0), TINY)

    def serve(z):
        return gan.generator_apply(params, z, TINY)

    cp = ControlPlane()
    be = cp.register_image_model("g", serve, np.zeros((TINY.z_dim,),
                                                      np.float32),
                                 buckets=(1, 4))
    clients = Clients(cp, "g", TINY.z_dim, outstanding=8)
    for _ in range(6):
        clients.pump()
    clients.drain()
    rids = [r.rid for r in clients.answered]
    assert sorted(rids) == list(range(len(clients.payloads)))  # each once
    assert sorted(r.rid for r in cp.done) == sorted(rids)
    ref = DynamicImageBatcher(serve, buckets=(1, 4))
    for seq, (bucket, live) in enumerate(be.batcher.launches, start=1):
        reqs = sorted((r for r in cp.done if r.launch_seq == seq),
                      key=lambda r: r.t_arrival)
        assert bucket == 4 and len(reqs) == live == 4
        want = ref.execute([clients.payloads[r.rid] for r in reqs], bucket)
        for r, w in zip(reqs, want):
            assert np.array_equal(r.out, w), r.rid


def test_at_most_one_launch_in_flight_after_each_pump():
    cp, _ = echo_plane()
    clients = Clients(cp, "echo", 4, outstanding=32)
    for _ in range(10):
        clients.pump()
        queued = [r for r in clients.reqs.values() if r.t_launch is None]
        in_flight = [r for r in clients.reqs.values()
                     if r.t_launch is not None and r.t_done is None]
        assert len({r.launch_seq for r in in_flight}) == 1
        assert len(in_flight) == 16                 # one B16 launch
        assert cp.pending() == len(queued) + len(in_flight) == 32
    clients.drain()
    assert cp.pending() == 0


def test_run_and_drain_answer_everything():
    cp, _ = echo_plane(buckets=(1, 4, 16, 64))
    zs = [np.full(4, i, np.float32) for i in range(70)]
    cp.run([ServeRequest(rid=i, model="echo", payload=z)
            for i, z in enumerate(zs)])
    assert cp.pending() == 0 and len(cp.done) == 70
    for r in cp.done:
        np.testing.assert_array_equal(r.out, zs[r.rid] * 2.0)
    cp, _ = echo_plane()
    clients = Clients(cp, "echo", 4, outstanding=40)
    clients.pump()
    clients.drain()
    rids = [r.rid for r in clients.answered]
    assert sorted(rids) == list(range(len(clients.payloads)))
    assert cp.stats()["queued"] == 0


def test_lone_launch_is_answered_by_its_own_pump():
    cp, _ = echo_plane(max_wait_ms=0.0)
    for rid in range(3):
        z = np.full(4, rid, np.float32)
        cp.submit(ServeRequest(rid=rid, model="echo", payload=z))
        (r,) = cp.pump()
        assert r.rid == rid and cp.pending() == 0
        np.testing.assert_array_equal(r.out, z * 2.0)
    assert cp.stats()["launches_overlapped"] == 0


@pytest.mark.parametrize("loop", ["closed", "lone"])
def test_overlapped_counts_launches_started_behind_another(loop, tmp_path):
    cp, be = echo_plane(max_wait_ms=0.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        if loop == "closed":
            clients = Clients(cp, "echo", 4, outstanding=32)
            for _ in range(8):
                clients.pump()
            clients.drain()
        else:
            for rid in range(5):
                cp.submit(ServeRequest(rid=rid, model="echo",
                                       payload=np.ones(4, np.float32)))
                cp.pump()
    finally:
        jax.profiler.stop_trace()
    n = len(be.batcher.launches)
    want = n - 1 if loop == "closed" else 0
    assert n == (10 if loop == "closed" else 5)
    assert cp.stats()["launches_overlapped"] == want
    flags = [a["overlapped"] for a in launch_args(tmp_path)]
    assert len(flags) == n and sum(flags) == want
    assert flags[0] == 0


def test_fault_on_the_second_of_two_launches_replays_only_it():
    zs = [np.full(4, i, np.float32) for i in range(8)]

    def reqs():
        return [ServeRequest(rid=i, model="echo", payload=z)
                for i, z in enumerate(zs)]
    ref, _ = echo_plane(buckets=(4,))
    ref.run(reqs())
    cp, _ = echo_plane(buckets=(4,), injector=FailureInjector((2,)))
    for r in reqs():
        cp.submit(r)
    first = cp.pump()                 # starts 1 and 2; 2 dies at start
    assert sorted(r.rid for r in first) == [0, 1, 2, 3]
    cp.run()
    (event,) = cp.fault_events
    assert event["launch"] == 2 and event["live"] == 4
    assert {r.rid: r.replays for r in cp.done} == {
        i: int(i >= 4) for i in range(8)}
    assert cp.stats()["served"] == 8 and cp.pending() == 0
    got, want = cp.results(), ref.results()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid])


def test_failure_at_finish_replays_only_that_launch():
    cp, be = echo_plane(buckets=(4,))
    finish, calls = be.finish, []

    def dying_finish(launch):
        calls.append(launch.seq)
        if len(calls) == 1:
            raise NodeFailure("device lost before the answer came back")
        return finish(launch)
    be.finish = dying_finish
    zs = [np.full(4, i, np.float32) for i in range(8)]
    cp.run([ServeRequest(rid=i, model="echo", payload=z)
            for i, z in enumerate(zs)])
    (event,) = cp.fault_events
    assert event["launch"] == 1 and event["live"] == 4
    assert {r.rid: r.replays for r in cp.done} == {
        i: int(i < 4) for i in range(8)}
    for r in cp.done:
        np.testing.assert_array_equal(r.out, zs[r.rid] * 2.0)
    assert cp.pending() == 0 and len(cp.done) == 8


def test_degrade_answers_the_launch_in_flight():
    cp, _ = echo_plane(buckets=(16,))
    clients = Clients(cp, "echo", 4, outstanding=32)
    cp.pump()                         # answers launch 1, leaves 2 in flight
    assert not cp.queues["echo"]["interactive"] and cp.pending() == 16
    cp.degrade(1)
    assert cp.pending() == 0 and len(cp.done) == 32
    for r in cp.done:
        assert r.status == "served"
        np.testing.assert_array_equal(r.out, clients.payloads[r.rid] * 2.0)
    back = cp.pump()                  # the answers degrade took come back
    assert sorted(r.rid for r in back) == list(range(16, 32))
    assert cp.pump() == []
