"""SLO-aware serving control plane: one admission + scheduling layer in
front of every model backend (the bucket-coalescing image batchers and the
LM slot scheduler), with fault-injected replay wired into the launch path.

The two schedulers that grew out of PRs 1-4 — ``serving/batcher.py``
(continuous LM batching) and ``serving/image_batcher.py`` (bucket
coalescing) — were peer entry points with separate queues, no deadlines,
and no survival story when a device disappears mid-batch.  Here they become
*backends* of one control plane:

admission → schedule → launch → replay

- **Admission** (``submit``): a request carries an SLO (``slo_ms``) and a
  priority class (``interactive`` > ``batch``).  When the backend has
  measured launch costs, the control plane estimates wait + service for
  the backlog ahead of the request; if the estimate already blows the
  deadline the request is **rejected at admission** (cheapest possible
  failure: no queue space, no compute, an immediate answer to the client).
  Without measured costs admission is permissive — estimates, never
  guesses.
- **Schedule** (``pump``): per-model, per-class FIFO queues.  Interactive
  requests launch first, but starvation is bounded: a batch request older
  than ``starvation_ms`` is scheduled ahead of fresher interactive work.
  Across models the head-of-line request with the earliest deadline wins
  (EDF).  A launch takes from the chosen class, then *backfills* the
  remaining bucket slots with the other class's requests — padding with
  real work instead of zeros.  Requests whose deadline has already passed
  are **shed before launch** (never compute something the client stopped
  waiting for) and counted separately from served ones.
- **Launch**: image models go through the backend's bucket executables
  (``DynamicImageBatcher.start`` / ``finish`` — plans pre-built at model
  load, bucket costs shared via the ``RouteCache``); LM models advance one
  ``ContinuousBatcher`` decode step per pump.  An image launch is started
  (stack, H2D, dispatch, the output's copy to the host requested) and
  finished (wait, the live rows on the host) in two halves, and at most
  one started launch stays in flight across a pump: a pump starts the due
  launch, and a second one if another is due at once (a full queue's cold
  start), then finishes launches oldest first until one is left — or
  none, if it finished no other.  So a lone launch is answered by its own
  pump, and under a backlog the device runs launch k+1 while launch k's
  answer copies to the host.  The rule reads only the queues, never the
  model.  Every launch's wall-time, start to answers in hand, feeds a
  per-(model, bucket) ``StragglerMonitor``; flagged buckets surface as the
  slow-bucket alert in ``stats()``.  Each start stamps its requests with
  ``launch_seq`` and ``t_launch``, so queue wait (``t_launch -
  t_arrival``) and service (``t_done - t_launch``) are read per request.
- **Replay** (the fault ladder): a ``FailureInjector`` (or a real
  ``NodeFailure``) firing at a launch's start or finish kills that
  launch's results alone; a launch already in flight is finished as
  usual.  The control plane re-queues the dead launch's live requests at
  the *front* of their class queues in arrival order and replays them on
  a later pump — zero requests dropped, zero answered twice, and (the
  relaunches hit the same bucket executables on the same payloads)
  responses bit-equal to a fault-free run — asserted in
  ``tests/test_control_plane.py``.  When the failure means a lost replica,
  ``degrade`` finishes every launch in flight, then shrinks the mesh via
  ``runtime.elastic.shrink_mesh`` and re-jits every image backend under
  the surviving data-parallel extent; with ``spatial_tiles=`` it instead
  re-tiles the survivors as a spatial mesh and re-plans plane-parallel
  ``dev_tiles`` (``core.spatial``).

Multi-model hosting: ``register_image_model`` / ``register_lm_model`` put
a GAN, a segnet, and a VAE (or anything with a ``serve_fn``) behind one
process; each backend pre-builds its plans at registration (model load)
and the batchers share one ``RouteCache`` for measured bucket costs.

``stats()`` reports per-class p50/p95/p99, **goodput under SLO** (served
within deadline / submitted — rejected, shed, and served-but-late all
count against it), fault/replay records, the straggler alert, and
``launches_overlapped`` (image launches started while another was in
flight: how often the pipeline engages); the
open-loop tail-latency harness in ``benchmarks/serve_bench.py`` turns the
same report into ``BENCH_slo.json``.

Tracing: schedule and launch are ``jax.profiler.TraceAnnotation`` spans
(``huge2.schedule``, ``huge2.launch`` around a launch's start and, inside
the batcher, its ``huge2.launch.*`` steps; ``wait`` and ``d2h`` fall in
the launch's finish, which may come a pump later), recorded only while a
profiler traces the process and on the same clock as the device's
operations (docs/ARCHITECTURE.md, "Tracing").
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.plan import BATCH_BUCKETS
from repro.runtime.fault import NodeFailure, StragglerMonitor
from repro.serving.batcher import ContinuousBatcher, Request
from repro.serving.image_batcher import DynamicImageBatcher, InFlight
from repro.serving.metrics import latency_stats

PRIORITIES = ("interactive", "batch")


@dataclasses.dataclass
class ServeRequest:
    """One request under the control plane.

    Status lifecycle: ``queued`` -> ``served`` | ``rejected`` | ``shed``
    (a fault replay moves a request back to ``queued`` transiently and
    bumps ``replays``).  ``slo_ms=None`` means no deadline: never rejected
    or shed, excluded from the goodput denominator's miss accounting.
    """

    rid: int
    model: str
    payload: np.ndarray                     # image/latent, or (P,) int32 LM prompt
    priority: str = "interactive"
    slo_ms: Optional[float] = None
    max_new: int = 16                       # LM backends only
    # None = stamped by the control plane's injected clock at submit
    # (deadline tests / open-loop drivers stamp explicitly, same domain)
    t_arrival: Optional[float] = None
    # stamped at a launch's start (image backends): the control plane's
    # launch_seq of the launch that answered, and its start on the same
    # clock
    launch_seq: Optional[int] = None
    t_launch: Optional[float] = None
    t_done: Optional[float] = None
    out: Optional[np.ndarray] = None
    status: str = "queued"
    replays: int = 0
    reason: str = ""                        # why rejected / shed

    def __post_init__(self):
        if self.priority not in PRIORITIES:
            raise ValueError(f"priority must be one of {PRIORITIES}, "
                             f"got {self.priority!r}")

    @property
    def deadline(self) -> Optional[float]:
        if self.slo_ms is None or self.t_arrival is None:
            return None
        return self.t_arrival + self.slo_ms / 1e3

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_done is None or self.t_arrival is None:
            return None
        return self.t_done - self.t_arrival

    @property
    def in_slo(self) -> Optional[bool]:
        """Served within deadline; ``None`` when no SLO was attached."""
        if self.slo_ms is None:
            return None
        return self.t_done is not None and self.t_done <= self.deadline


class ImageBackend:
    """Image/latent launch engine: wraps a ``DynamicImageBatcher`` for its
    per-bucket executables, measured bucket costs, and cover planning; the
    control plane owns admission and ordering (the batcher's internal
    queue stays empty in this mode)."""

    kind = "image"
    # the control plane's launch_seq of the launch being started, set before
    # each ``start`` (and ``launch``, whose signature callers wrap) to label
    # its spans
    launch_seq: Optional[int] = None

    def __init__(self, name: str, serve_fn: Callable, proto: np.ndarray, *,
                 buckets: Sequence[int] = BATCH_BUCKETS,
                 max_wait_ms: float = 2.0, dist=None,
                 cache=None, cache_key: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.name = name
        self.proto = np.asarray(proto)
        self.batcher = DynamicImageBatcher(
            serve_fn, buckets=buckets, max_wait_ms=max_wait_ms, dist=dist,
            cache=cache, cache_key=cache_key or name, clock=clock)

    @property
    def max_wait_s(self) -> float:
        return self.batcher.max_wait_s

    @property
    def largest_bucket(self) -> int:
        return self.batcher.buckets[-1]

    def warmup(self, **kw):
        return self.batcher.warmup(self.proto, **kw)

    def next_launch_size(self, n: int) -> int:
        return self.batcher._first_launch_size(n)

    def estimate_s(self, ahead: list, req: ServeRequest) -> Optional[float]:
        """Admission estimate: measured cost of covering the ``ahead``
        backlog plus this request (``None`` until costs are measured)."""
        if not self.batcher.bucket_cost_s:
            return None
        n = len(ahead) + 1
        self.batcher._plan_cover(n)
        return self.batcher._sched_memo[n][0]

    def launch(self, payloads: Sequence[np.ndarray],
               bucket: int) -> np.ndarray:
        """One launch, start to finish (the control plane itself drives
        ``start`` and ``finish`` to keep a launch in flight)."""
        return self.batcher.execute(payloads, bucket, seq=self.launch_seq)

    def start(self, payloads: Sequence[np.ndarray], bucket: int) -> InFlight:
        return self.batcher.start(payloads, bucket, seq=self.launch_seq)

    def finish(self, launch: InFlight) -> np.ndarray:
        return self.batcher.finish(launch)

    def rebind(self, dist, serve_fn: Optional[Callable] = None):
        self.batcher.rebind_dist(dist, serve_fn)


class LMBackend:
    """LM slot-scheduler backend: the control plane feeds admitted prompts
    into a ``ContinuousBatcher`` as slots free up (priority order held at
    the control-plane queue, not inside the batcher) and advances it one
    decode step per pump.  On device loss every in-flight slot is evicted
    — caches reset, partial output discarded — and the requests go back
    to the control plane for replay (greedy decode is deterministic, so a
    replayed request's tokens are bit-equal to a fault-free run)."""

    kind = "lm"
    max_wait_s = 0.0                        # LM decodes continuously

    def __init__(self, name: str, cfg, params, *, slots: int = 4,
                 max_len: int = 128, memory=None):
        self.name = name
        self.cb = ContinuousBatcher(cfg, params, slots=slots,
                                    max_len=max_len, memory=memory)
        self._wrapped: dict[int, ServeRequest] = {}
        self._consumed = 0                  # cb.done prefix already reported
        self.steps = 0
        self.step_cost_s: Optional[float] = None

    def free_slots(self) -> int:
        return sum(1 for s in self.cb.slots if s.req is None)

    def active(self) -> bool:
        return bool(self.cb.queue) or any(s.req for s in self.cb.slots)

    def feed(self, sreq: ServeRequest):
        self._wrapped[sreq.rid] = sreq
        self.cb.submit(Request(rid=sreq.rid,
                               prompt=np.asarray(sreq.payload, np.int32),
                               max_new=sreq.max_new))

    def estimate_s(self, ahead: list, req: ServeRequest) -> Optional[float]:
        """Admission estimate: backlog tokens spread over the slots, plus
        this request's own prefill + decode, at the EWMA step cost."""
        if self.step_cost_s is None:
            return None
        backlog = sum(len(r.payload) + r.max_new for r in ahead)
        own = len(req.payload) + req.max_new
        return (backlog / max(1, self.cb.n) + own) * self.step_cost_s

    def step(self) -> list[ServeRequest]:
        """One decode step; returns the requests that finished on it."""
        t0 = time.perf_counter()
        self.cb.step()
        dt = time.perf_counter() - t0
        self.steps += 1
        self.step_cost_s = (dt if self.step_cost_s is None
                            else 0.8 * self.step_cost_s + 0.2 * dt)
        finished = []
        for r in self.cb.done[self._consumed:]:
            sreq = self._wrapped.pop(r.rid)
            sreq.out = np.asarray(r.out, np.int32)
            sreq.t_done = r.t_done
            finished.append(sreq)
        self._consumed = len(self.cb.done)
        return finished

    def evict_live(self) -> list[ServeRequest]:
        """Device loss mid-step: evict every in-flight slot and queued
        request, reset the slot caches, and hand the ``ServeRequest``s
        back for control-plane re-queue + replay."""
        live = []
        for si, s in enumerate(self.cb.slots):
            if s.req is not None:
                live.append(self._wrapped.pop(s.req.rid))
                s.req, s.pos, s.prompt_left = None, 0, 0
                self.cb.slot_caches[si] = jax.tree.map(jnp.copy,
                                                       self.cb.cache1)
        while self.cb.queue:
            live.append(self._wrapped.pop(self.cb.queue.popleft().rid))
        return live


@dataclasses.dataclass
class _Started:
    """An image launch in flight: who runs it, whom it answers, the
    batcher's handle and the start stamp on the control plane's clock."""
    be: ImageBackend
    reqs: list[ServeRequest]
    launch: InFlight
    t_launch: float


class ControlPlane:
    """Admission + scheduling + fault replay over registered backends.

    ``injector`` is a ``runtime.fault.FailureInjector`` keyed by *launch
    sequence number* (every image bucket launch and every LM decode step
    increments it) — ``FailureInjector((3,))`` kills the third launch
    mid-batch, exercising the re-queue/replay path on purpose.
    """

    def __init__(self, *, starvation_ms: float = 50.0, injector=None,
                 admission: bool = True, straggler_k: float = 3.0,
                 straggler_warmup: int = 3,
                 on_fault: Optional[Callable] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.backends: dict[str, object] = {}
        self.queues: dict[str, dict[str, deque]] = {}
        self.starvation_s = starvation_ms / 1e3
        # ONE monotonic clock for every scheduling timestamp: arrivals,
        # admission ('now + est > deadline'), shedding ('now > deadline'),
        # max-wait expiry, launch start and end — and it is handed down to
        # every image backend's batcher, so admission and the batcher's
        # coalescing deadline can never disagree about 'now'.  An image
        # launch's duration for its StragglerMonitor is taken from the same
        # two stamps as t_launch and t_done.
        self.clock = clock
        self.injector = injector
        self.admission = admission
        self.on_fault = on_fault
        self.done: list[ServeRequest] = []
        self.rejected: list[ServeRequest] = []
        self.shed: list[ServeRequest] = []
        self.submitted = 0
        self._submitted_by_class = {c: 0 for c in PRIORITIES}
        self.launch_seq = 0
        self.fault_events: list[dict] = []
        self.degraded: Optional[dict] = None
        self._served_rids: set = set()      # zero-duplicate guard
        self.monitors: dict[tuple, StragglerMonitor] = {}
        self._straggler_kw = dict(k=straggler_k, warmup=straggler_warmup)
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._in_flight: deque[_Started] = deque()   # oldest first
        # answered outside the pump that returns them (``degrade``)
        self._answered: list[ServeRequest] = []
        self.launches_overlapped = 0

    # -- model registration (model load: plans pre-built here) ---------------
    def register_image_model(self, name: str, serve_fn: Callable,
                             proto: np.ndarray, *, warmup: bool = False,
                             **kw) -> ImageBackend:
        kw.setdefault("clock", self.clock)   # one clock, both layers
        be = ImageBackend(name, serve_fn, proto, **kw)
        self._register(name, be)
        if warmup:
            be.warmup()
        return be

    def register_lm_model(self, name: str, cfg, params,
                          **kw) -> LMBackend:
        be = LMBackend(name, cfg, params, **kw)
        self._register(name, be)
        return be

    def _register(self, name, be):
        if name in self.backends:
            raise ValueError(f"model {name!r} already registered")
        self.backends[name] = be
        self.queues[name] = {c: deque() for c in PRIORITIES}

    def warmup(self):
        """Compile every image backend's bucket executables up front."""
        for be in self.backends.values():
            if isinstance(be, ImageBackend):
                be.warmup()

    # -- admission ------------------------------------------------------------
    def submit(self, req: ServeRequest) -> bool:
        """Admit or reject (``False``) a request.  Rejection happens only
        when the measured-cost estimate for the backlog ahead of the
        request already exceeds its deadline."""
        if req.model not in self.backends:
            raise ValueError(f"unknown model {req.model!r} "
                             f"(registered: {sorted(self.backends)})")
        self.submitted += 1
        self._submitted_by_class[req.priority] += 1
        if req.t_arrival is None:
            req.t_arrival = self.clock()
        if self._t_first is None:
            self._t_first = self.clock()
        ddl = req.deadline
        if ddl is not None and self.admission:
            ahead = self._ahead_of(req)
            est = self.backends[req.model].estimate_s(ahead, req)
            if est is not None and self.clock() + est > ddl:
                req.status = "rejected"
                req.reason = (f"admission: backlog estimate {est * 1e3:.2f} "
                              f"ms blows slo {req.slo_ms:.2f} ms")
                self.rejected.append(req)
                return False
        self.queues[req.model][req.priority].append(req)
        return True

    def _ahead_of(self, req: ServeRequest) -> list:
        """Queued requests that will be scheduled before ``req``:
        same-class backlog, plus the interactive queue for a batch
        request (interactive preempts batch up to the starvation bound)."""
        q = self.queues[req.model]
        ahead = list(q[req.priority])
        if req.priority == "batch":
            ahead += list(q["interactive"])
        return ahead

    # -- scheduling -----------------------------------------------------------
    def _pick_class(self, q: dict, now: float) -> str:
        """Interactive first; a batch head past the starvation bound (or an
        empty interactive queue) flips the choice."""
        inter, batch = q["interactive"], q["batch"]
        if batch and (not inter
                      or now - batch[0].t_arrival >= self.starvation_s):
            return "batch"
        return "interactive" if inter else "batch"

    def _launch_due(self, name: str, now: float, drain: bool) -> bool:
        be, q = self.backends[name], self.queues[name]
        n = len(q["interactive"]) + len(q["batch"])
        if n == 0:
            return False
        if drain or n >= be.largest_bucket:
            return True
        heads = [c[0] for c in q.values() if c]
        oldest = min(h.t_arrival for h in heads)
        if now - oldest >= be.max_wait_s:
            return True
        # deadline urgency: coalescing any longer would blow the head SLO
        ddls = [h.deadline for h in heads if h.deadline is not None]
        return bool(ddls) and min(ddls) - now <= be.max_wait_s

    def pump(self, *, drain: bool = False) -> list[ServeRequest]:
        """One scheduling round: advance every LM backend a step, start the
        due image launch (and a second if another is due at once), then
        finish started launches oldest first until one is left in flight,
        or none when this round finished no other; returns the requests
        completed."""
        now = self.clock()
        finished = self._pump_lm(now)
        if self._start_due(now, drain):
            self._start_due(now, drain)
        n = 0
        while len(self._in_flight) > 1 or (self._in_flight and not n):
            self._finish_oldest()
            n += 1
        finished += self._answered
        self._answered = []
        return finished

    def _start_due(self, now: float, drain: bool) -> bool:
        """Schedule and start the due image launch, if any; ``True`` when
        one was started."""
        due = [n for n, b in self.backends.items()
               if isinstance(b, ImageBackend) and self._launch_due(n, now,
                                                                   drain)]
        if not due:
            return False

        # EDF across models: earliest head-of-line deadline wins
        def urgency(name):
            heads = [c[0] for c in self.queues[name].values() if c]
            ddl = min((h.deadline for h in heads
                       if h.deadline is not None), default=float("inf"))
            return (ddl, min(h.t_arrival for h in heads))
        with TraceAnnotation("huge2.schedule", seq=self.launch_seq + 1):
            name = min(due, key=urgency)
            reqs, size = self._schedule_image(name, now)
        return bool(reqs) and self._start(self.backends[name], reqs, size)

    def _take(self, name: str, cls: str, want: int,
              now: float) -> list[ServeRequest]:
        """Pop up to ``want`` launchable requests from one class queue,
        shedding the expired (deadline already passed — never compute what
        the client stopped waiting for)."""
        out, q = [], self.queues[name][cls]
        while q and len(out) < want:
            r = q.popleft()
            ddl = r.deadline
            if ddl is not None and now > ddl:
                r.status = "shed"
                r.reason = f"shed: deadline passed {(now - ddl) * 1e3:.2f} ms ago"
                self.shed.append(r)
            else:
                out.append(r)
        return out

    def _schedule_image(self, name: str,
                        now: float) -> tuple[list[ServeRequest], int]:
        """The requests of model ``name``'s next launch and its bucket."""
        be, q = self.backends[name], self.queues[name]
        cls = self._pick_class(q, now)
        n = len(q["interactive"]) + len(q["batch"])
        size = be.next_launch_size(n)
        reqs = self._take(name, cls, size, now)
        other = "batch" if cls == "interactive" else "interactive"
        reqs += self._take(name, other, size - len(reqs), now)  # backfill
        return reqs, size

    def _pump_lm(self, now: float) -> list[ServeRequest]:
        finished = []
        for name, be in self.backends.items():
            if not isinstance(be, LMBackend):
                continue
            q = self.queues[name]
            while be.free_slots() and (q["interactive"] or q["batch"]):
                for r in self._take(name, self._pick_class(q, now), 1, now):
                    be.feed(r)
            if not be.active():
                continue
            self.launch_seq += 1
            try:
                if self.injector is not None:
                    self.injector.check(self.launch_seq)
                t0 = time.perf_counter()
                done = be.step()
                self._observe(be.name, "step", time.perf_counter() - t0)
            except NodeFailure as e:
                self._on_failure(be, be.evict_live(), e, self.launch_seq)
                continue
            for r in done:
                self._commit(r)
            finished += done
        return finished

    # -- launch + replay ------------------------------------------------------
    def _start(self, be: ImageBackend, reqs: list[ServeRequest],
               bucket: int) -> bool:
        self.launch_seq += 1
        seq = be.launch_seq = self.launch_seq
        t0 = self.clock()
        waits = [t0 - r.t_arrival for r in reqs]
        for r in reqs:
            r.launch_seq, r.t_launch = seq, t0
        overlapped = int(bool(self._in_flight))
        try:
            if self.injector is not None:
                self.injector.check(seq)              # device lost mid-batch
            with TraceAnnotation(
                    "huge2.launch", seq=seq, model=be.name, bucket=bucket,
                    live=len(reqs), wait_us_sum=sum(waits) * 1e6,
                    wait_us_max=max(waits) * 1e6, overlapped=overlapped):
                launch = be.start([r.payload for r in reqs], bucket)
        except NodeFailure as e:
            self._on_failure(be, reqs, e, seq)
            return False
        self.launches_overlapped += overlapped
        self._in_flight.append(_Started(be, reqs, launch, t0))
        return True

    def _finish_oldest(self):
        st = self._in_flight.popleft()
        try:
            outs = st.be.finish(st.launch)
        except NodeFailure as e:
            self._on_failure(st.be, st.reqs, e, st.launch.seq)
            return
        t1 = self.clock()
        self._observe(st.be.name, st.launch.bucket, t1 - st.t_launch,
                      seq=st.launch.seq)
        for r, out in zip(st.reqs, outs):
            r.out = out
            r.t_done = t1
            self._commit(r)
        self._answered += st.reqs

    def _commit(self, r: ServeRequest):
        if r.rid in self._served_rids:
            raise AssertionError(f"request {r.rid} answered twice")
        self._served_rids.add(r.rid)
        r.status = "served"
        self.done.append(r)
        self._t_last = self.clock()

    def _on_failure(self, be, live: list[ServeRequest], err: Exception,
                    seq: int):
        """The fault ladder, rung one: discard the dead launch ``seq``,
        re-queue its live requests at the front of their class queues in
        arrival order, and replay on a later pump.  Rung two (replica
        actually lost) is ``degrade``, reachable via the ``on_fault``
        hook."""
        self.fault_events.append({
            "launch": seq, "model": be.name,
            "live": len(live), "error": str(err)})
        for r in sorted(live, key=lambda r: (r.t_arrival, r.rid),
                        reverse=True):
            r.replays += 1
            r.status = "queued"
            r.out = r.t_done = r.launch_seq = r.t_launch = None
            self.queues[be.name][r.priority].appendleft(r)
        if self.on_fault is not None:
            self.on_fault(self, err)

    def degrade(self, devices_left: int, *, model_parallel: int = 1,
                pod: int = 0, serve_fns: Optional[dict] = None,
                spatial_tiles: Optional[tuple] = None):
        """Degraded serving after replica loss: finish every image launch
        in flight (its answers come back from the next ``pump``), shrink
        the mesh to the surviving chips and re-jit every image backend
        under the new extent.  ``serve_fns`` optionally maps model name ->
        a rebuilt closure over re-placed params (the
        ``elastic.restore_on_mesh`` path); without it the existing
        closures re-jit under the shrunk mesh.

        Data-parallel (default): ``runtime.elastic.shrink_mesh`` — TP
        preserved, whole DP replicas dropped.

        Plane-parallel (``spatial_tiles=(D_h, D_w)``): the survivors are
        re-tiled as a spatial mesh (``launch.mesh.make_spatial_mesh``,
        leftover extent on the leading 'data' axis) and installed as the
        active spatial mesh, so plans re-built against the new tiling emit
        matching ``dev_tiles`` verdicts.  ``serve_fns`` should close over
        model configs whose ``spatial=`` matches ``spatial_tiles`` — that
        is the re-plan: the new closures trace through the shard_map
        executor on the shrunk mesh."""
        from repro.sharding import DistContext
        while self._in_flight:
            self._finish_oldest()
        if spatial_tiles is not None:
            from repro.core import spatial as spatialmod
            from repro.launch.mesh import make_spatial_mesh
            sp_h, sp_w = (int(v) for v in spatial_tiles)
            if devices_left % (sp_h * sp_w):
                raise ValueError(
                    f"degrade: spatial_tiles {sp_h}x{sp_w} does not divide "
                    f"{devices_left} surviving devices")
            mesh = make_spatial_mesh(
                sp_h, sp_w, data=devices_left // (sp_h * sp_w))
            spatialmod.set_spatial_mesh(mesh)
        else:
            from repro.runtime.elastic import shrink_mesh
            mesh = shrink_mesh(devices_left, model_parallel, pod)
        dist = DistContext(mesh=mesh)
        for name, be in self.backends.items():
            if isinstance(be, ImageBackend):
                be.rebind(dist, (serve_fns or {}).get(name))
        self.degraded = {"devices_left": devices_left,
                         "mesh_shape": dict(mesh.shape),
                         "at_launch": self.launch_seq}
        if spatial_tiles is not None:
            self.degraded["spatial_tiles"] = (sp_h, sp_w)
        return mesh

    def _observe(self, model: str, bucket, dt: float,
                 seq: Optional[int] = None):
        """One launch's duration for its (model, bucket) monitor; ``seq``
        labels a flagged launch (default: the newest launch number)."""
        key = (model, bucket)
        if key not in self.monitors:
            self.monitors[key] = StragglerMonitor(**self._straggler_kw)
        self.monitors[key].record(self.launch_seq if seq is None else seq, dt)

    # -- drivers --------------------------------------------------------------
    def run(self, reqs: Optional[Sequence[ServeRequest]] = None,
            *, max_pumps: int = 100_000) -> list[ServeRequest]:
        """Submit ``reqs`` and pump to empty (drain mode)."""
        for r in reqs or ():
            self.submit(r)
        pumps = 0
        while self.pending() and pumps < max_pumps:
            self.pump(drain=True)
            pumps += 1
        return self.done

    def pending(self) -> int:
        """Requests not yet answered: queued, or in a launch in flight
        (plus one per active LM backend)."""
        n = sum(len(c) for q in self.queues.values() for c in q.values())
        n += sum(len(st.reqs) for st in self._in_flight)
        n += sum(1 for be in self.backends.values()
                 if isinstance(be, LMBackend) and be.active())
        return n

    def results(self) -> dict[int, np.ndarray]:
        return {r.rid: r.out for r in self.done}

    # -- reporting ------------------------------------------------------------
    def stats(self) -> dict:
        window = None
        if self._t_first is not None and self._t_last is not None:
            window = self._t_last - self._t_first
        per_class = {}
        for cls in PRIORITIES:
            rs = [r for r in self.done if r.priority == cls]
            st = latency_stats([r.latency_s for r in rs], window_s=window)
            good = sum(1 for r in rs if r.in_slo is not False)
            n_sub = self._submitted_by_class[cls]
            st["slo_miss"] = sum(1 for r in rs if r.in_slo is False)
            st["rejected"] = sum(1 for r in self.rejected
                                 if r.priority == cls)
            st["shed"] = sum(1 for r in self.shed if r.priority == cls)
            st["goodput_rps"] = (good / window if window else 0.0)
            st["goodput_under_slo"] = (good / n_sub) if n_sub else 1.0
            per_class[cls] = st
        per_model = {}
        for name, be in self.backends.items():
            served = sum(1 for r in self.done if r.model == name)
            m = {"kind": be.kind, "served": served}
            if isinstance(be, ImageBackend):
                launches = be.batcher.launches
                m["launches"] = len(launches)
                m["pad_fraction"] = (
                    1.0 - (sum(live for _, live in launches)
                           / max(1, sum(b for b, _ in launches))))
            else:
                m["steps"] = be.steps
                m["step_cost_ms"] = (None if be.step_cost_s is None
                                     else be.step_cost_s * 1e3)
            per_model[name] = m
        slow = sorted(f"{m}/b{b}" for (m, b), mon in self.monitors.items()
                      if mon.events)
        good = sum(1 for r in self.done if r.in_slo is not False)
        return {
            "submitted": self.submitted,
            "served": len(self.done),
            "rejected": len(self.rejected),
            "shed": len(self.shed),
            "queued": self.pending(),
            "replayed_requests": sum(1 for r in self.done if r.replays),
            "launches_overlapped": self.launches_overlapped,
            "goodput_rps": (good / window if window else 0.0),
            "goodput_under_slo": ((good / self.submitted)
                                  if self.submitted else 1.0),
            "per_class": per_class,
            "per_model": per_model,
            "faults": {"events": len(self.fault_events),
                       "records": list(self.fault_events),
                       "degraded": self.degraded},
            "stragglers": {
                "events": sum(len(m.events) for m in self.monitors.values()),
                "slow_buckets": slow},
        }
