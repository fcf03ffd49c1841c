"""Dynamic batching for image/latent serving (the bucket-aware sibling of
the LM slot scheduler in ``serving/batcher.py``).

Generative-image requests are single tensors (a latent vector for a GAN /
VAE decoder, an image for segmentation) with no autoregressive state, so
the scheduling problem is pure *coalescing*: gather whatever is queued,
pad it up to the nearest plan batch bucket (``core.plan.BATCH_BUCKETS`` —
the sizes every ``ConvPlan`` routed at build time), and launch one jitted
call.  The bucket set keeps the number of compiled executables bounded
(one jit per bucket, compiled on first use or eagerly via ``warmup``) and
keeps execution on plan-time routes — ``route_for_batch`` never has to
size a route for an arbitrary traced batch.

Scheduling policy (classic dynamic batching, cf. TF-Serving / Triton):

- launch immediately when a full largest bucket is queued;
- otherwise wait for more arrivals, but never longer than
  ``max_wait_ms`` past the oldest request's arrival — then serve the queue
  on bucket-sized launches, padding the tail;
- ``drain=True`` (offline / shutdown) flushes without waiting.

**Cost-aware launch planning.**  Buckets quantize compile count, but the
mapping queue-length -> launch sizes is a policy choice: padding 5 requests
up to bucket 16 can cost 2x a bucket-4 launch plus a single.  ``warmup``
therefore *measures* each bucket's launch wall-time (the serving analog of
the engine's plan-time route choice), and the scheduler covers the queue
with the bucket multiset minimizing total measured cost (a tiny
coin-change DP, memoized per queue length).  Until costs are measured the
policy degrades to round-up-to-nearest-bucket.

The measured costs can be **persisted**: pass a ``repro.core.autotune``
``RouteCache`` (plus a ``cache_key`` naming the served model) and the
batcher preloads ``bucket_cost_s`` from the cache at construction and
writes back any buckets ``warmup`` measures — a restarted server with a
warm cache compiles its buckets but re-measures none of them.

Data-parallel serving: pass a ``DistContext`` and the batcher constrains
the batched input over the mesh's data axes inside the jitted call, so the
padded bucket shards across devices under ``NamedSharding`` (weights are
sharded at init by the model's ``dist``-aware ``*_init``).

Under the SLO-aware control plane (``serving/control_plane.py``) this
batcher is no longer a peer entry point but the *launch engine* of an
``ImageBackend``: the control plane owns admission/priorities/deadlines
and drives launches directly; ``rebind_dist`` is its elastic-degrade
hook after replica loss.

**A launch in two halves.**  ``start`` stacks and pads the rows, puts the
batch on the device, dispatches the bucket executable and asks for the
output's copy to the host (``copy_to_host_async``), then returns an
``InFlight`` handle without waiting; ``finish`` waits for that output and
returns its live rows.  ``execute`` is ``finish(start(...))``: the
batcher's own ``pump`` / ``run`` / ``drive_open_loop`` stay synchronous.
The control plane keeps one launch in flight across a pump, so the
device runs launch k+1 while launch k's answer lands on the host.  Each
step is a ``jax.profiler.TraceAnnotation`` carrying the control plane's
launch number, recorded only while a profiler traces the process:
``huge2.launch.stack``, ``.h2d`` and ``.dispatch`` in ``start``,
``huge2.launch.wait`` and ``.d2h`` in ``finish``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional, Sequence

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.plan import BATCH_BUCKETS
from repro.serving.metrics import latency_stats


@dataclasses.dataclass
class ImageRequest:
    rid: int
    payload: np.ndarray                    # (z_dim,) latent or (H, W, C) image
    # None = stamped by the batcher's injected clock at submit (open-loop
    # drivers stamp scheduled arrivals explicitly, in the same clock domain)
    t_arrival: Optional[float] = None
    t_done: Optional[float] = None
    out: Optional[np.ndarray] = None

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_done is None or self.t_arrival is None:
            return None
        return self.t_done - self.t_arrival


@dataclasses.dataclass
class InFlight:
    """A started launch: its device output (the whole padded bucket), the
    live row count, the bucket and the control plane's launch number."""
    out: jax.Array
    live: int
    bucket: int
    seq: Optional[int] = None


class DynamicImageBatcher:
    """Coalesce image requests into plan batch buckets, one jit per bucket.

    ``serve_fn(batch) -> batch`` is the model forward with parameters
    already bound (e.g. ``lambda z: generator_apply(params, z, cfg)``); the
    batcher jits it once and relies on shape specialization for the
    per-bucket executables.
    """

    def __init__(self, serve_fn: Callable, *,
                 buckets: Sequence[int] = BATCH_BUCKETS,
                 max_wait_ms: float = 2.0, dist=None,
                 cache=None, cache_key: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"bad buckets {buckets}")
        self.max_wait_s = max_wait_ms / 1e3
        # ONE monotonic clock for every scheduling timestamp (arrival,
        # max-wait expiry, completion).  The control plane injects its own
        # clock here so a request can't be admitted under one clock and
        # deadline-expired under another; compute-cost *durations*
        # (``warmup`` timing loops) stay on ``time.perf_counter`` — they
        # measure the device, not the schedule.
        self.clock = clock
        # bucket-cost persistence: a repro.core.autotune.RouteCache plus a
        # key naming the served model (costs are per model + per host)
        self.cache = cache
        self.cache_key = cache_key
        self.rebind_dist(dist, serve_fn)
        self.queue: deque[ImageRequest] = deque()
        self.done: list[ImageRequest] = []
        self.launches: list[tuple[int, int]] = []   # (bucket, live) per call
        self.bucket_cost_s: dict[int, float] = {}   # measured by warmup
        if cache is not None and cache_key is not None:
            self.bucket_cost_s = {
                b: c for b, c in cache.get_bucket_costs(cache_key).items()
                if b in self.buckets}
        self._sched_memo: dict[int, tuple[float, int]] = {0: (0.0, 0)}
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    def rebind_dist(self, dist, serve_fn: Optional[Callable] = None):
        """(Re-)jit the serve closure under ``dist`` — the elastic-degrade
        path: after replica loss the control plane shrinks the mesh and
        rebinds every backend to the surviving data-parallel extent.
        Bucket executables recompile lazily on the next launch; measured
        costs are kept (same kernels, fewer replicas — ``warmup(force=
        True)`` re-measures).  ``serve_fn`` defaults to the current one
        (pass a rebuilt closure when params were re-placed via
        ``elastic.restore_on_mesh``)."""
        self.dist = dist
        if serve_fn is not None:
            self._serve_fn = serve_fn
        fn = self._serve_fn

        def batched(x):
            if dist is not None:
                x = dist.constrain(x, dist.image_spec())
            return fn(x)

        if dist is not None and dist.spatial_tiles() != (1, 1):
            # plane-parallel serving: bind the mesh as the active spatial
            # mesh while tracing, so conv plans whose routes carry matching
            # ``dev_tiles`` dispatch through the shard_map executor.  The
            # binding only matters at trace time — compiled bucket
            # executables keep the sharded program afterwards.
            from repro.core import spatial as _spatial
            inner = batched

            def batched(x, _inner=inner):
                with _spatial.use_spatial_mesh(dist.mesh):
                    return _inner(x)

        self._serve = jax.jit(batched)

    # -- client API ----------------------------------------------------------
    def submit(self, req: ImageRequest):
        if req.t_arrival is None:
            req.t_arrival = self.clock()
        if self._t_first is None:
            self._t_first = self.clock()
        self.queue.append(req)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket that fits ``n`` (the largest bucket caps a launch)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def warmup(self, proto: Optional[np.ndarray] = None, *, iters: int = 2,
               force: bool = False) -> tuple[int, ...]:
        """Eagerly compile every bucket (zeros payload) so serving latency
        never includes a compile, and *measure* each bucket's launch cost
        (min of ``iters``) for the cost-aware scheduler.  Buckets whose cost
        was preloaded from the route cache are compiled but NOT re-timed
        unless ``force=True`` — a restarted server with a warm cache pays
        zero measurement loops.  Newly measured costs are written back to
        the cache (when one is attached).  ``proto`` is one request payload
        (shape/dtype template); defaults to the oldest queued request's.
        Returns the buckets that were actually timed."""
        if proto is None:
            if not self.queue:
                raise ValueError("warmup needs a proto payload or a queued "
                                 "request for the shape")
            proto = self.queue[0].payload
        timed = []
        for b in self.buckets:
            x = jax.numpy.asarray(np.zeros((b,) + proto.shape, proto.dtype))
            jax.block_until_ready(self._serve(x))       # compile
            if b in self.bucket_cost_s and not force:
                continue                                # cache hit: no timing
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(self._serve(x))
                ts.append(time.perf_counter() - t0)
            self.bucket_cost_s[b] = min(ts)
            timed.append(b)
        self._sched_memo = {0: (0.0, 0)}                # rebuild on new costs
        if timed and self.cache is not None and self.cache_key is not None:
            self.cache.put_bucket_costs(self.cache_key, self.bucket_cost_s)
            self.cache.save()
        return tuple(timed)

    def _first_launch_size(self, n: int) -> int:
        """Bucket of the next launch for a queue of ``n``: head of the
        cheapest bucket cover under the measured costs (largest-first so
        the most waiters complete earliest), else round-up-to-bucket."""
        if not self.bucket_cost_s:
            return self.bucket_for(n)
        best = max(self._plan_cover(n))
        return best

    def _plan_cover(self, n: int) -> tuple[int, ...]:
        """Bucket multiset covering ``n`` requests at minimum measured cost
        (classic coin-change DP over launch sizes; overshoot = tail pad)."""
        memo = self._sched_memo
        for i in range(1, n + 1):                        # bottom-up, O(n·|B|)
            if i not in memo:
                memo[i] = min(
                    (self.bucket_cost_s[b] + memo[max(0, i - b)][0], b)
                    for b in self.buckets)
        cover, k = [], n
        while k > 0:
            b = memo[k][1]
            cover.append(b)
            k = max(0, k - b)
        return tuple(cover)

    # -- scheduler -----------------------------------------------------------
    def pump(self, *, drain: bool = False) -> list[ImageRequest]:
        """Launch at most one batch if the policy says go; returns the
        requests completed by that launch (empty when still coalescing)."""
        if not self.queue:
            return []
        now = self.clock()
        full = len(self.queue) >= self.buckets[-1]
        expired = now - self.queue[0].t_arrival >= self.max_wait_s
        if not (full or expired or drain):
            return []
        size = self._first_launch_size(len(self.queue))
        take = min(len(self.queue), size)
        reqs = [self.queue.popleft() for _ in range(take)]
        return self._launch(reqs, bucket=size)

    def run(self, reqs=None, *, drain: bool = True) -> list[ImageRequest]:
        """Submit ``reqs`` (optional) and pump until the queue is empty.
        With ``drain=False`` the loop sleeps out the oldest request's
        max-wait deadline instead of spinning on empty pumps."""
        for r in reqs or ():
            self.submit(r)
        while self.queue:
            if not self.pump(drain=drain) and not drain and self.queue:
                wait = self.max_wait_s - (self.clock()
                                          - self.queue[0].t_arrival)
                if wait > 0:
                    time.sleep(min(wait, 1e-3))
        return self.done

    def start(self, rows: Sequence[np.ndarray],
              bucket: Optional[int] = None,
              seq: Optional[int] = None) -> InFlight:
        """Pad ``rows`` up to ``bucket``, put the batch on the device and
        dispatch ONE jitted launch without waiting for it; the output's
        copy to the host is requested at once, so it can land while the
        host does other work.  The launch is recorded in ``launches`` here
        (pad-fraction stats cover every caller); ``seq`` (the control
        plane's ``launch_seq``) labels the launch's trace spans."""
        bucket = self.bucket_for(len(rows)) if bucket is None else bucket
        with TraceAnnotation("huge2.launch.stack", seq=seq):
            batch = np.stack([np.asarray(r) for r in rows])
            if len(rows) < bucket:                   # pad the tail
                pad = np.zeros((bucket - len(rows),) + batch.shape[1:],
                               batch.dtype)
                batch = np.concatenate([batch, pad])
        with TraceAnnotation("huge2.launch.h2d", seq=seq):
            x = jax.device_put(batch)
        with TraceAnnotation("huge2.launch.dispatch", seq=seq):
            out = self._serve(x)
            out.copy_to_host_async()
        self.launches.append((bucket, len(rows)))
        return InFlight(out=out, live=len(rows), bucket=bucket, seq=seq)

    def finish(self, launch: InFlight) -> np.ndarray:
        """Wait for a started launch and return its live output rows."""
        with TraceAnnotation("huge2.launch.wait", seq=launch.seq):
            out = jax.block_until_ready(launch.out)
        with TraceAnnotation("huge2.launch.d2h", seq=launch.seq):
            return np.asarray(out)[:launch.live]

    def execute(self, rows: Sequence[np.ndarray],
                bucket: Optional[int] = None,
                seq: Optional[int] = None) -> np.ndarray:
        """One launch, start to finish: the live output rows of ``rows``
        padded up to ``bucket``, with no request bookkeeping."""
        return self.finish(self.start(rows, bucket, seq))

    def _launch(self, reqs: list[ImageRequest],
                bucket: Optional[int] = None) -> list[ImageRequest]:
        out = self.execute([r.payload for r in reqs], bucket)
        now = self.clock()
        for i, r in enumerate(reqs):
            r.out = out[i]
            r.t_done = now
        self.done.extend(reqs)
        self._t_last = now
        return reqs

    def reset_stats(self):
        """Drop request/launch history for a fresh measurement window; the
        compiled bucket executables and measured costs are kept (benchmark
        repeats must not pay recompilation)."""
        self.queue.clear()
        self.done = []
        self.launches = []
        self._t_first = self._t_last = None

    # -- open-loop driver (shared by the serve examples / benches) -----------
    def drive_open_loop(self, make_payload: Callable[[int], np.ndarray],
                        requests: int, rate: float = 0.0
                        ) -> list[ImageRequest]:
        """Submit ``requests`` payloads at ``rate`` req/s (0 = one burst),
        pumping as arrivals trickle in, then drain the tail."""
        gap = 1.0 / rate if rate > 0 else 0.0
        for i in range(requests):
            if gap:
                time.sleep(gap)
            self.submit(ImageRequest(rid=i, payload=make_payload(i)))
            self.pump()
        return self.run()

    # -- reporting -----------------------------------------------------------
    def stats(self) -> dict:
        window = None
        if self._t_first is not None and self._t_last is not None:
            window = self._t_last - self._t_first
        st = latency_stats([r.latency_s for r in self.done], window_s=window)
        st["launches"] = len(self.launches)
        st["bucket_histogram"] = {
            b: sum(1 for bb, _ in self.launches if bb == b)
            for b in self.buckets}
        st["pad_fraction"] = (
            1.0 - (sum(live for _, live in self.launches)
                   / max(1, sum(b for b, _ in self.launches))))
        return st
