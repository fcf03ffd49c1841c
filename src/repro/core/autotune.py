"""Measured route autotuning: microbenchmark-backed plan decisions with a
persistent per-host route cache.

The heuristic route builders (``plan._single_route`` /
``plan._transposed_route``) decide execution paths from plane-bytes caps
and VMEM estimates — pure arithmetic over the spec constants.  That
arithmetic is host-blind, and the perf record shows it losing (BENCH_fig7:
DC2 routes ``fused_plane`` while the per-phase executor is ~1.4x faster on
the dev host).  Kernel-Segregated Transpose Convolution (2502.20493) and
EcoFlow (2202.02310) make the general argument: the best kernel layout for
a transposed/dilated conv is geometry- *and* machine-dependent, so the
plan step should **measure, not guess**.

This module is that measurement step:

- ``measure_fn``       — the one noise-robust timing loop (block-until-
  ready inside the timed region, min + median reported).  It is the shared
  implementation: ``benchmarks/util.time_fn`` delegates here, so plan-time
  microbenchmarks and bench-time wall-clocks are the same code.
- ``candidate_routes`` — the 2–4 feasible candidates the heuristic already
  enumerates for a (site, bucket): Pallas whole-plane and spatially tiled
  variants (``plan.pallas_single_routes`` /
  ``plan.pallas_transposed_routes``),
  ``fused_tap``, ``fused_plane``, ``taps``, and — transposed only — the
  ``per_phase`` executor as a first-class route.
- ``measure_bucket``   — time every measurable candidate on the live
  device and pick the winner; the heuristic route only loses when a
  challenger beats it by ``AutotunePolicy.min_gain`` (guards against
  noise-driven flips).
- ``RouteCache``       — persistent per-host winners, keyed by the spec
  constants + a device fingerprint, in the same JSON route schema as the
  golden fixture ``tests/fixtures/route_table.json`` /
  ``tools/gen_route_table.py``.  A fleet of identical hosts ships one
  cache and pays the search once at model load.  Corrupt, truncated,
  stale-schema, or wrong-fingerprint files fall back to heuristic routes
  with a warning — never a crash.  The file also carries the serving
  layer's warmup-measured per-bucket launch costs
  (``DynamicImageBatcher``), so a restarted server skips re-measuring.
- ``autotune_plan``    — the entry ``plan.plan_conv(spec, autotune=...)``
  dispatches to: per bucket, cache hit → cached ``Route`` (zero
  microbenchmark runs), miss under ``mode='measure'`` → measure + persist,
  miss under ``mode='cache'`` → heuristic route unchanged.

The fallback ladder, end to end::

    cache hit  →  measured winner (no timing runs)
    cache miss + mode='measure'  →  microbenchmark candidates, persist
    cache miss + mode='cache'    →  heuristic route
    unmeasurable heuristic route (Pallas interpret on CPU)  →  heuristic
    unreadable/stale/foreign cache  →  warn once, heuristic

Pallas candidates are only ever *timed* on a real TPU backend: on CPU
hosts Pallas runs in interpret mode, whose wall-clock says nothing about
the kernel (same rule as the benches' ``pallas_tiled`` column).
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as planmod
from repro.core.plan import BATCH_BUCKETS, ConvPlan, ConvSpec, Route

SCHEMA = "huge2-route-cache/v1"
CACHE_ENV = "HUGE2_ROUTE_CACHE"
DEFAULT_CACHE = "~/.cache/huge2/route_cache.json"

# monotonic count of microbenchmark runs this process has performed —
# tests assert warm-cache model loads leave it unchanged
_MEASURE_CALLS = 0

# in-process singletons: one loaded cache per path, one tuned plan per
# (spec, policy) — cleared by ``reset()`` / ``plan.plan_cache_clear()``
_OPEN_CACHES: dict[str, "RouteCache"] = {}
_TUNED: dict[tuple[ConvSpec, "AutotunePolicy"], ConvPlan] = {}


def measure_calls() -> int:
    """Total microbenchmark runs so far (monotonic; compare before/after)."""
    return _MEASURE_CALLS


def reset():
    """Drop in-process autotune state (tuned plans + loaded caches) so the
    next build re-reads the cache file.  The measurement counter stays
    monotonic."""
    _OPEN_CACHES.clear()
    _TUNED.clear()


# ---------------------------------------------------------------------------
# timing: the one noise-robust implementation (benches delegate here)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Timing:
    """One microbenchmark result.  ``min_s`` is the headline (every source
    of interference only ever adds time, so the minimum is the closest
    observable to the uncontended cost); ``median_s`` is reported alongside
    as the robustness check — a median far above the min flags a noisy
    measurement window."""

    min_s: float
    median_s: float
    iters: int

    @property
    def min_us(self) -> float:
        return self.min_s * 1e6


def measure_fn(fn: Callable, *args, iters: int = 10, warmup: int = 3
               ) -> Timing:
    """Time a jitted callable: ``warmup`` untimed runs (absorbing compile),
    then ``iters`` timed runs with ``block_until_ready`` **inside** the
    timed region (async dispatch must not leak work past the clock)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return Timing(float(np.min(ts)), float(np.median(ts)), iters)


# ---------------------------------------------------------------------------
# cache schema: spec keys, route (de)serialization, device fingerprint
# ---------------------------------------------------------------------------

def device_fingerprint() -> dict:
    """What has to match for measured winners to transfer between hosts:
    accelerator platform + device kind + count, and the jax version (a
    runtime upgrade can reshuffle route rankings)."""
    dev = jax.devices()[0]
    return {
        "platform": str(jax.default_backend()),
        "device_kind": str(getattr(dev, "device_kind", "unknown")),
        "device_count": int(jax.device_count()),
        "jax": str(jax.__version__),
    }


def spec_key(spec: ConvSpec) -> str:
    """Deterministic cache key over every plan-relevant spec constant.
    The spatial / wdtype suffixes only appear for device-tiled / quantized
    specs, so every pre-existing cache entry keeps its key."""
    (ph, pw) = spec.padding
    key = (f"{spec.kind}:{spec.in_hw[0]}x{spec.in_hw[1]}"
           f":c{spec.in_c}->{spec.out_c}"
           f":k{spec.kernel_hw[0]}x{spec.kernel_hw[1]}"
           f":s{spec.strides[0]}x{spec.strides[1]}"
           f":p{ph[0]},{ph[1]},{pw[0]},{pw[1]}"
           f":d{spec.dilation[0]}x{spec.dilation[1]}"
           f":{spec.dtype}:{spec.backend}")
    if spec.spatial != (1, 1):
        key += f":sp{spec.spatial[0]}x{spec.spatial[1]}"
    if spec.wdtype != "float32":
        key += f":w{spec.wdtype}"
    return key


def spec_to_json(spec: ConvSpec) -> dict:
    """The fixture's spec record (``tools/gen_route_table.py`` shares it)."""
    return {
        "kind": spec.kind, "in_hw": list(spec.in_hw),
        "in_c": spec.in_c, "out_c": spec.out_c,
        "kernel_hw": list(spec.kernel_hw),
        "strides": list(spec.strides),
        "padding": [list(p) for p in spec.padding],
        "dilation": list(spec.dilation),
        "spatial": list(spec.spatial),
        "wdtype": spec.wdtype,
    }


def route_to_json(route: Route) -> dict:
    """The fixture's route record — one schema for the golden fixture and
    the per-host cache."""
    return {
        "batch": route.batch,
        "path": route.path,
        "tiles": list(route.tiles) if route.tiles else None,
        "sp_tiles": list(route.sp_tiles) if route.sp_tiles else None,
        "dev_tiles": list(route.dev_tiles) if route.dev_tiles else None,
        "fused_bwd": route.fused_bwd,
        "b_tile": route.b_tile,
    }


def route_from_json(d: dict) -> Route:
    return Route(
        batch=int(d["batch"]), path=str(d["path"]),
        tiles=tuple(d["tiles"]) if d.get("tiles") else None,
        fused_bwd=bool(d.get("fused_bwd", True)),
        sp_tiles=tuple(d["sp_tiles"]) if d.get("sp_tiles") else None,
        dev_tiles=tuple(d["dev_tiles"]) if d.get("dev_tiles") else None,
        b_tile=int(d.get("b_tile", 1)))


def cache_path(path: Optional[str] = None) -> Optional[str]:
    """Resolve the cache location: explicit arg > ``$HUGE2_ROUTE_CACHE`` >
    the per-user default.  ``''`` means memory-only (no file)."""
    if path == "":
        return None
    if path is None:
        path = os.environ.get(CACHE_ENV) or DEFAULT_CACHE
    return str(pathlib.Path(path).expanduser())


class RouteCache:
    """Persistent per-host route winners + serving bucket costs.

    One JSON file, schema-versioned and fingerprint-guarded.  Every load
    failure mode (missing file, corrupt/truncated JSON, stale schema,
    foreign fingerprint, malformed entries) degrades to an *empty* cache
    with a ``RuntimeWarning`` — the caller falls back to heuristic routes
    and a later ``save`` rewrites the file cleanly."""

    def __init__(self, path: Optional[str] = None):
        self.path = cache_path(path)
        self.fingerprint = device_fingerprint()
        # spec_key -> {"spec": {...}, "routes": {batch(str): route-json}}
        self.entries: dict[str, dict] = {}
        # serving-side warmup costs: cache_key -> {bucket(str): seconds}
        self.bucket_costs: dict[str, dict] = {}
        self.loaded_from_disk = False
        if self.path is not None:
            self._load()

    # -- persistence ---------------------------------------------------------
    def _warn(self, why: str):
        warnings.warn(
            f"route cache {self.path}: {why} — falling back to heuristic "
            f"routes (the cache will be rewritten on the next save)",
            RuntimeWarning, stacklevel=3)

    def _load(self):
        p = pathlib.Path(self.path)
        if not p.exists():
            return
        try:
            raw = json.loads(p.read_text())
        except (OSError, ValueError) as e:
            self._warn(f"unreadable ({e.__class__.__name__}: {e})")
            return
        if not isinstance(raw, dict) or raw.get("schema") != SCHEMA:
            self._warn(f"stale or unknown schema {raw.get('schema')!r} "
                       f"(want {SCHEMA!r})")
            return
        if raw.get("fingerprint") != self.fingerprint:
            self._warn(f"device fingerprint mismatch "
                       f"(file {raw.get('fingerprint')!r}, "
                       f"host {self.fingerprint!r})")
            return
        try:
            entries = dict(raw.get("entries", {}))
            # validate eagerly: every route record must deserialize
            for key, ent in entries.items():
                for b, rj in ent["routes"].items():
                    int(b), route_from_json(rj)
            self.entries = entries
            self.bucket_costs = {
                k: {str(b): float(c) for b, c in v.items()}
                for k, v in dict(raw.get("bucket_costs", {})).items()}
        except (KeyError, TypeError, ValueError, AttributeError) as e:
            self._warn(f"malformed entries ({e.__class__.__name__}: {e})")
            self.entries, self.bucket_costs = {}, {}
            return
        self.loaded_from_disk = True

    def save(self):
        """Atomic write (tmp + rename) of the full cache state."""
        if self.path is None:
            return
        p = pathlib.Path(self.path)
        p.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "schema": SCHEMA,
            "fingerprint": self.fingerprint,
            "generated_by": "repro.core.autotune",
            "entries": self.entries,
            "bucket_costs": self.bucket_costs,
        }
        tmp = p.with_suffix(p.suffix + ".tmp")
        tmp.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
        tmp.replace(p)

    # -- routes --------------------------------------------------------------
    def get(self, spec: ConvSpec, batch: int) -> Optional[Route]:
        ent = self.entries.get(spec_key(spec))
        if ent is None:
            return None
        rj = ent["routes"].get(str(batch))
        return None if rj is None else route_from_json(rj)

    def put(self, spec: ConvSpec, route: Route,
            timings: Optional[dict] = None):
        ent = self.entries.setdefault(
            spec_key(spec), {"spec": spec_to_json(spec),
                             "backend": spec.backend, "routes": {}})
        rj = route_to_json(route)
        if timings:
            rj["measured_us"] = {k: round(v * 1e6, 3)
                                 for k, v in timings.items()}
        ent["routes"][str(route.batch)] = rj

    # -- serving bucket costs ------------------------------------------------
    def get_bucket_costs(self, key: str) -> dict[int, float]:
        return {int(b): float(c)
                for b, c in self.bucket_costs.get(key, {}).items()}

    def put_bucket_costs(self, key: str, costs: dict[int, float]):
        self.bucket_costs[key] = {str(b): float(c) for b, c in costs.items()}


def open_cache(path: Optional[str] = None) -> RouteCache:
    """Load-or-create the cache at ``path`` (process-wide singleton per
    resolved path, so concurrent plan builds share one view and saves
    merge instead of clobbering)."""
    resolved = cache_path(path)
    if resolved is None:
        return RouteCache("")
    if resolved not in _OPEN_CACHES:
        _OPEN_CACHES[resolved] = RouteCache(resolved)
    return _OPEN_CACHES[resolved]


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AutotunePolicy:
    """How ``plan_conv(spec, autotune=...)`` resolves routes.

    ``mode``: ``'measure'`` microbenchmarks cache misses on the live device
    and persists winners; ``'cache'`` only consumes cached winners (a fleet
    host that ships the cache — never runs a timing loop); ``'off'`` is the
    heuristic (same as passing ``autotune=None``).

    ``cache_path``: ``None`` → ``$HUGE2_ROUTE_CACHE`` or the per-user
    default; ``''`` → memory-only (measure, never touch disk — what the
    benches use).  ``buckets`` limits tuning to a subset of the plan's
    batch buckets (``None`` = all); untuned buckets keep heuristic routes.

    ``min_gain``: a measured challenger must beat the heuristic route's
    min time by this factor to flip it — the hysteresis that keeps noise
    from rewriting routes that are actually ties."""

    mode: str = "measure"             # 'off' | 'cache' | 'measure'
    cache_path: Optional[str] = None  # None=env/default, ''=memory-only
    buckets: Optional[tuple[int, ...]] = None
    iters: int = 5
    warmup: int = 2
    min_gain: float = 1.03

    def __post_init__(self):
        if self.mode not in ("off", "cache", "measure"):
            raise ValueError(f"bad autotune mode {self.mode!r}")


# ---------------------------------------------------------------------------
# candidate enumeration: the feasible set the heuristic already knows
# ---------------------------------------------------------------------------

def _dedupe(routes: Sequence[Route]) -> tuple[Route, ...]:
    seen, out = set(), []
    for r in routes:
        k = (r.path, r.tiles, r.sp_tiles, r.dev_tiles, r.b_tile)
        if k not in seen:
            seen.add(k)
            out.append(r)
    return tuple(out)


def _with_dev_candidates(plan: ConvPlan, batch: int,
                         cands: Sequence[Route]) -> tuple[Route, ...]:
    """Device-tiled candidates for a spatial spec: each single-device
    candidate paired with its plane-parallel twin (same per-shard path,
    ``dev_tiles`` attached), so ``measure_bucket`` ranks sharded vs
    single-device execution on the live mesh like any other route flip."""
    if plan.spec.spatial == (1, 1):
        return _dedupe(cands)
    from repro.core import spatial as spatialmod
    if spatialmod.spatial_plan(plan.spec) is None:
        return _dedupe(cands)
    both = []
    for r in cands:
        both.append(dataclasses.replace(r, dev_tiles=None))
        both.append(dataclasses.replace(r, dev_tiles=plan.spec.spatial))
    return _dedupe(both)


def candidate_routes(plan: ConvPlan, batch: int) -> tuple[Route, ...]:
    """Every feasible whole-conv route for this (site, bucket) — the same
    set the heuristic chooses *one* of, enumerated for measurement.  All
    candidates share the bucket's ``fused_bwd`` verdict (a memory cap on
    the backward, not a tunable)."""
    spec = plan.spec
    itemsize = jnp.dtype(spec.dtype).itemsize
    n = spec.out_c
    oh, ow = plan.out_hw

    if spec.kind == "transposed":
        if plan.total_taps == 0:
            return (Route(batch, "taps", None),)
        (glh, ghh), (glw, ghw) = plan.gpad
        hg = spec.in_hw[0] + glh + ghh
        wg = spec.in_hw[1] + glw + ghw
        cands = planmod.pallas_transposed_routes(
            spec, hg, wg, plan.out_hw, plan.total_taps, plan.sum_uv,
            plan.uniform, plan.phases, itemsize, batch)
        ps = planmod._pixel_shuffle_route(spec, plan.phases, batch)
        if ps is not None:
            cands.append(ps)
        plane_bytes = 4 * batch * hg * wg * plan.total_taps * n
        if plane_bytes <= planmod._PLANE_BYTES_MAX:
            cands.append(Route(batch, "fused_plane", None))
        if plan.uniform:
            cands.append(Route(batch, "fused_tap", None))
        cands.append(Route(batch, "taps", None))
        cands.append(Route(batch, "per_phase", None))
        return _with_dev_candidates(plan, batch, cands)

    # 'conv' / 'dilated': the single-correlation feasible set
    (ph, pw) = spec.padding
    hp = spec.in_hw[0] + ph[0] + ph[1]
    wp = spec.in_hw[1] + pw[0] + pw[1]
    r, s = spec.kernel_hw
    fused_ok = (4 * batch * oh * ow * r * s * spec.in_c
                <= planmod._PLANE_BYTES_MAX)
    cands = planmod.pallas_single_routes(spec, hp, wp, plan.out_hw, itemsize,
                                         batch, fused_ok)
    if fused_ok:
        cands.append(Route(batch, "fused_tap", None, fused_bwd=True))
    cands.append(Route(batch, "taps", None, fused_bwd=fused_ok))
    return _with_dev_candidates(plan, batch, cands)


def _measurable(route: Route) -> bool:
    """Pallas wall-clock is only meaningful on a real TPU backend; interpret
    mode (CPU hosts) would time the Python interpreter, not the kernel.
    Device-tiled routes need the matching spatial mesh bound — without it
    the forced plan would silently measure the single-device fallback."""
    if route.path == "pallas":
        if jax.default_backend() != "tpu":
            return False
    if route.dev_tiles is not None:
        from repro.core import spatial as spatialmod
        active = spatialmod.active_spatial_mesh()
        if active is None:
            return False
        mesh, axes = active
        if not spatialmod.mesh_matches(mesh, axes, route.dev_tiles):
            return False
    return True


def route_label(route: Route) -> str:
    lab = route.path
    if route.tiles:
        lab += f"@{route.tiles[0]}x{route.tiles[1]}"
    if route.sp_tiles:
        lab += f"@sp{route.sp_tiles[0]}x{route.sp_tiles[1]}"
    if route.dev_tiles:
        lab += f"@dev{route.dev_tiles[0]}x{route.dev_tiles[1]}"
    if route.b_tile != 1:
        lab += f"@bt{route.b_tile}"
    return lab


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _bench_inputs(plan: ConvPlan, batch: int):
    """Seeded synthetic (x, packed) at the bucket's batch — same
    distribution every host, so identical hardware measures identical
    work."""
    spec = plan.spec
    dtype = jnp.dtype(spec.dtype)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(
        k1, (batch, spec.in_hw[0], spec.in_hw[1], spec.in_c), dtype)
    kernel = jax.random.normal(
        k2, (*spec.kernel_hw, spec.in_c, spec.out_c), dtype)
    packed = plan.pack(kernel)
    return jax.block_until_ready(x), jax.block_until_ready(packed)


def measure_route(plan: ConvPlan, route: Route, x, packed, *,
                  iters: int = 5, warmup: int = 2) -> Timing:
    """Microbenchmark ONE candidate route: jit the plan's apply with the
    route forced for every bucket, time it with the shared loop.  This is
    the single choke point every timing run goes through — the monotonic
    counter behind ``measure_calls()`` lives here (and is what the
    warm-cache "zero microbenchmark runs" test asserts on)."""
    global _MEASURE_CALLS
    _MEASURE_CALLS += 1
    forced = plan.with_routes((route,))
    return measure_fn(jax.jit(forced.apply), x, packed,
                      iters=iters, warmup=warmup)


def measure_bucket(plan: ConvPlan, batch: int,
                   policy: Optional[AutotunePolicy] = None
                   ) -> tuple[Route, dict[str, float]]:
    """Measure every feasible candidate for (plan, bucket) and return
    ``(winner, {label: min_seconds})``.

    The heuristic route is always in the candidate set and wins ties: a
    challenger must beat it by ``policy.min_gain``.  If the heuristic
    route itself cannot be measured honestly (Pallas interpret mode on a
    CPU host) the bucket is not tuned at all."""
    policy = policy or AutotunePolicy()
    heuristic = plan.route_for_batch(batch)
    if not _measurable(heuristic):
        return heuristic, {}
    cands = [r for r in _dedupe((heuristic,) + candidate_routes(plan, batch))
             if _measurable(r)]
    if len(cands) < 2:
        return heuristic, {}
    x, packed = _bench_inputs(plan, batch)
    timings: dict[str, float] = {}
    for cand in cands:
        t = measure_route(plan, cand, x, packed,
                          iters=policy.iters, warmup=policy.warmup)
        timings[route_label(cand)] = t.min_s
    h_t = timings[route_label(heuristic)]
    best_route, best_t = heuristic, None
    for cand in cands:
        t = timings[route_label(cand)]
        if cand == heuristic:
            continue
        if t * policy.min_gain < h_t and (best_t is None or t < best_t):
            best_route, best_t = cand, t
    return best_route, timings


# ---------------------------------------------------------------------------
# the plan-level entry: what plan_conv(spec, autotune=...) dispatches to
# ---------------------------------------------------------------------------

def autotune_plan(plan: ConvPlan, policy: AutotunePolicy) -> ConvPlan:
    """Resolve measured routes for ``plan`` under ``policy`` and return the
    tuned plan (in-process singleton per (spec, policy) — repeated model
    loads reuse it).  Fallback ladder per bucket: cache hit → cached
    winner; miss + ``mode='measure'`` → microbenchmark + persist; miss +
    ``mode='cache'`` → heuristic route unchanged."""
    if policy.mode == "off":
        return plan
    key = (plan.spec, policy)
    if key in _TUNED:
        return _TUNED[key]
    cache = open_cache(policy.cache_path)
    tune_buckets = (set(policy.buckets) if policy.buckets is not None
                    else set(BATCH_BUCKETS))
    routes, dirty = [], False
    for hr in plan.routes:
        if hr.batch not in tune_buckets:
            routes.append(hr)
            continue
        cached = cache.get(plan.spec, hr.batch)
        if cached is not None:
            routes.append(cached)
            continue
        if policy.mode != "measure":
            routes.append(hr)
            continue
        best, timings = measure_bucket(plan, hr.batch, policy)
        routes.append(best)
        if timings:
            cache.put(plan.spec, best, timings)
            dirty = True
    if dirty:
        cache.save()
    tuned = plan.with_routes(tuple(routes))
    _TUNED[key] = tuned
    return tuned
