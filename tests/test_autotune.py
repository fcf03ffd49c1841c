"""Measured route autotuning: timing loop, candidate set, cache robustness,
fallback ladder, and oracle parity of tuned plans.

The cache-corruption suite is the load-bearing part: a route cache is an
*accelerator*, so every failure mode (corrupt JSON, truncated file, stale
schema, foreign device fingerprint, malformed entries) must degrade to
heuristic routes with a ``RuntimeWarning`` — never a crash, never a wrong
route.  The warm-cache tests assert the acceptance criterion directly:
a second model load against a populated cache performs ZERO microbenchmark
runs (``autotune.measure_calls()`` unchanged).
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.autotune as at
from repro.core.autotune import (SCHEMA, AutotunePolicy, RouteCache, Timing,
                                 candidate_routes, device_fingerprint,
                                 measure_bucket, measure_fn, route_from_json,
                                 route_label, route_to_json)
from repro.core.plan import (BATCH_BUCKETS, ConvSpec, Route,
                             plan_cache_clear, plan_conv)
from tests.conftest import (TOL_GRAD, assert_close, oracle_transposed,
                            random_case)

# a tiny transposed site: cheap to jit, has the full transposed candidate
# set (fused_plane / fused_tap / taps / per_phase)
TINY = ConvSpec(kind="transposed", in_hw=(4, 4), in_c=4, out_c=4,
                kernel_hw=(3, 3), strides=(2, 2),
                padding=((1, 0), (1, 0)))
# fast measure policy for tests: one bucket, one timed iteration
FAST = dict(buckets=(1,), iters=1, warmup=0)


@pytest.fixture(autouse=True)
def _fresh_caches():
    plan_cache_clear()
    yield
    plan_cache_clear()


def tiny_spec(**kw):
    """A distinct tiny spec per test (vary in_c/out_c to dodge the
    in-process tuned-plan singleton across tests)."""
    return dataclasses.replace(TINY, **kw)


# ---------------------------------------------------------------------------
# timing loop
# ---------------------------------------------------------------------------

def test_measure_fn_min_le_median_and_iters():
    f = jax.jit(lambda x: x * 2.0)
    t = measure_fn(f, jnp.ones((8, 8)), iters=5, warmup=1)
    assert isinstance(t, Timing)
    assert 0.0 < t.min_s <= t.median_s
    assert t.iters == 5
    assert t.min_us == pytest.approx(t.min_s * 1e6)


def test_bench_util_time_fn_is_the_shared_loop():
    import benchmarks.util as bu
    assert bu.measure_fn is measure_fn          # ONE implementation
    f = jax.jit(lambda x: x + 1.0)
    assert bu.time_fn(f, jnp.ones(4), iters=3, warmup=1) > 0.0
    assert isinstance(bu.time_stats(f, jnp.ones(4), iters=3, warmup=1),
                      Timing)


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------

def test_candidates_include_heuristic_and_per_phase():
    plan = plan_conv(tiny_spec())
    for b in BATCH_BUCKETS:
        cands = candidate_routes(plan, b)
        labels = [route_label(r) for r in cands]
        assert len(labels) == len(set(labels))          # deduped
        assert plan.route_for_batch(b) in cands         # heuristic is in set
        assert any(r.path == "per_phase" for r in cands)
        assert all(r.batch == b for r in cands)


def test_candidates_single_kind_feasible_set():
    spec = ConvSpec(kind="conv", in_hw=(8, 8), in_c=4, out_c=4,
                    kernel_hw=(3, 3), padding=((1, 1), (1, 1)))
    plan = plan_conv(spec)
    cands = candidate_routes(plan, 1)
    paths = {r.path for r in cands}
    assert "taps" in paths and "fused_tap" in paths
    assert "per_phase" not in paths          # transposed-only executor
    assert plan.route_for_batch(1) in cands


# ---------------------------------------------------------------------------
# cache round-trip + corruption ladder
# ---------------------------------------------------------------------------

def test_cache_roundtrip_identical_routes(tmp_path):
    path = str(tmp_path / "c.json")
    spec = tiny_spec()
    routes = (Route(1, "per_phase", None),
              Route(4, "fused_plane", None),
              Route(16, "pallas", (8, 8), sp_tiles=(4, 4)),
              Route(64, "taps", None, fused_bwd=False))
    cache = RouteCache(path)
    for r in routes:
        cache.put(spec, r, {"taps": 1e-4})
    cache.save()
    fresh = RouteCache(path)
    assert fresh.loaded_from_disk
    for r in routes:
        assert fresh.get(spec, r.batch) == r            # exact Route tuples
    assert fresh.get(spec, 2) is None
    assert fresh.get(tiny_spec(in_c=8), 1) is None


def test_route_json_schema_matches_fixture():
    r = Route(4, "pallas", (8, 8), sp_tiles=(4, 4), fused_bwd=False)
    rj = route_to_json(r)
    assert set(rj) == {"batch", "path", "tiles", "sp_tiles", "fused_bwd",
                       "dev_tiles", "b_tile"}
    assert route_from_json(rj) == r
    dev = Route(16, "pallas", (8, 8), sp_tiles=(4, 4), dev_tiles=(2, 2))
    assert route_from_json(route_to_json(dev)) == dev


def test_route_batch_tile_roundtrip_and_label():
    """``Route.b_tile`` survives the cache's JSON and shows in the label; a
    cached record from before the field loads as one image per step."""
    r = Route(64, "pallas", (128, 128), b_tile=32)
    assert route_from_json(route_to_json(r)) == r
    assert route_label(r) == "pallas@128x128@bt32"
    assert route_label(Route(1, "pallas", (128, 128))) == "pallas@128x128"
    old = route_to_json(r)
    del old["b_tile"]
    assert route_from_json(old) == Route(64, "pallas", (128, 128))


@pytest.mark.parametrize("poison", ["corrupt", "truncated", "stale_schema",
                                    "bad_fingerprint", "malformed_entries"])
def test_cache_poison_warns_and_falls_back(tmp_path, poison):
    path = tmp_path / "c.json"
    good = {"schema": SCHEMA, "fingerprint": device_fingerprint(),
            "entries": {"k": {"spec": {}, "routes": {
                "1": route_to_json(Route(1, "taps", None))}}},
            "bucket_costs": {}}
    if poison == "corrupt":
        path.write_text("{this is not json")
    elif poison == "truncated":
        full = json.dumps(good)
        path.write_text(full[:len(full) // 2])
    elif poison == "stale_schema":
        path.write_text(json.dumps({**good, "schema": "huge2-route-cache/v0"}))
    elif poison == "bad_fingerprint":
        path.write_text(json.dumps(
            {**good, "fingerprint": {"platform": "mars"}}))
    elif poison == "malformed_entries":
        path.write_text(json.dumps(
            {**good, "entries": {"k": {"routes": {"1": {"batch": "NaN?"}}}}}))
    with pytest.warns(RuntimeWarning, match="falling back to heuristic"):
        cache = RouteCache(str(path))
    assert cache.entries == {} and not cache.loaded_from_disk
    assert cache.get(tiny_spec(), 1) is None
    cache.save()                                     # rewrites cleanly
    assert RouteCache(str(path)).fingerprint == device_fingerprint()


def test_poisoned_cache_never_crashes_plan_build(tmp_path):
    path = tmp_path / "c.json"
    path.write_text("garbage")
    spec = tiny_spec(out_c=8)
    with pytest.warns(RuntimeWarning):
        plan = plan_conv(spec, autotune=AutotunePolicy(
            mode="cache", cache_path=str(path), **FAST))
    assert plan.routes == plan_conv(spec).routes     # heuristic fallback


# ---------------------------------------------------------------------------
# fallback ladder + warm-cache zero-measurement acceptance
# ---------------------------------------------------------------------------

def test_cache_mode_cold_is_heuristic_and_measures_nothing(tmp_path):
    spec = tiny_spec(in_c=8)
    before = at.measure_calls()
    plan = plan_conv(spec, autotune=AutotunePolicy(
        mode="cache", cache_path=str(tmp_path / "c.json"), **FAST))
    assert at.measure_calls() == before              # cold + cache-only
    assert plan.tuned
    assert plan.routes == plan_conv(spec).routes


def test_measure_mode_persists_then_warm_load_measures_zero(tmp_path):
    path = str(tmp_path / "c.json")
    spec = tiny_spec(in_c=8, out_c=8)
    policy = AutotunePolicy(mode="measure", cache_path=path, **FAST)

    before = at.measure_calls()
    plan1 = plan_conv(spec, autotune=policy)
    assert at.measure_calls() > before               # cold: measured
    raw = json.loads((tmp_path / "c.json").read_text())
    assert raw["schema"] == SCHEMA                   # file produced + valid
    assert raw["fingerprint"] == device_fingerprint()
    (ent,) = raw["entries"].values()
    assert "1" in ent["routes"]
    assert "measured_us" in ent["routes"]["1"]

    plan_cache_clear()                               # simulate a restart
    before = at.measure_calls()
    plan2 = plan_conv(spec, autotune=policy)
    assert at.measure_calls() == before              # warm: ZERO runs
    assert plan2.routes == plan1.routes
    assert plan2.tuned


def test_untuned_buckets_keep_heuristic_routes(tmp_path):
    spec = tiny_spec(kernel_hw=(5, 5), padding=((2, 1), (2, 1)))
    heur = plan_conv(spec)
    plan = plan_conv(spec, autotune=AutotunePolicy(
        mode="measure", cache_path=str(tmp_path / "c.json"), buckets=(1,),
        iters=1, warmup=0))
    for b in BATCH_BUCKETS[1:]:
        assert plan.route_for_batch(b) == heur.route_for_batch(b)


def test_min_gain_hysteresis(monkeypatch):
    spec = tiny_spec(in_c=16)
    plan = plan_conv(spec)
    heur = plan.route_for_batch(1)

    def fake_measure(plan_, route, x, packed, *, iters, warmup):
        # challenger 2% faster than the heuristic: inside min_gain=1.03
        t = 1.00e-3 if route == heur else 0.98e-3
        return Timing(t, t, iters)

    monkeypatch.setattr(at, "measure_route", fake_measure)
    winner, timings = measure_bucket(plan, 1, AutotunePolicy(**FAST))
    assert winner == heur                            # tie stays heuristic
    assert timings[route_label(heur)] == pytest.approx(1.00e-3)

    def fake_measure_big(plan_, route, x, packed, *, iters, warmup):
        t = 1.00e-3 if route == heur else 0.50e-3    # 2x: a real flip
        return Timing(t, t, iters)

    monkeypatch.setattr(at, "measure_route", fake_measure_big)
    winner, _ = measure_bucket(plan, 1, AutotunePolicy(**FAST))
    assert winner != heur


# ---------------------------------------------------------------------------
# tuned plans stay correct: fwd + VJP oracle parity
# ---------------------------------------------------------------------------

def test_autotuned_plan_oracle_parity():
    spec = tiny_spec(in_hw=(6, 6))
    plan = plan_conv(spec, autotune=AutotunePolicy(
        mode="measure", cache_path="", **FAST))      # memory-only
    x, k = random_case(0, 1, 6, 6, spec.in_c, spec.out_c, 3, 3)
    packed = plan.pack(k)
    want = oracle_transposed(x, k, strides=spec.strides,
                             padding=spec.padding)
    assert_close(plan.apply(x, packed), want)
    gx, gk = jax.grad(lambda a, w: plan.apply(a, w).sum(),
                      argnums=(0, 1))(x, packed)
    ox, ok = jax.grad(
        lambda a, w: oracle_transposed(a, w, strides=spec.strides,
                                       padding=spec.padding).sum(),
        argnums=(0, 1))(x, k)
    assert_close(gx, ox, TOL_GRAD)
    assert_close(gk, plan.pack(ok), TOL_GRAD)


@pytest.mark.parametrize("batch", [1, 3])
def test_forced_per_phase_route_parity(batch):
    spec = tiny_spec(in_hw=(8, 8))
    base = plan_conv(spec)
    plan = base.with_routes(tuple(
        Route(r.batch, "per_phase", None, fused_bwd=r.fused_bwd)
        for r in base.routes))
    x, k = random_case(1, batch, 8, 8, spec.in_c, spec.out_c, 3, 3)
    packed = plan.pack(k)
    want = oracle_transposed(x, k, strides=spec.strides,
                             padding=spec.padding)
    assert_close(plan.apply(x, packed), want)
    gx = jax.grad(lambda a: plan.apply(a, packed).sum())(x)
    ox = jax.grad(lambda a: oracle_transposed(
        a, k, strides=spec.strides, padding=spec.padding).sum())(x)
    assert_close(gx, ox, TOL_GRAD)


# ---------------------------------------------------------------------------
# serving: bucket-cost persistence through the same cache file
# ---------------------------------------------------------------------------

def test_batcher_bucket_costs_persist_and_skip_remeasure(tmp_path):
    from repro.serving.image_batcher import DynamicImageBatcher

    path = str(tmp_path / "c.json")
    serve = lambda x: x * 2.0                        # noqa: E731
    proto = np.zeros((3,), np.float32)

    cache = RouteCache(path)
    b1 = DynamicImageBatcher(serve, buckets=(1, 4), cache=cache,
                             cache_key="m")
    assert b1.warmup(proto) == (1, 4)                # cold: both timed
    assert set(b1.bucket_cost_s) == {1, 4}

    cache2 = RouteCache(path)                        # restarted server
    assert cache2.loaded_from_disk
    b2 = DynamicImageBatcher(serve, buckets=(1, 4), cache=cache2,
                             cache_key="m")
    assert set(b2.bucket_cost_s) == {1, 4}           # preloaded
    assert b2.warmup(proto) == ()                    # compiles, times none
    assert b2.bucket_cost_s == pytest.approx(b1.bucket_cost_s)
    assert b2.warmup(proto, force=True) == (1, 4)    # explicit re-measure


def test_batcher_foreign_cache_key_measures(tmp_path):
    from repro.serving.image_batcher import DynamicImageBatcher

    cache = RouteCache(str(tmp_path / "c.json"))
    cache.put_bucket_costs("other-model", {1: 1.0})
    b = DynamicImageBatcher(lambda x: x, buckets=(1,), cache=cache,
                            cache_key="mine")
    assert b.bucket_cost_s == {}                     # keys don't bleed
    assert b.warmup(np.zeros((2,), np.float32)) == (1,)


# ---------------------------------------------------------------------------
# model zoo threads the policy
# ---------------------------------------------------------------------------

def test_models_thread_policy_to_plans():
    from repro.models import gan, segnet, vae

    policy = AutotunePolicy(mode="cache", cache_path="", **FAST)
    g = gan.GANConfig("t", (gan.DeconvLayer(4, 8, 4, 3, 2),),
                      autotune=policy)
    s = dataclasses.replace(segnet.SEGNET_TINY, autotune=policy)
    v = dataclasses.replace(vae.VAE_TINY, autotune=policy)
    before = at.measure_calls()
    for plans in (gan.generator_plans(g), gan.discriminator_plans(g),
                  segnet.segnet_plans(s), vae.vae_plans(v)):
        assert plans and all(p.tuned for p in plans)
    assert at.measure_calls() == before              # cache-mode: zero runs
    assert not any(p.tuned for p in gan.generator_plans(
        gan.GANConfig("t2", g.layers)))              # None policy: untouched
