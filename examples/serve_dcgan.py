"""End-to-end serving driver (the paper's kind: an *inference engine*):
serve the DCGAN generator through the SLO-aware control plane.

Latent requests arrive on an open loop (``--rate`` req/s; 0 = one burst)
with a priority class and an optional deadline; the control plane admits
(or rejects) them against the measured backlog, coalesces them into the
plan batch buckets (1/4/16/64 — the sizes every ``ConvPlan`` routed at
build time) via its ``DynamicImageBatcher`` backend, and sheds anything
whose deadline passed before launch.  Model load builds every conv plan
and packs the weights ONCE; the server then only ever executes plan-time
routes.

The break-it-on-purpose path is runnable by hand: ``--inject-fault-at N``
kills the N-th launch mid-batch with a ``NodeFailure`` — the control
plane re-queues the launch's live requests and replays them, and the
driver proves zero drops/duplicates and bit-equal outputs against a
fault-free reference pass.

With ``--autotune cache|measure`` the plans use measured routes from the
per-host route cache (``--route-cache PATH``, default
``$HUGE2_ROUTE_CACHE`` or ``~/.cache/huge2/route_cache.json``); the same
cache persists the backend's measured bucket costs, so a restarted server
skips both the route microbenchmarks and the bucket cost measurements.

    PYTHONPATH=src python examples/serve_dcgan.py [--requests 64]
        [--rate 0] [--max-wait-ms 2] [--backend xla] [--small]
        [--slo-ms 0] [--priority interactive] [--inject-fault-at 0]
        [--autotune off|cache|measure] [--route-cache PATH]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import autotune as at
from repro.models import gan
from repro.runtime.fault import FailureInjector
from repro.serving.control_plane import ControlPlane, ServeRequest
from repro.serving.metrics import format_stats
from repro.runtime.compile_cache import enable_compile_cache

SMALL_LAYERS = (
    gan.DeconvLayer(4, 128, 64, 5, 2),
    gan.DeconvLayer(8, 64, 32, 5, 2),
    gan.DeconvLayer(16, 32, 3, 5, 2),
)


def build_control_plane(serve_fn, proto, *, max_wait_ms, cache, cache_key,
                        fault_at=0):
    injector = FailureInjector((fault_at,)) if fault_at > 0 else None
    cp = ControlPlane(injector=injector)
    be = cp.register_image_model("dcgan", serve_fn, proto,
                                 max_wait_ms=max_wait_ms, cache=cache,
                                 cache_key=cache_key)
    return cp, be


def drive(cp, payloads, *, rate, priority, slo_ms):
    gap = 1.0 / rate if rate > 0 else 0.0
    for i, z in enumerate(payloads):
        if gap:
            time.sleep(gap)
        cp.submit(ServeRequest(rid=i, model="dcgan", payload=z,
                               priority=priority,
                               slo_ms=slo_ms if slo_ms > 0 else None))
        cp.pump()
    cp.run()                       # drain
    return cp


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="arrival rate in req/s (0 = submit all at once)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--backend", choices=("xla", "pallas"), default="xla")
    ap.add_argument("--small", action="store_true",
                    help="reduced 32px generator (CI smoke)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request SLO in ms (0 = no deadline); "
                         "blown backlogs reject at admission, expired "
                         "requests shed before launch")
    ap.add_argument("--priority", choices=("interactive", "batch"),
                    default="interactive")
    ap.add_argument("--inject-fault-at", type=int, default=0,
                    help="kill the N-th launch mid-batch with a "
                         "NodeFailure (0 = off) and prove replay")
    ap.add_argument("--autotune", choices=("off", "cache", "measure"),
                    default="off",
                    help="measured routes: 'cache' = use cached winners only,"
                         " 'measure' = microbenchmark on cache miss")
    ap.add_argument("--route-cache", default=None,
                    help="route/bucket-cost cache path (default "
                         "$HUGE2_ROUTE_CACHE or ~/.cache/huge2)")
    args = ap.parse_args()

    policy = None
    cache = None
    if args.autotune != "off":
        policy = at.AutotunePolicy(mode=args.autotune,
                                   cache_path=args.route_cache)
        cache = at.open_cache(args.route_cache)
    layers = SMALL_LAYERS if args.small else gan.DCGAN_LAYERS
    cfg = gan.GANConfig("dcgan", layers, backend=args.backend,
                        autotune=policy)
    key = jax.random.PRNGKey(0)
    # model load: build every conv plan + pack weights ONCE, serve forever
    t_load = time.perf_counter()
    plans = gan.generator_plans(cfg)
    params, _ = gan.generator_init(key, cfg)
    jax.block_until_ready(params)
    t_load = time.perf_counter() - t_load
    print(f"model load: {len(plans)} conv plans built + weights packed "
          f"in {t_load * 1e3:.1f} ms "
          f"(plan build {sum(p.build_ms for p in plans):.2f} ms)")

    serve_fn = lambda z: gan.generator_apply(params, z, cfg)  # noqa: E731
    proto = np.zeros((cfg.z_dim,), np.float32)
    cache_key = f"serve_dcgan/{cfg.name}{'-small' if args.small else ''}"
    cp, be = build_control_plane(serve_fn, proto,
                                 max_wait_ms=args.max_wait_ms, cache=cache,
                                 cache_key=cache_key,
                                 fault_at=args.inject_fault_at)
    t0 = time.perf_counter()
    timed = be.warmup()            # compile every bucket up front
    print(f"warmup: {len(be.batcher.buckets)} bucket executables compiled "
          f"in {time.perf_counter() - t0:.2f} s "
          f"(buckets {be.batcher.buckets}, "
          f"{len(timed)} timed / {len(be.batcher.buckets) - len(timed)} "
          f"from cache)")

    rng = np.random.default_rng(0)
    payloads = [rng.standard_normal(cfg.z_dim).astype(np.float32)
                for _ in range(args.requests)]
    drive(cp, payloads, rate=args.rate, priority=args.priority,
          slo_ms=args.slo_ms)

    st = cp.stats()
    cls = st["per_class"][args.priority]
    print(f"served {st['served']} / rejected {st['rejected']} / "
          f"shed {st['shed']} of {st['submitted']} submitted "
          f"({st['per_model']['dcgan']['launches']} launches, pad fraction "
          f"{st['per_model']['dcgan']['pad_fraction']:.2f}, goodput "
          f"{st['goodput_under_slo']:.2f})")
    print(format_stats(cls, unit="img"))
    assert st["submitted"] == st["served"] + st["rejected"] + st["shed"]
    rids = [r.rid for r in cp.done]
    assert len(rids) == len(set(rids)), "a request was answered twice"
    assert all(np.isfinite(r.out).all() for r in cp.done)

    if args.inject_fault_at > 0:
        assert st["faults"]["events"] >= 1, "fault never fired"
        assert st["replayed_requests"] >= 1, "no request was replayed"
        if args.rate == 0:
            # fault-free reference pass on the same burst + measured costs:
            # launch grouping is deterministic, so replayed responses must
            # be bit-equal (replay restores the exact pre-launch queue)
            ref, ref_be = build_control_plane(
                serve_fn, proto, max_wait_ms=args.max_wait_ms, cache=cache,
                cache_key=cache_key)
            ref_be.batcher.bucket_cost_s = dict(be.batcher.bucket_cost_s)
            drive(ref, payloads, rate=0.0, priority=args.priority,
                  slo_ms=0.0)
            got, want = cp.results(), ref.results()
            assert set(got) <= set(want), "faulted run served unknown rids"
            if args.slo_ms <= 0:
                assert sorted(got) == sorted(want), "served sets differ"
            assert all(np.array_equal(got[rid], want[rid]) for rid in got)
            print(f"fault at launch {args.inject_fault_at}: "
                  f"{st['faults']['records'][0]['live']} live requests "
                  f"re-queued + replayed; zero dropped, zero duplicated, "
                  f"outputs bit-equal to the fault-free pass ✓")
        else:
            print(f"fault at launch {args.inject_fault_at}: "
                  f"{st['faults']['records'][0]['live']} live requests "
                  f"re-queued + replayed; zero dropped, zero duplicated ✓ "
                  f"(bit-equal reference pass needs --rate 0: open-loop "
                  f"arrival timing changes the launch grouping)")
    if cp.done:
        imgs = cp.done[-1].out
        print(f"output image shape: {imgs.shape} "
              f"({'32x32x3 reduced' if args.small else '64x64x3 from Table 1'})")


if __name__ == "__main__":
    main()
