"""Serve semantic segmentation through the SLO-aware control plane: the
second image workload on the same admission/scheduling path as the DCGAN
generator.

Image requests arrive on an open loop (``--rate`` req/s; 0 = one burst)
with a priority class and an optional deadline; the control plane admits
(or rejects) them against the measured backlog, coalesces them into the
plan batch buckets (1/4/16/64) via its ``DynamicImageBatcher`` backend,
and sheds anything whose deadline passed before launch.  Each launch is
one jitted SegNet forward + argmax on a plan-time route — the whole model
is planned conv sites on superpacked weights, so serving never re-slices
a kernel.

The break-it-on-purpose path is runnable by hand: ``--inject-fault-at N``
kills the N-th launch mid-batch with a ``NodeFailure`` — the control
plane re-queues the launch's live requests and replays them, and the
driver proves zero drops/duplicates and bit-equal outputs against a
fault-free reference pass.  This is the CI fault-injection smoke.

    PYTHONPATH=src python examples/serve_segnet.py [--requests 32]
        [--rate 0] [--max-wait-ms 2] [--full]
        [--slo-ms 0] [--priority interactive] [--inject-fault-at 0]
        [--autotune off|cache|measure] [--route-cache PATH]

``--full`` serves the 64px/width-128 edge config; default is the tiny
config so the CI smoke step finishes in seconds.  ``--autotune`` switches
the plans to measured routes backed by the per-host route cache
(``--route-cache``), which also persists the batcher's bucket costs — a
restarted server re-measures nothing.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune as at
from repro.models import segnet
from repro.runtime.fault import FailureInjector
from repro.serving.control_plane import ControlPlane, ServeRequest
from repro.serving.metrics import format_stats
from repro.runtime.compile_cache import enable_compile_cache


def build_control_plane(serve_fn, proto, *, max_wait_ms, cache, cache_key,
                        fault_at=0):
    injector = FailureInjector((fault_at,)) if fault_at > 0 else None
    cp = ControlPlane(injector=injector)
    be = cp.register_image_model("segnet", serve_fn, proto,
                                 max_wait_ms=max_wait_ms, cache=cache,
                                 cache_key=cache_key)
    return cp, be


def drive(cp, payloads, *, rate, priority, slo_ms):
    gap = 1.0 / rate if rate > 0 else 0.0
    for i, x in enumerate(payloads):
        if gap:
            time.sleep(gap)
        cp.submit(ServeRequest(rid=i, model="segnet", payload=x,
                               priority=priority,
                               slo_ms=slo_ms if slo_ms > 0 else None))
        cp.pump()
    cp.run()                       # drain
    return cp


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="arrival rate in req/s (0 = submit all at once)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--full", action="store_true",
                    help="64px width-128 config instead of the tiny one")
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
    ap.add_argument("--wdtype", choices=("float32", "int8"),
                    default="float32",
                    help="weight storage dtype: 'int8' serves quantized "
                         "superpacks (~0.26x weight bytes) and asserts the "
                         "logit error vs an f32 twin under the documented "
                         "bound before serving")
    args = ap.parse_args()

    policy = None
    cache = None
    if args.autotune != "off":
        policy = at.AutotunePolicy(mode=args.autotune,
                                   cache_path=args.route_cache)
        cache = at.open_cache(args.route_cache)
    base = segnet.SEGNET if args.full else segnet.SEGNET_TINY
    cfg = dataclasses.replace(base, autotune=policy, wdtype=args.wdtype)

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    params, _ = segnet.segnet_init(key, cfg)
    plans = segnet.segnet_plans(cfg)
    jax.block_until_ready(params)
    print(f"model load: {cfg.name} (wdtype={cfg.wdtype}), "
          f"{len(plans)} planned conv sites "
          f"({sum(1 for p in plans if p.spec.kind == 'dilated')} dilated) "
          f"in {(time.perf_counter() - t0) * 1e3:.1f} ms")

    if args.wdtype == "int8":
        # quantized-serving gate: same init key through an f32 twin config,
        # logits compared on one random batch.  Documented bound: each of
        # the L conv layers contributes at most ~1/2 an int8 grid step of
        # relative weight error (0.5/127 ≈ 0.4%), and the ReLU cascade
        # compounds at worst additively — rel L∞ ≤ L/127 with ~3x measured
        # headroom on the zoo configs (see docs/BENCHMARKS.md).
        twin = dataclasses.replace(cfg, name=cfg.name + "-f32twin",
                                   wdtype="float32")
        params_f, _ = segnet.segnet_init(key, twin)
        xq = jax.random.uniform(jax.random.PRNGKey(7),
                                (4, cfg.in_hw, cfg.in_hw, cfg.in_c),
                                minval=-1.0, maxval=1.0)
        lq = segnet.segnet_apply(params, xq, cfg)
        lf = segnet.segnet_apply(params_f, xq, twin)
        rel = float(jnp.max(jnp.abs(lq - lf)) / jnp.max(jnp.abs(lf)))
        bound = len(plans) / 127.0
        qb = sum(w.nbytes() for k, w in params.items() if k.startswith("w"))
        fb = sum(int(w.nbytes) for k, w in params_f.items()
                 if k.startswith("w"))
        print(f"int8 weights: {qb / fb:.2f}x f32 bytes "
              f"({qb} vs {fb}); logit rel err {rel:.4f} "
              f"(bound {bound:.4f} = {len(plans)} layers / 127)")
        assert rel <= bound, (rel, bound)
        del params_f

    def serve_fn(x):
        # logits -> per-pixel class ids; argmax rides inside the jit
        return jnp.argmax(segnet.segnet_apply(params, x, cfg), axis=-1)

    cache_key = f"serve_segnet/{cfg.name}/{cfg.wdtype}"
    proto = np.zeros((cfg.in_hw, cfg.in_hw, cfg.in_c), np.float32)
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
    payloads = [rng.uniform(-1, 1, (cfg.in_hw, cfg.in_hw,
                                    cfg.in_c)).astype(np.float32)
                for _ in range(args.requests)]
    drive(cp, payloads, rate=args.rate, priority=args.priority,
          slo_ms=args.slo_ms)

    st = cp.stats()
    cls = st["per_class"][args.priority]
    print(f"served {st['served']} / rejected {st['rejected']} / "
          f"shed {st['shed']} of {st['submitted']} submitted "
          f"({st['per_model']['segnet']['launches']} launches, pad fraction "
          f"{st['per_model']['segnet']['pad_fraction']:.2f}, goodput "
          f"{st['goodput_under_slo']:.2f})")
    print(format_stats(cls, unit="img"))
    assert st["submitted"] == st["served"] + st["rejected"] + st["shed"]
    rids = [r.rid for r in cp.done]
    assert len(rids) == len(set(rids)), "a request was answered twice"

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
        seg = cp.done[-1].out
        print(f"segmentation map: {seg.shape} int{seg.dtype.itemsize * 8}, "
              f"classes used {np.unique(seg).size}/{cfg.num_classes}")
        assert seg.shape == (cfg.out_hw, cfg.out_hw)
        assert (seg >= 0).all() and (seg < cfg.num_classes).all()


if __name__ == "__main__":
    main()
