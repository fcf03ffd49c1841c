"""Plane-parallel conv bench: single-device vs shard_map halo exchange.

One conv plane spread across a device mesh (``core.spatial``): the plan's
``dev_tiles`` route shards H/W over 'sp_h'/'sp_w', each shard runs the
SAME superpack schedule on its slab, and boundaries arrive by one-hop
``ppermute`` halo exchange.  This bench times both executions of the
geometries the ISSUE names — the 385x385 dilated-context site and the
large transposed decoder — checks they agree with each other to float
round-off, and counts the collectives in the sharded jaxpr (halo traffic
must lower to ``ppermute`` only; an ``all_gather`` would mean the plane
was silently replicated).

On an accelerator the bench runs in-process on the real devices (a chip
belongs to one process: a JAX child of a JAX parent could not reach it),
and tilings that need more devices than the host has are skipped.  On the
CPU, multi-device meshes need ``--xla_force_host_platform_device_count``
set BEFORE jax initializes, and ``benchmarks.run`` has long since imported
jax — so there ``main()`` re-execs this module in a child process with the
flag forced and the child writes the JSON.  Run standalone:

    PYTHONPATH=src python -m benchmarks.spatial_bench --emit BENCH_spatial.json

Timing caveat (docs/BENCHMARKS.md): on a dev host the 8 "devices" are
threads of one CPU, so ``speedup`` measures shard_map + halo *overhead*,
not the paper's multi-chip scaling — CI gates structure and parity, not
the ratio.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_FLAG = "--xla_force_host_platform_device_count=8"

# site -> device tilings benched (full mode benches all, --quick the first)
BENCH_TILES = {
    "dilated_context_385": ((4, 1), (2, 2)),
    "decoder_96": ((2, 2), (4, 1)),
}


def _records(quick: bool) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from benchmarks.util import time_fn
    from repro.core import spatial
    from repro.core.plan import plan_conv
    from repro.launch.dryrun import CONVPLANE_SITES, convplane_spec
    from repro.launch.mesh import make_spatial_mesh

    iters, warmup = (3, 1) if quick else (5, 2)
    out = []
    for site, tilings in BENCH_TILES.items():
        geom = CONVPLANE_SITES[site]
        batch = 1 if quick else geom["batch"]
        for dev_tiles in tilings[:1] if quick else tilings:
            if dev_tiles[0] * dev_tiles[1] > jax.device_count():
                print(f"{site}@{dev_tiles[0]}x{dev_tiles[1]}: skipped "
                      f"({jax.device_count()} devices)", flush=True)
                continue
            spec1 = convplane_spec(site, (1, 1))
            specd = convplane_spec(site, dev_tiles)
            plan1, pland = plan_conv(spec1), plan_conv(specd)
            h, w = spec1.in_hw
            kx, kk = jax.random.split(jax.random.PRNGKey(0))
            x = jax.random.normal(kx, (batch, h, w, spec1.in_c), jnp.float32)
            pk = jax.random.normal(
                kk, (plan1.total_taps * spec1.in_c, spec1.out_c),
                jnp.float32) * 0.1

            f1 = jax.jit(lambda a, k: plan1.apply(a, k))
            y1 = jax.block_until_ready(f1(x, pk))
            single_us = time_fn(f1, x, pk, iters=iters, warmup=warmup) * 1e6

            mesh = make_spatial_mesh(*dev_tiles)
            fd = jax.jit(lambda a, k: pland.apply(a, k))
            with spatial.use_spatial_mesh(mesh):
                text = str(jax.make_jaxpr(lambda a, k: pland.apply(a, k))(
                    x, pk))
                yd = jax.block_until_ready(fd(x, pk))
                sharded_us = time_fn(fd, x, pk, iters=iters,
                                     warmup=warmup) * 1e6

            err = float(jnp.max(jnp.abs(yd - y1))
                        / (jnp.max(jnp.abs(y1)) + 1e-30))
            route = pland.route_for_batch(batch)
            rec = {
                "name": f"{site}@{dev_tiles[0]}x{dev_tiles[1]}",
                "site": site, "kind": geom["kind"],
                "in_hw": list(geom["in_hw"]), "in_c": geom["c"],
                "out_c": geom["n"], "kernel": list(geom["kernel"]),
                "strides": list(geom["strides"]),
                "dilation": list(geom["dilation"]), "batch": batch,
                "dev_tiles": list(dev_tiles),
                "route_path": route.path,
                "route_dev_tiles": (list(route.dev_tiles)
                                    if route.dev_tiles else None),
                "single_us": single_us, "sharded_us": sharded_us,
                "speedup": single_us / sharded_us,
                "max_rel_err": err,
                "ppermute": text.count("ppermute"),
                "all_gather": text.count("all_gather"),
            }
            out.append(rec)
            print(f"{rec['name']},{sharded_us:.1f},"
                  f"single={single_us:.1f}us x{rec['speedup']:.2f} "
                  f"err={err:.2e} pp={rec['ppermute']} "
                  f"ag={rec['all_gather']}", flush=True)
    return out


def child_main(quick: bool, json_path: str) -> None:
    import jax
    doc = {
        "schema": "huge2-bench-spatial/v1",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "quick": quick,
        "sites": _records(quick),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {json_path}", flush=True)


def main(quick: bool = False, json_path: str | None = "BENCH_spatial.json"):
    """Parent entry point: in-process on an accelerator, else re-exec under
    the forced-device-count flag."""
    import jax
    if jax.default_backend() != "cpu":
        child_main(quick, json_path or "")
        return
    env = dict(os.environ)
    if "xla_force_host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + _FLAG).strip()
    cmd = [sys.executable, "-m", "benchmarks.spatial_bench", "--emit",
           json_path or ""]
    if quick:
        cmd.append("--quick")
    subprocess.run(cmd, env=env, check=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--emit", default="BENCH_spatial.json")
    args = ap.parse_args()
    if "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        child_main(args.quick, args.emit)
    else:
        main(args.quick, args.emit or None)
