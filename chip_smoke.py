#!/usr/bin/env python3
"""Bring-up check: the DCGAN serving path on a TPU, Pallas kernels compiled.

One process, one chip (no arguments):

1. requires a TPU — any other platform exits non-zero before any phase;
2. builds the paper's Table-1 DCGAN (4x4x1024 -> 64x64x3) with
   ``backend="pallas"`` and asserts that every generator and discriminator
   conv site routes ``pallas`` at every batch bucket (route table printed);
3. registers the generator with a ``ControlPlane``, warms every bucket
   (compile time printed apart from serving), serves bursts that cover
   more than one bucket, and asserts conservation (submitted = served +
   rejected + shed, no request answered twice) and finite images;
4. checks the compiled generator and discriminator HLO for one
   ``tpu_custom_call`` per conv site;
5. compares the served images, and one discriminator forward on them,
   against a plain f32 ``lax.conv_general_dilated`` reference at
   ``precision=HIGHEST`` built from the unpacked HWIO kernels: each
   ``max|y-ref| / max|ref|`` must be <= 2e-2.

``--four-chips`` runs only the plane-parallel phase: ``dilated_context_385``
at ``dev_tiles=(2, 2)`` over ``make_spatial_mesh(2, 2)``, against the same
plan on one of those chips.

Weights are random, drawn from ``--seed``.  Any failed phase raises; the
last line of standard output is the JSON verdict with the device JAX
reports.  The numbers printed are bring-up checks, not speed measurements.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

KERNEL = 'custom_call_target="tpu_custom_call"'
REL_ERR_MAX = 2e-2


def require_tpu(n_chips: int):
    """The device list, or exit non-zero naming what JAX found instead."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind}). No phase "
                 f"runs on another platform or in the Pallas interpreter.")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips; JAX found "
                 f"{len(devs)}")
    print(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
          f"device_count={len(devs)}", flush=True)
    return devs


def rel_err(y, ref) -> float:
    import numpy as np
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


# ---------------------------------------------------------------------------
# the plain f32 reference (no plan code: unpacked HWIO kernels + lax convs)
# ---------------------------------------------------------------------------

def reference_generator(p_hwio, z, cfg):
    import jax
    import jax.numpy as jnp
    from repro.models.gan import deconv_padding
    hi = jax.lax.Precision.HIGHEST
    l0 = cfg.layers[0]
    x = jnp.dot(z, p_hwio["proj"], precision=hi)
    x = jax.nn.relu(x.reshape(z.shape[0], l0.in_hw, l0.in_hw, l0.in_c))
    for i, l in enumerate(cfg.layers):
        x = jax.lax.conv_general_dilated(
            x, p_hwio[f"dc{i}"], window_strides=(1, 1),
            padding=deconv_padding(l.kernel, l.stride),
            lhs_dilation=(l.stride, l.stride),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        x = x + p_hwio[f"b{i}"]
        x = jnp.tanh(x) if i == len(cfg.layers) - 1 else jax.nn.relu(x)
    return x


def reference_discriminator(p_hwio, x, cfg):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    for i, l in enumerate(reversed(cfg.layers)):
        k = l.kernel
        x = jax.lax.conv_general_dilated(
            x, p_hwio[f"c{i}"], window_strides=(l.stride, l.stride),
            padding=((k // 2, (k - 1) // 2), (k // 2, (k - 1) // 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
        x = jax.nn.leaky_relu(x, 0.2)
    return jnp.dot(x.reshape(x.shape[0], -1), p_hwio["head"], precision=hi)


# ---------------------------------------------------------------------------
# one chip: the DCGAN serving path
# ---------------------------------------------------------------------------

def print_routes(name, plans):
    from repro.core.plan import BATCH_BUCKETS
    for i, plan in enumerate(plans):
        sp = plan.spec
        cells = []
        for b in BATCH_BUCKETS:
            r = plan.route_for_batch(b)
            cells.append(f"B{b}:{r.path}{list(r.tiles or ())}"
                         + (f"sp{list(r.sp_tiles)}" if r.sp_tiles else ""))
        print(f"  {name}{i} {sp.kind} {sp.in_hw[0]}x{sp.in_hw[1]}x{sp.in_c}"
              f"->{sp.out_c} k{sp.kernel_hw[0]} s{sp.strides[0]}: "
              + " ".join(cells), flush=True)


def assert_all_pallas(plans):
    from repro.core.plan import BATCH_BUCKETS
    for plan in plans:
        for b in BATCH_BUCKETS:
            route = plan.route_for_batch(b)
            assert route.path == "pallas", (plan.spec, b, route)


def count_op(hlo: str, op: str) -> int:
    """HLO instructions of ``op``, synchronous or async (``op-start``)."""
    return len(re.findall(rf"\b{op}(-start)?\(", hlo))


def count_kernels(fn, *args) -> int:
    import jax
    return jax.jit(fn).lower(*args).compile().as_text().count(KERNEL)


def serve_bursts(cp, payloads, waves):
    """Submit ``payloads`` in waves (each drained before the next), so the
    launches cover more than one bucket; returns the wall seconds."""
    from repro.serving.control_plane import ServeRequest
    t0, rid = time.perf_counter(), 0
    for n in waves:
        for _ in range(n):
            cp.submit(ServeRequest(rid=rid, model="dcgan",
                                   payload=payloads[rid]))
            rid += 1
        cp.run()
    return time.perf_counter() - t0


def smoke_one_chip(cfg, seed: int, waves=(1, 3, 13, 29)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import gan
    from repro.serving.control_plane import ControlPlane

    gen_plans = gan.generator_plans(cfg)
    disc_plans = gan.discriminator_plans(cfg)
    print("routes (backend='pallas', autotune=None):", flush=True)
    print_routes("gen", gen_plans)
    print_routes("disc", disc_plans)
    assert_all_pallas(gen_plans + disc_plans)
    print(f"every site routes pallas at every bucket: "
          f"{len(gen_plans)} generator + {len(disc_plans)} discriminator",
          flush=True)

    kg, kd = jax.random.split(jax.random.PRNGKey(seed))
    gp, _ = gan.generator_init(kg, cfg)
    dp, _ = gan.discriminator_init(kd, cfg)
    serve_fn = lambda z: gan.generator_apply(gp, z, cfg)  # noqa: E731
    cp = ControlPlane()
    be = cp.register_image_model("dcgan", serve_fn,
                                 np.zeros((cfg.z_dim,), np.float32),
                                 max_wait_ms=0.0)
    t0 = time.perf_counter()
    be.warmup()
    t_warm = time.perf_counter() - t0
    costs = {b: round(c * 1e3, 3) for b, c in be.batcher.bucket_cost_s.items()}
    print(f"compile: {len(be.batcher.buckets)} bucket executables compiled "
          f"and warmed in {t_warm:.2f} s (buckets {be.batcher.buckets}; "
          f"measured launch ms {costs})", flush=True)

    rng = np.random.default_rng(seed)
    payloads = [rng.standard_normal(cfg.z_dim).astype(np.float32)
                for _ in range(sum(waves))]
    t_serve = serve_bursts(cp, payloads, waves)
    st = cp.stats()
    buckets = [b for b, _ in be.batcher.launches]
    print(f"serve: {st['served']} served / {st['rejected']} rejected / "
          f"{st['shed']} shed of {st['submitted']} submitted in "
          f"{t_serve * 1e3:.1f} ms steady state; launch buckets {buckets}",
          flush=True)
    assert st["submitted"] == sum(waves)
    assert st["submitted"] == st["served"] + st["rejected"] + st["shed"]
    rids = [r.rid for r in cp.done]
    assert len(rids) == len(set(rids)), "a request was answered twice"
    assert len(set(buckets)) > 1, f"one bucket only: {buckets}"
    out = cp.results()
    assert all(np.isfinite(y).all() for y in out.values())
    l_last = cfg.layers[-1]
    img_hw = l_last.in_hw * l_last.stride
    assert all(y.shape == (img_hw, img_hw, l_last.out_c)
               for y in out.values())

    z0 = jnp.zeros((1, cfg.z_dim), jnp.float32)
    x0 = jnp.zeros((1, img_hw, img_hw, l_last.out_c), jnp.float32)
    n_gen = count_kernels(serve_fn, z0)
    n_disc = count_kernels(lambda x: gan.discriminator_apply(dp, x, cfg), x0)
    print(f"hlo: generator {n_gen} tpu_custom_call for {len(gen_plans)} "
          f"sites; discriminator {n_disc} for {len(disc_plans)} sites",
          flush=True)
    assert n_gen == len(gen_plans) and n_disc == len(disc_plans)

    served = sorted(out)
    z = jnp.asarray(np.stack([payloads[rid] for rid in served]))
    imgs = jnp.asarray(np.stack([out[rid] for rid in served]))
    ref_imgs = reference_generator(gan.generator_unpack(gp, cfg), z, cfg)
    d = jax.jit(lambda x: gan.discriminator_apply(dp, x, cfg))(imgs)
    ref_d = reference_discriminator(gan.discriminator_unpack(dp, cfg), imgs,
                                    cfg)
    e_gen, e_disc = rel_err(imgs, ref_imgs), rel_err(d, ref_d)
    print(f"reference (f32 lax convs, precision=HIGHEST): generator "
          f"max|y-ref|/max|ref| = {e_gen:.3e} over {len(served)} images; "
          f"discriminator = {e_disc:.3e}; kernel matmul precision: "
          f"{jax.config.jax_default_matmul_precision or 'platform default'}"
          f" (f32 operands, f32 accumulation)", flush=True)
    assert e_gen <= REL_ERR_MAX and e_disc <= REL_ERR_MAX, (e_gen, e_disc)


# ---------------------------------------------------------------------------
# four chips: plane-parallel dilated_context_385 on a 2x2 spatial mesh
# ---------------------------------------------------------------------------

def smoke_four_chips(spec, batch: int, seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import spatial
    from repro.core.plan import plan_conv
    from repro.launch.mesh import make_spatial_mesh

    plan = plan_conv(spec)
    route = plan.route_for_batch(batch)
    sp = spatial.spatial_plan(spec)
    local = plan_conv(sp.local_spec).route_for_batch(batch)
    print(f"site {spec.kind} {spec.in_hw}x{spec.in_c}->{spec.out_c} "
          f"d{spec.dilation} B={batch}: route {route.path} "
          f"tiles={route.tiles} sp_tiles={route.sp_tiles} "
          f"dev_tiles={route.dev_tiles}; per-shard route {local.path} "
          f"tiles={local.tiles} sp_tiles={local.sp_tiles}", flush=True)
    assert route.path == "pallas" and local.path == "pallas"
    assert route.dev_tiles == spec.spatial, route

    kx, kk = jax.random.split(jax.random.PRNGKey(seed))
    h, w = spec.in_hw
    x = jax.random.normal(kx, (batch, h, w, spec.in_c), np.float32)
    pk = jax.random.normal(kk, (plan.total_taps * spec.in_c, spec.out_c),
                           np.float32) * 0.1

    one = jax.devices()[0]
    f1 = jax.jit(lambda a, k: plan.apply(a, k))
    x1, pk1 = jax.device_put(x, one), jax.device_put(pk, one)
    t0 = time.perf_counter()
    c1 = f1.lower(x1, pk1).compile()
    t_c1 = time.perf_counter() - t0
    y1 = jax.block_until_ready(c1(x1, pk1))
    assert c1.as_text().count(KERNEL) >= 1

    # the plane-parallel launch (halo exchange + per-shard kernels), whose
    # device-aligned output stays one block per chip
    mesh = make_spatial_mesh(*spec.spatial)
    fp = jax.jit(lambda a, k: spatial.spatial_apply_padded(sp, a, k, mesh))
    t0 = time.perf_counter()
    cp = fp.lower(x, pk).compile()
    t_cp = time.perf_counter() - t0
    hlo = cp.as_text()
    yp = jax.block_until_ready(cp(x, pk))
    n_perm, n_gather = count_op(hlo, "collective-permute"), count_op(
        hlo, "all-gather")
    shards = yp.addressable_shards
    quarter = (yp.shape[1] // 2) * (yp.shape[2] // 2)
    print(f"compile: single-chip {t_c1:.2f} s, 2x2 mesh {t_cp:.2f} s; launch "
          f"hlo: {hlo.count(KERNEL)} tpu_custom_call, {n_perm} "
          f"collective-permute, {n_gather} all-gather", flush=True)
    print(f"placement: {yp.sharding.spec} over {dict(mesh.shape)}; shards "
          + ", ".join(f"dev{s.device.id}:{tuple(s.data.shape)}"
                      for s in shards), flush=True)
    assert hlo.count(KERNEL) >= 1
    assert n_perm >= 1 and n_gather == 0, (n_perm, n_gather)
    assert len({s.device.id for s in shards}) == 4
    assert all(s.data.shape[1] * s.data.shape[2] == quarter for s in shards)
    assert len({(s.index[1].start, s.index[2].start) for s in shards}) == 4
    oh, ow = plan.out_hw
    err = rel_err(np.asarray(yp)[:, :oh, :ow], y1)

    # the user's entry point: plan.apply under the bound mesh, exact extent
    with spatial.use_spatial_mesh(mesh):
        fd = jax.jit(lambda a, k: plan.apply(a, k))
        cd = fd.lower(x, pk).compile()
    yd = jax.block_until_ready(cd(x, pk))
    err_d = rel_err(yd, y1)
    print(f"2x2 vs single chip: max|y-ref|/max|ref| = {err:.3e} (launch "
          f"output {tuple(yp.shape)}), {err_d:.3e} through plan.apply "
          f"(output {tuple(yd.shape)}, "
          f"{count_op(cd.as_text(), 'all-gather')} all-gather to slice "
          f"{yp.shape[1]}->{oh} rows)", flush=True)
    assert err <= REL_ERR_MAX and err_d <= REL_ERR_MAX, (err, err_d)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the plane-parallel phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)

    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        from repro.launch.dryrun import CONVPLANE_SITES, convplane_spec
        spec = dataclasses.replace(
            convplane_spec("dilated_context_385", (2, 2)), backend="pallas")
        smoke_four_chips(spec, CONVPLANE_SITES["dilated_context_385"]["batch"],
                         args.seed)
    else:
        from repro.models import gan
        smoke_one_chip(dataclasses.replace(gan.DCGAN, backend="pallas"),
                       args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
