"""Latent-diffusion U-Net on the engine: every conv kind in one model.

Builds the diffusion U-Net (``models/unet.py``) — strided downsamples,
dilated bottleneck, transposed upsamples, skip-concat fuse convs — with
every site planned once at load and all weights in tap-major superpacks.
The k=4/s=2 upsample sites plan the **sub-pixel route**
(``Route.path='pixel_shuffle'``): the transposed conv is rewritten at plan
time into one dense ``dot_general`` plus a depth-to-space reshape.

Runs one denoising-score-matching training step (loss + grads through the
packed VJPs, including the skip-concat cotangent split) and an Euler
denoising loop, printing per-step latency.

    PYTHONPATH=src python examples/denoise_unet.py [--steps N] [--full]

``--full`` uses the 32px edge config; default is the tiny config so the
CI smoke step finishes in seconds.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.models import unet
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8,
                    help="Euler denoising steps (CI smoke uses 2)")
    ap.add_argument("--full", action="store_true",
                    help="32px base-32 config instead of the tiny one")
    args = ap.parse_args()
    cfg = unet.UNET if args.full else unet.UNET_TINY

    t0 = time.perf_counter()
    params, _ = unet.unet_init(jax.random.PRNGKey(0), cfg)
    t_build = time.perf_counter() - t0

    # one model, every route kind: the plan inspection the paper's
    # "untangled" claim rests on — no site falls back to lax conv
    routes = unet.unet_route_summary(cfg)
    kinds = {k for k, _ in routes.values()}
    paths = {p for _, p in routes.values()}
    assert kinds == {"conv", "dilated", "transposed"}, kinds
    assert "pixel_shuffle" in paths, paths
    for site, (kind, path) in routes.items():
        print(f"  {site:6s} {kind:10s} -> {path}")
    ps = [s for s, (_, p) in routes.items() if p == "pixel_shuffle"]
    print(f"{len(routes)} sites planned in {t_build:.2f}s; "
          f"sub-pixel route at {', '.join(ps)}")

    # one DSM training step through the packed VJPs
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (2, cfg.image_hw, cfg.image_hw, cfg.in_c),
                          jnp.float32)
    loss, grads = jax.value_and_grad(unet.unet_loss)(params, x, key, cfg)
    n_zero = sum(int(not jnp.any(g)) for g in jax.tree.leaves(grads))
    assert jnp.isfinite(loss) and n_zero == 0, (loss, n_zero)
    print(f"DSM loss {float(loss):.4f}; all "
          f"{len(jax.tree.leaves(grads))} grad leaves nonzero ✓")

    # Euler denoising loop: args.steps sequential U-Net calls
    loop = jax.jit(lambda xt: unet.denoise_loop(params, xt, cfg, args.steps))
    xt = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)
    out = jax.block_until_ready(loop(xt))
    t0 = time.perf_counter()
    out = jax.block_until_ready(loop(xt))
    dt = time.perf_counter() - t0
    assert out.shape == x.shape and bool(jnp.all(jnp.isfinite(out)))
    print(f"denoised {out.shape} in {args.steps} steps "
          f"({dt / args.steps * 1e3:.1f} ms/step steady-state) ✓")


if __name__ == "__main__":
    main()
