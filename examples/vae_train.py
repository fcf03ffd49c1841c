"""Train the convolutional VAE through the HUGE² engine — encoder strided
convs AND decoder transposed convs run the planned/packed formulation in
both directions (forward single-launch routes, §3.2.3 custom VJPs on the
superpacked weights).

    PYTHONPATH=src python examples/vae_train.py [--steps 100] [--full]

``--full`` trains the 32px width-(64,128) config; default is the tiny
16px config so the CI one-step smoke finishes in seconds.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import vae
from repro.runtime.compile_cache import enable_compile_cache


def batch_at(cfg, batch: int, step: int, seed: int = 0) -> np.ndarray:
    """Synthetic smooth images in [-1, 1] (low-frequency mixtures, so the
    ELBO has structure to learn), deterministic by (seed, step)."""
    rng = np.random.default_rng((seed, step))
    hw = cfg.image_hw
    yy, xx = np.mgrid[0:hw, 0:hw] / hw
    freq = rng.uniform(1.0, 4.0, (batch, cfg.in_c, 2, 1, 1))
    phase = rng.uniform(0, 2 * np.pi, (batch, cfg.in_c, 2, 1, 1))
    img = np.sin(2 * np.pi * freq[:, :, 0] * yy + phase[:, :, 0]) \
        * np.sin(2 * np.pi * freq[:, :, 1] * xx + phase[:, :, 1])
    return np.moveaxis(img, 1, -1).astype(np.float32)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="32px width-(64,128) config instead of the tiny one")
    args = ap.parse_args()
    cfg = vae.VAE if args.full else vae.VAE_TINY

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    params, _ = vae.vae_init(key, cfg)
    plans = vae.vae_plans(cfg)
    jax.block_until_ready(params)
    print(f"[load] {cfg.name}: {len(plans)} planned conv sites "
          f"({sum(1 for p in plans if p.spec.kind == 'transposed')} "
          f"transposed in the decoder), "
          f"plan build {sum(p.build_ms for p in plans):.2f} ms, "
          f"init total {(time.perf_counter() - t0) * 1e3:.1f} ms")
    print(f"[load] paths: {[p.path for p in plans]}")

    @jax.jit
    def step(p, x, k):
        loss, grads = jax.value_and_grad(
            lambda p: vae.elbo_loss(p, x, k, cfg))(p)
        p = jax.tree.map(lambda a, g: a - args.lr * g, p, grads)
        return p, loss

    # fixed-eval comparison: same batch, same reparameterization key before
    # and after training, so the improvement check measures the params only
    x0 = jnp.asarray(batch_at(cfg, args.batch, 0))
    eval_loss = jax.jit(lambda p: vae.elbo_loss(p, x0, jax.random.PRNGKey(1),
                                                cfg))
    before = float(eval_loss(params))
    losses = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        key, sub = jax.random.split(key)
        x = jnp.asarray(batch_at(cfg, args.batch, i))
        params, loss = step(params, x, sub)
        losses.append(float(loss))
        if i % 20 == 0:
            print(f"[train] step {i:4d}: -ELBO {losses[-1]:.2f}")
    dt = time.perf_counter() - t0
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({dt / max(1, args.steps) * 1e3:.0f} ms/step)")

    assert np.isfinite(losses).all()
    # one step must already move the ELBO; longer runs must keep improving
    final = float(eval_loss(params))
    assert final < before, (final, before)
    print(f"[train] -ELBO {before:.2f} -> {final:.2f} (fixed eval batch; "
          f"packed VJPs through encoder AND decoder)")
    imgs = vae.sample(params, jax.random.PRNGKey(2), cfg, n=4)
    assert np.isfinite(np.asarray(imgs)).all()
    print(f"[sample] prior draws decoded: {tuple(imgs.shape)}")


if __name__ == "__main__":
    main()
