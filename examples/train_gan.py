"""Train a (reduced) DCGAN for a few hundred steps through the HUGE2 engine
— every forward *and backward* convolution runs the paper's decomposition /
untangling formulation (custom VJPs, §3.2.3).

    PYTHONPATH=src python examples/train_gan.py [--steps 200]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import gan
from repro.models.gan import DeconvLayer
from repro.train.data import GANPipeline
from repro.runtime.compile_cache import enable_compile_cache

# a reduced DCGAN (same family, CIFAR-scale 32x32 output) that trains in
# minutes on one CPU core
SMALL_LAYERS = (
    DeconvLayer(4, 128, 64, 5, 2),
    DeconvLayer(8, 64, 32, 5, 2),
    DeconvLayer(16, 32, 3, 5, 2),
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-4)
    args = ap.parse_args()

    cfg = gan.GANConfig("dcgan-small", SMALL_LAYERS)
    key = jax.random.PRNGKey(0)
    kg, kd = jax.random.split(key)
    # load-time planning: generator weights are packed into the plans'
    # GEMM-ready layout; fwd AND bwd run on packed buffers from here on.
    g_plans = gan.generator_plans(cfg)
    d_plans = gan.discriminator_plans(cfg)
    gp, _ = gan.generator_init(kg, cfg)
    dp, _ = gan.discriminator_init(kd, cfg)
    print(f"planned {len(g_plans)} deconv + {len(d_plans)} conv sites "
          f"at model load "
          f"({sum(p.build_ms for p in g_plans + d_plans):.2f} ms plan build)")
    pipe = GANPipeline(cfg, args.batch, image_hw=32)

    @jax.jit
    def step(gp, dp, z, real):
        def d_loss_fn(dp):
            return gan.gan_losses(gp, dp, z, real, cfg)[1]

        def g_loss_fn(gp):
            return gan.gan_losses(gp, dp, z, real, cfg)[0]

        d_loss, d_grad = jax.value_and_grad(d_loss_fn)(dp)
        g_loss, g_grad = jax.value_and_grad(g_loss_fn)(gp)
        dp2 = jax.tree.map(lambda p, g: p - args.lr * g, dp, d_grad)
        gp2 = jax.tree.map(lambda p, g: p - args.lr * g, gp, g_grad)
        return gp2, dp2, g_loss, d_loss

    t0 = time.time()
    g_hist, d_hist = [], []
    for s in range(args.steps):
        b = pipe.batch_at(s)
        gp, dp, gl, dl = step(gp, dp, jnp.asarray(b["z"]),
                              jnp.asarray(b["real"]))
        g_hist.append(float(gl))
        d_hist.append(float(dl))
        if s % 25 == 0:
            print(f"step {s:4d}  g_loss {gl:.4f}  d_loss {dl:.4f}")
    dt = time.time() - t0
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step)")
    print(f"d_loss {d_hist[0]:.4f} -> {d_hist[-1]:.4f} "
          f"(discriminator learning: {'yes' if d_hist[-1] < d_hist[0] else 'check'})")
    img = gan.generator_apply(gp, jnp.asarray(pipe.batch_at(0)["z"]), cfg)
    assert np.isfinite(np.asarray(img)).all()
    print(f"sample generation OK: {img.shape}")


if __name__ == "__main__":
    main()
