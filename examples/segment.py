"""Semantic segmentation on the engine: the paper's dilated-conv scenario
end-to-end.

Builds the DilatedNet-style SegNet (strided front-end + atrous context
module, ``models/segnet.py``), with every conv site planned once at load
and all weights held in the tap-major (R·S·C, N) superpack.  Runs one
jitted inference pass and one training step (the §3.2.3 custom VJPs on the
packed layout), printing plan-build cost and steady-state latency.

    PYTHONPATH=src python examples/segment.py [--steps N] [--full]

``--full`` uses the 64px/width-128 edge config; default is the tiny config
so the CI smoke step finishes in seconds.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import segnet
from repro.runtime.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="64px width-128 config instead of the tiny one")
    args = ap.parse_args()
    cfg = segnet.SEGNET if args.full else segnet.SEGNET_TINY

    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    params, _ = segnet.segnet_init(key, cfg)
    plans = segnet.segnet_plans(cfg)
    load_ms = (time.perf_counter() - t0) * 1e3
    n_sites = len(plans)
    plan_ms = sum(p.build_ms for p in plans)
    print(f"[load] {cfg.name}: {n_sites} planned conv sites "
          f"({sum(1 for p in plans if p.spec.kind == 'dilated')} dilated), "
          f"plan build {plan_ms:.1f} ms, init total {load_ms:.1f} ms")
    print(f"[load] paths: {[p.path for p in plans]}")

    kx, kl = jax.random.split(key)
    x = jax.random.normal(kx, (2, cfg.in_hw, cfg.in_hw, cfg.in_c),
                          jnp.float32)
    labels = jax.random.randint(kl, (2, cfg.out_hw, cfg.out_hw), 0,
                                cfg.num_classes)

    fwd = jax.jit(lambda p, x: segnet.segnet_apply(p, x, cfg))
    logits = jax.block_until_ready(fwd(params, x))     # compile
    assert logits.shape == (2, cfg.out_hw, cfg.out_hw, cfg.num_classes)
    assert np.isfinite(np.asarray(logits)).all()
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fwd(params, x))
    print(f"[infer] logits {tuple(logits.shape)} "
          f"(upsampled {tuple(segnet.upsample_logits(logits).shape)}), "
          f"{(time.perf_counter() - t0) / 5 * 1e3:.2f} ms/batch steady-state")

    step = jax.jit(jax.value_and_grad(
        lambda p: segnet.segnet_loss(p, x, labels, cfg)))
    loss0 = None
    for i in range(args.steps):
        loss, grads = step(params)
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        loss0 = loss0 if loss0 is not None else float(loss)
        print(f"[train] step {i}: loss {float(loss):.4f}")
    final = float(step(params)[0])
    assert np.isfinite(final)
    if args.steps >= 1:
        assert final < loss0, (final, loss0)
        print(f"[train] loss {loss0:.4f} -> {final:.4f} "
              f"(custom VJPs on the superpack)")


if __name__ == "__main__":
    main()
