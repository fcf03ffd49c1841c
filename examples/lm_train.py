"""Train a reduced llama3.2-1b on a 2x2 CPU mesh with checkpointing and an
injected node failure at step 12 — demonstrating the full distributed
runtime: sharded train step, atomic checkpoints, restart-on-failure with
exact data-pipeline resume.

    PYTHONPATH=src python examples/lm_train.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import tempfile                                       # noqa: E402

from repro.launch.train import train                  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    with tempfile.TemporaryDirectory() as d:
        losses, final = train(
            "llama3.2-1b", reduced=True, steps=30, batch=8, seq=64,
            ckpt_dir=os.path.join(d, "ckpt"), ckpt_every=5,
            fail_at=[12],                   # inject a node failure
            data=2, model=2)                # 2x2 mesh on host devices
    print(f"\nfinal step {final}; loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert final == 30
    assert losses[-1] < losses[0] + 0.05      # random tokens: bound drift
    print("survived injected failure, resumed from checkpoint ✓")


if __name__ == "__main__":
    main()
