"""Quickstart: run a transposed convolution through the HUGE2 engine and
compare against the naive (DarkNet-style) zero-insertion engine.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import huge_conv_transpose2d, reference as ref
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

# DCGAN DC2: 8x8x512 -> 16x16x256, 5x5 kernel, stride 2
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (1, 8, 8, 512), jnp.float32)
k = jax.random.normal(key, (5, 5, 512, 256), jnp.float32)
strides, pad = (2, 2), ((2, 3), (2, 3))

huge = jax.jit(lambda x, k: huge_conv_transpose2d(x, k, strides, pad))
naive = jax.jit(lambda x, k: ref.naive_conv_transpose2d(
    x, k, strides=strides, padding=pad))
oracle = jax.jit(lambda x, k: ref.oracle_conv_transpose2d(
    x, k, strides=strides, padding=pad))

y_h, y_n, y_o = huge(x, k), naive(x, k), oracle(x, k)
np.testing.assert_allclose(np.asarray(y_h), np.asarray(y_o), rtol=2e-4,
                           atol=2e-4)
np.testing.assert_allclose(np.asarray(y_n), np.asarray(y_o), rtol=2e-4,
                           atol=2e-4)
print(f"output {y_h.shape} — HUGE2 == naive == XLA oracle  ✓")

for name, fn in (("naive(zero-insert+im2col)", naive), ("HUGE2", huge)):
    jax.block_until_ready(fn(x, k))
    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(fn(x, k))
    print(f"{name:28s} {(time.perf_counter() - t0) / 10 * 1e3:7.2f} ms/call")

# the same op through the Pallas TPU kernel (interpret mode on CPU)
y_p = huge_conv_transpose2d(x, k, strides, pad, "pallas")
np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_o), rtol=2e-4,
                           atol=2e-4)
print("Pallas kernel path (interpret=True) matches  ✓")
