"""Shared layer primitives: params are plain pytrees; every creator returns
``(params, specs)`` where ``specs`` mirrors the params with *logical*
PartitionSpecs (resolved to mesh axes by ``repro.sharding``).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Pytree = Any


def spec(*axes) -> P:
    """Logical partition spec (axis names resolved later)."""
    return P(*axes)


def dense_init(key, in_dim, out_dim, in_axis, out_axis, dtype=jnp.bfloat16,
               bias=False, scale=None):
    scale = scale if scale is not None else in_dim ** -0.5
    w = jax.random.normal(key, (in_dim, out_dim), dtype) * scale
    params = {"w": w}
    specs = {"w": spec(in_axis, out_axis)}
    if bias:
        params["b"] = jnp.zeros((out_dim,), dtype)
        specs["b"] = spec(out_axis)
    return params, specs


def _upcast_bf16() -> bool:
    """XLA:CPU's thunk runtime lacks some fused BF16xBF16->F32 dot kernels,
    so bf16 dots upcast on the CPU backend only.  The dry-run (compile-only,
    ``REPRO_DRYRUN=1``) keeps bf16 so cost_analysis reports the
    TPU-faithful byte counts.  Asked at trace time: importing this module
    initialises no backend."""
    return (jax.default_backend() == "cpu"
            and os.environ.get("REPRO_DRYRUN") != "1")


def _dot_operands(x, w):
    if x.dtype == jnp.bfloat16 and _upcast_bf16():
        return x.astype(jnp.float32), w.astype(jnp.float32)
    return x, w


def dense_apply(p, x):
    xx, ww = _dot_operands(x, p["w"])
    y = jax.lax.dot_general(xx, ww, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm_init(dim, dtype=jnp.float32):
    return {"g": jnp.ones((dim,), dtype)}, {"g": spec(None)}


def rmsnorm_apply(p, x, eps=1e-6, gemma_style=False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    g = p["g"].astype(jnp.float32)
    y = y * (1.0 + g) if gemma_style else y * g
    return y.astype(x.dtype)


def layernorm_init(dim, dtype=jnp.float32):
    return ({"g": jnp.ones((dim,), dtype), "b": jnp.zeros((dim,), dtype)},
            {"g": spec(None), "b": spec(None)})


def layernorm_apply(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]
    return y.astype(x.dtype)


def embed_init(key, vocab, dim, dtype=jnp.bfloat16):
    w = jax.random.normal(key, (vocab, dim), dtype) * (dim ** -0.5)
    return {"w": w}, {"w": spec("vocab", None)}


def embed_apply(p, ids):
    return jnp.take(p["w"], ids, axis=0)


def embed_logits(p, x):
    """Tied readout: (B, S, D) @ (V, D)^T."""
    xx, ww = _dot_operands(x, p["w"])
    return jax.lax.dot_general(
        xx, ww, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


ACTS: dict[str, Callable] = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
    "relu": jax.nn.relu,
}
