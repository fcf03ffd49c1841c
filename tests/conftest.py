"""Shared oracle / property-test harness for the HUGE² engine suite.

One home for everything the per-file suites used to duplicate:

- ``assert_close`` / ``count_eqns`` — tolerance assertion and the jaxpr
  equation counter (descends into sub-jaxprs but never into a
  ``pallas_call`` body: its interior matmuls live inside the one launch
  being counted).
- NHWC oracle wrappers over ``lax.conv_general_dilated``
  (``oracle_transposed`` / ``oracle_single``) and the **float64 numpy
  oracle** ``conv_oracle_f64`` with its ULP-scaled error bound
  ``ulp_bound`` — the principled replacement for widened rtols (see the
  bound derivation on ``ulp_bound``).
- superpack round-trip builders (``random_case`` / ``packed_roundtrip``)
  and plan-builder fixtures (``dcgan_plan`` / ``single_plan``).
- plan-constant patch helpers (``plane_bytes_cap`` / ``vmem_budget``)
  that swap a route-builder cap and clear the plan cache on both sides —
  the one sanctioned way tests force a route.
- the **seeded-shuffle** collection hook: set ``PYTEST_SHUFFLE_SEED=<int>``
  to run the suite in a deterministic random order (flushes test-order
  dependence without a pytest-randomly dependency; CI runs one shuffled
  pass per build).
"""
from __future__ import annotations

import contextlib
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.plan as planmod
from repro.core import reference as ref
from repro.core.plan import ConvSpec, conv_spec, plan_cache_clear, plan_conv

# shared tolerance constants (f32 forward / VJP-vs-autodiff / bf16)
TOL_FWD = 2e-4
TOL_GRAD = 1e-3
TOL_BF16 = 2e-2


def pytest_collection_modifyitems(config, items):
    seed = os.environ.get("PYTEST_SHUFFLE_SEED")
    if seed:
        random.Random(int(seed)).shuffle(items)
        print(f"\n[conftest] shuffled {len(items)} tests "
              f"(PYTEST_SHUFFLE_SEED={seed})")


# ---------------------------------------------------------------------------
# assertion + jaxpr helpers
# ---------------------------------------------------------------------------

def assert_close(a, b, tol=TOL_FWD):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=tol, atol=tol)


def count_eqns(jaxpr, prim_name):
    """Recursively count equations named ``prim_name``, descending into
    sub-jaxprs (custom_vjp calls, pjit bodies, ...) — but not into a
    pallas_call's kernel body: its interior matmuls live inside the one
    launch being counted."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == prim_name:
            total += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "eqns"):
                    total += count_eqns(sub, prim_name)
                elif hasattr(sub, "jaxpr"):
                    total += count_eqns(sub.jaxpr, prim_name)
    return total


# ---------------------------------------------------------------------------
# NHWC oracles: the lax wrappers and the float64 reference
# ---------------------------------------------------------------------------

def oracle_transposed(x, k, *, strides, padding):
    """XLA's lhs-dilated conv — the transposed-kind correctness oracle."""
    return ref.oracle_conv_transpose2d(x, k, strides=strides, padding=padding)


def oracle_single(x, k, *, strides=(1, 1), dilation=(1, 1),
                  padding=((0, 0), (0, 0))):
    """XLA's rhs-dilated conv — the 'conv'/'dilated'-kind oracle."""
    return ref.oracle_dilated_conv2d(x, k, dilation=dilation, strides=strides,
                                     padding=padding)


def conv_oracle_f64(x, k, *, strides=(1, 1), dilation=(1, 1),
                    padding=((0, 0), (0, 0))):
    """Float64 numpy correlation oracle: returns ``(y64, amax64)`` where
    ``y64`` is the exact-to-f64 output and ``amax64`` the same contraction
    over ``|x|·|k|`` — the condition-number companion every ULP-scaled
    error bound needs.  Tap loop over (R, S) with strided/dilated slices,
    accumulated in float64; no jax x64 flag required."""
    x64 = np.asarray(x, np.float64)
    k64 = np.asarray(k, np.float64)
    (sh, sw), (dh, dw) = strides, dilation
    (ph, pw) = padding
    r, s, c, n = k64.shape
    x64 = np.pad(x64, ((0, 0), (ph[0], ph[1]), (pw[0], pw[1]), (0, 0)))
    b, hp, wp, _ = x64.shape
    oh = (hp - (r - 1) * dh - 1) // sh + 1
    ow = (wp - (s - 1) * dw - 1) // sw + 1
    y = np.zeros((b, oh, ow, n))
    amax = np.zeros((b, oh, ow, n))
    for m in range(r):
        for nn in range(s):
            xs = x64[:, m * dh:m * dh + (oh - 1) * sh + 1:sh,
                     nn * dw:nn * dw + (ow - 1) * sw + 1:sw, :]
            y += xs @ k64[m, nn]
            amax += np.abs(xs) @ np.abs(k64[m, nn])
    return y, amax


def ulp_bound(y64, amax64, n_terms, out_dtype=jnp.float32):
    """Elementwise absolute error bound for an f32-accumulated contraction
    of ``n_terms`` products, checked against the float64 oracle.

    Derivation (standard recursive-summation forward error, Higham §4.2):
    for any summation order of ``n`` f32 terms, ``|fl(Σ) - Σ| ≤ γ_n·Σ|t_i|``
    with ``γ_n = n·u/(1 - n·u)`` and ``u = 2^-24`` (the products themselves
    are exact in f32 for bf16 inputs and one-rounding for f32 inputs, which
    the ``n+1`` below absorbs).  The kernel and any reference ordering both
    satisfy the bound, so vs the exact f64 value we allow ``γ_{n+1}·amax``.
    A final cast to ``out_dtype`` adds half an output ULP: ``ε_out·|y|``.
    Unlike an rtol on ``|y|``, this scales with the *condition* of each
    output element — catastrophic cancellation widens it honestly, and a
    genuine defect (wrong tap, wrong offset) lands orders of magnitude
    outside it."""
    u = np.float64(2) ** -24
    eps_out = np.finfo(np.dtype(jnp.dtype(out_dtype)).name).eps \
        if jnp.dtype(out_dtype) != jnp.bfloat16 else np.float64(2) ** -8
    gamma = (n_terms + 1) * u / (1 - (n_terms + 1) * u)
    return gamma * amax64 + eps_out * np.abs(y64) + np.finfo(np.float32).tiny


def assert_close_ulp(got, y64, amax64, n_terms, out_dtype=jnp.float32):
    """Assert ``got`` is within the ULP-scaled bound of the f64 oracle."""
    err = np.abs(np.asarray(got, np.float64) - y64)
    bound = ulp_bound(y64, amax64, n_terms, out_dtype)
    worst = np.max(err - bound)
    assert np.all(err <= bound), (
        f"max excess over ULP-scaled bound: {worst:.3e} "
        f"(n_terms={n_terms}, max_err={err.max():.3e}, "
        f"max_bound={bound.max():.3e})")


def vmem_slab(rows, cols, itemsize):
    """Bytes of one ``(rows, cols)`` VMEM slab in Mosaic's layout: rows pad
    to the sublane tile (8 for 4-byte elements, 16 / 32 for 2 / 1), cols to
    the 128-lane tile — the reference the VMEM-estimate tests spell their
    expected working sets in."""
    sub = 8 * 4 // itemsize
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


# ---------------------------------------------------------------------------
# superpack round-trip builders
# ---------------------------------------------------------------------------

def random_case(seed, b, h, w, c, n, r, s, dtype=jnp.float32):
    """(x, kernel) drawn from a seeded normal — the standard test inputs."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (b, h, w, c), dtype)
    k = jax.random.normal(k2, (r, s, c, n), dtype)
    return x, k


def packed_roundtrip(plan, kernel):
    """Pack onto the superpack, assert the exact unpack round-trip, return
    the packed buffer — the invariant every packed-weight test leans on."""
    packed = plan.pack(kernel)
    np.testing.assert_array_equal(np.asarray(plan.unpack(packed)),
                                  np.asarray(kernel))
    return packed


# ---------------------------------------------------------------------------
# plan-constant patches (route forcing) — save/restore + cache clear
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def plane_bytes_cap(cap):
    """Temporarily swap ``plan._PLANE_BYTES_MAX`` (the fused-buffer cap the
    route builders evaluate per bucket) and clear the plan cache."""
    old = planmod._PLANE_BYTES_MAX
    planmod._PLANE_BYTES_MAX = cap
    plan_cache_clear()
    try:
        yield
    finally:
        planmod._PLANE_BYTES_MAX = old
        plan_cache_clear()


@contextlib.contextmanager
def vmem_budget(budget):
    """Temporarily swap ``plan._VMEM_BUDGET`` (what the Pallas tile searches
    fit against) and clear the plan cache — small geometries then exercise
    the spatially tiled routes real segmentation planes would take."""
    old = planmod._VMEM_BUDGET
    planmod._VMEM_BUDGET = budget
    plan_cache_clear()
    try:
        yield
    finally:
        planmod._VMEM_BUDGET = old
        plan_cache_clear()


# ---------------------------------------------------------------------------
# plan-builder fixtures
# ---------------------------------------------------------------------------

@pytest.fixture
def dcgan_plan():
    """Factory: Table-1 DCGAN layer record -> transposed ConvPlan."""
    from repro.models.gan import deconv_padding

    def build(l, backend="xla"):
        return plan_conv(ConvSpec(
            kind="transposed", in_hw=(l.in_hw, l.in_hw), in_c=l.in_c,
            out_c=l.out_c, kernel_hw=(l.kernel, l.kernel),
            strides=(l.stride, l.stride),
            padding=deconv_padding(l.kernel, l.stride), backend=backend))

    return build


@pytest.fixture
def single_plan():
    """Factory: (h, w, c, n, r, s, strides, dil, pads[, backend]) ->
    (single-correlation ConvPlan, kind)."""

    def build(h, w, c, n, r, s, strides, dil, pads, backend="xla"):
        kind = "dilated" if tuple(dil) != (1, 1) else "conv"
        return plan_conv(conv_spec(kind, (1, h, w, c), (r, s, c, n),
                                   strides=strides, padding=pads,
                                   dilation=dil, backend=backend)), kind

    return build
