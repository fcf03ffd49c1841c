"""Unified single-correlation executor ('conv' / 'dilated' kinds): one
Pallas launch / one wide GEMM per conv site on the (R·S·C, N) tap superpack,
parity with the XLA oracle, and the custom VJP on the packed layout across
odd dilations, asymmetric padding, and dilation >= kernel extent.
No hypothesis dependency — this file must run everywhere tier-1 runs.
Shared helpers (oracles, assertions, jaxpr counting, plan builders) live in
``tests/conftest.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.plan as planmod
from repro.core import reference as ref
from repro.core.plan import ConvSpec, conv_spec, plan_conv

from tests.conftest import (assert_close, count_eqns, plane_bytes_cap,
                            vmem_slab)


# ---------------------------------------------------------------------------
# the acceptance property: ONE launch / ONE wide GEMM per conv site
# ---------------------------------------------------------------------------

SEG_SITES = [
    # (h, c, n, k, d) — SegNet context blocks + a strided front-end site
    (16, 16, 24, 3, 2),
    (16, 16, 24, 3, 8),
    (33, 8, 8, 3, 4),
]


@pytest.mark.parametrize("h,c,n,k,d", SEG_SITES)
def test_xla_forward_is_single_wide_gemm(h, c, n, k, d, single_plan):
    """Every planned dilated site on the fused_tap route lowers to exactly
    one dot_general (and no pallas_call)."""
    pad = ((d, d), (d, d))
    plan, _ = single_plan(h, h, c, n, k, k, (1, 1), (d, d), pad)
    assert plan.path == "fused_tap", plan.path
    x = jnp.zeros((1, h, h, c), jnp.float32)
    packed = jnp.zeros((k * k * c, n), jnp.float32)
    jaxpr = jax.make_jaxpr(plan.apply)(x, packed)
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 1
    assert count_eqns(jaxpr.jaxpr, "pallas_call") == 0
    assert count_eqns(jaxpr.jaxpr, "conv_general_dilated") == 0


def test_pallas_forward_is_single_launch(single_plan):
    """backend='pallas' lowers the whole dilated conv to one pallas_call
    (and no XLA GEMM outside it)."""
    plan, _ = single_plan(13, 13, 8, 8, 3, 3, (1, 1), (2, 2),
                          ((2, 2), (2, 2)), backend="pallas")
    assert plan.path == "pallas" and plan.tiles is not None
    x = jnp.zeros((2, 13, 13, 8), jnp.float32)
    packed = jnp.zeros((9 * 8, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(plan.apply)(x, packed)
    assert count_eqns(jaxpr.jaxpr, "pallas_call") == 1
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 0


def test_strided_conv_is_single_wide_gemm(single_plan):
    """The strided 'conv' kind rides the same route: one dot_general."""
    plan, kind = single_plan(12, 12, 6, 8, 3, 3, (2, 2), (1, 1),
                             ((1, 1), (1, 1)))
    assert kind == "conv" and plan.path == "fused_tap"
    jaxpr = jax.make_jaxpr(plan.apply)(
        jnp.zeros((1, 12, 12, 6)), jnp.zeros((9 * 6, 8)))
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 1


# ---------------------------------------------------------------------------
# superpack layout invariants
# ---------------------------------------------------------------------------

def test_superpack_layout_row_offsets_and_roundtrip(single_plan):
    k = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 5, 4), jnp.float32)
    plan, _ = single_plan(9, 9, 5, 4, 3, 2, (1, 1), (2, 3), ((2, 2), (1, 1)))
    packed = plan.pack(k)
    c, n = 5, 4
    assert packed.shape == (3 * 2 * c, n)
    # tap (m, nn) owns rows [(m*S+nn)*C, (m*S+nn+1)*C) — plan-time schedule
    for (m, nn, row) in plan.dx_taps:
        np.testing.assert_array_equal(
            np.asarray(packed[row * c:(row + 1) * c]), np.asarray(k[m, nn]))
    np.testing.assert_array_equal(np.asarray(plan.unpack(packed)),
                                  np.asarray(k))
    # a dilated kernel packs identically to a dense one: layout is geometry-free
    plan_dense, _ = single_plan(9, 9, 5, 4, 3, 2, (1, 1), (1, 1),
                                ((1, 1), (0, 1)))
    np.testing.assert_array_equal(np.asarray(plan_dense.pack(k)),
                                  np.asarray(packed))


def test_full_kernel_adapts_to_superpack(single_plan):
    """Legacy params holding (R,S,C,N) HWIO kernels still apply/unpack."""
    k = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 6), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 9, 4), jnp.float32)
    plan, _ = single_plan(9, 9, 4, 6, 3, 3, (1, 1), (2, 2), ((2, 2), (2, 2)))
    np.testing.assert_array_equal(np.asarray(plan.apply(x, k)),
                                  np.asarray(plan.apply(x, plan.pack(k))))
    np.testing.assert_array_equal(np.asarray(plan.unpack(k)), np.asarray(k))


# ---------------------------------------------------------------------------
# fused-vs-baseline parity: odd dilations, asymmetric padding, dilation >=
# kernel extent, strided+dilated — on both backends
# ---------------------------------------------------------------------------

PARITY_CASES = [
    # (h, w, r, s, strides, dil, pads)
    (9, 9, 3, 3, (1, 1), (2, 2), ((2, 2), (2, 2))),      # SAME atrous
    (13, 11, 3, 2, (1, 1), (3, 5), ((2, 4), (3, 1))),    # odd dil, asym pads
    (17, 17, 3, 3, (1, 1), (4, 4), ((4, 4), (4, 4))),    # dil >= kernel
    (19, 19, 2, 2, (1, 1), (7, 7), ((0, 0), (0, 0))),    # dil >> kernel, VALID
    (12, 12, 3, 3, (2, 2), (1, 1), ((1, 1), (1, 1))),    # strided conv
    (10, 9, 4, 3, (3, 2), (2, 2), ((3, 2), (2, 2))),     # strided + dilated
    (8, 8, 1, 1, (1, 1), (1, 1), ((0, 0), (0, 0))),      # pure 1x1
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", PARITY_CASES)
def test_planned_matches_oracle(case, backend, single_plan):
    h, w, r, s, strides, dil, pads = case
    key = jax.random.PRNGKey(abs(hash(case)) % (2 ** 31))
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (2, h, w, 3), jnp.float32)
    k = jax.random.normal(k2, (r, s, 3, 4), jnp.float32)
    plan, _ = single_plan(h, w, 3, 4, r, s, strides, dil, pads,
                          backend=backend)
    want = ref.oracle_dilated_conv2d(x, k, dilation=dil, strides=strides,
                                     padding=pads)
    assert_close(plan.apply(x, plan.pack(k)), want)


def test_taps_fallback_matches_fused(single_plan):
    """Force the per-tap fallback (buffer cap) and check parity."""
    case = (9, 9, 3, 3, (1, 1), (2, 2), ((2, 2), (2, 2)))
    h, w, r, s, strides, dil, pads = case
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (1, h, w, 3), jnp.float32)
    k = jax.random.normal(key, (r, s, 3, 4), jnp.float32)
    plan, _ = single_plan(h, w, 3, 4, r, s, strides, dil, pads)
    assert plan.path == "fused_tap"
    with plane_bytes_cap(0):
        plan_t, _ = single_plan(h, w, 3, 4, r, s, strides, dil, pads)
        assert plan_t.path == "taps"
        want = ref.oracle_dilated_conv2d(x, k, dilation=dil, strides=strides,
                                         padding=pads)
        assert_close(plan_t.apply(x, plan_t.pack(k)), want)
        # VJP parity holds on the fallback route too
        y, vjp = jax.vjp(plan_t.apply, x, plan_t.pack(k))
        y_o, vjp_o = jax.vjp(lambda x, k: ref.oracle_dilated_conv2d(
            x, k, dilation=dil, strides=strides, padding=pads), x, k)
        dy = jax.random.normal(key, y.shape)
        (dx, dpk), (dx_o, dk_o) = vjp(dy), vjp_o(dy)
        assert_close(dx, dx_o, tol=1e-3)
        assert_close(plan_t.unpack(dpk), dk_o, tol=1e-3)


# ---------------------------------------------------------------------------
# custom VJP on the superpack vs autodiff of the XLA oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", PARITY_CASES[:6])
def test_grad_of_apply_on_superpack(case, backend, single_plan):
    """VJP through the planned executor, on the superpacked layout, matches
    autodiff of the XLA oracle (dx directly; dK after unpack) — odd
    dilations, asymmetric padding, dilation >= kernel extent, strides."""
    h, w, r, s, strides, dil, pads = case
    key = jax.random.PRNGKey(abs(hash(case)) % (2 ** 31) + 1)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, h, w, 3), jnp.float32)
    k = jax.random.normal(k2, (r, s, 3, 4), jnp.float32)
    plan, _ = single_plan(h, w, 3, 4, r, s, strides, dil, pads,
                          backend=backend)
    packed = plan.pack(k)
    y, vjp = jax.vjp(plan.apply, x, packed)
    y_o, vjp_o = jax.vjp(
        lambda x, k: ref.oracle_dilated_conv2d(
            x, k, dilation=dil, strides=strides, padding=pads), x, k)
    assert_close(y, y_o)
    dy = jax.random.normal(k3, y.shape)
    (dx, dpacked), (dx_o, dk_o) = vjp(dy), vjp_o(dy)
    assert dpacked.shape == packed.shape       # grads stay superpacked
    assert_close(dx, dx_o, tol=1e-3)
    assert_close(plan.unpack(dpacked), dk_o, tol=1e-3)


def test_grad_with_full_kernel_cotangent_shape():
    """Callers passing the HWIO kernel get an HWIO cotangent back."""
    from repro.core import huge_dilated_conv2d
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (1, 9, 9, 2), jnp.float32)
    k = jax.random.normal(key, (3, 3, 2, 4), jnp.float32)

    def f(x, k):
        return huge_dilated_conv2d(x, k, dilation=(3, 3),
                                   padding=((3, 3), (3, 3)))

    y, vjp = jax.vjp(f, x, k)
    dx, dk = vjp(jnp.ones_like(y))
    assert dk.shape == k.shape
    y_o, vjp_o = jax.vjp(lambda x, k: ref.oracle_dilated_conv2d(
        x, k, dilation=(3, 3), padding=((3, 3), (3, 3))), x, k)
    dx_o, dk_o = vjp_o(jnp.ones_like(y_o))
    assert_close(dx, dx_o, tol=1e-3)
    assert_close(dk, dk_o, tol=1e-3)


def test_negative_padding_vjp(single_plan):
    """pad_or_crop's crop branch transposes correctly in the backward."""
    key = jax.random.PRNGKey(13)
    x = jax.random.normal(key, (1, 12, 12, 3), jnp.float32)
    k = jax.random.normal(key, (3, 3, 3, 2), jnp.float32)
    pads = ((-1, -2), (-2, -1))
    plan, _ = single_plan(12, 12, 3, 2, 3, 3, (1, 1), (2, 2), pads)
    y, vjp = jax.vjp(plan.apply, x, plan.pack(k))
    y_o, vjp_o = jax.vjp(lambda x, k: ref.oracle_dilated_conv2d(
        x, k, dilation=(2, 2), padding=pads), x, k)
    assert_close(y, y_o)
    dy = jax.random.normal(key, y.shape)
    (dx, dpk), (dx_o, dk_o) = vjp(dy), vjp_o(dy)
    assert_close(dx, dx_o, tol=1e-3)
    assert_close(plan.unpack(dpk), dk_o, tol=1e-3)


# ---------------------------------------------------------------------------
# satellite: the dilation-aware VMEM estimate
# ---------------------------------------------------------------------------

def test_vmem_estimate_superpack_is_dilation_aware():
    from repro.kernels.untangled_conv import vmem_bytes_estimate_superpack
    # same tap count, larger plane: dilation grows the plane term only —
    # the double-buffered plane block, each row padded to (8, 128) tiles
    small = vmem_bytes_estimate_superpack(18, 18, 8, 9, 8, 16, 16)
    big = vmem_bytes_estimate_superpack(32, 32, 8, 9, 8, 16, 16)
    assert big > small
    assert big - small == 2 * (32 * vmem_slab(32, 8, 4)
                               - 18 * vmem_slab(18, 8, 4))
    # f32 accumulator and tap product are itemsize-independent
    for itemsize in (1, 2, 4):
        est = vmem_bytes_estimate_superpack(18, 18, 8, 9, 8, 16, 16,
                                            itemsize)
        streamed = (2 * 18 * vmem_slab(18, 8, itemsize)
                    + 2 * 9 * vmem_slab(8, 8, itemsize)
                    + 2 * 16 * vmem_slab(16, 8, itemsize)
                    + vmem_slab(16 * 16, 8, itemsize))
        assert est - streamed == 2 * vmem_slab(16 * 16, 8, 4)


def test_pallas_plan_tiles_respect_budget():
    plan = plan_conv(ConvSpec(
        kind="dilated", in_hw=(33, 33), in_c=256, out_c=256,
        kernel_hw=(3, 3), strides=(1, 1), padding=((4, 4), (4, 4)),
        dilation=(4, 4), backend="pallas"))
    if plan.path != "pallas":
        pytest.skip("no VMEM-feasible tiling on this geometry")
    from repro.kernels.untangled_conv import vmem_bytes_estimate_superpack
    c_t, n_t = plan.tiles
    est = vmem_bytes_estimate_superpack(41, 41, c_t, 9, n_t, *plan.out_hw)
    assert est <= planmod._VMEM_BUDGET
