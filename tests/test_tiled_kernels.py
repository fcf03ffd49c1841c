"""Spatially tiled Pallas superpack kernels: halo'd output tiles so no
plane ever leaves the Pallas route.

What this file proves:

- **same accumulation order, same bound**: the tiled kernels accumulate
  each output pixel in the order of the whole-plane kernels (tap-major
  inside a C tile, C tiles outer), and both sit inside the ULP-scaled
  float64-oracle bound (``tests/conftest.py``) across strides, dilations,
  ragged channel tiles, ragged spatial tiles, and empty deconv phases.
  Bit equality is not promised: in interpret mode every tap GEMM is an
  XLA:CPU dot whose internal summation order depends on its row count
  (a tile's pixels vs the whole plane's);
- **plan-level fwd+VJP parity**: with the VMEM budget shrunk just under the
  whole-plane working set, so test geometries take the routes real
  segmentation/decoder planes take, the
  planned executors (both kinds) match the lax oracle forward and through
  ``jax.vjp`` on the superpack — and every batch bucket, B=64 included,
  stays on the Pallas route;
- **jaxpr proofs on reclaimed geometries**: layers that routed to ``taps``
  (big atrous planes: whole-plane VMEM infeasible *and* the fused tap-stack
  over the byte cap) or to an XLA fallback at HEAD now lower to exactly ONE
  ``pallas_call`` with zero ``dot_general`` outside it;
- the ``vmem_bytes_estimate_tiled`` accounting: double-buffered halo tile
  at the input itemsize, f32 accumulator at a fixed 4 bytes/elem, every
  slab padded to Mosaic's (sublane, 128-lane) tiles.

The hypothesis sweep drives the same checkers as the fixed-case tests (thin
strategy plumbing over ``check_tiled_single`` / ``check_tiled_deconv``), so
hosts without hypothesis still exercise every code path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.plan as planmod
from repro.core import reference as ref
from repro.core.plan import (BATCH_BUCKETS, conv_spec, pick_vmem_tiles,
                             plan_conv)
from repro.kernels.untangled_conv import (lane_tile,
                                          untangled_conv2d_superpack_pallas,
                                          untangled_deconv2d_pallas,
                                          vmem_bytes_estimate_fused,
                                          vmem_bytes_estimate_superpack)

from tests.conftest import (assert_close, assert_close_ulp, conv_oracle_f64,
                            count_eqns, vmem_budget, vmem_slab)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # pragma: no cover - exercised on minimal hosts
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# checkers (shared between fixed cases and the hypothesis sweep)
# ---------------------------------------------------------------------------

def check_tiled_single(b, hp, wp, c, n, r, s, strides, dil, c_tile, n_tile,
                       sp_tiles, seed=0):
    """Tiled and untiled runs of one valid (pre-padded) single-correlation
    case, each inside the f64 oracle's ULP-scaled bound."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (b, hp, wp, c), jnp.float32)
    k = jax.random.normal(k2, (r, s, c, n), jnp.float32)
    sp = k.reshape(r * s * c, n)
    got = untangled_conv2d_superpack_pallas(
        x, sp, taps_hw=(r, s), strides=strides, rhs_dilation=dil,
        c_tile=c_tile, n_tile=n_tile, sp_tiles=sp_tiles, interpret=True)
    untiled = untangled_conv2d_superpack_pallas(
        x, sp, taps_hw=(r, s), strides=strides, rhs_dilation=dil,
        c_tile=c_tile, n_tile=n_tile, interpret=True)
    y64, amax64 = conv_oracle_f64(x, k, strides=strides, dilation=dil)
    # same per-pixel order, but interpret mode's XLA:CPU dot sums in an
    # order that depends on its row count: not bit-equal, same bound
    assert_close_ulp(untiled, y64, amax64, n_terms=r * s * c)
    assert_close_ulp(got, y64, amax64, n_terms=r * s * c)


def deconv_oracle_f64(x, k, *, strides, pads):
    """Float64 transposed conv — the lhs-dilated correlation
    ``ref.oracle_conv_transpose2d`` computes, as a zero-inserted plane
    through ``conv_oracle_f64``: returns ``(y64, amax64)``."""
    x64 = np.asarray(x, np.float64)
    b, h, w, c = x64.shape
    sh, sw = strides
    xd = np.zeros((b, (h - 1) * sh + 1, (w - 1) * sw + 1, c))
    xd[:, ::sh, ::sw] = x64
    return conv_oracle_f64(xd, k, padding=pads)


def check_tiled_deconv(b, h, w, c, n, r, s, strides, pads, c_tile, n_tile,
                       sp_tiles, seed=0):
    """Tiled and untiled runs of one transposed case (uniform phases —
    tile sizes are phase-output coordinates), each inside the f64 oracle's
    ULP-scaled bound and the lax oracle's tolerance."""
    plan = plan_conv(conv_spec("transposed", (b, h, w, c), (r, s, c, n),
                               strides=strides, padding=pads))
    assert plan.uniform, "tiled deconv checker needs uniform phases"
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (b, h, w, c), jnp.float32)
    k = jax.random.normal(k2, (r, s, c, n), jnp.float32)
    packed = plan.pack(k)
    xg = planmod._global_plane(plan, x)
    kw = dict(phases=plan.phases, out_hw=plan.out_hw, strides=strides,
              sum_uv=plan.sum_uv, c_tile=c_tile, n_tile=n_tile,
              out_dtype=x.dtype, interpret=True)
    got = untangled_deconv2d_pallas(xg, packed, sp_tiles=sp_tiles, **kw)
    untiled = untangled_deconv2d_pallas(xg, packed, **kw)
    # not bit-equal in interpret mode (see ``check_tiled_single``)
    y64, amax64 = deconv_oracle_f64(x, k, strides=strides, pads=pads)
    assert_close_ulp(untiled, y64, amax64, n_terms=r * s * c)
    assert_close_ulp(got, y64, amax64, n_terms=r * s * c)
    want = ref.oracle_conv_transpose2d(x, k, strides=strides, padding=pads)
    assert_close(got, want, tol=2e-5)


def whole_plane_budget(plan) -> int:
    """A VMEM budget one byte under the whole-plane kernel's working set:
    the planner must then take the spatially tiled kernel."""
    spec = plan.spec
    c_t, n_t = lane_tile(spec.in_c), lane_tile(spec.out_c)
    if spec.kind == "transposed":
        (glh, ghh), (glw, ghw) = plan.gpad
        tap_rows = max(ex.out_hw[0] * ex.out_hw[1] for ex in plan.phases)
        est = vmem_bytes_estimate_fused(
            spec.in_hw[0] + glh + ghh, spec.in_hw[1] + glw + ghw, c_t,
            plan.total_taps, n_t, plan.sum_uv, *plan.out_hw, tap_rows)
    else:
        (ph, pw) = spec.padding
        est = vmem_bytes_estimate_superpack(
            spec.in_hw[0] + sum(ph), spec.in_hw[1] + sum(pw), c_t,
            plan.total_taps, n_t, *plan.out_hw)
    return est - 1


# ---------------------------------------------------------------------------
# fixed-case kernel sweeps (run everywhere tier-1 runs)
# ---------------------------------------------------------------------------

SINGLE_CASES = [
    # (b, hp, wp, c, n, r, s, strides, dil, c_t, n_t, sp_tiles)
    (2, 13, 11, 5, 7, 3, 2, (1, 1), (1, 1), 8, 8, (4, 4)),    # ragged edge
    (1, 17, 17, 8, 8, 3, 3, (2, 2), (1, 1), 8, 8, (3, 5)),    # strided
    (1, 21, 21, 4, 4, 3, 3, (1, 1), (3, 3), 4, 4, (8, 8)),    # big halo
    (2, 14, 14, 130, 40, 2, 2, (2, 2), (2, 2), 128, 32, (2, 7)),  # ragged C
    (1, 9, 9, 3, 4, 1, 1, (1, 1), (1, 1), 8, 8, (4, 4)),      # 1x1, no halo
    (1, 16, 16, 6, 5, 3, 3, (1, 1), (1, 1), 8, 8, (16, 16)),  # 1 tile = plane
]


@pytest.mark.parametrize("case", SINGLE_CASES)
def test_tiled_single_bit_compat_and_oracle(case):
    check_tiled_single(*case, seed=abs(hash(case)) % (2 ** 31))


DECONV_CASES = [
    # (b, h, w, c, n, r, s, strides, pads, c_t, n_t, sp_tiles)
    (2, 8, 8, 6, 4, 5, 5, (2, 2), ((2, 3), (2, 3)), 8, 8, (3, 3)),  # DCGAN
    (1, 8, 8, 5, 4, 4, 4, (2, 2), ((1, 3), (1, 3)), 8, 8, (8, 2)),  # cGAN
    (2, 6, 6, 5, 4, 2, 2, (3, 3), ((0, 0), (0, 0)), 8, 8, (2, 3)),  # empty q
    (1, 7, 5, 4, 3, 3, 3, (1, 1), ((1, 1), (1, 1)), 4, 8, (3, 2)),  # stride 1
]


@pytest.mark.parametrize("case", DECONV_CASES)
def test_tiled_deconv_bit_compat_and_oracle(case):
    check_tiled_deconv(*case, seed=abs(hash(case)) % (2 ** 31))


# ---------------------------------------------------------------------------
# hypothesis property sweep over (plane, stride, dilation, halo, tile size)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 2), st.integers(6, 18), st.integers(6, 18),
           st.integers(1, 9), st.integers(1, 9), st.integers(1, 3),
           st.integers(1, 3), st.integers(1, 3), st.integers(1, 3),
           st.integers(1, 9), st.integers(1, 9), st.integers(0, 1))
    def test_tiled_single_property(b, hp, wp, c, n, r, s, stride, dil,
                                   toh, tow, ragged_c):
        if hp < (r - 1) * dil + 1 or wp < (s - 1) * dil + 1:
            return                      # no valid output
        c_t = max(1, c - 1) if ragged_c else c
        check_tiled_single(b, hp, wp, c, n, r, s, (stride, stride),
                           (dil, dil), c_t, 8, (toh, tow),
                           seed=b + hp * 13 + c * 7 + toh)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 2), st.integers(3, 8), st.integers(3, 8),
           st.integers(1, 6), st.integers(1, 5), st.integers(1, 5),
           st.integers(1, 3), st.integers(1, 6), st.integers(1, 6))
    def test_tiled_deconv_property(b, h, w, c, n, k, stride, tu, tv):
        from repro.models.gan import deconv_padding
        pads = deconv_padding(k, stride)    # out = stride*in -> uniform
        check_tiled_deconv(b, h, w, c, n, k, k, (stride, stride), pads,
                           8, 8, (tu, tv), seed=h * 11 + k + tu)


# ---------------------------------------------------------------------------
# plan-level: forced tiled routes, fwd + VJP vs the oracle, both kinds
# ---------------------------------------------------------------------------

TILED_ROUTE_CASES = [
    # (h, w, c, n, r, s, strides, dil, pads) — planes big enough that the
    # halo'd tile undercuts the whole plane's working set
    (48, 48, 8, 8, 3, 3, (1, 1), (2, 2), ((2, 2), (2, 2))),
    (64, 56, 6, 8, 3, 3, (2, 2), (1, 1), ((1, 1), (1, 1))),
    (72, 64, 4, 3, 4, 3, (3, 2), (2, 2), ((3, 2), (2, 2))),
]


@pytest.mark.parametrize("case", TILED_ROUTE_CASES)
def test_single_tiled_route_fwd_and_vjp_parity(case):
    h, w, c, n, r, s, strides, dil, pads = case
    kind = "dilated" if dil != (1, 1) else "conv"
    spec = conv_spec(kind, (1, h, w, c), (r, s, c, n), strides=strides,
                     padding=pads, dilation=dil, backend="pallas")
    with vmem_budget(whole_plane_budget(plan_conv(spec))):
        plan = plan_conv(spec)
        route = plan.routes[0]
        assert route.path == "pallas" and route.sp_tiles is not None, route
        key = jax.random.PRNGKey(h)
        x = jax.random.normal(key, (2, h, w, c), jnp.float32)
        k = jax.random.normal(key, (r, s, c, n), jnp.float32)
        packed = plan.pack(k)
        want = ref.oracle_dilated_conv2d(x, k, dilation=dil, strides=strides,
                                         padding=pads)
        assert_close(plan.apply(x, packed), want)
        y, vjp = jax.vjp(plan.apply, x, packed)
        _, vjp_o = jax.vjp(lambda x, k: ref.oracle_dilated_conv2d(
            x, k, dilation=dil, strides=strides, padding=pads), x, k)
        dy = jax.random.normal(key, y.shape)
        (dx, dpk), (dx_o, dk_o) = vjp(dy), vjp_o(dy)
        assert dpk.shape == packed.shape       # grads stay superpacked
        assert_close(dx, dx_o, tol=1e-3)
        assert_close(plan.unpack(dpk), dk_o, tol=1e-3)


TILED_TRANSPOSED_CASES = [
    # (h, w, c, n, r, s, strides, pads)
    (32, 32, 8, 8, 5, 5, (2, 2), ((2, 3), (2, 3))),   # DCGAN
    (32, 32, 8, 8, 4, 4, (2, 2), ((1, 3), (1, 3))),   # cGAN
]


@pytest.mark.parametrize("case", TILED_TRANSPOSED_CASES)
def test_transposed_tiled_route_fwd_and_vjp_parity(case):
    h, w, c, n, r, s, strides, pads = case
    spec = conv_spec("transposed", (1, h, w, c), (r, s, c, n),
                     strides=strides, padding=pads, backend="pallas")
    with vmem_budget(whole_plane_budget(plan_conv(spec))):
        plan = plan_conv(spec)
        route = plan.routes[0]
        assert route.path == "pallas" and route.sp_tiles is not None, route
        key = jax.random.PRNGKey(h + r)
        x = jax.random.normal(key, (2, h, w, c), jnp.float32)
        k = jax.random.normal(key, (r, s, c, n), jnp.float32)
        packed = plan.pack(k)
        want = ref.oracle_conv_transpose2d(x, k, strides=strides,
                                           padding=pads)
        assert_close(plan.apply(x, packed), want)
        y, vjp = jax.vjp(plan.apply, x, packed)
        _, vjp_o = jax.vjp(lambda x, k: ref.oracle_conv_transpose2d(
            x, k, strides=strides, padding=pads), x, k)
        dy = jax.random.normal(key, y.shape)
        (dx, dpk), (dx_o, dk_o) = vjp(dy), vjp_o(dy)
        assert dpk.shape == packed.shape
        assert_close(dx, dx_o, tol=1e-3)
        assert_close(plan.unpack(dpk), dk_o, tol=1e-3)


def test_every_bucket_stays_on_the_pallas_route():
    """Under a tight budget the whole bucket table — B=64 included — rides
    the tiled Pallas route (the old verdict sent big buckets to 'taps')."""
    spec = conv_spec("dilated", (1, 48, 48, 8), (3, 3, 8, 8),
                     dilation=(2, 2), padding=((2, 2), (2, 2)),
                     backend="pallas")
    with vmem_budget(whole_plane_budget(plan_conv(spec))):
        plan = plan_conv(spec)
        assert tuple(r.batch for r in plan.routes) == BATCH_BUCKETS
        for route in plan.routes:
            assert route.path == "pallas" and route.sp_tiles is not None
        assert plan.route_for_batch(64).sp_tiles is not None


# ---------------------------------------------------------------------------
# jaxpr proofs: reclaimed geometries lower to exactly ONE pallas_call
# ---------------------------------------------------------------------------

def test_big_atrous_plane_reclaims_pallas_from_taps():
    """DeepLab-scale 385x385 atrous layer (the BENCH_dilated addition): at
    HEAD the pallas verdict failed (whole plane over the VMEM budget even at
    the smallest C tile) and the fused tap-stack busted _PLANE_BYTES_MAX, so
    backend='pallas' fell all the way to 'taps'.  Now it routes to the tiled
    kernel: one pallas_call, no XLA GEMM, at every bucket."""
    h, c, n, k, d = 385, 32, 32, 3, 2
    pad = ((d, d), (d, d))
    itemsize = 4
    # the HEAD verdicts, re-derived from the plan constants
    assert pick_vmem_tiles(h + 2 * d, h + 2 * d, c, n, k, k, h, h,
                           itemsize) is None
    assert 4 * 1 * h * h * k * k * c > planmod._PLANE_BYTES_MAX
    plan = plan_conv(conv_spec("dilated", (1, h, h, c), (k, k, c, n),
                               dilation=(d, d), padding=pad,
                               backend="pallas"))
    for route in plan.routes:
        assert route.path == "pallas" and route.sp_tiles is not None, route
    x = jnp.zeros((1, h, h, c), jnp.float32)
    packed = jnp.zeros((k * k * c, n), jnp.float32)
    jaxpr = jax.make_jaxpr(plan.apply)(x, packed)
    assert count_eqns(jaxpr.jaxpr, "pallas_call") == 1
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 0


def test_big_decoder_plane_reclaims_pallas_from_xla():
    """A 256->512 px VAE-decoder-scale deconv: at HEAD the whole-plane fused
    kernel was VMEM-infeasible so backend='pallas' fell back to an XLA wide
    GEMM; now the tiled kernel keeps it on the Pallas route — one
    pallas_call, zero dot_general."""
    from repro.core.plan import pick_fused_tiles
    from repro.models.gan import deconv_padding
    h, c, n, k, s = 256, 32, 16, 4, 2
    pads = deconv_padding(k, s)
    plan = plan_conv(conv_spec("transposed", (1, h, h, c), (k, k, c, n),
                               strides=(s, s), padding=pads,
                               backend="pallas"))
    (glh, ghh), (glw, ghw) = plan.gpad
    tap_rows = max(ex.out_hw[0] * ex.out_hw[1] for ex in plan.phases)
    assert pick_fused_tiles(h + glh + ghh, h + glw + ghw, c, n,
                            plan.total_taps, plan.sum_uv, *plan.out_hw,
                            tap_rows, itemsize=4) is None  # no whole-plane fit
    for route in plan.routes:
        assert route.path == "pallas" and route.sp_tiles is not None, route
    x = jnp.zeros((1, h, h, c), jnp.float32)
    packed = jnp.zeros((plan.total_taps * c, n), jnp.float32)
    jaxpr = jax.make_jaxpr(plan.apply)(x, packed)
    assert count_eqns(jaxpr.jaxpr, "pallas_call") == 1
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 0


# ---------------------------------------------------------------------------
# the tiled VMEM estimate: double buffer at input itemsize, f32 accumulator
# ---------------------------------------------------------------------------

def test_vmem_estimate_tiled_accounting():
    from repro.kernels.untangled_conv import (halo_extent,
                                              vmem_bytes_estimate_tiled)
    tin_h = halo_extent(8, 3, 1, 2)      # (8-1)*1 + (3-1)*2 + 1 = 12
    assert tin_h == 12
    assert halo_extent(8, 3, 2, 1) == 17  # strided footprint dominates
    for itemsize in (1, 2, 4):
        est = vmem_bytes_estimate_tiled(12, 12, 8, 9, 8, (8, 8), 64, 64,
                                        itemsize)
        streamed = (2 * 12 * vmem_slab(12, 8, itemsize)   # 2 DMA slots
                    + 2 * 9 * vmem_slab(8, 8, itemsize)   # superpack tile
                    + 2 * 8 * vmem_slab(8, 8, itemsize)   # output tile
                    + vmem_slab(64, 8, itemsize))         # tap operand
        # f32 accumulator + tap product are itemsize-independent
        assert est - streamed == 2 * vmem_slab(64, 8, 4)
    # the double buffer is charged twice: halving the halo tile's rows
    # saves exactly two slots of those rows
    a = vmem_bytes_estimate_tiled(12, 12, 8, 9, 8, (8, 8), 64, 64)
    b = vmem_bytes_estimate_tiled(6, 12, 8, 9, 8, (8, 8), 64, 64)
    assert a - b == 2 * 6 * vmem_slab(12, 8, 4)
    # lanes pad to 128: a 3-channel tile costs what a 128-channel one does
    assert (vmem_bytes_estimate_tiled(12, 12, 3, 9, 8, (8, 8), 64, 64)
            - vmem_bytes_estimate_tiled(12, 12, 128, 9, 8, (8, 8), 64, 64)
            == 2 * 9 * (vmem_slab(3, 8, 4) - vmem_slab(128, 8, 4)))


def test_route_tiles_fit_budget():
    """The tile search's winning (C_t, N_t, sp_tiles) actually fits the
    budget it was searched against."""
    from repro.kernels.untangled_conv import (halo_extent,
                                              vmem_bytes_estimate_tiled)
    h, c, n, k, d = 385, 32, 32, 3, 2
    plan = plan_conv(conv_spec("dilated", (1, h, h, c), (k, k, c, n),
                               dilation=(d, d), padding=((d, d), (d, d)),
                               backend="pallas"))
    route = plan.routes[0]
    c_t, n_t = route.tiles
    toh, tow = route.sp_tiles
    est = vmem_bytes_estimate_tiled(
        halo_extent(toh, k, 1, d), halo_extent(tow, k, 1, d),
        c_t, k * k, n_t, (toh, tow), toh * tow, toh * tow)
    assert est <= planmod._VMEM_BUDGET
