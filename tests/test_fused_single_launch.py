"""Fused single-launch transposed conv: one Pallas launch / one wide GEMM
per conv site, superpacked weight layout, and fused-vs-per-phase parity.
No hypothesis dependency — this file must run everywhere tier-1 runs.
Shared helpers (oracles, assertions, jaxpr counting) live in
``tests/conftest.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reference as ref
from repro.core.plan import ConvSpec, conv_spec, plan_conv
from repro.models.gan import DCGAN_LAYERS

from tests.conftest import (assert_close, assert_close_ulp, count_eqns,
                            vmem_slab)


# ---------------------------------------------------------------------------
# the acceptance property: ONE launch / ONE wide GEMM per conv site
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("i", range(len(DCGAN_LAYERS)))
def test_xla_forward_is_single_wide_gemm(i, dcgan_plan):
    """Every Table-1 DCGAN deconv site lowers to exactly one dot_general."""
    l = DCGAN_LAYERS[i]
    plan = dcgan_plan(l)
    assert plan.path in ("fused_tap", "fused_plane"), plan.path
    x = jnp.zeros((1, l.in_hw, l.in_hw, l.in_c), jnp.float32)
    packed = jnp.zeros((plan.total_taps * l.in_c, l.out_c), jnp.float32)
    jaxpr = jax.make_jaxpr(plan.apply)(x, packed)
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 1
    assert count_eqns(jaxpr.jaxpr, "pallas_call") == 0


def test_pallas_forward_is_single_launch():
    """backend='pallas' lowers the whole transposed conv to one pallas_call
    (and no XLA GEMM outside it)."""
    plan = plan_conv(ConvSpec(
        kind="transposed", in_hw=(4, 4), in_c=64, out_c=32, kernel_hw=(5, 5),
        strides=(2, 2), padding=((2, 3), (2, 3)), backend="pallas"))
    assert plan.path == "pallas" and plan.tiles is not None
    x = jnp.zeros((2, 4, 4, 64), jnp.float32)
    packed = jnp.zeros((plan.total_taps * 64, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(plan.apply)(x, packed)
    assert count_eqns(jaxpr.jaxpr, "pallas_call") == 1
    assert count_eqns(jaxpr.jaxpr, "dot_general") == 0


# ---------------------------------------------------------------------------
# superpack layout invariants
# ---------------------------------------------------------------------------

def test_superpack_layout_and_offsets():
    from tests.conftest import packed_roundtrip
    k = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 3, 2), jnp.float32)
    plan = plan_conv(conv_spec("transposed", (1, 4, 4, 3), k.shape,
                               strides=(2, 3), padding=((2, 2), (1, 1))))
    packed = packed_roundtrip(plan, k)
    c, n = plan.spec.in_c, plan.spec.out_c
    assert packed.shape == (plan.total_taps * c, n)
    # each phase's rows sit at tap_off*C and match the per-phase slicing
    from repro.core.decompose import decompose_kernel
    subs = decompose_kernel(k, (2, 3), ((2, 2), (1, 1)))
    for ex in plan.phases:
        th, tw = ex.taps
        if th * tw == 0:
            continue
        seg = packed[ex.tap_off * c:(ex.tap_off + th * tw) * c]
        np.testing.assert_array_equal(
            np.asarray(seg), np.asarray(subs[ex.q].reshape(th * tw * c, n)))
    # offsets partition the buffer exactly (round-trip asserted above)
    assert sum(ex.taps[0] * ex.taps[1] for ex in plan.phases) \
        == plan.total_taps


def test_legacy_phase_dict_adapts_to_superpack():
    """Pre-superpack checkpoints ({key: per-phase buf}) still apply/unpack."""
    k = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 6, 8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 5, 6), jnp.float32)
    pads = ((1, 2), (1, 2))
    plan = plan_conv(conv_spec("transposed", x.shape, k.shape,
                               strides=(2, 2), padding=pads))
    from repro.core.decompose import decompose_kernel
    subs = decompose_kernel(k, (2, 2), pads)
    legacy = {ex.key: subs[ex.q].reshape(-1, 8) for ex in plan.phases}
    np.testing.assert_array_equal(np.asarray(plan.apply(x, legacy)),
                                  np.asarray(plan.apply(x, plan.pack(k))))
    np.testing.assert_array_equal(np.asarray(plan.unpack(legacy)),
                                  np.asarray(k))


# ---------------------------------------------------------------------------
# fused-vs-per-phase parity: odd strides, asymmetric padding, non-uniform
# phase sizes (the general interleave path), every whole-conv route
# ---------------------------------------------------------------------------

PARITY_CASES = [
    (4, 5, 5, 4, 3, 2, ((2, 3), (1, 0))),    # odd stride, asymmetric pads
    (5, 4, 3, 3, 3, 3, ((0, 2), (1, 1))),    # non-uniform phase extents
    (6, 6, 2, 2, 3, 3, ((0, 0), (0, 0))),    # stride > kernel: empty phases
    (5, 5, 5, 5, 1, 1, ((2, 2), (2, 2))),    # stride 1 degenerate
    (4, 4, 5, 5, 2, 2, ((2, 3), (2, 3))),    # DCGAN geometry (fused_tap)
    (8, 8, 4, 4, 2, 2, ((1, 3), (1, 3))),    # cGAN geometry (fused_plane)
    (7, 3, 6, 2, 4, 2, ((3, 1), (0, 1))),    # wildly asymmetric
]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", PARITY_CASES)
def test_fused_matches_per_phase_and_oracle(case, backend):
    h, w, r, s, sh, sw, pads = case
    key = jax.random.PRNGKey(abs(hash(case)) % (2 ** 31))
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (2, h, w, 3), jnp.float32)
    k = jax.random.normal(k2, (r, s, 3, 4), jnp.float32)
    plan = plan_conv(conv_spec("transposed", x.shape, k.shape,
                               strides=(sh, sw), padding=pads,
                               backend=backend))
    packed = plan.pack(k)
    want = ref.oracle_conv_transpose2d(x, k, strides=(sh, sw), padding=pads)
    assert_close(plan.apply(x, packed), want)
    assert_close(plan.apply_per_phase(x, packed), want)


@pytest.mark.parametrize("case", PARITY_CASES[:4])
def test_grad_of_apply_on_superpack(case):
    """VJP through the fused executor, on the superpacked layout, matches
    autodiff of the XLA oracle (dx directly; dK after unpack)."""
    h, w, r, s, sh, sw, pads = case
    key = jax.random.PRNGKey(abs(hash(case)) % (2 ** 31) + 1)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, h, w, 3), jnp.float32)
    k = jax.random.normal(k2, (r, s, 3, 4), jnp.float32)
    plan = plan_conv(conv_spec("transposed", x.shape, k.shape,
                               strides=(sh, sw), padding=pads))
    packed = plan.pack(k)
    y, vjp = jax.vjp(plan.apply, x, packed)
    y_o, vjp_o = jax.vjp(
        lambda x, k: ref.oracle_conv_transpose2d(
            x, k, strides=(sh, sw), padding=pads), x, k)
    assert_close(y, y_o)
    dy = jax.random.normal(k3, y.shape)
    (dx, dpacked), (dx_o, dk_o) = vjp(dy), vjp_o(dy)
    assert dpacked.shape == packed.shape       # grads stay superpacked
    assert_close(dx, dx_o)
    assert_close(plan.unpack(dpacked), dk_o)


def test_fused_pallas_kernel_direct():
    """Kernel-level fused deconv entry (interpret mode) vs the oracle."""
    from repro.kernels.ops import untangled_deconv2d
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (2, 4, 4, 64), jnp.float32)
    k = jax.random.normal(k2, (5, 5, 64, 32), jnp.float32)
    got = untangled_deconv2d(x, k, strides=(2, 2), padding=((2, 3), (2, 3)),
                             interpret=True)
    want = ref.oracle_conv_transpose2d(x, k, strides=(2, 2),
                                       padding=((2, 3), (2, 3)))
    assert_close(got, want, tol=2e-5)


def test_fused_pallas_bf16_and_ragged_tiles():
    """bf16 input + channel counts that don't divide the tile size."""
    from repro.kernels.ops import untangled_deconv2d
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (1, 5, 5, 130), jnp.bfloat16)
    k = jax.random.normal(k2, (3, 3, 130, 40), jnp.bfloat16)
    got = untangled_deconv2d(x, k, strides=(2, 2), padding=((1, 1), (1, 1)),
                             interpret=True)
    want = ref.oracle_conv_transpose2d(x.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       strides=(2, 2), padding=((1, 1), (1, 1)))
    assert_close(got, want, tol=2e-2)


# ---------------------------------------------------------------------------
# satellite: VMEM estimates count the f32 accumulator at 4 bytes
# ---------------------------------------------------------------------------

def test_vmem_estimate_accumulator_is_f32():
    from repro.kernels.untangled_conv import (vmem_bytes_estimate_fused,
                                              vmem_bytes_estimate_superpack)
    hp = wp = 16; c_t = n_t = 8; r = s = 3; oh = ow = 14
    for itemsize in (1, 2, 4):
        est = vmem_bytes_estimate_superpack(hp, wp, c_t, r * s, n_t, oh, ow,
                                            itemsize)
        streamed = (2 * hp * vmem_slab(wp, c_t, itemsize)
                    + 2 * r * s * vmem_slab(c_t, n_t, itemsize)
                    + 2 * oh * vmem_slab(ow, n_t, itemsize)
                    + vmem_slab(oh * ow, c_t, itemsize))
        # accumulator + tap product are itemsize-independent: always f32
        assert est - streamed == 2 * vmem_slab(oh * ow, n_t, 4)
    # the fused kernel: a sum_uv-row accumulator, a tap_rows-row product
    est2 = vmem_bytes_estimate_fused(hp, wp, c_t, r * s, n_t, 4 * oh * ow,
                                     oh, ow, oh * ow, itemsize=2)
    streamed2 = (2 * hp * vmem_slab(wp, c_t, 2)
                 + 2 * r * s * vmem_slab(c_t, n_t, 2)
                 + 2 * oh * vmem_slab(ow, n_t, 2)
                 + vmem_slab(oh * ow, c_t, 2))
    assert est2 - streamed2 == (vmem_slab(4 * oh * ow, n_t, 4)
                                + vmem_slab(oh * ow, n_t, 4))


def test_bf16_plan_picks_tiles_accounting_f32_scratch():
    """A bf16 spec must not get bigger tiles than the f32 scratch allows:
    the estimate at itemsize=2 still carries the 4-byte accumulator."""
    import repro.core.plan as planmod
    from repro.kernels.untangled_conv import vmem_bytes_estimate_fused
    plan = plan_conv(ConvSpec(
        kind="transposed", in_hw=(16, 16), in_c=256, out_c=256,
        kernel_hw=(5, 5), strides=(2, 2), padding=((2, 3), (2, 3)),
        dtype="bfloat16", backend="pallas"))
    if plan.path != "pallas":
        pytest.skip("no VMEM-feasible tiling on this geometry")
    c_t, n_t = plan.tiles
    (glh, ghh), (glw, ghw) = plan.gpad
    hg, wg = 16 + glh + ghh, 16 + glw + ghw
    tap_rows = max(ex.out_hw[0] * ex.out_hw[1] for ex in plan.phases)
    est = vmem_bytes_estimate_fused(hg, wg, c_t, plan.total_taps, n_t,
                                    plan.sum_uv, *plan.out_hw, tap_rows,
                                    itemsize=2)
    assert est <= planmod._VMEM_BUDGET


# ---------------------------------------------------------------------------
# batch-blocked grid: B_t images share one fetch of each superpack tile
# ---------------------------------------------------------------------------

def _deconv_case(b, h, c, n, k, s, pads, wdtype="float32", seed=0):
    """(plan, x, kernel, packed) of one transposed site on the Pallas
    backend; an int8 site's kernel is its dequantized superpack."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (b, h, h, c), jnp.float32)
    kern = jax.random.normal(k2, (k, k, c, n), jnp.float32)
    plan = plan_conv(conv_spec("transposed", x.shape, kern.shape,
                               strides=(s, s), padding=pads,
                               backend="pallas", wdtype=wdtype))
    packed = plan.pack(kern)
    if wdtype == "int8":
        kern = plan.unpack(packed)
    return plan, x, kern, packed


def _deconv_blocked(plan, x, packed, b_tile, c_tile):
    """The fused kernel on the plan's global plane at ``b_tile`` images
    per grid step and ``c_tile`` channels per reduction step."""
    import repro.core.plan as planmod
    from repro.kernels.untangled_conv import untangled_deconv2d_pallas
    quant = isinstance(packed, planmod.QuantizedSuperpack)
    return untangled_deconv2d_pallas(
        planmod._global_plane(plan, x), packed.q if quant else packed,
        scales=packed.scale if quant else None, phases=plan.phases,
        out_hw=plan.out_hw, strides=plan.spec.strides, sum_uv=plan.sum_uv,
        c_tile=c_tile, b_tile=b_tile, interpret=True)


# (in_hw, C, N, k, stride, pads, wdtype), every site over two C tiles: the
# DCGAN k5 s2 site with narrowed channels; a k4 s2 site with an odd output
# (non-uniform phases: 5x5 and 4x4 rows); the DCGAN site on an int8
# superpack
BLOCKED_SITES = {
    "dcgan_k5s2": (4, 32, 8, 5, 2, ((2, 3), (2, 3)), "float32"),
    "k4s2_odd_out": (5, 32, 8, 4, 2, ((1, 2), (1, 2)), "float32"),
    "dcgan_k5s2_int8": (4, 32, 8, 5, 2, ((2, 3), (2, 3)), "int8"),
}


@pytest.mark.parametrize("site,b,b_tile,chunk_rows", [
    ("dcgan_k5s2", 4, 1, None), ("dcgan_k5s2", 4, 2, None),
    ("dcgan_k5s2", 4, 4, None), ("dcgan_k5s2", 8, 2, None),
    ("dcgan_k5s2", 8, 4, None),
    # chunks of two images: two per batch block unroll, four loop
    ("dcgan_k5s2", 8, 4, 32), ("dcgan_k5s2", 8, 8, 32),
    ("k4s2_odd_out", 8, 8, 50), ("dcgan_k5s2_int8", 8, 8, 32),
])
def test_batch_blocked_deconv_matches_oracle(site, b, b_tile, chunk_rows,
                                             monkeypatch):
    """Every one of the B/B_t batch blocks, and every chunk of a block,
    against the lax oracle and within the f32 rounding bound of the
    float64 one."""
    import repro.kernels.untangled_conv as uc
    from tests.test_tiled_kernels import deconv_oracle_f64
    if chunk_rows is not None:
        monkeypatch.setattr(uc, "CHUNK_ROWS", chunk_rows)
    h, c, n, k, s, pads, wdtype = BLOCKED_SITES[site]
    plan, x, kern, packed = _deconv_case(b, h, c, n, k, s, pads, wdtype,
                                         seed=b * 10 + b_tile)
    assert plan.uniform == (site != "k4s2_odd_out")
    tap_rows = max(ex.out_hw[0] * ex.out_hw[1] for ex in plan.phases)
    if chunk_rows is not None:
        assert uc.deconv_chunk(b_tile, tap_rows) == 2
    got = _deconv_blocked(plan, x, packed, b_tile, c_tile=c // 2)
    assert got.shape == (b, *plan.out_hw, n)
    y64, amax64 = deconv_oracle_f64(x, kern, strides=(s, s), pads=pads)
    assert_close_ulp(got, y64, amax64, n_terms=k * k * c)
    assert_close(got, ref.oracle_conv_transpose2d(
        x, kern, strides=(s, s), padding=pads))


@pytest.mark.parametrize("b", [3, 6])
def test_partial_batch_runs_whole_batch_blocks(b, monkeypatch):
    """A batch below its bucket that the route's ``B_t`` does not divide
    runs whole batch blocks of a zero-padded batch (never a smaller
    ``B_t``), and only its own images come back."""
    import repro.kernels.untangled_conv as uc
    h, c, n, k, s, pads, wdtype = BLOCKED_SITES["dcgan_k5s2"]
    plan, x, kern, packed = _deconv_case(b, h, c, n, k, s, pads, seed=b)
    route = plan.route_for_batch(b)
    assert route.path == "pallas" and route.b_tile > 1 and b % route.b_tile
    launched = []
    kernel = uc.untangled_deconv2d_pallas

    def spy(xg, *args, **kw):
        launched.append((xg.shape[0], kw["b_tile"]))
        return kernel(xg, *args, **kw)

    monkeypatch.setattr(uc, "untangled_deconv2d_pallas", spy)
    got = plan.apply(x, packed)
    assert launched == [(-(-b // route.b_tile) * route.b_tile, route.b_tile)]
    assert got.shape == (b, *plan.out_hw, n)
    assert_close(got, ref.oracle_conv_transpose2d(
        x, kern, strides=(s, s), padding=pads))


def test_fused_kernel_refuses_a_ragged_batch_block():
    """``b_tile`` must divide the batch: the plan layer pads, the kernel
    does not pick another block."""
    plan, x, _, packed = _deconv_case(6, *BLOCKED_SITES["dcgan_k5s2"])
    with pytest.raises(ValueError, match="does not divide"):
        _deconv_blocked(plan, x, packed, b_tile=4, c_tile=16)
