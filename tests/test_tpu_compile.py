"""The Pallas routes of the main models compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
lane-misaligned slices, strided accesses on a wide lane dim, or more VMEM
than the scoped limit.  Here each route the planner emits under
``backend='pallas'`` is lowered with ``interpret=False`` for one chip of a
*described* ``v5e:2x2`` topology and compiled by the TPU compiler — no chip
is needed, nothing runs.  The sites are chained per model (one compile per
model and bucket), at real widths: the Table-1 DCGAN generator and its
discriminator at every batch bucket (and the generator once with int8
weights), the VAE decoder's and the U-Net's transposed sites at the
largest bucket, SegNet, and the spatially tiled kernels of the two
plane-parallel geometries.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.plan as planmod
from repro.core.plan import BATCH_BUCKETS, plan_conv
from repro.models import gan, segnet, unet, vae

_KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 — built only once a test of this
    file runs, never at import (one process at a time may load libtpu)."""
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described chip's executables can be written to the persistent
    cache but never read back; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


def compile_chain(plans, batch, sharding):
    """Lower the sites as one chained program — each through its planned
    executor with ``interpret=False`` — and compile it for ``sharding``'s
    device.  Returns the compiled HLO text."""
    def fwd(plan):
        f = (planmod._transposed_fwd if plan.spec.kind == "transposed"
             else planmod._single_fwd)
        return lambda x, w: f(plan, x, w, interpret=False)

    def chain(x, *ws):
        for plan, w in zip(plans, ws):
            x = fwd(plan)(x, w)
        return x

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=sharding)

    def weights(plan):
        rows, n = plan.total_taps * plan.spec.in_c, plan.spec.out_c
        if plan.spec.wdtype == "int8":
            return planmod.QuantizedSuperpack(
                jax.ShapeDtypeStruct((rows, n), jnp.int8, sharding=sharding),
                shape(rows, 1))
        return shape(rows, n)

    s0 = plans[0].spec
    x = shape(batch, *s0.in_hw, s0.in_c)
    ws = [weights(p) for p in plans]
    return jax.jit(chain).lower(x, *ws).compile().as_text()


def kernel_names(hlo: str) -> list[str]:
    """Each kernel's HLO instruction name, less its numeric suffix: the
    ``pallas_call`` name that finds the kernel in a profile."""
    return re.findall(r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*" + re.escape(_KERNEL),
                      hlo)


def assert_all_pallas(plans, batch):
    for plan in plans:
        route = plan.route_for_batch(batch)
        assert route.path == "pallas", (plan.spec, route)


DCGAN = dataclasses.replace(gan.DCGAN, backend="pallas")


@pytest.mark.parametrize("batch", BATCH_BUCKETS)
def test_dcgan_generator_kernels_compile(one_chip, batch):
    """Every bucket's fused deconv kernels, batch-blocked above B1: the
    B64 grid steps over 64 / 32 / 8 / 2 images of dc0-dc3."""
    plans = gan.generator_plans(DCGAN)
    assert_all_pallas(plans, batch)
    b_tiles = [p.route_for_batch(batch).b_tile for p in plans]
    if batch == 1:
        assert b_tiles == [1] * len(plans)
    else:
        assert all(b_t > 1 and batch % b_t == 0 for b_t in b_tiles), b_tiles
    hlo = compile_chain(plans, batch, one_chip)
    assert hlo.count(_KERNEL) == len(plans)
    assert kernel_names(hlo) == ["untangled_deconv"] * len(plans)


@pytest.mark.parametrize("batch", BATCH_BUCKETS)
def test_dcgan_discriminator_kernels_compile(one_chip, batch):
    """The stride-2 sites: strided tap reads are strided ref loads."""
    plans = gan.discriminator_plans(DCGAN)
    assert_all_pallas(plans, batch)
    hlo = compile_chain(plans, batch, one_chip)
    assert hlo.count(_KERNEL) == len(plans)
    assert kernel_names(hlo) == ["untangled_conv"] * len(plans)


def test_dcgan_int8_generator_kernels_compile(one_chip):
    """int8 superpacks: 1-byte weight tiles plus their f32 scale column,
    dequantized per tap panel inside the kernel."""
    plans = gan.generator_plans(dataclasses.replace(DCGAN, wdtype="int8"))
    assert_all_pallas(plans, 1)
    hlo = compile_chain(plans, 1, one_chip)
    assert hlo.count(_KERNEL) == len(plans)


def _transposed_plans(model: str):
    if model == "vae_decoder":
        return vae.decoder_plans(dataclasses.replace(vae.VAE,
                                                     backend="pallas"))
    plans = unet.unet_plans(dataclasses.replace(unet.UNET, backend="pallas"))
    return tuple(p for p in plans.values() if p.spec.kind == "transposed")


@pytest.mark.parametrize("model", ("vae_decoder", "unet_up"))
def test_transposed_sites_compile_at_largest_bucket(one_chip, model):
    """The other fused deconv sites, each at its largest bucket's batch
    block: the VAE decoder and the U-Net's transposed ups."""
    batch = BATCH_BUCKETS[-1]
    plans = _transposed_plans(model)
    assert plans
    assert_all_pallas(plans, batch)
    for plan in plans:
        route = plan.route_for_batch(batch)
        assert route.sp_tiles is None and batch % route.b_tile == 0, route
        hlo = compile_chain((plan,), batch, one_chip)
        assert kernel_names(hlo) == ["untangled_deconv"], plan.spec


def test_dcgan_int8_generator_batch_blocked_kernels_compile(one_chip):
    """The int8 superpacks' scale columns ride the batch-blocked grid of
    the largest bucket as well."""
    batch = BATCH_BUCKETS[-1]
    plans = gan.generator_plans(dataclasses.replace(DCGAN, wdtype="int8"))
    assert_all_pallas(plans, batch)
    assert all(p.route_for_batch(batch).b_tile > 1 for p in plans)
    hlo = compile_chain(plans, batch, one_chip)
    assert kernel_names(hlo) == ["untangled_deconv"] * len(plans)


@pytest.mark.parametrize("batch", (1, BATCH_BUCKETS[-1]))
def test_segnet_kernels_compile(one_chip, batch):
    """Strided front end, dilated context, 1x1 head — including the
    3-channel stem whose plane block pads to 128 lanes."""
    plans = segnet.segnet_plans(
        dataclasses.replace(segnet.SEGNET, backend="pallas"))
    assert_all_pallas(plans, batch)
    hlo = compile_chain(plans, batch, one_chip)
    assert hlo.count(_KERNEL) == len(plans)


@pytest.mark.parametrize("site", ("dilated_context_385", "decoder_96"))
def test_tiled_kernels_compile(one_chip, site):
    """The halo-DMA kernels at the plane-parallel geometries, whose
    32/64-channel planes ride zero-padded to one 128-lane C tile."""
    from repro.launch.dryrun import convplane_spec
    plan = plan_conv(dataclasses.replace(convplane_spec(site, (1, 1)),
                                         backend="pallas"))
    for b in BATCH_BUCKETS:
        route = plan.route_for_batch(b)
        assert route.path == "pallas" and route.sp_tiles is not None, route
    hlo = compile_chain((plan,), 1, one_chip)
    assert hlo.count(_KERNEL) == 1
    assert kernel_names(hlo) == [
        "untangled_deconv_tiled" if plan.spec.kind == "transposed"
        else "untangled_conv_tiled"]
