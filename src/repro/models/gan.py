"""DCGAN / cGAN (paper Table 1) built on the HUGE² plan/executor engine.

Generators stack the exact Table-1 transposed-conv layers; discriminators
mirror them with strided convs.  Every convolution site gets a ``ConvPlan``
built **once at model load** (``generator_plans`` / ``discriminator_plans``,
backed by the keyed plan cache) and the generator's deconv weights are stored
**superpacked** — every phase sub-kernel concatenated into one tap-major
``(Σ T_h·T_w·C, N)`` buffer per layer — so the generator never re-slices a
kernel inside a jitted call, every transposed conv executes as a single
launch, and each layer's weights are one shardable array.  The plans'
custom VJPs implement the paper's §3.2.3 training formulation directly on
the superpacked layout, so both inference *and* training exercise the
engine.  (Pre-superpack checkpoints that stored per-phase dicts still load:
``ConvPlan.apply`` / ``unpack`` adapt them via ``as_superpack``.)
The discriminator now follows the same convention: its strided-conv weights
are stored as single-phase ``(R·S·C, N)`` superpacks, and its custom VJP
runs the §3.2.3 backward directly on that layout (pre-superpack checkpoints
holding HWIO kernels adapt via ``as_superpack``).

The ``backend`` field of ``GANConfig`` is a plan policy ('xla' | 'pallas' |
'auto') consumed at plan-build time; it is no longer threaded through the
apply functions call-by-call.  ``autotune`` is the second plan policy: an
optional ``repro.core.autotune.AutotunePolicy`` that replaces the heuristic
per-bucket routes with measured winners (per-host cache hits at model load,
live microbenchmarks on a miss) — see ``docs/ARCHITECTURE.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.autotune import AutotunePolicy
from repro.core.plan import ConvPlan, ConvSpec, plan_conv
from repro.layers import common as cm


@dataclasses.dataclass(frozen=True)
class DeconvLayer:
    in_hw: int
    in_c: int
    out_c: int
    kernel: int
    stride: int


# paper Table 1
DCGAN_LAYERS = (
    DeconvLayer(4, 1024, 512, 5, 2),
    DeconvLayer(8, 512, 256, 5, 2),
    DeconvLayer(16, 256, 128, 5, 2),
    DeconvLayer(32, 128, 3, 5, 2),
)
CGAN_LAYERS = (
    DeconvLayer(8, 256, 128, 4, 2),
    DeconvLayer(16, 128, 3, 4, 2),
)


def deconv_padding(kernel: int, stride: int):
    """'SAME'-style transposed padding: out = stride * in.

    out = (h-1)*s + pl + ph - k + 2 == s*h  =>  pl + ph = k + s - 2.
    """
    total = kernel + stride - 2
    pl = max(0, (kernel - stride + 1) // 2)
    ph = total - pl
    return ((pl, ph), (pl, ph))


@dataclasses.dataclass(frozen=True)
class GANConfig:
    name: str
    layers: tuple[DeconvLayer, ...]
    z_dim: int = 100
    backend: str = "xla"            # plan policy: 'xla' | 'pallas' | 'auto'
    # measured-route policy (None = heuristic routes); model load pays any
    # cache-miss microbenchmarks once, apply only ever sees tuned plans
    autotune: Optional[AutotunePolicy] = None
    # plane-parallel policy: (D_h, D_w) device tiling requested for every
    # conv site (``ConvSpec.spatial``).  Plans keep single-device routes as
    # the fallback, so (2, 1) on a mesh-less host is still correct — set
    # from ``DistContext.spatial_tiles()`` when serving over a spatial mesh
    spatial: tuple[int, int] = (1, 1)
    # weight storage dtype for every conv site: 'float32' (dense) or 'int8'
    # (quantized superpacks — ``ConvSpec.wdtype``); activations stay f32
    wdtype: str = "float32"


DCGAN = GANConfig("dcgan", DCGAN_LAYERS)
CGAN = GANConfig("cgan", CGAN_LAYERS, z_dim=110)   # z + 10-class condition


# ---------------------------------------------------------------------------
# load-time planning: one ConvPlan per convolution site
# ---------------------------------------------------------------------------

def generator_plans(cfg: GANConfig, dtype=jnp.float32) -> tuple[ConvPlan, ...]:
    """Plans for every generator deconv site (cached; build cost paid once
    — including any autotune microbenchmarks the config's policy asks for)."""
    plans = []
    for l in cfg.layers:
        plans.append(plan_conv(ConvSpec(
            kind="transposed", in_hw=(l.in_hw, l.in_hw), in_c=l.in_c,
            out_c=l.out_c, kernel_hw=(l.kernel, l.kernel),
            strides=(l.stride, l.stride),
            padding=deconv_padding(l.kernel, l.stride),
            dtype=str(jnp.dtype(dtype)), backend=cfg.backend,
            spatial=cfg.spatial, wdtype=cfg.wdtype),
            autotune=cfg.autotune))
    return tuple(plans)


def discriminator_plans(cfg: GANConfig,
                        dtype=jnp.float32) -> tuple[ConvPlan, ...]:
    """Plans for the mirrored strided-conv sites (image -> features)."""
    plans = []
    for l in reversed(cfg.layers):
        k = l.kernel
        plans.append(plan_conv(ConvSpec(
            kind="conv", in_hw=(l.in_hw * l.stride, l.in_hw * l.stride),
            in_c=l.out_c, out_c=l.in_c, kernel_hw=(k, k),
            strides=(l.stride, l.stride),
            padding=((k // 2, (k - 1) // 2), (k // 2, (k - 1) // 2)),
            dtype=str(jnp.dtype(dtype)), backend=cfg.backend,
            spatial=cfg.spatial, wdtype=cfg.wdtype),
            autotune=cfg.autotune))
    return tuple(plans)


# ---------------------------------------------------------------------------
# generator: packed deconv weights, planned execution
# ---------------------------------------------------------------------------

def generator_init(key, cfg: GANConfig, dtype=jnp.float32, dist=None):
    """Init generator params with the deconv weights already *packed* into
    the plans' GEMM-ready per-phase layout (the load-time decomposition).

    Each superpack is ONE shardable buffer with logical axes
    ``(conv_taps, conv_out)`` (``sharding.SUPERPACK_SPEC``); pass a
    ``DistContext`` and the params come back placed on its mesh
    (out-channels sharded under the default rules), ready for
    data-parallel serving/training under ``jax.jit``."""
    plans = generator_plans(cfg, dtype)
    l0 = cfg.layers[0]
    ks = jax.random.split(key, len(cfg.layers) + 1)
    p = {"proj": jax.random.normal(
        ks[0], (cfg.z_dim, l0.in_hw * l0.in_hw * l0.in_c), dtype) * 0.02}
    s = {"proj": cm.spec(None, "conv_out")}
    for i, l in enumerate(cfg.layers):
        kernel = jax.random.normal(
            ks[i + 1], (l.kernel, l.kernel, l.in_c, l.out_c), dtype) * 0.02
        p[f"dc{i}"] = plans[i].pack(kernel)
        p[f"b{i}"] = jnp.zeros((l.out_c,), dtype)
        # the superpack is one (Σ T_h*T_w*C, N) buffer: shard out-channels
        s[f"dc{i}"] = cm.spec("conv_taps", "conv_out")
        s[f"b{i}"] = cm.spec("conv_out")
    if dist is not None:
        p = dist.shard_params(p, s)
    return p, s


def generator_apply(p, z, cfg: GANConfig):
    plans = generator_plans(cfg, z.dtype)      # cache hits after model load
    l0 = cfg.layers[0]
    with jax.named_scope("proj"):
        x = z @ p["proj"]
    x = jax.nn.relu(x.reshape(z.shape[0], l0.in_hw, l0.in_hw, l0.in_c))
    for i, plan in enumerate(plans):
        with jax.named_scope(f"dc{i}"):         # names the site in a profile
            x = plan.apply(x, p[f"dc{i}"])
        x = x + p[f"b{i}"]
        x = jnp.tanh(x) if i == len(plans) - 1 else jax.nn.relu(x)
    return x


def generator_unpack(p, cfg: GANConfig):
    """Packed generator params -> full (R,S,C,N) HWIO kernels (offline use:
    export, or feeding baselines that expect undecomposed weights)."""
    plans = generator_plans(cfg)
    out = dict(p)
    for i, plan in enumerate(plans):
        out[f"dc{i}"] = plan.unpack(p[f"dc{i}"])
    return out


# ---------------------------------------------------------------------------
# discriminator: planned strided convs (identity packing)
# ---------------------------------------------------------------------------

def discriminator_init(key, cfg: GANConfig, dtype=jnp.float32, dist=None):
    plans = discriminator_plans(cfg, dtype)
    layers = tuple(reversed(cfg.layers))
    ks = jax.random.split(key, len(layers) + 1)
    p, s = {}, {}
    for i, l in enumerate(layers):
        # mirror: out_c -> in_c, stride-2 downsample; stored superpacked
        # (R*S*C, N) like the generator deconvs — one shardable buffer
        kernel = jax.random.normal(
            ks[i], (l.kernel, l.kernel, l.out_c, l.in_c), dtype) * 0.02
        p[f"c{i}"] = plans[i].pack(kernel)
        s[f"c{i}"] = cm.spec("conv_taps", "conv_out")
    l_last = layers[-1]
    fdim = l_last.in_hw ** 2 * l_last.in_c
    p["head"] = jax.random.normal(ks[-1], (fdim, 1), dtype) * 0.02
    s["head"] = cm.spec("model", None)
    if dist is not None:
        p = dist.shard_params(p, s)
    return p, s


def discriminator_apply(p, x, cfg: GANConfig):
    plans = discriminator_plans(cfg, x.dtype)
    for i, plan in enumerate(plans):
        with jax.named_scope(f"c{i}"):
            x = plan.apply(x, p[f"c{i}"])   # superpack or legacy HWIO kernel
        x = jax.nn.leaky_relu(x, 0.2)
    return x.reshape(x.shape[0], -1) @ p["head"]


def discriminator_unpack(p, cfg: GANConfig):
    """Packed discriminator params -> full (R,S,C,N) HWIO kernels."""
    plans = discriminator_plans(cfg)
    out = dict(p)
    for i, plan in enumerate(plans):
        out[f"c{i}"] = plan.unpack(p[f"c{i}"])
    return out


def gan_losses(gp, dp, z, real, cfg: GANConfig):
    """Non-saturating GAN loss pair."""
    fake = generator_apply(gp, z, cfg)
    d_fake = discriminator_apply(dp, fake, cfg)
    d_real = discriminator_apply(dp, real, cfg)
    d_loss = (jax.nn.softplus(-d_real) + jax.nn.softplus(d_fake)).mean()
    g_loss = jax.nn.softplus(-d_fake).mean()
    return g_loss, d_loss
