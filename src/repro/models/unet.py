"""Latent-diffusion-style U-Net on the HUGE² plan/executor engine.

The ROADMAP's last open model-zoo item and *the* upsampling-heavy
production workload: a strided 'conv' encoder, a dilated bottleneck, a
transposed decoder, and skip concatenations — every convolution kind the
engine plans, in one forward pass.  Each site gets a ``ConvPlan`` built
once at model load (``unet_plans``) and every conv weight is stored
**superpacked** (``wdtype='int8'`` flips all of them to quantized
superpacks), with logical sharding axes ``(conv_taps, conv_out)`` like the
rest of the zoo.  Training differentiates **through the packed custom
VJPs** on all three kinds, and the skip concatenations split their
cotangents into the decoder and encoder halves through those same VJPs.

The decoder's transposed sites use ``up_kernel % stride == 0`` ('SAME'
``deconv_padding``) geometry on purpose: every phase shares its tap
footprint and pad, so the sites are eligible for the engine's
'pixel_shuffle' (sub-pixel convolution) route — one dense stride-1 conv +
depth-to-space per upsample instead of a phase-interleaved launch (the
geometry-dependent transposed-vs-sub-pixel tradeoff of arXiv:2107.07647,
decided per (site, bucket) by the route heuristic or the autotuner).

Denoising: ``unet_apply(p, x_t, t, cfg)`` predicts the noise ``eps`` given
the corrupted image and a timestep in ``[0, 1]`` (sinusoidal embedding +
one per-level projection).  ``unet_loss`` is the standard denoising score
matching MSE under a cosine ``alpha_bar``; ``denoise_loop`` runs the
sequential Euler refinement the serving bench drives through the control
plane (many decoder calls per request).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.autotune import AutotunePolicy
from repro.core.plan import ConvPlan, ConvSpec, plan_conv
from repro.layers import common as cm
from repro.models.gan import deconv_padding
from repro.models.segnet import atrous_padding


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    name: str
    image_hw: int = 32
    in_c: int = 3
    base: int = 32                  # encoder widths: base · 2^level
    depth: int = 2                  # stride-2 down/up stages
    mid_dilations: tuple[int, ...] = (1, 2)   # bottleneck 'dilated' sites
    kernel: int = 3                 # stem / down / fuse / head kernel
    up_kernel: int = 4              # transposed up kernel; % stride == 0
    time_dim: int = 64              # sinusoidal timestep embedding width
    backend: str = "xla"            # plan policy: 'xla' | 'pallas' | 'auto'
    autotune: Optional[AutotunePolicy] = None
    spatial: tuple[int, int] = (1, 1)
    wdtype: str = "float32"         # 'float32' | 'int8' superpacks

    def width(self, level: int) -> int:
        return self.base * (2 ** level)

    def hw(self, level: int) -> int:
        return self.image_hw // (2 ** level)


UNET = UNetConfig("unet")                                    # 32px latents
UNET_TINY = UNetConfig("unet-tiny", image_hw=16, base=8, time_dim=16)


# ---------------------------------------------------------------------------
# sites: every conv in forward order, as (name, ConvSpec)
# ---------------------------------------------------------------------------

def unet_sites(cfg: UNetConfig,
               dtype="float32") -> tuple[tuple[str, ConvSpec], ...]:
    """(name, ConvSpec) for every conv site, forward order.  One list
    drives planning, init, apply, the golden route table, and the route
    property tests — the site set cannot drift between them."""
    k = cfg.kernel
    same = ((k // 2, (k - 1) // 2), (k // 2, (k - 1) // 2))

    def spec(kind, hw, c_in, c_out, kernel, stride=1, dilation=1,
             padding=None):
        return ConvSpec(
            kind=kind, in_hw=(hw, hw), in_c=c_in, out_c=c_out,
            kernel_hw=(kernel, kernel), strides=(stride, stride),
            padding=padding if padding is not None else same,
            dilation=(dilation, dilation), dtype=str(jnp.dtype(dtype)),
            backend=cfg.backend, spatial=cfg.spatial, wdtype=cfg.wdtype)

    sites = [("stem", spec("conv", cfg.image_hw, cfg.in_c, cfg.base, k))]
    for i in range(cfg.depth):
        sites.append((f"down{i}", spec(
            "conv", cfg.hw(i), cfg.width(i), cfg.width(i + 1), k, stride=2)))
    for j, d in enumerate(cfg.mid_dilations):
        sites.append((f"mid{j}", spec(
            "dilated", cfg.hw(cfg.depth), cfg.width(cfg.depth),
            cfg.width(cfg.depth), k, dilation=d,
            padding=atrous_padding(k, d))))
    for i in reversed(range(cfg.depth)):
        sites.append((f"up{i}", spec(
            "transposed", cfg.hw(i + 1), cfg.width(i + 1), cfg.width(i),
            cfg.up_kernel, stride=2,
            padding=deconv_padding(cfg.up_kernel, 2))))
        sites.append((f"fuse{i}", spec(
            "conv", cfg.hw(i), 2 * cfg.width(i), cfg.width(i), k)))
    sites.append(("head", spec("conv", cfg.image_hw, cfg.base, cfg.in_c, k)))
    return tuple(sites)


def unet_plans(cfg: UNetConfig, dtype=jnp.float32) -> dict[str, ConvPlan]:
    return {name: plan_conv(s, autotune=cfg.autotune)
            for name, s in unet_sites(cfg, str(jnp.dtype(dtype)))}


def unet_route_summary(cfg: UNetConfig, batch: int = 1,
                       dtype=jnp.float32) -> dict[str, tuple[str, str]]:
    """{site: (conv kind, route path at ``batch``)} — plan inspection for
    the 'one pass runs every kind' assertion and the bench's route
    report."""
    return {name: (plan.spec.kind, plan.route_for_batch(batch).path)
            for name, plan in unet_plans(cfg, dtype).items()}


# ---------------------------------------------------------------------------
# params: superpacked conv weights + timestep-embedding projections
# ---------------------------------------------------------------------------

def unet_init(key, cfg: UNetConfig, dtype=jnp.float32, dist=None):
    """Superpacked params + logical specs; He init for the correlation
    sites, the zoo's 0.02 normal for the transposed ups.  Pass a
    ``DistContext`` to get the tree placed on its mesh."""
    plans = unet_plans(cfg, dtype)
    sites = unet_sites(cfg, str(jnp.dtype(dtype)))
    ks = iter(jax.random.split(key, len(sites) + cfg.depth + 2))
    p, s = {}, {}
    for name, spec in sites:
        r, c, n = spec.kernel_hw[0], spec.in_c, spec.out_c
        scale = 0.02 if spec.kind == "transposed" \
            else (2.0 / (r * r * c)) ** 0.5
        kernel = jax.random.normal(next(ks), (r, r, c, n), dtype) * scale
        p[name] = plans[name].pack(kernel)
        p[f"{name}_b"] = jnp.zeros((n,), dtype)
        s[name] = cm.spec("conv_taps", "conv_out")
        s[f"{name}_b"] = cm.spec("conv_out")
    # timestep MLP + one projection per encoder level (applied after each
    # down, and after the first bottleneck site at the deepest level)
    p["temb_w"] = jax.random.normal(
        next(ks), (cfg.time_dim, cfg.time_dim), dtype) * cfg.time_dim ** -0.5
    p["temb_b"] = jnp.zeros((cfg.time_dim,), dtype)
    s["temb_w"] = cm.spec(None, None)
    s["temb_b"] = cm.spec(None)
    for i in range(cfg.depth + 1):
        # tproj{i} is added right after down{i} (channels width(i+1)); the
        # last one conditions the bottleneck entry at width(depth)
        n = cfg.width(min(i + 1, cfg.depth))
        p[f"tproj{i}"] = jax.random.normal(
            next(ks), (cfg.time_dim, n), dtype) * cfg.time_dim ** -0.5
        s[f"tproj{i}"] = cm.spec(None, "conv_out")
    if dist is not None:
        p = dist.shard_params(p, s)
    return p, s


# ---------------------------------------------------------------------------
# apply: planned execution on the superpacks, end to end
# ---------------------------------------------------------------------------

def time_embedding(t, dim: int):
    """Sinusoidal embedding of ``t`` in [0, 1] -> (B, dim)."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0)
                    * jnp.arange(half, dtype=t.dtype) / max(1, half - 1))
    ang = (t * 1000.0)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def unet_apply(p, x, t, cfg: UNetConfig):
    """(x_t (B,H,W,C), t (B,) in [0,1]) -> predicted noise eps (B,H,W,C).

    Encoder activations are kept as skips and concatenated after each
    transposed up; the fuse conv contracts the doubled channels, so the
    concat's cotangent splits into both halves through the packed VJPs."""
    plans = unet_plans(cfg, x.dtype)           # cache hits after model load

    def conv(name, h):
        with jax.named_scope(name):
            h = plans[name].apply(h, p[name])
        return h + p[f"{name}_b"]

    emb = jax.nn.silu(
        time_embedding(t.astype(x.dtype), cfg.time_dim)
        @ p["temb_w"] + p["temb_b"])

    h = jax.nn.relu(conv("stem", x))
    skips = []
    for i in range(cfg.depth):
        skips.append(h)
        h = conv(f"down{i}", h) + (emb @ p[f"tproj{i}"])[:, None, None, :]
        h = jax.nn.relu(h)
    h = h + (emb @ p[f"tproj{cfg.depth}"])[:, None, None, :]
    for j in range(len(cfg.mid_dilations)):
        h = jax.nn.relu(conv(f"mid{j}", h))
    for i in reversed(range(cfg.depth)):
        h = jax.nn.relu(conv(f"up{i}", h))
        h = jnp.concatenate([h, skips[i]], axis=-1)
        h = jax.nn.relu(conv(f"fuse{i}", h))
    return conv("head", h)


# ---------------------------------------------------------------------------
# denoising: cosine schedule, DSM loss, sequential refinement loop
# ---------------------------------------------------------------------------

def alpha_bar(t):
    """Cosine noise schedule (Nichol & Dhariwal): abar(t), t in [0, 1]."""
    return jnp.cos((t + 0.008) / 1.008 * jnp.pi / 2) ** 2


def unet_loss(p, x0, key, cfg: UNetConfig):
    """Denoising score matching: corrupt x0 at a uniform timestep, predict
    the noise, MSE.  Every gradient flows through the packed VJPs of all
    three conv kinds and both sides of every skip concat."""
    kt, kn = jax.random.split(key)
    b = x0.shape[0]
    t = jax.random.uniform(kt, (b,), x0.dtype)
    ab = alpha_bar(t)[:, None, None, None]
    noise = jax.random.normal(kn, x0.shape, x0.dtype)
    x_t = jnp.sqrt(ab) * x0 + jnp.sqrt(1.0 - ab) * noise
    eps = unet_apply(p, x_t, t, cfg)
    return jnp.mean(jnp.square(eps - noise))


def denoise_step(p, x_t, t_frac, cfg: UNetConfig, dt: float):
    """One refinement step: predict eps at ``t_frac`` (B,) and take an
    Euler step of size ``dt`` toward t=0.  The serving bench wraps this as
    its backend fn — each step is its own request, so one step == one
    bucket-batched pass through every planned site."""
    eps = unet_apply(p, x_t, t_frac, cfg)
    return x_t - eps * dt


def denoise_loop(p, x_t, cfg: UNetConfig, steps: int):
    """Sequential Euler refinement, ``steps`` planned decoder calls."""
    for s in reversed(range(steps)):
        tf = jnp.full((x_t.shape[0],), (s + 1) / steps, x_t.dtype)
        eps = unet_apply(p, x_t, tf, cfg)
        x_t = x_t - eps / steps
    return x_t


def sample(p, key, cfg: UNetConfig, n: int = 4, steps: int = 8):
    """Draw from the prior and refine — the serving path's closed form."""
    x_t = jax.random.normal(
        key, (n, cfg.image_hw, cfg.image_hw, cfg.in_c), jnp.float32)
    return denoise_loop(p, x_t, cfg, steps)
