"""Convolutional VAE on the HUGE² plan/executor engine (paper Fig. 1).

The abstract names GANs *and* VAEs as the upsampling-bound generative
workloads; this module makes the VAE an end-to-end resident of the engine:

- **encoder** — strided 'conv' sites (kernel 4, stride 2, the DCGAN-
  discriminator mirror) down to a small feature plane, then dense heads for
  ``mu`` / ``logvar``;
- **decoder** — the paper's Fig. 1 shape: a dense projection up to the
  feature plane followed by transposed-conv sites back to image resolution
  (the part HUGE² untangles — every deconv is phase-decomposed at plan
  time and executes as a single launch).

Every convolution site gets a ``ConvPlan`` built once at model load
(``vae_plans``) and every conv weight is stored **superpacked** — the
encoder's single-phase ``(R·S·C, N)`` flatten, the decoder's multi-phase
``(Σ T_h·T_w·C, N)`` concatenation — with logical sharding axes
``(conv_taps, conv_out)`` like ``models/gan.py`` / ``models/segnet.py``.
Training maximizes the ELBO with a Gaussian likelihood (MSE reconstruction
+ KL to the unit prior), differentiating **through the packed custom
VJPs** in both halves: the encoder backward runs the mirrored transposed-
tap schedule, the decoder backward the §3.2.3 strided/dilated forms,
directly on the superpacked layout.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.autotune import AutotunePolicy
from repro.core.plan import ConvPlan, ConvSpec, plan_conv
from repro.layers import common as cm
from repro.models.gan import DeconvLayer, deconv_padding


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    name: str
    image_hw: int = 32
    in_c: int = 3
    widths: tuple[int, ...] = (64, 128)   # one stride-2 stage per width
    latent_dim: int = 64
    kernel: int = 4
    backend: str = "xla"            # plan policy: 'xla' | 'pallas' | 'auto'
    # measured-route policy (None = heuristic routes)
    autotune: Optional[AutotunePolicy] = None
    # plane-parallel policy: (D_h, D_w) requested device tiling per site
    # (see ``GANConfig.spatial``); single-device fallback is always kept
    spatial: tuple[int, int] = (1, 1)
    # weight storage dtype for every conv site: 'float32' (dense) or 'int8'
    # (quantized superpacks — ``ConvSpec.wdtype``); activations stay f32
    wdtype: str = "float32"

    @property
    def feat_hw(self) -> int:
        return self.image_hw // (2 ** len(self.widths))

    @property
    def feat_c(self) -> int:
        return self.widths[-1]

    @property
    def encoder_layers(self) -> tuple[DeconvLayer, ...]:
        """Strided 'conv' stages, image -> feature plane (in_hw is the
        stage's input resolution; reusing DeconvLayer keeps one layer
        record across all engine model zoos)."""
        chans = (self.in_c,) + self.widths
        return tuple(
            DeconvLayer(self.image_hw // 2 ** i, chans[i], chans[i + 1],
                        self.kernel, 2)
            for i in range(len(self.widths)))

    @property
    def decoder_layers(self) -> tuple[DeconvLayer, ...]:
        """Transposed stages, feature plane -> image (the Fig. 1 decoder) —
        the exact mirror of the encoder."""
        chans = (self.in_c,) + self.widths
        return tuple(
            DeconvLayer(self.image_hw // 2 ** (i + 1), chans[i + 1], chans[i],
                        self.kernel, 2)
            for i in reversed(range(len(self.widths))))


VAE = VAEConfig("vae")                                       # 32px CIFAR-ish
VAE_TINY = VAEConfig("vae-tiny", image_hw=16, widths=(16, 32), latent_dim=8)


# ---------------------------------------------------------------------------
# load-time planning: one ConvPlan per site, both halves
# ---------------------------------------------------------------------------

def encoder_plans(cfg: VAEConfig, dtype=jnp.float32) -> tuple[ConvPlan, ...]:
    plans = []
    for l in cfg.encoder_layers:
        k = l.kernel
        plans.append(plan_conv(ConvSpec(
            kind="conv", in_hw=(l.in_hw, l.in_hw), in_c=l.in_c,
            out_c=l.out_c, kernel_hw=(k, k), strides=(l.stride, l.stride),
            padding=((k // 2, (k - 1) // 2), (k // 2, (k - 1) // 2)),
            dtype=str(jnp.dtype(dtype)), backend=cfg.backend,
            spatial=cfg.spatial, wdtype=cfg.wdtype),
            autotune=cfg.autotune))
    return tuple(plans)


def decoder_plans(cfg: VAEConfig, dtype=jnp.float32) -> tuple[ConvPlan, ...]:
    plans = []
    for l in cfg.decoder_layers:
        plans.append(plan_conv(ConvSpec(
            kind="transposed", in_hw=(l.in_hw, l.in_hw), in_c=l.in_c,
            out_c=l.out_c, kernel_hw=(l.kernel, l.kernel),
            strides=(l.stride, l.stride),
            padding=deconv_padding(l.kernel, l.stride),
            dtype=str(jnp.dtype(dtype)), backend=cfg.backend,
            spatial=cfg.spatial, wdtype=cfg.wdtype),
            autotune=cfg.autotune))
    return tuple(plans)


def vae_plans(cfg: VAEConfig, dtype=jnp.float32):
    return encoder_plans(cfg, dtype) + decoder_plans(cfg, dtype)


# ---------------------------------------------------------------------------
# params: every conv weight superpacked, dense heads for the latent
# ---------------------------------------------------------------------------

def vae_init(key, cfg: VAEConfig, dtype=jnp.float32, dist=None):
    """Superpacked params + logical specs; pass a ``DistContext`` to get
    the tree placed on its mesh (out-channels sharded by default)."""
    enc, dec = encoder_plans(cfg, dtype), decoder_plans(cfg, dtype)
    n_keys = len(enc) + len(dec) + 4
    ks = iter(jax.random.split(key, n_keys))
    p, s = {}, {}
    for i, (l, plan) in enumerate(zip(cfg.encoder_layers, enc)):
        fan_in = l.kernel * l.kernel * l.in_c
        kernel = jax.random.normal(
            next(ks), (l.kernel, l.kernel, l.in_c, l.out_c),
            dtype) * (2.0 / fan_in) ** 0.5
        p[f"enc{i}"] = plan.pack(kernel)
        p[f"encb{i}"] = jnp.zeros((l.out_c,), dtype)
        s[f"enc{i}"] = cm.spec("conv_taps", "conv_out")
        s[f"encb{i}"] = cm.spec("conv_out")
    fdim = cfg.feat_hw * cfg.feat_hw * cfg.feat_c
    for head in ("mu", "lv"):
        p[f"{head}_w"] = jax.random.normal(
            next(ks), (fdim, cfg.latent_dim), dtype) * fdim ** -0.5
        p[f"{head}_b"] = jnp.zeros((cfg.latent_dim,), dtype)
        s[f"{head}_w"] = cm.spec(None, None)
        s[f"{head}_b"] = cm.spec(None)
    p["proj"] = jax.random.normal(
        next(ks), (cfg.latent_dim, fdim), dtype) * cfg.latent_dim ** -0.5
    p["projb"] = jnp.zeros((fdim,), dtype)
    s["proj"] = cm.spec(None, "conv_out")
    s["projb"] = cm.spec("conv_out")
    for i, (l, plan) in enumerate(zip(cfg.decoder_layers, dec)):
        kernel = jax.random.normal(
            next(ks), (l.kernel, l.kernel, l.in_c, l.out_c), dtype) * 0.02
        p[f"dec{i}"] = plan.pack(kernel)
        p[f"decb{i}"] = jnp.zeros((l.out_c,), dtype)
        s[f"dec{i}"] = cm.spec("conv_taps", "conv_out")
        s[f"decb{i}"] = cm.spec("conv_out")
    if dist is not None:
        p = dist.shard_params(p, s)
    return p, s


# ---------------------------------------------------------------------------
# apply: planned execution on the superpacks, end to end
# ---------------------------------------------------------------------------

def encode(p, x, cfg: VAEConfig):
    """x (B, H, W, C) -> (mu, logvar), each (B, latent_dim)."""
    plans = encoder_plans(cfg, x.dtype)        # cache hits after model load
    for i, plan in enumerate(plans):
        with jax.named_scope(f"enc{i}"):
            x = plan.apply(x, p[f"enc{i}"])
        x = jax.nn.relu(x + p[f"encb{i}"])
    h = x.reshape(x.shape[0], -1)
    return h @ p["mu_w"] + p["mu_b"], h @ p["lv_w"] + p["lv_b"]


def decode(p, z, cfg: VAEConfig):
    """z (B, latent_dim) -> recon (B, H, W, C) — the Fig. 1 decoder, every
    transposed conv one planned launch on its superpack."""
    plans = decoder_plans(cfg, z.dtype)
    h = jax.nn.relu(z @ p["proj"] + p["projb"])
    x = h.reshape(z.shape[0], cfg.feat_hw, cfg.feat_hw, cfg.feat_c)
    for i, plan in enumerate(plans):
        with jax.named_scope(f"dec{i}"):
            x = plan.apply(x, p[f"dec{i}"])
        x = x + p[f"decb{i}"]
        x = jnp.tanh(x) if i == len(plans) - 1 else jax.nn.relu(x)
    return x


def reparameterize(key, mu, logvar):
    return mu + jnp.exp(0.5 * logvar) * jax.random.normal(
        key, mu.shape, mu.dtype)


def vae_apply(p, x, key, cfg: VAEConfig):
    mu, logvar = encode(p, x, cfg)
    z = reparameterize(key, mu, logvar)
    return decode(p, z, cfg), mu, logvar


def elbo_loss(p, x, key, cfg: VAEConfig, beta: float = 1.0):
    """Negative ELBO: Gaussian reconstruction (MSE, unit variance) + KL to
    the unit prior, both per-image sums averaged over the batch.  Every
    gradient flows through the packed custom VJPs of both halves."""
    recon, mu, logvar = vae_apply(p, x, key, cfg)
    se = jnp.square(recon - x).sum(axis=(1, 2, 3))
    kl = -0.5 * (1.0 + logvar - jnp.square(mu)
                 - jnp.exp(logvar)).sum(axis=-1)
    return (se + beta * kl).mean()


def sample(p, key, cfg: VAEConfig, n: int = 16):
    """Decode n draws from the prior (generation path == serving path)."""
    z = jax.random.normal(key, (n, cfg.latent_dim))
    return decode(p, z, cfg)
