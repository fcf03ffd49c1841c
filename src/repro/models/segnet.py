"""DilatedNet-style semantic segmentation on the HUGE² plan/executor engine.

The paper motivates the dilated (atrous) convolution with the semantic-
segmentation workload (DeepLab / DilatedNet context aggregation); this model
makes that scenario an end-to-end resident of the engine rather than a
benchmark docstring:

- a small strided **front-end** (3x3 convs, two stride-2 downsamples) built
  from planned 'conv' sites, and
- an **atrous context module** (3x3 dilated convs, exponentially growing
  dilation 1,2,4,8,1 at constant resolution — the DilatedNet trick for
  growing receptive field without losing resolution or inserting a single
  kernel zero) built from planned 'dilated' sites, capped by a 1x1
  classifier head.

Every convolution site gets a ``ConvPlan`` built once at model load
(``segnet_plans``), and **all** weights are stored in the single-phase
tap-major superpack ``(R·S·C, N)`` — mirroring ``models/gan.py``'s packed
convention — so inference never re-slices a kernel and training runs the
§3.2.3 custom VJPs directly on the packed layout.  The ``backend`` field is
a plan policy ('xla' | 'pallas' | 'auto') consumed at plan-build time.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.autotune import AutotunePolicy
from repro.core.plan import ConvPlan, ConvSpec, plan_conv
from repro.layers import common as cm


@dataclasses.dataclass(frozen=True)
class SegLayer:
    kind: str          # 'conv' (front-end / head) | 'dilated' (context)
    in_hw: int
    in_c: int
    out_c: int
    kernel: int = 3
    stride: int = 1
    dilation: int = 1


def atrous_padding(kernel: int, dilation: int):
    """'SAME'-style padding for an odd kernel at dilation d: the dilated tap
    reach is (k-1)·d + 1, so pad d·(k-1)/2 per side keeps the resolution
    (stride 1) or halves it exactly (stride 2, even input)."""
    half = dilation * (kernel - 1) // 2
    return ((half, half), (half, half))


def _front_end(in_hw: int, in_c: int, width: int) -> tuple[SegLayer, ...]:
    return (
        SegLayer("conv", in_hw, in_c, width // 4),
        SegLayer("conv", in_hw, width // 4, width // 2, stride=2),
        SegLayer("conv", in_hw // 2, width // 2, width // 2),
        SegLayer("conv", in_hw // 2, width // 2, width, stride=2),
    )


def _context(hw: int, width: int) -> tuple[SegLayer, ...]:
    return tuple(SegLayer("dilated", hw, width, width, dilation=d)
                 for d in (1, 2, 4, 8, 1))


@dataclasses.dataclass(frozen=True)
class SegNetConfig:
    name: str
    in_hw: int = 64
    in_c: int = 3
    width: int = 128
    num_classes: int = 21
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
    def layers(self) -> tuple[SegLayer, ...]:
        front = _front_end(self.in_hw, self.in_c, self.width)
        ctx = _context(self.in_hw // 4, self.width)
        head = (SegLayer("conv", self.in_hw // 4, self.width,
                         self.num_classes, kernel=1),)
        return front + ctx + head

    @property
    def out_hw(self) -> int:
        return self.in_hw // 4


SEGNET = SegNetConfig("segnet")                        # edge default
SEGNET_TINY = SegNetConfig("segnet-tiny", in_hw=32, width=32, num_classes=5)


# ---------------------------------------------------------------------------
# load-time planning: one ConvPlan per convolution site
# ---------------------------------------------------------------------------

def segnet_plans(cfg: SegNetConfig, dtype=jnp.float32) -> tuple[ConvPlan, ...]:
    """Plans for every front-end / context / head site (cached; the build
    cost is paid once at model load)."""
    plans = []
    for l in cfg.layers:
        plans.append(plan_conv(ConvSpec(
            kind=l.kind, in_hw=(l.in_hw, l.in_hw), in_c=l.in_c,
            out_c=l.out_c, kernel_hw=(l.kernel, l.kernel),
            strides=(l.stride, l.stride),
            padding=atrous_padding(l.kernel, l.dilation),
            dilation=(l.dilation, l.dilation),
            dtype=str(jnp.dtype(dtype)), backend=cfg.backend,
            spatial=cfg.spatial, wdtype=cfg.wdtype),
            autotune=cfg.autotune))
    return tuple(plans)


# ---------------------------------------------------------------------------
# params: every conv weight stored superpacked (R·S·C, N)
# ---------------------------------------------------------------------------

def segnet_init(key, cfg: SegNetConfig, dtype=jnp.float32, dist=None):
    """Superpacked params with ``(conv_taps, conv_out)`` logical specs;
    pass a ``DistContext`` to get them placed on its mesh (out-channels
    sharded under the default rules) for data-parallel serving."""
    plans = segnet_plans(cfg, dtype)
    ks = jax.random.split(key, len(cfg.layers))
    p, s = {}, {}
    for i, (l, plan) in enumerate(zip(cfg.layers, plans)):
        fan_in = l.kernel * l.kernel * l.in_c
        kernel = jax.random.normal(
            ks[i], (l.kernel, l.kernel, l.in_c, l.out_c),
            dtype) * (2.0 / fan_in) ** 0.5
        p[f"w{i}"] = plan.pack(kernel)          # (R·S·C, N) superpack
        p[f"b{i}"] = jnp.zeros((l.out_c,), dtype)
        s[f"w{i}"] = cm.spec("conv_taps", "conv_out")   # shard out-channels
        s[f"b{i}"] = cm.spec("conv_out")
    if dist is not None:
        p = dist.shard_params(p, s)
    return p, s


def segnet_apply(p, x, cfg: SegNetConfig):
    """x: (B, in_hw, in_hw, in_c) -> logits (B, in_hw/4, in_hw/4, classes).

    Every conv is ``plan.apply`` on the stored superpack — one launch / one
    wide GEMM per site, custom VJP on the packed layout under ``jax.grad``.
    """
    plans = segnet_plans(cfg, x.dtype)          # cache hits after model load
    n_layers = len(plans)
    for i, plan in enumerate(plans):
        with jax.named_scope(f"w{i}"):
            x = plan.apply(x, p[f"w{i}"])
        x = x + p[f"b{i}"]
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


def segnet_unpack(p, cfg: SegNetConfig):
    """Packed params -> full (R,S,C,N) HWIO kernels (offline export)."""
    plans = segnet_plans(cfg)
    out = dict(p)
    for i, plan in enumerate(plans):
        out[f"w{i}"] = plan.unpack(p[f"w{i}"])
    return out


def upsample_logits(logits, factor: int = 4):
    """Nearest-neighbour upsample back to input resolution (the DilatedNet
    paper uses learned/bilinear upsampling; nearest keeps the example pure
    engine work)."""
    return jnp.repeat(jnp.repeat(logits, factor, axis=-3), factor, axis=-2)


def segnet_loss(p, x, labels, cfg: SegNetConfig):
    """Mean pixel cross-entropy at feature resolution.

    labels: (B, out_hw, out_hw) int class ids.
    """
    logits = segnet_apply(p, x, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -ll.mean()
