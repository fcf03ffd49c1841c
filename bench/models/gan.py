"""The DCGAN generator as the benchmark serves and checks it.

``init_weights`` makes plain HWIO kernels from the seed (for the
reference), and ``pack`` lays them out as the program's superpacks
(``ConvPlan.pack``) for the served path; the harness does both on the
device in one jitted call.  The served function is the program's own
``repro.models.gan.generator_apply``.

``reference`` is the plain float32 forward written here with ``lax``
alone, its products at the precision the configuration's check names: a
dense projection of ``z`` to the first plane, then per layer a transposed
conv written as the lhs-dilated correlation with the HWIO kernel and
'SAME' padding ``(pl, ph)``,
``pl = max(0, (k - s + 1) // 2)``, ``ph = k + s - 2 - pl`` (output
``s·H``), a bias, and ReLU (tanh after the last layer).  It imports
nothing of the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import convcount


def _padding(k: int, s: int):
    pl = max(0, (k - s + 1) // 2)
    return (pl, k + s - 2 - pl)


def sites(model: dict) -> list[convcount.Site]:
    return [convcount.Site(f"dc{i}", "transposed", hw, c, n, k, s)
            for i, (hw, c, n, k, s) in enumerate(model["layers"])]


def model_flops(model: dict) -> int:
    """Operations of one image: the projection and every conv site."""
    hw, c = model["layers"][0][0], model["layers"][0][1]
    proj = 2 * model["z_dim"] * hw * hw * c
    return proj + sum(convcount.flops(s) for s in sites(model))


def init_weights(key, model: dict):
    """HWIO weights and biases, He-scaled so every layer's output stays
    of order one."""
    layers = model["layers"]
    hw0, c0 = layers[0][0], layers[0][1]
    ks = jax.random.split(key, 2 * len(layers) + 1)
    w = {"proj": jax.random.normal(ks[0], (model["z_dim"], hw0 * hw0 * c0),
                                   jnp.float32) * model["z_dim"] ** -0.5}
    for i, (hw, c, n, k, s) in enumerate(layers):
        fan_in = k * k * c / (s * s)        # taps one output pixel meets
        gain = 1.0 if i == len(layers) - 1 else 2.0
        w[f"k{i}"] = jax.random.normal(ks[2 * i + 1], (k, k, c, n),
                                       jnp.float32) * (gain / fan_in) ** 0.5
        w[f"b{i}"] = jax.random.normal(ks[2 * i + 2], (n,),
                                       jnp.float32) * 0.05
    return w


def reference(w: dict, z, model: dict, dtype=jnp.float32,
              precision="highest"):
    """The plain forward of a batch of latents ``z``; ``precision`` is the
    products' (``"highest"``: exact float32; ``"default"``: the platform's
    default, one bfloat16 pass on a TPU), and ``dtype`` other than float32
    runs it in that type (the lower-precision reading)."""
    prec = precision if dtype == jnp.float32 else None
    layers = model["layers"]
    hw0, c0 = layers[0][0], layers[0][1]
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    x = jnp.dot(z.astype(dtype), w["proj"], precision=prec)
    x = jax.nn.relu(x.reshape(z.shape[0], hw0, hw0, c0))
    for i, (hw, c, n, k, s) in enumerate(layers):
        pad = _padding(k, s)
        x = jax.lax.conv_general_dilated(
            x, w[f"k{i}"], window_strides=(1, 1), padding=(pad, pad),
            lhs_dilation=(s, s), dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=prec)
        x = x + w[f"b{i}"]
        x = jnp.tanh(x) if i == len(layers) - 1 else jax.nn.relu(x)
    return x.astype(jnp.float32)


def payload_shape(model: dict) -> tuple[int, ...]:
    return (model["z_dim"],)


def make_pool(key, n: int, model: dict):
    """``n`` latents z ~ N(0, 1)."""
    return jax.random.normal(key, (n,) + payload_shape(model), jnp.float32)


def program_config(model: dict, **over):
    from repro.models import gan
    layers = tuple(gan.DeconvLayer(*l) for l in model["layers"])
    cfg = gan.GANConfig(model.get("name", "dcgan"), layers,
                        z_dim=model["z_dim"], backend=model["backend"],
                        wdtype=model.get("wdtype", "float32"))
    return dataclasses.replace(cfg, **over)


def program_plans(cfg):
    from repro.models import gan
    return gan.generator_plans(cfg)


def pack(w: dict, cfg) -> dict:
    """The program's parameter dict from the HWIO weights: superpacks by
    the program's own plans, the layout ``generator_init`` produces."""
    p = {"proj": w["proj"]}
    for i, plan in enumerate(program_plans(cfg)):
        p[f"dc{i}"] = plan.pack(w[f"k{i}"])
        p[f"b{i}"] = w[f"b{i}"]
    return p


def serve_fn(params: dict, cfg):
    from repro.models import gan
    return lambda z: gan.generator_apply(params, z, cfg)


def check_geometry(cfg, model: dict) -> None:
    """The program's sites must be the geometry the reference computes."""
    got = [(p.spec.kind, p.spec.in_hw[0], p.spec.in_c, p.spec.out_c,
            p.spec.kernel_hw[0], p.spec.strides[0], p.spec.padding[0])
           for p in program_plans(cfg)]
    want = [("transposed", hw, c, n, k, s, _padding(k, s))
            for hw, c, n, k, s in model["layers"]]
    if got != want:
        raise RuntimeError(f"program sites {got} differ from the "
                           f"configuration's {want}")
