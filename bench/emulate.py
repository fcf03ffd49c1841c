#!/usr/bin/env python3
"""The chip's arithmetic emulated on the CPU, for the readings a limit is
set from when no chip run has them yet.

    JAX_PLATFORMS=cpu python3 bench/emulate.py --seeds 1,2,3 --answers 64
    JAX_PLATFORMS=cpu python3 bench/emulate.py --smoke

For each seed it makes the weights and latents of ``dcgan-lsun64`` as a
run does and prints one JSON line with ``rel_err_max`` (``check``) of
three forwards against the reference at ``highest``:

- ``program``: every operand rounded to bfloat16 before its product and
  the products summed in float32, as one MXU pass at the TPU's default
  precision does;
- ``int8``: the same after per-row int8 weights (the program's own
  int8 path: each row of the superpack over its outputs, scaled by its
  largest magnitude);
- ``bf16``: the reference itself computed in bfloat16.

``--smoke`` is the witness that the emulation is the chip's arithmetic:
it repeats ``chip_smoke.py``'s generator check (the program's own
weights from ``generator_init`` at seed 0, its 46 latents) and prints
the emulated ``program`` reading beside two others, ``exact_kernels``
(only the XLA projection rounded) and ``exact_projection`` (only the
convs rounded), to set against the reading that ``chip_smoke.py`` took
on a TPU v5e.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, harness  # noqa: E402
from bench.models import gan  # noqa: E402


def r16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def q8(k):
    s = jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0
    s = jnp.clip(s, 1e-30, 1e30)
    return jnp.clip(jnp.round(k / s), -127, 127) * s


def one_pass(w, z, model, int8: bool, proj=True, convs=True):
    layers = model["layers"]
    hw0, c0 = layers[0][0], layers[0][1]
    rp = r16 if proj else (lambda a: a)
    rc = r16 if convs else (lambda a: a)
    x = jnp.dot(rp(z), rp(w["proj"]), precision="highest")
    x = jax.nn.relu(x.reshape(z.shape[0], hw0, hw0, c0))
    for i, (hw, c, n, k, s) in enumerate(layers):
        pad = gan._padding(k, s)
        kk = q8(w[f"k{i}"]) if int8 else w[f"k{i}"]
        x = jax.lax.conv_general_dilated(
            rc(x), rc(kk), (1, 1), (pad, pad), lhs_dilation=(s, s),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision="highest")
        x = x + w[f"b{i}"]
        x = jnp.tanh(x) if i == len(layers) - 1 else jax.nn.relu(x)
    return x


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="")
    ap.add_argument("--answers", type=int, default=64)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    model = json.loads((ROOT / "bench/configs/dcgan-lsun64.json")
                       .read_text())["model"]
    if args.smoke:
        return smoke(model)
    fs = {"reference": lambda w, x: gan.reference(w, x, model),
          "program": lambda w, x: one_pass(w, x, model, False),
          "int8": lambda w, x: one_pass(w, x, model, True),
          "bf16": lambda w, x: gan.reference(w, x, model,
                                             dtype=jnp.bfloat16)}
    fs = {k: jax.jit(f) for k, f in fs.items()}
    for seed in (int(s) for s in args.seeds.split(",")):
        k_w, k_pool = jax.random.split(
            jax.random.PRNGKey(harness.seed32(seed)))
        w = gan.init_weights(k_w, model)
        x = gan.make_pool(k_pool, args.answers, model)
        ref = np.asarray(fs["reference"](w, x))
        line = {"seed": seed}
        for name in ("program", "int8", "bf16"):
            y = np.asarray(fs[name](w, x))
            line[name] = float(check.rel_errors(y, ref).max())
        print(json.dumps(line), flush=True)
    return 0


def smoke(model: dict) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.models import gan as program
    cfg = program.DCGAN
    k_g, _ = jax.random.split(jax.random.PRNGKey(0))
    gp, _ = program.generator_init(k_g, cfg)
    p = program.generator_unpack(gp, cfg)
    w = {("k" + k[2:] if k.startswith("dc") else k): v for k, v in p.items()}
    rng = np.random.default_rng(0)
    z = jnp.asarray(np.stack([rng.standard_normal(cfg.z_dim).astype(
        np.float32) for _ in range(1 + 3 + 13 + 29)]))
    ref = np.asarray(gan.reference(w, z, model))
    line = {}
    for name, proj, convs in (("program", True, True),
                              ("exact_kernels", True, False),
                              ("exact_projection", False, True)):
        y = np.asarray(one_pass(w, z, model, False, proj, convs))
        line[name] = float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
