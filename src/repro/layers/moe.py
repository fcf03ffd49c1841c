"""Mixture-of-Experts with expert parallelism.

Two interchangeable implementations:

* ``dense``  — exact: every expert runs on every token, combined by the top-k
  gate mask.  Used by reduced smoke configs (small E) and as the oracle in
  tests.
* ``ep``     — production path: experts sharded over the ``model`` mesh axis
  via ``jax.shard_map``.  Each model-rank serves its E_l local experts for all
  locally-resident tokens with capacity-bounded gather -> FFN -> scatter-add,
  then a ``psum`` over the model axis combines disjoint expert outputs.  This
  keeps routing/token movement *local to each shard* (no SPMD surprise
  all-gathers) and reproduces real MoE FLOPs (cap = T*k*cf/E per expert).

Routers: 'softmax' (DBRX: top-k softmax renormalized) and 'sigmoid_bias'
(DeepSeek-V3 aux-loss-free: sigmoid affinity + selection-only bias).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.layers import common as cm


def moe_init(key, cfg, dtype=jnp.bfloat16):
    d, de, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * scale,
        "bias": jnp.zeros((e,), jnp.float32),   # aux-free balance bias
        "wi": jax.random.normal(ks[1], (e, d, de), dtype) * scale,
        "wg": jax.random.normal(ks[2], (e, d, de), dtype) * scale,
        "wo": jax.random.normal(ks[3], (e, de, d), dtype) * (de ** -0.5),
    }
    s = {
        "router": cm.spec(None, None),
        "bias": cm.spec(None),
        "wi": cm.spec("expert", None, "expert_ffn"),
        "wg": cm.spec("expert", None, "expert_ffn"),
        "wo": cm.spec("expert", "expert_ffn", None),
    }
    return p, s


def _route(x2d, p, cfg):
    """x2d: (T, D) -> (weights (T,k), idx (T,k))."""
    logits = (x2d.astype(jnp.float32) @ p["router"])
    if cfg.router_type == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["bias"]
        _, idx = jax.lax.top_k(sel, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        w = w * cfg.routed_scaling
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(scores, cfg.top_k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx


def _expert_ffn(wi, wg, wo, x, act):
    h = cm.ACTS[act]((x @ wg).astype(jnp.float32)) * (x @ wi).astype(jnp.float32)
    return h.astype(x.dtype) @ wo


def moe_apply_dense(p, x, cfg):
    """Exact all-experts-all-tokens combine (oracle / smoke path)."""
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    w, idx = _route(x2, p, cfg)
    gates = jnp.zeros((b * s, cfg.n_experts), jnp.float32).at[
        jnp.arange(b * s)[:, None], idx].add(w)
    # (T, E) x per-expert FFN, contracted over E
    h_g = jnp.einsum("td,edf->tef", x2.astype(jnp.float32),
                     p["wg"].astype(jnp.float32))
    h_i = jnp.einsum("td,edf->tef", x2.astype(jnp.float32),
                     p["wi"].astype(jnp.float32))
    h = cm.ACTS[cfg.act](h_g) * h_i
    y = jnp.einsum("tef,efd->ted", h, p["wo"].astype(jnp.float32))
    out = jnp.einsum("ted,te->td", y, gates)
    return out.astype(x.dtype).reshape(b, s, d)


def _ep_local_body(x2, router, bias, wi, wg, wo, *, cfg, model_axis,
                   n_model: int):
    """Per-shard body under shard_map. x2: (T_l, D); wi/wg/wo: (E_l, ...)."""
    t_l, d = x2.shape
    e_l = wi.shape[0]
    rank = jax.lax.axis_index(model_axis)
    w, idx = _route(x2, {"router": router, "bias": bias}, cfg)     # (T_l, k)
    cap = min(t_l, max(1, int(t_l * cfg.top_k * cfg.capacity_factor)
                       // cfg.n_experts))
    out = jnp.zeros((t_l, d), jnp.float32)
    for e in range(e_l):
        gid = rank * e_l + e
        gate_e = jnp.where(idx == gid, w, 0.0).sum(-1)             # (T_l,)
        gv, tok = jax.lax.top_k(gate_e, cap)                       # capacity
        xe = jnp.take(x2, tok, axis=0)                             # (cap, D)
        ye = _expert_ffn(wi[e], wg[e], wo[e], xe, cfg.act)
        ye = ye.astype(jnp.float32) * gv[:, None]
        out = out.at[tok].add(jnp.where((gv > 0)[:, None], ye, 0.0))
    out = jax.lax.psum(out, model_axis)
    return out.astype(x2.dtype)


def moe_apply_ep(p, x, cfg, dist):
    """Expert-parallel MoE via shard_map (see module docstring)."""
    b, s, d = x.shape
    mesh = dist.mesh
    ba, ma = dist.batch_axes, dist.model_axis
    n_model = mesh.shape[ma]
    body = partial(_ep_local_body, cfg=cfg, model_axis=ma, n_model=n_model)
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(ba, None), P(None, None), P(None),
                  P(ma), P(ma), P(ma)),
        out_specs=P(ba, None), check_vma=False)
    y = f(x.reshape(b * s, d), p["router"], p["bias"], p["wi"], p["wg"],
          p["wo"])
    return y.reshape(b, s, d)


# ---------------------------------------------------------------------------
# all-to-all expert parallelism (experts sharded over data*model, 1/chip)
# ---------------------------------------------------------------------------
#
# §Perf P2: with 256 experts stored ZeRO-3-sharded over data*model, the
# psum-EP path needs each layer's experts all-gathered over 'data' — XLA
# hoists that gather out of the layer scan, materializing six 54 GB f32
# buffers (measured; see EXPERIMENTS.md).  Production EP instead routes
# *tokens* to resident experts with all_to_all (DeepSeek's own deployment
# shape).  Weights never move; expert grads stay fully sharded.

def _ep_a2a_body(x2, valid, router, bias, wi, wg, wo, *, cfg, axes):
    """Per-shard body. x2: (T_l, D) local tokens; wi/wg/wo: (E_l, ...) the
    experts resident on this chip (usually E_l == 1).  ``valid`` masks
    padding tokens (decode batches are padded up to the EP extent)."""
    t_l, d = x2.shape
    e_l = wi.shape[0]
    e = cfg.n_experts
    n_dev = e // e_l
    w, idx = _route(x2, {"router": router, "bias": bias}, cfg)    # (T_l, k)
    w = w * valid[:, None].astype(w.dtype)
    cap = min(t_l, max(1, int(t_l * cfg.top_k * cfg.capacity_factor) // e))
    # dense gate matrix, then per-expert top-cap (expert-capacity dropping)
    gates = jnp.zeros((t_l, e), jnp.float32).at[
        jnp.arange(t_l)[:, None], idx].add(w)                      # (T_l, E)
    gv, tok = jax.lax.top_k(gates.T, cap)                          # (E, cap)
    buf = jnp.take(x2, tok.reshape(-1), axis=0).reshape(e, cap, d)
    buf = jnp.where((gv > 0)[..., None], buf, 0)
    # route token blocks to their expert's chip
    recv = jax.lax.all_to_all(buf, axes, split_axis=0, concat_axis=0,
                              tiled=True)                          # (E, cap, D)
    recv = recv.reshape(n_dev, e_l, cap, d)
    outs = []
    for el in range(e_l):                                          # static
        h = _expert_ffn(wi[el], wg[el], wo[el],
                        recv[:, el].reshape(n_dev * cap, d), cfg.act)
        outs.append(h.reshape(n_dev, cap, d))
    back = jnp.stack(outs, 1).reshape(e, cap, d)
    ret = jax.lax.all_to_all(back, axes, split_axis=0, concat_axis=0,
                             tiled=True)                           # (E, cap, D)
    y = jnp.zeros((t_l, d), jnp.float32)
    flat_tok = tok.reshape(-1)
    flat_val = (ret.astype(jnp.float32)
                * gv[..., None]).reshape(-1, d)
    y = y.at[flat_tok].add(jnp.where((gv > 0).reshape(-1, 1), flat_val, 0))
    return y.astype(x2.dtype)


def moe_apply_ep_a2a(p, x, cfg, dist):
    b, s, d = x.shape
    mesh = dist.mesh
    ep_axes = dist.rules["expert"]          # e.g. ('data', 'model')
    ba = dist.batch_axes
    n_ep = 1
    for a in ep_axes:
        n_ep *= mesh.shape[a]
    # tokens split over every EP axis (batch axes may overlap with them)
    ba_t = tuple(ba) if isinstance(ba, tuple) else ((ba,) if ba else ())
    tok_axes = tuple(dict.fromkeys(ba_t + tuple(ep_axes)))
    tokens = b * s
    padded = -(-tokens // n_ep) * n_ep
    x2 = x.reshape(tokens, d)
    valid = jnp.ones((tokens,), jnp.bool_)
    if padded != tokens:
        # decode batches smaller than the EP extent: pad with masked tokens
        # (zero gate weight -> dropped at dispatch), §Perf P2 iteration 3
        x2 = jnp.pad(x2, ((0, padded - tokens), (0, 0)))
        valid = jnp.pad(valid, (0, padded - tokens))
    body = partial(_ep_a2a_body, cfg=cfg, axes=ep_axes)
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(tok_axes, None), P(tok_axes), P(None, None), P(None),
                  P(ep_axes), P(ep_axes), P(ep_axes)),
        out_specs=P(tok_axes, None), check_vma=False)
    y = f(x2, valid, p["router"], p["bias"], p["wi"], p["wg"], p["wo"])
    return y[:tokens].reshape(b, s, d)


def update_balance_bias(bias, expert_load, gamma: float = 1e-3):
    """DeepSeek-V3 aux-loss-free balancing (arXiv:2408.15664): between steps,
    nudge each expert's selection bias against its load error.  Not part of
    the gradient — the driver applies it to params['...']['moe']['bias'].

    expert_load: (E,) fraction of routed tokens per expert this step.
    """
    target = 1.0 / bias.shape[-1]
    return bias - gamma * jnp.sign(expert_load - target)


def expert_load_from_idx(idx, n_experts: int):
    """(T, k) routing indices -> (E,) load fractions."""
    one = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    return one / idx.size


def moe_apply(p, x, cfg, dist=None):
    if dist is not None and getattr(cfg, "moe_impl", "dense") == "ep":
        if isinstance(dist.rules.get("expert"), tuple):
            return moe_apply_ep_a2a(p, x, cfg, dist)
        return moe_apply_ep(p, x, cfg, dist)
    return moe_apply_dense(p, x, cfg)
