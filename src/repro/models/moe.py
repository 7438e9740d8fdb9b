"""Mixture-of-Experts layer: top-k router + grouped-local gather dispatch.

Dispatch design (honest-roofline + communication-aware):

* Tokens are processed in G groups aligned with the mesh's data shards
  (G = ctx.data_shards()). Routing, position-in-expert, capacity and the
  dispatch gather are all *local to a group*, so no global token buffer is
  ever materialized — under pjit the gathers partition cleanly per data
  shard (a flat global gather forces GSPMD to replicate the (T, D) token
  buffer on every device, which is catastrophic at 1M tokens).
* Position-in-expert comes from an argsort over the group's assignment
  expert-ids (NOT a one-hot cumsum): HLO FLOPs track true expert FLOPs
  (2*T*k*3*D*F), keeping rooflines honest.
* Experts shard over the mesh "model" axis (EP) when E divides it;
  otherwise expert weights are TP-sharded on the hidden dim. Activations
  are replicated across "model" within a data row (Megatron-style), so
  dispatch needs no all_to_all; the combine gather across model-sharded
  expert outputs becomes the EP all-reduce.
* Capacity overflow drops tokens (capacity_factor 1.25 default), matching
  production dropping-MoE semantics; the aux loss balances load.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.common import dense_init, qdot
from repro.quant.qtypes import QTensor
from repro.quant.quantize import dequantize
from repro.sharding.ctx import constrain, data_shards, model_shards


def _expert_matmul(x: jax.Array, w) -> jax.Array:
    """x: (G, E, C, K) @ w: (E, F, K) -> (G, E, C, F); w may be a QTensor."""
    if isinstance(w, QTensor):
        w = dequantize(w, x.dtype)
    return jnp.einsum("geck,efk->gecf", x, w)


def capacity_of(num_tokens: int, num_experts: int, top_k: int,
                capacity_factor: float) -> int:
    c = int(math.ceil(num_tokens * top_k * capacity_factor / num_experts))
    return max(8, int(math.ceil(c / 8)) * 8)  # pad to VPU sublane


@obs.scoped("mlp")
def moe_block(p, x: jax.Array, *, num_experts: int, top_k: int,
              capacity_factor: float = 1.25):
    """x: (B, S, D) -> (y: (B, S, D), aux: dict with load-balancing loss)."""
    b, s, d = x.shape
    t = b * s
    e, k = num_experts, top_k
    g = data_shards()
    if t % g != 0 or t // g < e:  # decode with tiny batches etc.
        g = 1
    tg = t // g
    xt = constrain(x.reshape(g, tg, d), ("batch", None, None))

    # --- routing (f32 for numerics) ----------------------------------------
    router_logits = qdot(xt, p["router"], out_dtype=jnp.float32)  # (G,Tg,E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)                    # (G,Tg,K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # load-balancing auxiliary loss (Switch-style), computed globally
    me = jnp.mean(probs, axis=(0, 1))                             # (E,)
    ce = jnp.mean(jax.nn.one_hot(expert_idx, e, dtype=jnp.float32).sum(2),
                  axis=(0, 1))
    aux_loss = e * jnp.sum(me * ce)

    # --- per-group position-in-expert via stable argsort --------------------
    flat_e = expert_idx.reshape(g, tg * k)                         # (G, Tg*K)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    seg_start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(e)))(sorted_e)  # (G, E)
    pos_sorted = (jnp.arange(tg * k, dtype=jnp.int32)[None]
                  - jnp.take_along_axis(seg_start, sorted_e, axis=1))
    pos = jnp.zeros((g, tg * k), jnp.int32)
    pos = jax.vmap(lambda p_, o, v: p_.at[o].set(v))(pos, order, pos_sorted)

    cap = capacity_of(tg, e, k, capacity_factor)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)            # (G, Tg*K)

    # --- dispatch: per-group gather into (G, E, C, D) -----------------------
    tok_id = jnp.repeat(jnp.arange(tg, dtype=jnp.int32), k)[None]  # (1,Tg*K)
    tok_id = jnp.broadcast_to(tok_id, (g, tg * k))
    table = jax.vmap(lambda s_, t_: jnp.zeros((e * cap,), jnp.int32)
                     .at[s_].set(t_, mode="drop"))(slot, tok_id)
    xe = jax.vmap(lambda xg, tbl: jnp.take(xg, tbl, axis=0))(xt, table)
    xe = constrain(xe.reshape(g, e, cap, d),
                   ("batch", "expert", None, None))

    # zero out unfilled slots (token 0 would leak in otherwise)
    filled = jax.vmap(lambda s_: jnp.zeros((e * cap,), jnp.bool_)
                      .at[s_].set(True, mode="drop"))(slot)
    xe = xe * filled.reshape(g, e, cap, 1).astype(xe.dtype)

    # --- expert computation (SwiGLU) ----------------------------------------
    # EP when E divides the model axis; otherwise expert-TP: shard the
    # expert hidden dim F over "model" (E replicated) so the (E, C, F)
    # activations never materialize unsharded.
    ep = e % model_shards() == 0
    hid_spec = (("batch", "expert", None, None) if ep
                else ("batch", None, None, "model"))
    gt = constrain(_expert_matmul(xe, p["w_gate"]), hid_spec)
    up = constrain(_expert_matmul(xe, p["w_up"]), hid_spec)
    h = jax.nn.silu(gt.astype(jnp.float32)).astype(x.dtype) * up
    h = constrain(h, hid_spec)
    ye = _expert_matmul(h, p["w_down"])                            # (G,E,C,D)
    ye = constrain(ye, ("batch", "expert", None, None))

    # --- combine: per-group gather back, weight by gates ---------------------
    ye_flat = ye.reshape(g, e * cap, d)
    slot_c = jnp.minimum(slot, e * cap - 1)
    y_asgn = jax.vmap(lambda yg, s_: jnp.take(yg, s_, axis=0))(ye_flat,
                                                               slot_c)
    y_asgn = jnp.where(keep[..., None], y_asgn, 0)                 # (G,Tg*K,D)
    y = jnp.sum(y_asgn.reshape(g, tg, k, d)
                * gate.astype(y_asgn.dtype)[..., None], axis=2)
    y = constrain(y, ("batch", None, None))
    return y.reshape(b, s, d), {"moe_aux_loss": aux_loss}


def init_moe_params(key, d_model: int, expert_d_ff: int, num_experts: int,
                    num_layers: int, dtype):
    ks = jax.random.split(key, 4)
    e, d, f = num_experts, d_model, expert_d_ff
    down_scale = 1.0 / np.sqrt(2 * max(num_layers, 1))

    def stack(k, out, inp, scale=1.0):
        keys = jax.random.split(k, e)
        return jnp.stack([dense_init(kk, out, inp, dtype, scale=scale)
                          for kk in keys])

    return {
        "router": dense_init(ks[0], e, d, jnp.float32),
        "w_gate": stack(ks[1], f, d),
        "w_up": stack(ks[2], f, d),
        "w_down": stack(ks[3], d, f, scale=down_scale),
    }
