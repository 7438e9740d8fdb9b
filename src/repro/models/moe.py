"""Mixture-of-Experts layer: top-k router, expert dispatch, shared experts.

Routing runs in float32. Scores are the softmax of the router logits
(arctic, grok) or their sigmoid (DeepSeek-V3, ``scoring="sigmoid"``). An
optional per-expert correction bias (``e_bias``) is added to the scores
for choosing the top-k experts only: a chosen expert's weight is its score
without the bias, renormalized over the chosen ones and multiplied by
``routed_scaling``.

Dispatch takes one of two forms; both keep HLO FLOPs at the true expert
FLOPs (2*T*k*3*D*F, up to capacity padding), keeping rooflines honest.

* Unsharded traces (one device: serving on a chip, CPU): the T*k (token,
  expert) assignments are sorted by expert (stable, so a token's rows
  keep their order within an expert), the rows are gathered once, and
  each expert weight stack runs as one grouped matmul in which every
  expert multiplies exactly the rows routed to it. On a TPU that is JAX's
  megablox ``gmm`` Pallas kernel: XLA lowers ``jax.lax.ragged_dot`` there
  to a dense convolution over every group (64x the expert FLOPs at 64
  experts). Elsewhere it is ``jax.lax.ragged_dot``.
* Sharded traces (a mesh; the compiler cannot partition a Pallas call or
  a ragged dot by group): tokens are processed in G groups aligned with
  the mesh's data shards (G = ctx.data_shards()). Position-in-expert (an
  argsort over the group's assignment expert-ids), capacity and the
  dispatch gather are all *local to a group*, so no global token buffer
  is ever materialized — under pjit the gathers partition cleanly per
  data shard (a flat global gather forces GSPMD to replicate the (T, D)
  token buffer on every device, which is catastrophic at 1M tokens).
  Experts shard over the mesh "model" axis (EP) when E divides it;
  otherwise expert weights are TP-sharded on the hidden dim. Activations
  are replicated across "model" within a data row (Megatron-style), so
  dispatch needs no all_to_all; the combine gather across model-sharded
  expert outputs becomes the EP all-reduce. Each expert has ``capacity``
  rows per group; dropless, that is the group's token count.

A quantized expert stack is dequantized whole first, under the
``ewq/dequant`` scope.

Serving (prefill and decode) never drops a token: ``capacity_factor=None``
is dropless. Training may cap each expert at ``capacity_of(...)``
assignments (1.25 by default): the assignments past it weigh nothing,
production dropping-MoE semantics; the aux loss balances load.

Shared experts (DeepSeek-V3: ``n_shared_experts`` MLPs of the expert
width, served as one SwiGLU MLP of their total width) see every token and
are added to the routed output.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.qmatmul.ops import count_dispatch
from repro.models import mlp as M
from repro.models.common import dense_init
from repro.quant.qtypes import QTensor
from repro.quant.quantize import dequantize
from repro.sharding.ctx import (constrain, data_shards, model_shards,
                                sharded_trace)

# rows (M, K) x stack (E, N, K) -> (M, N), rows grouped by expert
_RAGGED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (2,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])


def capacity_of(num_tokens: int, num_experts: int, top_k: int,
                capacity_factor: float) -> int:
    c = int(math.ceil(num_tokens * top_k * capacity_factor / num_experts))
    return max(8, int(math.ceil(c / 8)) * 8)  # pad to VPU sublane


# VMEM a megablox step may take: double-buffered row, weight and output
# tiles plus the f32 accumulator (the compiler's scoped limit is ~28 MiB)
GMM_VMEM_BYTES = 14 * 2**20


def _gmm_tiling(m: int, k: int, n: int) -> tuple:
    """(tm, tk, tn) of a megablox call: row tiles of 128 (decode: a few
    rows per expert) or 512 (prefill), output columns whole up to 2048
    (else blocks of 512, 256 or 128 that divide N), and the widest
    contraction block dividing K that keeps a step within
    ``GMM_VMEM_BYTES``."""
    tm = 128 if m <= 2048 else 512
    tn = n if n <= 2048 and n % 128 == 0 else next(
        c for c in (512, 256, 128) if n % c == 0)
    for tk in (k, 2048, 1024, 512, 256, 128):
        if (k % tk == 0 and tk % 128 == 0 and 4 * tm * tk + 4 * tn * tk
                + 12 * tm * tn <= GMM_VMEM_BYTES):
            return tm, tk, tn
    return tm, 128, tn


def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
        interpret: bool = False) -> jax.Array:
    """Megablox grouped matmul of expert-sorted rows ``x`` (M, K) with the
    (E, N, K) stack ``w`` -> (M, N) f32; rows are padded to the row tile."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm
    m, k = x.shape
    tiling = _gmm_tiling(m, k, w.shape[1])
    pad = -m % tiling[0]
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    y = _gmm(x, w, group_sizes, preferred_element_type=jnp.float32,
             tiling=tiling, transpose_rhs=True, interpret=interpret)
    return y[:m]


def _dequant_experts(w, x: jax.Array):
    """The expert stack ``w`` in ``x``'s dtype; a QTensor is dequantized
    whole (and counted) under ``ewq/dequant``."""
    if isinstance(w, QTensor):
        count_dispatch("dequant", x.shape[0], regime="expert")
        with jax.named_scope("ewq/dequant"):
            w = dequantize(w, x.dtype)
    return w.astype(x.dtype)


def grouped_matmul(x: jax.Array, w, group_sizes: jax.Array) -> jax.Array:
    """y[i] = x[i] @ w[e(i)].T for rows ``x`` (M, K) sorted by expert,
    ``group_sizes[e]`` rows for expert e; ``w`` (E, N, K) may be a QTensor.
    Returns (M, N) f32."""
    w = _dequant_experts(w, x)
    if jax.default_backend() == "tpu" and not sharded_trace():
        return gmm(x, w, group_sizes)
    return jax.lax.ragged_dot_general(x, w, group_sizes, _RAGGED,
                                      preferred_element_type=jnp.float32)


def route(p, xt: jax.Array, top_k: int, scoring: str = "softmax",
          routed_scaling: float = 1.0):
    """xt: (T, D) -> (expert ids (T, k), weights (T, k) f32, aux loss)."""
    e = p["router"].shape[0]
    logits = jnp.einsum("td,ed->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    choice = scores
    if "e_bias" in p:
        choice = scores + p["e_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(choice, top_k)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * routed_scaling
    # load-balancing auxiliary loss (Switch-style) over normalized scores
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    ce = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1), axis=0)
    aux_loss = e * jnp.sum(jnp.mean(probs, axis=0) * ce)
    return idx, gate, aux_loss


def _sorted_dispatch(p, xt, idx, gate, e: int, k: int,
                     capacity_factor: Optional[float]) -> jax.Array:
    """Unsharded traces: grouped matmuls over expert-sorted rows.
    (T, D) -> (T, D) f32."""
    t, d = xt.shape
    with jax.named_scope("moe/dispatch"):
        flat = idx.reshape(t * k)
        order = jnp.argsort(flat, stable=True)         # rows by expert
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        xs = jnp.take(xt, order // k, axis=0)          # (T*k, D)
    with jax.named_scope("moe/experts"):
        gt = grouped_matmul(xs, p["w_gate"], sizes)
        up = grouped_matmul(xs, p["w_up"], sizes)
        h = (jax.nn.silu(gt) * up).astype(xt.dtype)
        ys = grouped_matmul(h, p["w_down"], sizes)     # (T*k, D) f32
    with jax.named_scope("moe/combine"):
        w_sorted = jnp.take(gate.reshape(t * k), order)
        if capacity_factor is not None:
            start = jnp.cumsum(sizes) - sizes
            pos = jnp.arange(t * k) - jnp.take(start, jnp.take(flat, order))
            cap = capacity_of(t, e, k, capacity_factor)
            w_sorted = jnp.where(pos < cap, w_sorted, 0.0)
        inv = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        y = jnp.take(ys * w_sorted[:, None], inv, axis=0)
        return jnp.sum(y.reshape(t, k, d), axis=1)


def _expert_matmul(x: jax.Array, w) -> jax.Array:
    """x: (G, E, C, K) @ w: (E, F, K) -> (G, E, C, F); w may be a QTensor."""
    return jnp.einsum("geck,efk->gecf", x, _dequant_experts(w, x))


def _local_dispatch(p, xt, idx, gate, e: int, k: int,
                    capacity_factor: Optional[float]) -> jax.Array:
    """Sharded traces: group-local capacity dispatch with expert-parallel
    (or expert-TP) activations. (T, D) -> (T, D) f32, summed in ``xt``'s
    dtype."""
    t, d = xt.shape
    g = data_shards()
    if t % g != 0 or t // g < e:  # decode with tiny batches etc.
        g = 1
    tg = t // g
    # a token picks k distinct experts, so tg rows per expert drop nothing
    cap = (tg if capacity_factor is None
           else capacity_of(tg, e, k, capacity_factor))
    with jax.named_scope("moe/dispatch"):
        xg = constrain(xt.reshape(g, tg, d), ("batch", None, None))
        # per-group position-in-expert via stable argsort
        flat_e = idx.reshape(g, tg * k)
        order = jnp.argsort(flat_e, axis=1, stable=True)
        sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
        seg_start = jax.vmap(
            lambda se: jnp.searchsorted(se, jnp.arange(e)))(sorted_e)
        pos_sorted = (jnp.arange(tg * k, dtype=jnp.int32)[None]
                      - jnp.take_along_axis(seg_start, sorted_e, axis=1))
        pos = jnp.zeros((g, tg * k), jnp.int32)
        pos = jax.vmap(lambda p_, o, v: p_.at[o].set(v))(pos, order,
                                                          pos_sorted)
        keep = pos < cap
        slot = jnp.where(keep, flat_e * cap + pos, e * cap)
        # per-group gather into (G, E, C, D)
        tok_id = jnp.broadcast_to(
            jnp.repeat(jnp.arange(tg, dtype=jnp.int32), k)[None],
            (g, tg * k))
        table = jax.vmap(lambda s_, t_: jnp.zeros((e * cap,), jnp.int32)
                         .at[s_].set(t_, mode="drop"))(slot, tok_id)
        xe = jax.vmap(lambda xs, tbl: jnp.take(xs, tbl, axis=0))(xg, table)
        xe = constrain(xe.reshape(g, e, cap, d),
                       ("batch", "expert", None, None))
        # zero out unfilled slots (token 0 would leak in otherwise)
        filled = jax.vmap(lambda s_: jnp.zeros((e * cap,), jnp.bool_)
                          .at[s_].set(True, mode="drop"))(slot)
        xe = xe * filled.reshape(g, e, cap, 1).astype(xe.dtype)
    with jax.named_scope("moe/experts"):
        # EP when E divides the model axis; otherwise expert-TP: shard the
        # expert hidden dim F over "model" (E replicated) so the (E, C, F)
        # activations never materialize unsharded.
        ep = e % model_shards() == 0
        hid_spec = (("batch", "expert", None, None) if ep
                    else ("batch", None, None, "model"))
        gt = constrain(_expert_matmul(xe, p["w_gate"]), hid_spec)
        up = constrain(_expert_matmul(xe, p["w_up"]), hid_spec)
        h = jax.nn.silu(gt.astype(jnp.float32)).astype(xt.dtype) * up
        h = constrain(h, hid_spec)
        ye = _expert_matmul(h, p["w_down"])                      # (G,E,C,D)
        ye = constrain(ye, ("batch", "expert", None, None))
    with jax.named_scope("moe/combine"):
        # per-group gather back, weighted by the gates
        ye_flat = ye.reshape(g, e * cap, d)
        slot_c = jnp.minimum(slot, e * cap - 1)
        y_asgn = jax.vmap(lambda yg, s_: jnp.take(yg, s_, axis=0))(ye_flat,
                                                                   slot_c)
        y_asgn = jnp.where(keep[..., None], y_asgn, 0)           # (G,Tg*K,D)
        y = jnp.sum(y_asgn.reshape(g, tg, k, d)
                    * gate.reshape(g, tg, k, 1).astype(y_asgn.dtype), axis=2)
        y = constrain(y, ("batch", None, None))
    return y.reshape(t, d).astype(jnp.float32)


@obs.scoped("mlp")
def moe_block(p, x: jax.Array, *, num_experts: int, top_k: int,
              capacity_factor: Optional[float] = 1.25,
              scoring: str = "softmax", routed_scaling: float = 1.0,
              shared: Any = None):
    """x: (B, S, D) -> (y: (B, S, D), aux: dict with load-balancing loss).
    ``capacity_factor=None`` is dropless; ``shared`` holds the shared
    experts' SwiGLU MLP."""
    b, s, d = x.shape
    t, e, k = b * s, num_experts, top_k
    xt = x.reshape(t, d)
    with jax.named_scope("moe/route"):
        idx, gate, aux_loss = route(p, xt, k, scoring, routed_scaling)
    dispatch = _local_dispatch if sharded_trace() else _sorted_dispatch
    y = dispatch(p, xt, idx, gate, e, k, capacity_factor)
    if shared is not None:
        with jax.named_scope("moe/shared"):
            y = y + M.swiglu(shared, xt).astype(jnp.float32)
    y = constrain(y.astype(x.dtype).reshape(b, s, d), ("batch", None, None))
    return y, {"moe_aux_loss": aux_loss}


def init_moe_params(key, d_model: int, expert_d_ff: int, num_experts: int,
                    num_layers: int, dtype, router_bias: bool = False):
    ks = jax.random.split(key, 4)
    e, d, f = num_experts, d_model, expert_d_ff
    down_scale = 1.0 / np.sqrt(2 * max(num_layers, 1))

    def stack(k, out, inp, scale=1.0):
        keys = jax.random.split(k, e)
        return jnp.stack([dense_init(kk, out, inp, dtype, scale=scale)
                          for kk in keys])

    p = {
        "router": dense_init(ks[0], e, d, jnp.float32),
        "w_gate": stack(ks[1], f, d),
        "w_up": stack(ks[2], f, d),
        "w_down": stack(ks[3], d, f, scale=down_scale),
    }
    if router_bias:
        p["e_bias"] = jnp.zeros((e,), jnp.float32)
    return p
