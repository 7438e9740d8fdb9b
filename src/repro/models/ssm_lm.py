"""Attention-free SSM LM (mamba2-780m): stacked Mamba2 SSD blocks."""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import ssm as S
from repro.models.common import (LayerBlocks, dtype_of, embed_init,
                                 embed_lookup, lm_head, norm)
from repro.sharding.ctx import constrain, unroll_flag, unshard_fsdp


class SSMLMCache(NamedTuple):
    conv: jax.Array    # (L, B, W-1, conv_dim)
    state: jax.Array   # (L, B, H, P, N) f32
    pos: jax.Array     # int32 nominal position (state is O(1)) — scalar or (B,)


CACHE_BATCH_AXES = SSMLMCache(conv=1, state=1, pos=0)
# attention-free: no KV cache for the engine's kv_precision knob to quantize
KV_CACHE_FIELDS = ()


def _init_layer(key, cfg, dtype):
    p = S.init_ssm_params(key, cfg, dtype)
    if not cfg.nonparametric_norm:
        p["ln"] = jnp.ones((cfg.d_model,), dtype)
    return p


def init(key, cfg):
    dtype = dtype_of(cfg)
    k_emb, k_layers = jax.random.split(key)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    params = {
        "embed": {"tok": embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                    dtype)},
        "layers": layers,
        "final": {"norm": jnp.ones((cfg.d_model,), dtype)},
    }
    # mamba2 ties embeddings (gpt-neox tokenizer family)
    return params


def apply(params, tokens: jax.Array, cfg, *, remat: bool = True,
          return_cache: bool = False, last_only: bool = False):
    dtype = dtype_of(cfg)
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = constrain(embed_lookup(embed_w, tokens, dtype),
                  ("batch", None, None))

    def body(h, p_layer):
        p_layer = unshard_fsdp(p_layer)
        y = S.ssm_block(p_layer, norm(h, p_layer.get("ln"), cfg), cfg)
        return constrain(h + y, ("batch", "seq", None)), {}

    from repro.quant.apply import segment_slices
    fn = jax.checkpoint(body) if remat else body
    for part, _, _ in segment_slices(params["layers"]):
        h, _ = jax.lax.scan(fn, h, part, unroll=unroll_flag())
    if last_only:
        h = h[:, -1:, :]
    h = norm(h, params["final"]["norm"], cfg)
    logits = constrain(lm_head(h, embed_w), ("batch", None, "model"))
    if return_cache:
        # SSM prefill-to-cache requires carrying final states; rerun decode
        # path is unnecessary — final_state is cheap to thread when needed.
        raise NotImplementedError("use decode_step from init_cache for SSM")
    return logits, {}


def init_cache(cfg, batch: int, max_seq: int) -> SSMLMCache:
    dtype = dtype_of(cfg)
    one = S.init_ssm_cache(batch, cfg, dtype)
    return SSMLMCache(
        conv=jnp.zeros((cfg.num_layers,) + one.conv.shape, dtype),
        state=jnp.zeros((cfg.num_layers,) + one.state.shape, jnp.float32),
        pos=jnp.int32(0))


def decode_step(params, cache: SSMLMCache, tokens: jax.Array, cfg):
    """tokens: (B, 1) -> (logits (B, 1, V), new cache). O(1) in seq_len."""
    dtype = dtype_of(cfg)
    b = tokens.shape[0]
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = embed_lookup(embed_w, tokens[:, 0], dtype)  # (B, D)

    def body(h, xs):
        p_layer, conv_l, state_l = xs
        p_layer = unshard_fsdp(p_layer)
        y, new = S.ssm_decode_step(
            p_layer, norm(h, p_layer.get("ln"), cfg),
            S.SSMCache(conv=conv_l, state=state_l), cfg)
        return h + y, (new.conv, new.state)

    from repro.quant.apply import segment_slices
    convs, states = [], []
    for part, lo, hi in segment_slices(params["layers"]):
        h, (nc, ns) = jax.lax.scan(
            body, h, (part, cache.conv[lo:hi], cache.state[lo:hi]),
            unroll=unroll_flag())
        convs.append(nc)
        states.append(ns)
    new_conv = jnp.concatenate(convs, axis=0) if len(convs) > 1 else convs[0]
    new_state = (jnp.concatenate(states, axis=0) if len(states) > 1
                 else states[0])
    h = norm(h, params["final"]["norm"], cfg)
    logits = lm_head(h[:, None, :], embed_w)
    return logits, SSMLMCache(conv=new_conv, state=new_state,
                              pos=cache.pos + 1)


def spec_verify(params, cache: SSMLMCache, tokens: jax.Array, cfg):
    """Score a verify window of ``tokens`` (B, K+1) by scanning single-token
    decode steps, checkpointing the sequential (conv, state) summaries
    after every step (snapshot 0 = the pre-verify state). The SSM state is
    O(1) and cannot be rewound by position arithmetic, so ``spec_commit``
    rolls back by SELECTING each slot's snapshot at its accepted length
    (docs/DESIGN.md §11). Returns (logits (B, K+1, V_pad), snap)."""

    def body(c, tok):
        logits, c2 = decode_step(params, c, tok[:, None], cfg)
        return c2, (logits[:, 0], c2.conv, c2.state)

    _, (lgs, convs, states) = jax.lax.scan(body, cache, tokens.T)
    convs = jnp.concatenate([cache.conv[None], convs])    # (K+2, L, B, ...)
    states = jnp.concatenate([cache.state[None], states])
    return jnp.moveaxis(lgs, 0, 1), (cache, convs, states)


def spec_commit(snap, committed: jax.Array) -> SSMLMCache:
    from repro.models.common import select_snapshot
    cache, convs, states = snap
    return SSMLMCache(conv=select_snapshot(convs, committed),
                      state=select_snapshot(states, committed),
                      pos=cache.pos + committed)


def block_params(params) -> LayerBlocks:
    return LayerBlocks(params["embed"], [params["layers"]])
