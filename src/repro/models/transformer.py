"""Decoder-only LM covering the dense and MoE families.

Architectures served: chameleon-34b (qk-norm, VQ-token vocab), minicpm-2b,
yi-9b, llama3.2-3b, olmo-1b (non-parametric LN, tied embeddings),
arctic-480b (MoE + dense residual), grok-1-314b (MoE).

Layers are stacked on a leading axis and executed with lax.scan (optionally
rematerialized); parameters may be raw arrays or QTensors (EWQ-quantized).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mlp as M
from repro.models import moe as MOE
from repro.models.common import (LayerBlocks, decode_positions, dtype_of,
                                 embed_init, embed_lookup, dense_init, lm_head,
                                 norm, qdot)
from repro.sharding.ctx import constrain, unroll_flag, unshard_fsdp


class DecodeCache(NamedTuple):
    k: jax.Array    # (L, B, S_max, Hkv, hd) — raw, or KVPage(s) (quantized)
    v: jax.Array    # (L, B, S_max, Hkv, hd)
    pos: jax.Array  # int32 next write position — scalar, or (B,) per-slot


# batch axis of each cache field once ``pos`` is a (B,) vector
# (serving/batch.py slotted layout; model.insert_cache_slot)
CACHE_BATCH_AXES = DecodeCache(k=1, v=1, pos=0)
# fields the engine may replace with quantized KVPages (quant/kvcache.py)
KV_CACHE_FIELDS = ("k", "v")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    p = {"attn": A.init_attention_params(ks[0], cfg, dtype,
                                         with_qk_norm=cfg.qk_norm)}
    if not cfg.nonparametric_norm:
        p["ln1"] = jnp.ones((cfg.d_model,), dtype)
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.num_experts > 0:
        p["moe"] = MOE.init_moe_params(ks[1], cfg.d_model, cfg.expert_d_ff,
                                       cfg.num_experts, cfg.num_layers, dtype)
        if cfg.dense_residual:
            p["mlp"] = M.init_mlp_params(ks[2], cfg.d_model, cfg.d_ff,
                                         cfg.num_layers, dtype, cfg.mlp_act)
    else:
        p["mlp"] = M.init_mlp_params(ks[2], cfg.d_model, cfg.d_ff,
                                     cfg.num_layers, dtype, cfg.mlp_act)
    return p


def init(key, cfg):
    dtype = dtype_of(cfg)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    params = {
        "embed": {"tok": embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                    dtype)},
        "layers": layers,
        "final": {},
    }
    if not cfg.nonparametric_norm:
        params["final"]["norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["final"]["head"] = dense_init(k_head, cfg.padded_vocab,
                                             cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------

def _layer(p, h, positions, cfg, cache_kv=None, cache_pos=None,
           valid_bias=None, fresh_kv=None):
    p = unshard_fsdp(p)
    ln1 = p.get("ln1")
    ln2 = p.get("ln2")
    a, new_kv = A.attention(
        p["attn"], norm(h, ln1, cfg),
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, positions=positions,
        rope_theta=cfg.rope_theta, causal=True, qk_norm=cfg.qk_norm,
        norm_eps=cfg.norm_eps, cache=cache_kv, cache_pos=cache_pos,
        valid_bias=valid_bias, fresh_kv=fresh_kv)
    h = h + a
    hn = norm(h, ln2, cfg)
    aux = {}
    if cfg.num_experts > 0:
        m, aux = MOE.moe_block(p["moe"], hn, num_experts=cfg.num_experts,
                               top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor)
        if cfg.dense_residual:
            m = m + M.mlp(p["mlp"], hn, cfg.mlp_act)
    else:
        m = M.mlp(p["mlp"], hn, cfg.mlp_act)
    h = constrain(h + m, ("batch", "seq", None))
    return h, aux, new_kv


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def apply(params, tokens: jax.Array, cfg, *, remat: bool = True,
          return_cache: bool = False, last_only: bool = False):
    """tokens: (B, S) int32 -> logits (B, S, V_pad) f32 (+ aux dict).

    last_only=True computes head logits for the final position only
    (serving prefill: next-token logits without a (B, S, V) temp)."""
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = constrain(embed_lookup(embed_w, tokens, dtype),
                  ("batch", None, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(h, p_layer):
        h2, aux, _ = _layer(p_layer, h, positions, cfg)
        return h2, aux

    def body_cache(h, p_layer):
        p_layer = unshard_fsdp(p_layer)
        hn = norm(h, p_layer.get("ln1"), cfg)
        a, kv = A.attention(
            p_layer["attn"], hn, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            positions=positions, rope_theta=cfg.rope_theta, causal=True,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, emit_kv=True)
        h = h + a
        hn2 = norm(h, p_layer.get("ln2"), cfg)
        if cfg.num_experts > 0:
            m, aux = MOE.moe_block(p_layer["moe"], hn2,
                                   num_experts=cfg.num_experts,
                                   top_k=cfg.top_k,
                                   capacity_factor=cfg.capacity_factor)
            if cfg.dense_residual:
                m = m + M.mlp(p_layer["mlp"], hn2, cfg.mlp_act)
        else:
            aux = {}
            m = M.mlp(p_layer["mlp"], hn2, cfg.mlp_act)
        return h + m, (aux, kv)

    from repro.quant.apply import segment_slices
    layers = params["layers"]
    if return_cache:
        fn = jax.checkpoint(body_cache) if remat else body_cache
        auxs, ks, vs = None, [], []
        for part, _, _ in segment_slices(layers):
            h, (seg_auxs, kv) = jax.lax.scan(fn, h, part,
                                             unroll=unroll_flag())
            ks.append(kv[0])
            vs.append(kv[1])
            auxs = seg_auxs if auxs is None else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), auxs, seg_auxs)
        kvs = (jnp.concatenate(ks, axis=0) if len(ks) > 1 else ks[0],
               jnp.concatenate(vs, axis=0) if len(vs) > 1 else vs[0])
        cache = DecodeCache(k=kvs[0], v=kvs[1], pos=jnp.int32(s))
    else:
        fn = jax.checkpoint(body) if remat else body
        auxs = None
        for part, _, _ in segment_slices(layers):
            h, seg_auxs = jax.lax.scan(fn, h, part, unroll=unroll_flag())
            auxs = seg_auxs if auxs is None else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), auxs, seg_auxs)
        cache = None

    if last_only:
        h = h[:, -1:, :]
    h = norm(h, params["final"].get("norm"), cfg)
    head_w = unshard_fsdp(params["final"]).get("head", embed_w)
    logits = constrain(lm_head(h, head_w), ("batch", None, "model"))
    aux = {k: jnp.sum(v) for k, v in (auxs or {}).items()}
    return (logits, aux, cache) if return_cache else (logits, aux)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int) -> DecodeCache:
    dtype = dtype_of(cfg)
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return DecodeCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                       pos=jnp.int32(0))


def decode_step(params, cache: DecodeCache, tokens: jax.Array, cfg):
    """tokens: (B, 1) -> (logits (B, 1, V_pad), new cache)."""
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = constrain(embed_lookup(embed_w, tokens, dtype),
                  ("batch", None, None))
    positions = decode_positions(cache.pos, b, s)
    # validity mask is layer-invariant: hoist it out of the per-layer
    # attention (None for quantized caches — the kernel masks by position;
    # s > 1 is the speculative verify window with per-query causal offsets)
    valid_bias = A.decode_step_bias(cache.k, cache.pos, s)

    def body(h, xs):
        p_layer, k_l, v_l = xs
        h2, _, new_kv = _layer(p_layer, h, positions, cfg,
                               cache_kv=A.KVCache(k=k_l, v=v_l),
                               cache_pos=cache.pos, valid_bias=valid_bias)
        return h2, (new_kv.k, new_kv.v)

    from repro.quant.apply import segment_slices
    from repro.quant.kvcache import kv_rejoin, kv_segment
    ks, vs = [], []
    for si, (part, lo, hi) in enumerate(segment_slices(params["layers"])):
        h, (nk, nv) = jax.lax.scan(
            body, h, (part, kv_segment(cache.k, si, lo, hi),
                      kv_segment(cache.v, si, lo, hi)),
            unroll=unroll_flag())
        ks.append(nk)
        vs.append(nv)
    new_k = kv_rejoin(cache.k, ks)
    new_v = kv_rejoin(cache.v, vs)
    h = norm(h, params["final"].get("norm"), cfg)
    head_w = unshard_fsdp(params["final"]).get("head", embed_w)
    logits = constrain(lm_head(h, head_w), ("batch", None, "model"))
    return logits, DecodeCache(k=new_k, v=new_v, pos=cache.pos + s)


def draft_propose_step(params, cache: DecodeCache, fresh_k, fresh_v,
                       count, tokens: jax.Array, cfg):
    """One READ-ONLY draft decode step (fused spec propose, docs/DESIGN.md
    §12): the cache is only read — each layer's new k/v land in row
    ``count`` of the raw per-layer side buffers ``fresh_k``/``fresh_v``
    ((L_draft, B, K, Hkv, hd)), and attention sweeps cache + buffer in one
    fused pass with buffer rows at logical positions ``cache.pos + j``.
    A k-round therefore costs ZERO draft-side cache writes (no throwaway
    cache copy, no k*L quantize-and-scatter) and one sweep per step.

    ``params`` may be a truncated draft (first N layers of the target —
    compile_draft_plan(draft_layers=N)); cache pages are sliced per draft
    segment, which always sits inside one page (kv_take_layers).

    tokens: (B, 1) -> (logits (B, 1, V_pad), fresh_k, fresh_v) with the
    updated buffers carrying row ``count``."""
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = constrain(embed_lookup(embed_w, tokens, dtype),
                  ("batch", None, None))
    positions = decode_positions(cache.pos + count, b, s)

    def body(h, xs):
        p_layer, k_l, v_l, fk_l, fv_l = xs
        h2, _, new_kv = _layer(p_layer, h, positions, cfg,
                               cache_kv=A.KVCache(k=k_l, v=v_l),
                               cache_pos=cache.pos,
                               fresh_kv=(fk_l, fv_l, count))
        return h2, (new_kv.k, new_kv.v)

    from repro.quant.apply import segment_slices
    from repro.quant.kvcache import kv_take_layers
    fks, fvs = [], []
    for part, lo, hi in segment_slices(params["layers"]):
        h, (nfk, nfv) = jax.lax.scan(
            body, h, (part, kv_take_layers(cache.k, lo, hi),
                      kv_take_layers(cache.v, lo, hi),
                      fresh_k[lo:hi], fresh_v[lo:hi]),
            unroll=unroll_flag())
        fks.append(nfk)
        fvs.append(nfv)
    fresh_k = jnp.concatenate(fks, axis=0) if len(fks) > 1 else fks[0]
    fresh_v = jnp.concatenate(fvs, axis=0) if len(fvs) > 1 else fvs[0]
    h = norm(h, params["final"].get("norm"), cfg)
    head_w = unshard_fsdp(params["final"]).get("head", embed_w)
    logits = constrain(lm_head(h, head_w), ("batch", None, "model"))
    return logits, fresh_k, fresh_v


# ---------------------------------------------------------------------------
# speculative verify (docs/DESIGN.md §11)
# ---------------------------------------------------------------------------

def spec_verify(params, cache: DecodeCache, tokens: jax.Array, cfg):
    """Score a verify window of ``tokens`` (B, K+1) in ONE fused multi-query
    decode pass. Returns (logits (B, K+1, V_pad), snap); the snap rolls the
    cache back to any per-slot accepted length via ``spec_commit`` —
    rollback is pure position arithmetic over the (quantized) KV cache:
    rows past the commit point stay in memory but are masked invalid."""
    logits, new_cache = decode_step(params, cache, tokens, cfg)
    return logits, (new_cache, tokens.shape[1])


def spec_commit(snap, committed: jax.Array) -> DecodeCache:
    """``committed`` (B,) tokens kept out of the verify window (0 rolls a
    slot all the way back to its pre-verify position)."""
    cache, s = snap
    return cache._replace(pos=cache.pos - s + committed)


# ---------------------------------------------------------------------------
# EWQ view
# ---------------------------------------------------------------------------

def block_params(params) -> LayerBlocks:
    """[embedding block, layer_0, ..., layer_{L-1}] — paper exec_index order."""
    return LayerBlocks(params["embed"], [params["layers"]])
