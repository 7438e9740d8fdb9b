"""Decoder-only LM covering the dense and MoE families.

Architectures served: chameleon-34b (qk-norm, VQ-token vocab), minicpm-2b,
yi-9b, llama3.2-3b, olmo-1b (non-parametric LN, tied embeddings),
arctic-480b (MoE + dense residual), grok-1-314b (MoE), moonlight-16b (the
DeepSeek-V3 block: multi-head latent attention, sigmoid-routed experts plus
shared experts, a leading dense layer).

Layers are stacked on a leading axis and executed with lax.scan (optionally
rematerialized); parameters may be raw arrays or QTensors (EWQ-quantized).
An MoE model with ``first_k_dense`` leading dense-MLP layers holds them in a
stack of their own (``dense_layers``) ahead of ``layers``; ``STACKS`` is the
execution order, and layer indices (cache layers, plan blocks) count across
both. MLA models (``cfg.is_mla``) cache one latent row per token and layer
(``LatentCache``) in place of K and V.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mla as MLA
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


class LatentCache(NamedTuple):
    c: jax.Array    # (L, B, S_max, r + rope) — raw, or flat KVPage(s)
    pos: jax.Array  # int32 next write position — scalar, or (B,) per-slot


# batch axis of each cache field once ``pos`` is a (B,) vector
# (serving/batch.py slotted layout; model.insert_cache_slot)
CACHE_BATCH_AXES = DecodeCache(k=1, v=1, pos=0)
LATENT_BATCH_AXES = LatentCache(c=1, pos=0)
# fields the engine may replace with quantized KVPages (quant/kvcache.py)
KV_CACHE_FIELDS = ("k", "v")
LATENT_FIELDS = ("c",)

# the layer stacks, in execution order
STACKS = ("dense_layers", "layers")


def layer_segments(params) -> list:
    """``(stacked params, lo, hi)`` for every scan segment of every stack,
    in execution order; [lo, hi) counts layers across the stacks, so it
    indexes the cache's layer axis."""
    from repro.quant.apply import segment_slices
    out, base = [], 0
    for key in STACKS:
        if key in params:
            segs = segment_slices(params[key])
            out += [(part, base + lo, base + hi) for part, lo, hi in segs]
            base += segs[-1][2]
    return out


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg, dtype, moe: bool):
    ks = jax.random.split(key, 4)
    if cfg.is_mla:
        p = {"attn": MLA.init_params(ks[0], cfg, dtype)}
    else:
        p = {"attn": A.init_attention_params(ks[0], cfg, dtype,
                                             with_qk_norm=cfg.qk_norm)}
    if not cfg.nonparametric_norm:
        p["ln1"] = jnp.ones((cfg.d_model,), dtype)
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
    if moe:
        p["moe"] = MOE.init_moe_params(ks[1], cfg.d_model, cfg.expert_d_ff,
                                       cfg.num_experts, cfg.num_layers, dtype,
                                       router_bias=cfg.router_bias)
        if cfg.n_shared_experts:
            p["shared_mlp"] = M.init_mlp_params(
                ks[3], cfg.d_model, cfg.n_shared_experts * cfg.expert_d_ff,
                cfg.num_layers, dtype)
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
    k_dense = cfg.first_k_dense if cfg.num_experts > 0 else 0
    params = {"embed": {"tok": embed_init(k_emb, cfg.padded_vocab,
                                          cfg.d_model, dtype)}}
    if k_dense:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, dtype, moe=False))(
                layer_keys[:k_dense])
    params["layers"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype, moe=cfg.num_experts > 0))(
            layer_keys[k_dense:])
    params["final"] = {}
    if not cfg.nonparametric_norm:
        params["final"]["norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["final"]["head"] = dense_init(k_head, cfg.padded_vocab,
                                             cfg.d_model, dtype)
    return params


# ---------------------------------------------------------------------------
# layer body
# ---------------------------------------------------------------------------

def _mlp(p, hn, cfg, capacity_factor):
    """The layer's feed-forward: a dense MLP, or the MoE block (with its
    shared experts and arctic's dense residual)."""
    if "moe" not in p:
        return M.mlp(p["mlp"], hn, cfg.mlp_act), {}
    m, aux = MOE.moe_block(p["moe"], hn, num_experts=cfg.num_experts,
                           top_k=cfg.top_k, capacity_factor=capacity_factor,
                           scoring=cfg.router_scoring,
                           routed_scaling=cfg.routed_scaling,
                           shared=p.get("shared_mlp"))
    if cfg.dense_residual:
        m = m + M.mlp(p["mlp"], hn, cfg.mlp_act)
    return m, aux


def _layer(p, h, positions, cfg, cache_kv=None, cache_pos=None,
           valid_bias=None, fresh_kv=None, emit_kv=False):
    """One block. Serving calls (a cache, or ``emit_kv`` for prefill) route
    MoE tokens dropless; training caps experts at ``cfg.capacity_factor``.
    Returns (h, aux, new_kv): the updated cache (decode), the emitted
    K/V or latent rows (``emit_kv``), else None."""
    p = unshard_fsdp(p)
    hn = norm(h, p.get("ln1"), cfg)
    if cfg.is_mla:
        a, new_kv = MLA.attention(p["attn"], hn, cfg, positions,
                                  cache=cache_kv, cache_pos=cache_pos)
    else:
        a, new_kv = A.attention(
            p["attn"], hn,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, positions=positions,
            rope_theta=cfg.rope_theta, causal=True, qk_norm=cfg.qk_norm,
            norm_eps=cfg.norm_eps, cache=cache_kv, cache_pos=cache_pos,
            valid_bias=valid_bias, fresh_kv=fresh_kv, emit_kv=emit_kv)
    h = h + a
    serving = emit_kv or cache_kv is not None
    m, aux = _mlp(p, norm(h, p.get("ln2"), cfg), cfg,
                  None if serving else cfg.capacity_factor)
    h = constrain(h + m, ("batch", "seq", None))
    return h, aux, new_kv


def _head(params, h, embed_w, cfg):
    """Final norm and logits, under the ``head`` scope (``lm_head`` opens
    it for the logits)."""
    with jax.named_scope("head"):
        h = norm(h, params["final"].get("norm"), cfg)
    head_w = unshard_fsdp(params["final"]).get("head", embed_w)
    return constrain(lm_head(h, head_w), ("batch", None, "model"))


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
        h2, aux, kv = _layer(p_layer, h, positions, cfg,
                             emit_kv=return_cache)
        return h2, (aux, kv)

    fn = jax.checkpoint(body) if remat else body
    auxs, kvs = None, []
    for part, _, _ in layer_segments(params):
        h, (seg_auxs, kv) = jax.lax.scan(fn, h, part, unroll=unroll_flag())
        kvs.append(kv)
        if seg_auxs:
            auxs = seg_auxs if auxs is None else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), auxs, seg_auxs)
    cache = None
    if return_cache:
        kvs = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0)
                           if len(xs) > 1 else xs[0], *kvs)
        cache = (LatentCache(c=kvs, pos=jnp.int32(s)) if cfg.is_mla
                 else DecodeCache(k=kvs.k, v=kvs.v, pos=jnp.int32(s)))

    if last_only:
        h = h[:, -1:, :]
    logits = _head(params, h, embed_w, cfg)
    aux = {k: jnp.sum(v) for k, v in (auxs or {}).items()}
    return (logits, aux, cache) if return_cache else (logits, aux)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int):
    dtype = dtype_of(cfg)
    if cfg.is_mla:
        shape = (cfg.num_layers, batch, max_seq, cfg.latent_dim)
        return LatentCache(c=jnp.zeros(shape, dtype), pos=jnp.int32(0))
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return DecodeCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                       pos=jnp.int32(0))


def decode_step(params, cache, tokens: jax.Array, cfg):
    """tokens: (B, s) -> (logits (B, s, V_pad), new cache); ``cache`` is a
    DecodeCache, or a LatentCache for MLA models."""
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = constrain(embed_lookup(embed_w, tokens, dtype),
                  ("batch", None, None))
    positions = decode_positions(cache.pos, b, s)
    latent = isinstance(cache, LatentCache)
    fields = LATENT_FIELDS if latent else KV_CACHE_FIELDS
    # validity mask is layer-invariant: hoist it out of the per-layer
    # attention (None for quantized caches — the kernel masks by position;
    # s > 1 is the speculative verify window with per-query causal offsets)
    valid_bias = None if latent else A.decode_step_bias(cache.k, cache.pos,
                                                        s)

    def body(h, xs):
        p_layer, layer_cache = xs
        kv = layer_cache[0] if latent else A.KVCache(*layer_cache)
        h2, _, new = _layer(p_layer, h, positions, cfg, cache_kv=kv,
                            cache_pos=cache.pos, valid_bias=valid_bias)
        return h2, (new,) if latent else (new.k, new.v)

    from repro.quant.kvcache import kv_rejoin, kv_segment
    outs = []
    # ``kv``: the per-precision cache segments, the layer scan that carries
    # them (its slicing and stacking of each layer's inputs and outputs)
    # and the rejoin; the layer body's own scopes nest inside
    with jax.named_scope("kv"):
        for si, (part, lo, hi) in enumerate(layer_segments(params)):
            segs = tuple(kv_segment(getattr(cache, f), si, lo, hi)
                         for f in fields)
            h, new = jax.lax.scan(body, h, (part, segs),
                                  unroll=unroll_flag())
            outs.append(new)
        joined = {f: kv_rejoin(getattr(cache, f), [o[i] for o in outs])
                  for i, f in enumerate(fields)}
    logits = _head(params, h, embed_w, cfg)
    return logits, cache._replace(pos=cache.pos + s, **joined)


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
    with jax.named_scope("kv"):
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
    logits = _head(params, h, embed_w, cfg)
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
    """[embedding block, layer_0, ..., layer_{L-1}] — paper exec_index order
    (leading dense layers first)."""
    return LayerBlocks(params["embed"], [params[k] for k in STACKS
                                         if k in params])
