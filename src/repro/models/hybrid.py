"""Hybrid Mamba2 + shared-attention LM (zamba2-2.7b).

Structure: num_layers Mamba2 blocks; ONE shared attention+MLP block (shared
weights, per Zamba2's design) is applied before every ``shared_attn_period``
Mamba2 layers. With 54 layers and period 6 the shared block runs 9 times.
Execution scans over 9 units; each unit = shared block + inner scan over the
unit's 6 stacked Mamba2 layers. The KV cache carries one (B, S, Hkv, hd)
entry per shared-block *application site* (activations differ per site even
though weights are shared).

Deviation noted in docs/DESIGN.md §2.1: Zamba2's per-application LoRA
adapters on the shared block are omitted; shared-block quantization applies
to all sites. Mixed-precision plans execute per-unit segments
(docs/DESIGN.md §8).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mlp as M
from repro.models import ssm as S
from repro.models.common import (LayerBlocks, decode_positions, dtype_of,
                                 embed_init, embed_lookup, lm_head, norm)
from repro.sharding.ctx import constrain, unroll_flag, unshard_fsdp


class HybridCache(NamedTuple):
    conv: jax.Array    # (L, B, W-1, conv_dim)
    state: jax.Array   # (L, B, H, P, N) f32
    k: jax.Array       # (U, B, S_max, Hkv, hd) — U shared-attn sites;
    v: jax.Array       #   raw, or a single KVPage (one shared-block decision)
    pos: jax.Array     # int32 — scalar, or (B,) per-slot


CACHE_BATCH_AXES = HybridCache(conv=1, state=1, k=1, v=1, pos=0)
# fields the engine may replace with quantized KVPages (quant/kvcache.py)
KV_CACHE_FIELDS = ("k", "v")


def _num_units(cfg) -> int:
    assert cfg.num_layers % cfg.shared_attn_period == 0
    return cfg.num_layers // cfg.shared_attn_period


def init(key, cfg):
    dtype = dtype_of(cfg)
    k_emb, k_layers, k_shared, k_mlp = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)

    def init_mamba_layer(k):
        p = S.init_ssm_params(k, cfg, dtype)
        p["ln"] = jnp.ones((cfg.d_model,), dtype)
        return p

    layers = jax.vmap(init_mamba_layer)(layer_keys)
    shared = {
        "attn": A.init_attention_params(k_shared, cfg, dtype),
        "mlp": M.init_mlp_params(k_mlp, cfg.d_model, cfg.d_ff, cfg.num_layers,
                                 dtype, cfg.mlp_act),
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }
    return {
        "embed": {"tok": embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                    dtype)},
        "layers": layers,
        "shared": shared,
        "final": {"norm": jnp.ones((cfg.d_model,), dtype)},
    }


def _shared_block(shared, h, positions, cfg, cache_kv=None, cache_pos=None,
                  valid_bias=None):
    a, new_kv = A.attention(
        shared["attn"], norm(h, shared["ln1"], cfg),
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, positions=positions,
        rope_theta=cfg.rope_theta, causal=True, norm_eps=cfg.norm_eps,
        cache=cache_kv, cache_pos=cache_pos, valid_bias=valid_bias)
    h = h + a
    h = h + M.mlp(shared["mlp"], norm(h, shared["ln2"], cfg), cfg.mlp_act)
    return h, new_kv


def _unit_stack(layers, cfg):
    """Reshape stacked (L, ...) mamba params into (U, period, ...)."""
    u, p = _num_units(cfg), cfg.shared_attn_period
    return jax.tree.map(lambda x: x.reshape((u, p) + x.shape[1:]), layers)


def _layer_stack(layers, cfg):
    """Resolve a (possibly segmented) mamba stack for execution.

    Returns ``(uniform_stack, segments_by_unit)``: exactly one is non-None.
    A plain stacked tree or a single-segment ``SegmentedParams`` (uniform
    plan) executes via the fused unit-scan fast path; a mixed-precision
    ``SegmentedParams`` (compiler cuts segments at unit boundaries —
    docs/DESIGN.md §8) executes per-unit, scanning each segment's slice
    inside its unit."""
    from repro.quant.apply import SegmentedParams
    if not isinstance(layers, SegmentedParams):
        return layers, None
    if len(layers.segments) == 1:
        return layers.segments[0].params, None
    period = cfg.shared_attn_period
    by_unit = [[] for _ in range(_num_units(cfg))]
    for seg in layers.segments:
        assert seg.start // period == (seg.stop - 1) // period, \
            f"segment [{seg.start},{seg.stop}) crosses a unit boundary"
        by_unit[seg.start // period].append(seg)
    return None, by_unit


def apply(params, tokens: jax.Array, cfg, *, remat: bool = True,
          last_only: bool = False):
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = constrain(embed_lookup(embed_w, tokens, dtype),
                  ("batch", None, None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    shared = unshard_fsdp(params["shared"])
    stacked, by_unit = _layer_stack(params["layers"], cfg)

    def mamba_body(h, p_layer):
        p_layer = unshard_fsdp(p_layer)
        y = S.ssm_block(p_layer, norm(h, p_layer["ln"], cfg), cfg)
        return constrain(h + y, ("batch", "seq", None)), None

    inner = jax.checkpoint(mamba_body) if remat else mamba_body

    if by_unit is not None:
        # mixed-precision: units unrolled, one scan per in-unit segment
        for unit_segs in by_unit:
            h, _ = _shared_block(shared, h, positions, cfg)
            for seg in unit_segs:
                h, _ = jax.lax.scan(inner, h, seg.params,
                                    unroll=unroll_flag())
    else:
        units = _unit_stack(stacked, cfg)

        def unit_body(h, unit_layers):
            h, _ = _shared_block(shared, h, positions, cfg)
            h, _ = jax.lax.scan(inner, h, unit_layers, unroll=unroll_flag())
            return h, None

        fn = jax.checkpoint(unit_body) if remat else unit_body
        h, _ = jax.lax.scan(fn, h, units, unroll=unroll_flag())
    if last_only:
        h = h[:, -1:, :]
    h = norm(h, params["final"]["norm"], cfg)
    logits = constrain(lm_head(h, embed_w), ("batch", None, "model"))
    return logits, {}


def init_cache(cfg, batch: int, max_seq: int) -> HybridCache:
    dtype = dtype_of(cfg)
    one = S.init_ssm_cache(batch, cfg, dtype)
    u = _num_units(cfg)
    kv_shape = (u, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return HybridCache(
        conv=jnp.zeros((cfg.num_layers,) + one.conv.shape, dtype),
        state=jnp.zeros((cfg.num_layers,) + one.state.shape, jnp.float32),
        k=jnp.zeros(kv_shape, dtype), v=jnp.zeros(kv_shape, dtype),
        pos=jnp.int32(0))


def decode_step(params, cache: HybridCache, tokens: jax.Array, cfg):
    dtype = dtype_of(cfg)
    b = tokens.shape[0]
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h2d = embed_lookup(embed_w, tokens[:, 0], dtype)  # (B, D)
    positions = decode_positions(cache.pos, b, 1)
    u, period = _num_units(cfg), cfg.shared_attn_period
    shared = unshard_fsdp(params["shared"])
    stacked, by_unit = _layer_stack(params["layers"], cfg)
    from repro.quant.kvcache import kv_layer, kv_stack
    valid_bias = A.decode_step_bias(cache.k, cache.pos)

    def mamba_body(h, xs_inner):
        p_layer, c_l, s_l = xs_inner
        p_layer = unshard_fsdp(p_layer)
        y, new = S.ssm_decode_step(
            p_layer, norm(h, p_layer["ln"], cfg),
            S.SSMCache(conv=c_l, state=s_l), cfg)
        return h + y, (new.conv, new.state)

    if by_unit is not None:
        # mixed-precision: units unrolled; each segment scans its slice of
        # the per-layer conv/state cache inside its unit
        convs, states, new_ks, new_vs = [], [], [], []
        for ui, unit_segs in enumerate(by_unit):
            h3 = h2d[:, None, :]  # (B, 1, D) for attention
            h3, new_kv = _shared_block(
                shared, h3, positions, cfg,
                cache_kv=A.KVCache(k=kv_layer(cache.k, ui),
                                   v=kv_layer(cache.v, ui)),
                cache_pos=cache.pos, valid_bias=valid_bias)
            h2d = h3[:, 0, :]
            new_ks.append(new_kv.k)
            new_vs.append(new_kv.v)
            for seg in unit_segs:
                h2d, (nc, ns) = jax.lax.scan(
                    mamba_body, h2d,
                    (seg.params, cache.conv[seg.start:seg.stop],
                     cache.state[seg.start:seg.stop]),
                    unroll=unroll_flag())
                convs.append(nc)
                states.append(ns)
        new_cache = HybridCache(
            conv=jnp.concatenate(convs, axis=0),
            state=jnp.concatenate(states, axis=0),
            k=kv_stack(cache.k, new_ks), v=kv_stack(cache.v, new_vs),
            pos=cache.pos + 1)
    else:
        units = _unit_stack(stacked, cfg)
        conv_u = cache.conv.reshape((u, period) + cache.conv.shape[1:])
        state_u = cache.state.reshape((u, period) + cache.state.shape[1:])

        def unit_body(h, xs):
            unit_layers, conv_l, state_l, k_l, v_l = xs
            h3 = h[:, None, :]  # (B, 1, D) for attention
            h3, new_kv = _shared_block(shared, h3, positions, cfg,
                                       cache_kv=A.KVCache(k=k_l, v=v_l),
                                       cache_pos=cache.pos,
                                       valid_bias=valid_bias)
            h = h3[:, 0, :]
            h, (nc, ns) = jax.lax.scan(mamba_body, h,
                                       (unit_layers, conv_l, state_l),
                                       unroll=unroll_flag())
            return h, (nc, ns, new_kv.k, new_kv.v)

        h2d, (new_conv, new_state, new_k, new_v) = jax.lax.scan(
            unit_body, h2d, (units, conv_u, state_u, cache.k, cache.v),
            unroll=unroll_flag())
        new_cache = HybridCache(
            conv=new_conv.reshape(cache.conv.shape),
            state=new_state.reshape(cache.state.shape),
            k=new_k, v=new_v, pos=cache.pos + 1)

    h = norm(h2d, params["final"]["norm"], cfg)
    logits = lm_head(h[:, None, :], embed_w)
    return logits, new_cache


def spec_verify(params, cache: HybridCache, tokens: jax.Array, cfg):
    """Score a verify window of ``tokens`` (B, K+1) by scanning single-token
    decode steps. Rollback is split by state kind (docs/DESIGN.md §11):
    the shared-attention K/V rows written past the commit point are rolled
    back by position arithmetic (they stay in memory, masked invalid),
    while the sequential Mamba2 (conv, state) summaries are checkpointed
    per step and selected per slot in ``spec_commit``."""

    def body(c, tok):
        logits, c2 = decode_step(params, c, tok[:, None], cfg)
        return c2, (logits[:, 0], c2.conv, c2.state)

    final, (lgs, convs, states) = jax.lax.scan(body, cache, tokens.T)
    convs = jnp.concatenate([cache.conv[None], convs])    # (K+2, L, B, ...)
    states = jnp.concatenate([cache.state[None], states])
    return jnp.moveaxis(lgs, 0, 1), (cache, final, convs, states)


def spec_commit(snap, committed: jax.Array) -> HybridCache:
    from repro.models.common import select_snapshot
    cache0, final, convs, states = snap
    return final._replace(conv=select_snapshot(convs, committed),
                          state=select_snapshot(states, committed),
                          pos=cache0.pos + committed)


def block_params(params) -> LayerBlocks:
    return LayerBlocks(params["embed"], [params["layers"]],
                       [params["shared"]])
