"""Encoder-decoder transformer (whisper-medium backbone).

The audio conv frontend is a STUB per the assignment: ``input_specs`` feeds
precomputed frame embeddings (B, encoder_seq, d_model). Encoder layers are
bidirectional self-attention + GeLU MLP; decoder layers are causal
self-attention + cross-attention + GeLU MLP; LayerNorm (scale-only), no rope
(whisper uses sinusoidal encoder / learned decoder positions — we use
sinusoidal for both; noted in docs/DESIGN.md §2.2).

Decode maintains per-layer self-attention KV caches plus precomputed
cross-attention K/V from the encoder pass.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mlp as M
from repro.models.common import (LayerBlocks, decode_positions, dtype_of,
                                 embed_init, embed_lookup, dense_init,
                                 layer_norm, lm_head, sinusoidal_positions)
from repro.sharding.ctx import constrain, unroll_flag, unshard_fsdp


class EncDecCache(NamedTuple):
    k: jax.Array        # (Ld, B, S_max, Hkv, hd) decoder self-attn
    v: jax.Array        #   raw, or KVPage(s) (quantized serving cache)
    cross_k: jax.Array  # (Ld, B, S_enc, Hkv, hd) precomputed encoder K/V
    cross_v: jax.Array  #   quantized once at admission (always fully valid)
    pos: jax.Array      # int32 — scalar, or (B,) per-slot


CACHE_BATCH_AXES = EncDecCache(k=1, v=1, cross_k=1, cross_v=1, pos=0)
# fields the engine may replace with quantized KVPages (quant/kvcache.py)
KV_CACHE_FIELDS = ("k", "v", "cross_k", "cross_v")


def _ln(x, w, cfg):
    return layer_norm(x, w, cfg.norm_eps)


def _init_enc_layer(key, cfg, dtype):
    ks = jax.random.split(key, 2)
    return {
        "attn": A.init_attention_params(ks[0], cfg, dtype),
        "mlp": M.init_mlp_params(ks[1], cfg.d_model, cfg.d_ff,
                                 cfg.num_encoder_layers, dtype, "gelu"),
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }


def _init_dec_layer(key, cfg, dtype):
    ks = jax.random.split(key, 3)
    return {
        "self_attn": A.init_attention_params(ks[0], cfg, dtype),
        "cross_attn": A.init_attention_params(ks[1], cfg, dtype),
        "mlp": M.init_mlp_params(ks[2], cfg.d_model, cfg.d_ff, cfg.num_layers,
                                 dtype, "gelu"),
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln_x": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }


def init(key, cfg):
    dtype = dtype_of(cfg)
    k_emb, k_enc, k_dec = jax.random.split(key, 3)
    enc_keys = jax.random.split(k_enc, cfg.num_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    return {
        "embed": {"tok": embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                    dtype)},
        "enc_layers": jax.vmap(lambda k: _init_enc_layer(k, cfg, dtype))(enc_keys),
        "dec_layers": jax.vmap(lambda k: _init_dec_layer(k, cfg, dtype))(dec_keys),
        "final": {"enc_norm": jnp.ones((cfg.d_model,), dtype),
                  "norm": jnp.ones((cfg.d_model,), dtype)},
    }


def encode(params, frames: jax.Array, cfg, *, remat: bool = True):
    """frames: (B, S_enc, D) precomputed embeddings -> (B, S_enc, D)."""
    dtype = dtype_of(cfg)
    b, s, _ = frames.shape
    h = constrain(frames.astype(dtype)
                  + sinusoidal_positions(s, cfg.d_model).astype(dtype)[None],
                  ("batch", None, None))

    def body(h, p):
        p = unshard_fsdp(p)
        a, _ = A.attention(p["attn"], _ln(h, p["ln1"], cfg),
                           num_heads=cfg.num_heads,
                           num_kv_heads=cfg.num_kv_heads,
                           head_dim=cfg.head_dim, causal=False,
                           norm_eps=cfg.norm_eps)
        h = h + a
        h = h + M.mlp(p["mlp"], _ln(h, p["ln2"], cfg), "gelu")
        return constrain(h, ("batch", "seq", None)), None

    from repro.quant.apply import segment_slices
    fn = jax.checkpoint(body) if remat else body
    for part, _, _ in segment_slices(params["enc_layers"]):
        h, _ = jax.lax.scan(fn, h, part, unroll=unroll_flag())
    return _ln(h, params["final"]["enc_norm"], cfg)


def _dec_layer(p, h, enc_out, cfg, cache_kv=None, cache_pos=None,
               cross_kv=None, valid_bias=None):
    p = unshard_fsdp(p)
    a, new_kv = A.attention(p["self_attn"], _ln(h, p["ln1"], cfg),
                            num_heads=cfg.num_heads,
                            num_kv_heads=cfg.num_kv_heads,
                            head_dim=cfg.head_dim, causal=True,
                            norm_eps=cfg.norm_eps, cache=cache_kv,
                            cache_pos=cache_pos, valid_bias=valid_bias)
    h = h + a
    if cross_kv is not None:
        x, _ = A.attention(p["cross_attn"], _ln(h, p["ln_x"], cfg),
                           num_heads=cfg.num_heads,
                           num_kv_heads=cfg.num_kv_heads,
                           head_dim=cfg.head_dim, cached_kv=cross_kv,
                           norm_eps=cfg.norm_eps)
    else:
        x, _ = A.attention(p["cross_attn"], _ln(h, p["ln_x"], cfg),
                           num_heads=cfg.num_heads,
                           num_kv_heads=cfg.num_kv_heads,
                           head_dim=cfg.head_dim, causal=False,
                           kv_x=enc_out, norm_eps=cfg.norm_eps)
    h = h + x
    h = constrain(h + M.mlp(p["mlp"], _ln(h, p["ln2"], cfg), "gelu"),
                  ("batch", "seq", None))
    return h, new_kv


def apply(params, tokens: jax.Array, frames: jax.Array, cfg, *,
          remat: bool = True, last_only: bool = False):
    """Full enc-dec forward: (B, S) tokens + (B, S_enc, D) frames -> logits."""
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    enc_out = encode(params, frames, cfg, remat=remat)
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = embed_lookup(embed_w, tokens, dtype)
    h = constrain(h + sinusoidal_positions(s, cfg.d_model).astype(dtype)[None],
                  ("batch", None, None))

    def body(h, p):
        h2, _ = _dec_layer(p, h, enc_out, cfg)
        return h2, None

    from repro.quant.apply import segment_slices
    fn = jax.checkpoint(body) if remat else body
    for part, _, _ in segment_slices(params["dec_layers"]):
        h, _ = jax.lax.scan(fn, h, part, unroll=unroll_flag())
    if last_only:
        h = h[:, -1:, :]
    h = _ln(h, params["final"]["norm"], cfg)
    logits = constrain(lm_head(h, embed_w),
                       ("batch", None, "model"))  # whisper ties emb/head
    return logits, {}


def init_cache(cfg, batch: int, max_seq: int) -> EncDecCache:
    dtype = dtype_of(cfg)
    shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    cross = (cfg.num_layers, batch, cfg.encoder_seq, cfg.num_kv_heads,
             cfg.head_dim)
    return EncDecCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                       cross_k=jnp.zeros(cross, dtype),
                       cross_v=jnp.zeros(cross, dtype), pos=jnp.int32(0))


def precompute_cross_kv(params, enc_out: jax.Array, cfg) -> tuple:
    """Encoder K/V for every decoder layer (run once per request)."""
    b, s, _ = enc_out.shape
    hkv, hd = cfg.num_kv_heads, cfg.head_dim

    def body(_, p):
        from repro.models.common import qdot
        k = qdot(enc_out, p["cross_attn"]["wk"]).reshape(b, s, hkv, hd)
        v = qdot(enc_out, p["cross_attn"]["wv"]).reshape(b, s, hkv, hd)
        return None, (k, v)

    from repro.quant.apply import segment_slices
    ks, vs = [], []
    for part, _, _ in segment_slices(params["dec_layers"]):
        _, (k_p, v_p) = jax.lax.scan(body, None, part)
        ks.append(k_p)
        vs.append(v_p)
    return (jnp.concatenate(ks, axis=0) if len(ks) > 1 else ks[0],
            jnp.concatenate(vs, axis=0) if len(vs) > 1 else vs[0])


def decode_step(params, cache: EncDecCache, tokens: jax.Array, cfg):
    dtype = dtype_of(cfg)
    b, s = tokens.shape
    embed_w = unshard_fsdp(params["embed"])["tok"]
    h = embed_lookup(embed_w, tokens, dtype)
    # sinusoidal positions from cache.pos (scalar, or (B,) per-slot vector);
    # s > 1 is a speculative verify window at consecutive positions
    half = cfg.d_model // 2
    freqs = 1.0 / (10000 ** (jnp.arange(half, dtype=jnp.float32) / half))
    pos = decode_positions(cache.pos, b, s)                     # (B, s)
    ang = pos.astype(jnp.float32)[..., None] * freqs[None, None]
    pos_emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)  # (B, s, D)
    h = h + pos_emb.astype(dtype)

    valid_bias = A.decode_step_bias(cache.k, cache.pos, s)

    def body(h, xs):
        p, k_l, v_l, ck_l, cv_l = xs
        h2, new_kv = _dec_layer(p, h, None, cfg,
                                cache_kv=A.KVCache(k=k_l, v=v_l),
                                cache_pos=cache.pos,
                                cross_kv=A.KVCache(k=ck_l, v=cv_l),
                                valid_bias=valid_bias)
        return h2, (new_kv.k, new_kv.v)

    from repro.quant.apply import segment_slices
    from repro.quant.kvcache import kv_rejoin, kv_segment
    ks, vs = [], []
    for si, (part, lo, hi) in enumerate(segment_slices(params["dec_layers"])):
        h, (nk, nv) = jax.lax.scan(
            body, h, (part, kv_segment(cache.k, si, lo, hi),
                      kv_segment(cache.v, si, lo, hi),
                      kv_segment(cache.cross_k, si, lo, hi),
                      kv_segment(cache.cross_v, si, lo, hi)),
            unroll=unroll_flag())
        ks.append(nk)
        vs.append(nv)
    new_k = kv_rejoin(cache.k, ks)
    new_v = kv_rejoin(cache.v, vs)
    h = _ln(h, params["final"]["norm"], cfg)
    logits = lm_head(h, embed_w)
    return logits, EncDecCache(k=new_k, v=new_v, cross_k=cache.cross_k,
                               cross_v=cache.cross_v, pos=cache.pos + s)


def spec_verify(params, cache: EncDecCache, tokens: jax.Array, cfg):
    """Fused multi-query verify over the decoder stack (cross-attention is
    non-causal over the fixed encoder K/V). Same contract as
    transformer.spec_verify — rollback is position arithmetic."""
    logits, new_cache = decode_step(params, cache, tokens, cfg)
    return logits, (new_cache, tokens.shape[1])


def spec_commit(snap, committed: jax.Array) -> EncDecCache:
    cache, s = snap
    return cache._replace(pos=cache.pos - s + committed)


def block_params(params) -> LayerBlocks:
    """[embed, enc_0..enc_{Le-1}, dec_0..dec_{Ld-1}] — two stacks, one plan."""
    return LayerBlocks(params["embed"],
                       [params["enc_layers"], params["dec_layers"]])
