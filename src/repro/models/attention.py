"""GQA attention with RoPE, KV cache, cross-attention and chunked
(online-softmax) execution for long sequences.

Weights per attention block (all stored (out, in)):
  wq: (H*hd, D)   wk: (Hkv*hd, D)   wv: (Hkv*hd, D)   wo: (D, H*hd)
Optionally q_norm / k_norm RMS weights (chameleon-style QK-norm).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.decode_attn.ops import decode_attention
from repro.models.common import qdot, rms_norm, rope
from repro.quant import kvcache as KV
from repro.sharding.ctx import constrain, model_shards, unroll_flag

NEG_INF = -1e30
# Prefill attention over more than CHUNK_THRESHOLD tokens runs the
# online-softmax chunked form, in Q_CHUNK x KV_CHUNK blocks.
CHUNK_THRESHOLD = 8192
Q_CHUNK = 2048
KV_CHUNK = 2048


class KVCache(NamedTuple):
    """Per-layer attention cache. ``k``/``v`` are raw (B, S_max, Hkv, hd)
    arrays on the bf16 path, or ``quant.kvcache.KVPage``s (int8 / packed
    int4 payload + per-group scales) when serving with a quantized KV
    cache (docs/DESIGN.md §10)."""
    k: jax.Array
    v: jax.Array


def init_kv_cache(batch: int, max_seq: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, num_kv_heads, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _project_qkv(p, x, kv_x, num_heads, num_kv_heads, head_dim, qk_norm,
                 norm_eps):
    b, s, _ = x.shape
    if kv_x is None:
        # self-attention: all three projections share x — one fused launch
        # on TPU, bit-identical qdot triple elsewhere
        from repro.kernels.qmatmul.ops import fused_qkv
        yq, yk, yv = fused_qkv(x, p["wq"], p["wk"], p["wv"])
        q = yq.reshape(b, s, num_heads, head_dim)
        k = yk.reshape(b, s, num_kv_heads, head_dim)
        v = yv.reshape(b, s, num_kv_heads, head_dim)
        skv = s
    else:
        q = qdot(x, p["wq"]).reshape(b, s, num_heads, head_dim)
        src = kv_x
        skv = src.shape[1]
        k = qdot(src, p["wk"]).reshape(b, skv, num_kv_heads, head_dim)
        v = qdot(src, p["wv"]).reshape(b, skv, num_kv_heads, head_dim)
    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    q = constrain(q, ("batch", None, "model", None))
    k = constrain(k, ("batch", None, "model", None))
    v = constrain(v, ("batch", None, "model", None))
    return q, k, v


def _flatten_gqa_for_sharding(q, k, v):
    """TP-align attention when head counts don't divide the model axis.

    56 q-heads (arctic) or 36 (minicpm) on a 16-way model axis would be
    REPLICATED by the divisibility rule — 16x redundant attention compute
    and 16x score memory (the dominant term in the baseline sweep). Instead:
    repeat KV heads to the flat q-head count (rep=1 grouping) and zero-pad
    heads up to a multiple of the axis, so scores shard cleanly. Padding
    waste is (pad/H) extra attention FLOPs (14% for arctic, 33% for
    llama3.2-3b) versus a 16x replication loss. The TPU-target flash kernel
    handles grouped heads natively; this is the XLA-level layout
    (docs/DESIGN.md §5). Returns (q, k, v, original_h).
    """
    ms = model_shards()
    h, hkv = q.shape[2], k.shape[2]
    if ms <= 1 or (h % ms == 0 and hkv % ms == 0):
        return q, k, v, h
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    pad = (-h) % ms
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths)
    q = constrain(q, ("batch", None, "model", None))
    k = constrain(k, ("batch", None, "model", None))
    v = constrain(v, ("batch", None, "model", None))
    return q, k, v, h


def decode_valid_bias(cache_pos, s: int, t: int):
    """Additive decode mask for ``s`` query positions written at
    ``cache_pos``: query i (absolute position ``cache_pos + i``) sees cache
    rows ``<= cache_pos + i`` — per-query causal offset masking, so a
    speculative verify window (s = K+1, docs/DESIGN.md §11) never attends
    to its own future. s=1 reduces to the plain decode validity mask.
    Broadcastable against (B, Hkv, rep, S, T) scores.

    Identical for every layer of a decode step, so families compute it ONCE
    per step (``decode_step_bias``) and pass it down instead of rebuilding
    the (T,) iota-compare in each of L layers."""
    rows = jnp.arange(t)
    qi = jnp.arange(s)
    if getattr(cache_pos, "ndim", 0) == 1:
        valid = (rows[None, None, :]
                 <= cache_pos[:, None, None] + qi[None, :, None])  # (B, S, T)
        return jnp.where(valid, 0.0, NEG_INF)[:, None, None, :, :]
    valid = rows[None, :] <= (cache_pos + qi[:, None])             # (S, T)
    return jnp.where(valid, 0.0, NEG_INF)[None, None, None, :, :]


def decode_step_bias(cache_k_field, cache_pos, s: int = 1):
    """Per-step hoisted validity bias for a family's stacked cache field
    ((L, B, S_max, Hkv, hd)) and ``s`` query positions. Quantized caches
    return None — the fused decode kernel masks by position arithmetic
    instead of a bias tensor."""
    if KV.is_kv_page(cache_k_field):
        return None
    return decode_valid_bias(cache_pos, s, cache_k_field.shape[2])


def _gqa_scores(q, k):
    """q: (B,S,Hkv,rep,hd), k: (B,T,Hkv,hd) -> (B,Hkv,rep,S,T) f32."""
    return jnp.einsum("bshrd,bthd->bhrst", q, k,
                      preferred_element_type=jnp.float32)


def _full_attention(q, k, v, mask_bias):
    """Materialized-scores attention (short sequences / decode)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    qh = q.reshape(b, s, hkv, rep, d)
    scores = _gqa_scores(qh, k) / jnp.sqrt(d).astype(jnp.float32)
    scores = scores + mask_bias  # (B,Hkv,rep,S,T) + broadcastable bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhrst,bthd->bshrd", probs.astype(v.dtype), v)
    return out.reshape(b, s, h, v.shape[-1])


def _chunked_causal_attention(q, k, v):
    """Online-softmax attention: scan over KV chunks for each Q chunk.

    Pure-JAX flash-attention analogue: temp memory is O(q_chunk * kv_chunk)
    instead of O(S*T). Causal masking via chunk-level position arithmetic.

    In ctx.cost_mode the loops fully unroll (XLA cost analysis counts while
    bodies once), with coarsened 4x4 chunking to bound HLO size — chunk
    granularity does not change the FLOP count.
    """
    from repro.sharding.ctx import in_cost_mode, unroll_flag
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    t = k.shape[1]
    q_chunk_pref = max(s // 4, 1) if in_cost_mode() else Q_CHUNK
    kv_chunk_pref = max(t // 4, 1) if in_cost_mode() else KV_CHUNK
    nq = s // q_chunk_pref if s % q_chunk_pref == 0 else 1
    q_chunk = q_chunk_pref if s % q_chunk_pref == 0 else s
    nk = t // kv_chunk_pref if t % kv_chunk_pref == 0 else 1
    kv_chunk = kv_chunk_pref if t % kv_chunk_pref == 0 else t
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    dv = v.shape[-1]
    qc = q.reshape(b, nq, q_chunk, hkv, rep, d)
    kc = k.reshape(b, nk, kv_chunk, hkv, d)
    vc = v.reshape(b, nk, kv_chunk, hkv, dv)

    q_pos = jnp.arange(q_chunk)
    k_pos = jnp.arange(kv_chunk)

    def one_q_chunk(qi, q_blk):
        # q_blk: (b, q_chunk, hkv, rep, d)
        def body(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            scores = jnp.einsum("bshrd,bthd->bhrst", q_blk, k_blk,
                                preferred_element_type=jnp.float32) * scale
            abs_q = qi * q_chunk + q_pos
            abs_k = ki * kv_chunk + k_pos
            causal = abs_q[:, None] >= abs_k[None, :]
            scores = jnp.where(causal[None, None, None], scores, NEG_INF)
            m_blk = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m, m_blk)
            p = jnp.exp(scores - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhrst,bthd->bhrsd", p.astype(v_blk.dtype), v_blk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, rep, q_chunk, dv), v.dtype)
        ks = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (ks, jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
            unroll=unroll_flag())
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return out  # (b, hkv, rep, q_chunk, d)

    _, outs = jax.lax.scan(
        lambda c, args: (c, one_q_chunk(*args)), None,
        (jnp.arange(nq), jnp.moveaxis(qc, 1, 0)), unroll=unroll_flag())
    # outs: (nq, b, hkv, rep, q_chunk, dv) -> (b, s, h, dv)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    return out.reshape(b, s, h, dv)


def causal_attention(q, k, v):
    """Causal self-attention of a whole sequence (train / prefill): the
    scores materialized up to ``CHUNK_THRESHOLD`` tokens, the online-softmax
    chunked form beyond. q/k: (B, S, H, d), v: (B, S, Hkv, dv), scaled by
    1/sqrt(d)."""
    s, t = q.shape[1], k.shape[1]
    if s > CHUNK_THRESHOLD:
        return _chunked_causal_attention(q, k, v)
    causal_mask = jnp.tril(jnp.ones((s, t), bool))
    bias = jnp.where(causal_mask, 0.0, NEG_INF)[None, None, None]
    return _full_attention(q, k, v, bias)


@obs.scoped("attn")
def attention(p, x, *, num_heads: int, num_kv_heads: int, head_dim: int,
              positions: Optional[jax.Array] = None,
              rope_theta: Optional[float] = None,
              causal: bool = True, qk_norm: bool = False,
              norm_eps: float = 1e-5,
              kv_x: Optional[jax.Array] = None,
              cache: Optional[KVCache] = None,
              cache_pos: Optional[jax.Array] = None,
              cached_kv: Optional[KVCache] = None,
              valid_bias: Optional[jax.Array] = None,
              fresh_kv: Optional[tuple] = None,
              emit_kv: bool = False):
    """General attention entry point.

    Modes:
      * prefill/train: cache=None — full or chunked causal attention.
      * decode: cache given, x is (B, 1, D); k/v written at cache_pos and
        attention runs against the cache. A raw cache masks with
        ``valid_bias`` (hoisted once per step by the family decode loop,
        rebuilt inline for direct callers); a quantized cache (KVPage)
        quantizes-on-insert and runs the fused streaming kernel —
        no (…, S_max) score tensor is materialized.
      * read-only decode (fused draft propose, docs/DESIGN.md §12):
        cache AND ``fresh_kv=(fresh_k, fresh_v, count)`` given — the new
        k/v are appended at row ``count`` of the raw (B, K, Hkv, hd) side
        buffers instead of being written to the cache; the decode kernel
        sweeps the buffer rows at logical positions ``cache_pos + j`` with
        the page's exact quantize-on-write math. Returns the UPDATED side
        buffers (as a KVCache) in the cache slot; the cache is untouched.
      * cross-attention decode: cached_kv given (precomputed encoder K/V,
        raw or quantized).
    Returns (out, new_cache_or_None).
    """
    b, s, _ = x.shape

    if cached_kv is not None:
        # Cross-attention against fixed precomputed K/V.
        q = qdot(x, p["wq"]).reshape(b, s, num_heads, head_dim)
        if qk_norm:
            q = rms_norm(q, p["q_norm"], norm_eps)
        if KV.is_kv_page(cached_kv.k):
            # non-causal: every (verify) query sees the whole encoder cache
            out = decode_attention(q, cached_kv.k, cached_kv.v, causal=False)
        else:
            out = _full_attention(q, cached_kv.k, cached_kv.v, 0.0)
        return qdot(out.reshape(b, s, num_heads * head_dim), p["wo"]), None

    q, k, v = _project_qkv(p, x, kv_x, num_heads, num_kv_heads, head_dim,
                           qk_norm, norm_eps)
    if rope_theta is not None and positions is not None:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)

    if cache is not None and fresh_kv is not None:
        # Read-only draft propose: k/v go into the side buffer at row
        # ``count``; the cache itself is never written (zero draft-side
        # KV traffic — the whole point of the fused propose path).
        fk, fv, count = fresh_kv
        with jax.named_scope("kv"):
            fk = jax.lax.dynamic_update_slice(
                fk, k.astype(fk.dtype), (0, count, 0, 0))
            fv = jax.lax.dynamic_update_slice(
                fv, v.astype(fv.dtype), (0, count, 0, 0))
        out = decode_attention(q, cache.k, cache.v,
                               valid_len=cache_pos + count + s,
                               fresh_kv=(fk, fv, cache_pos))
        new_cache = KVCache(k=fk, v=fv)          # the updated side buffers
    elif cache is not None:
        # Decode: insert new k/v at cache_pos, attend over the cache.
        # cache_pos is a scalar (whole batch at one position) or a (B,)
        # vector (continuous batching: per-slot positions).
        if KV.is_kv_page(cache.k):
            # Quantized KV cache: quantize-on-insert, then stream the int8
            # / int4 pages through the fused online-softmax decode kernel.
            with jax.named_scope("kv"):
                k_cache = KV.update_page(cache.k, k, cache_pos)
                v_cache = KV.update_page(cache.v, v, cache_pos)
            out = decode_attention(q, k_cache, v_cache,
                                   valid_len=cache_pos + s)
        else:
            with jax.named_scope("kv"):
                if getattr(cache_pos, "ndim", 0) == 1:
                    write = jax.vmap(
                        lambda c, n, p: jax.lax.dynamic_update_slice(
                            c, n, (p, 0, 0)))
                    k_cache = write(cache.k, k.astype(cache.k.dtype),
                                    cache_pos)
                    v_cache = write(cache.v, v.astype(cache.v.dtype),
                                    cache_pos)
                else:
                    k_cache = jax.lax.dynamic_update_slice(
                        cache.k, k.astype(cache.k.dtype),
                        (0, cache_pos, 0, 0))
                    v_cache = jax.lax.dynamic_update_slice(
                        cache.v, v.astype(cache.v.dtype),
                        (0, cache_pos, 0, 0))
            bias = valid_bias if valid_bias is not None else \
                decode_valid_bias(cache_pos, s, k_cache.shape[1])
            out = _full_attention(q, k_cache, v_cache, bias)
        new_cache = KVCache(k=k_cache, v=v_cache)
    elif causal:
        new_cache = KVCache(k=k, v=v) if emit_kv else None
        q, k, v, h_orig = _flatten_gqa_for_sharding(q, k, v)
        out = causal_attention(q, k, v)[:, :, :h_orig, :]
    else:  # bidirectional (encoder)
        new_cache = KVCache(k=k, v=v) if emit_kv else None
        q, k, v, h_orig = _flatten_gqa_for_sharding(q, k, v)
        out = _full_attention(q, k, v, 0.0)
        out = out[:, :, :h_orig, :]

    out = constrain(out, ("batch", None, "model", None))
    out = qdot(out.reshape(b, s, num_heads * head_dim), p["wo"])
    out = constrain(out, ("batch", None, None))
    return out, new_cache


def init_attention_params(key, cfg, dtype, with_qk_norm=False):
    import numpy as np
    ks = jax.random.split(key, 4)
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    from repro.models.common import dense_init
    p = {
        "wq": dense_init(ks[0], h * hd, d, dtype),
        "wk": dense_init(ks[1], hkv * hd, d, dtype),
        "wv": dense_init(ks[2], hkv * hd, d, dtype),
        "wo": dense_init(ks[3], d, h * hd, dtype,
                         scale=1.0 / np.sqrt(2 * max(cfg.num_layers, 1))),
    }
    if with_qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p
