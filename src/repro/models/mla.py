"""Multi-head latent attention (MLA) of DeepSeek-V2/V3, without q-LoRA.

Weights per attention block (stored (out, in), as everywhere):
  wq: (H*(nope+rope), D)   wkv_a: (r+rope, D)   latent/norm: (r,)
  wkv_b: (H*(nope+v), r)   wo: (D, H*v)

with r = kv_lora_rank. A token's ``wkv_a`` output splits into the latent
c (r wide, RMS-normalized) and one key of ``rope`` width shared by every
head, roped. The cache holds exactly that, one row per token and layer:
[c | k_pe], r + rope = 576 values at Moonlight's widths, in place of a K and
a V per head. The rows carry no head axis, raw (B, S, r + rope) or a flat
KVPage (quant/kvcache.py): a size-1 head axis is a tiled minor dimension
that made XLA relayout the whole cache several times a layer and step.

* Prefill runs the expanded form: ``wkv_b`` turns each c into per-head
  k_nope and v, and attention is causal attention over q = [q_nope | q_pe],
  k = [k_nope | k_pe], scaled by 1/sqrt(nope + rope).
* Decode runs the absorbed form: q_nope W_UK gives an r-wide latent query
  per head, so attention is multi-query attention of H heads over one
  latent "kv head" of width r + rope, whose first r output columns go out
  through W_UV. It runs through the ``decode_attn`` kernel with one KV
  head, V passed as the same cache; q is prescaled by
  sqrt((r + rope) / (nope + rope)) so that the kernel's 1/sqrt(r + rope)
  gives MLA's 1/sqrt(nope + rope). Exact, with no kernel change.

Rope: the rope dims of q and k are stored interleaved (DeepSeek-V3's
``rope_interleave``, true in its published configs); they are
de-interleaved (evens, then odds) before the half-split rotation, as
``apply_rotary_pos_emb_interleave`` does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.qmatmul.ops import count_dispatch
from repro.models.attention import causal_attention
from repro.models.common import dense_init, qdot, rms_norm, rope
from repro.quant import kvcache as KV
from repro.quant.qtypes import QTensor
from repro.quant.quantize import dequantize

# epsilon of the latent's RMSNorm (DeepSeek-V3's kv_a_layernorm keeps the
# norm's default, not the model's rms_norm_eps)
LATENT_EPS = 1e-6


def init_params(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    return {
        "wq": dense_init(ks[0], h * cfg.qk_head_dim, d, dtype),
        "wkv_a": dense_init(ks[1], cfg.latent_dim, d, dtype),
        "latent": {"norm": jnp.ones((r,), dtype)},
        "wkv_b": dense_init(ks[2], h * (cfg.qk_nope_head_dim
                                        + cfg.v_head_dim), r, dtype),
        "wo": dense_init(ks[3], d, h * cfg.v_head_dim, dtype,
                         scale=1.0 / np.sqrt(2 * max(cfg.num_layers, 1))),
    }


def _rope(x, positions, cfg):
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rope(x, positions, cfg.rope_theta)


def _project(p, x, positions, cfg):
    """-> q_nope (B, S, H, nope), q_pe (B, S, H, rope) roped, and the
    cache rows (B, S, r + rope): normalized latent, roped k_pe."""
    b, s, _ = x.shape
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = qdot(x, p["wq"]).reshape(b, s, cfg.num_heads, cfg.qk_head_dim)
    kv = qdot(x, p["wkv_a"])
    c = rms_norm(kv[..., :r], p["latent"]["norm"], LATENT_EPS)
    k_pe = _rope(kv[..., None, r:], positions, cfg)[:, :, 0]
    q_pe = _rope(q[..., nope:], positions, cfg)
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe], -1)


def _wkv_b(p, x, cfg):
    """``wkv_b`` as (H, nope + v, r), dequantized where it is quantized."""
    w = p["wkv_b"]
    if isinstance(w, QTensor):
        count_dispatch("dequant", x.shape[0] * x.shape[1])
        with jax.named_scope("ewq/dequant"):
            w = dequantize(w, x.dtype)
    return w.reshape(cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim,
                     cfg.kv_lora_rank)


def _write(field, rows, pos):
    """Store ``rows`` (B, s, r + rope) at ``pos`` (scalar or (B,)):
    quantize-on-insert into a flat KVPage, or a plain write into a raw
    cache."""
    if KV.is_kv_page(field):
        return KV.update_page(field, rows, pos)
    rows = rows.astype(field.dtype)
    if getattr(pos, "ndim", 0) == 1:
        return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
            c, n, (i, 0)))(field, rows, pos)
    return jax.lax.dynamic_update_slice(field, rows, (0, pos, 0))


@obs.scoped("attn")
def attention(p, x, cfg, positions, cache=None, cache_pos=None):
    """x: (B, S, D). Without ``cache``: causal prefill over x, returning
    (out, the S cache rows (B, S, r + rope)). With ``cache`` (the
    layer's raw (B, T, r + rope) rows or its flat KVPage) and ``cache_pos``
    (scalar or (B,)): the S tokens are written at ``cache_pos`` and attend
    the cache (query i sees rows <= cache_pos + i); returns (out, the
    updated cache)."""
    b, s, _ = x.shape
    h, nope, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    vd = cfg.v_head_dim
    q_nope, q_pe, rows = _project(p, x, positions, cfg)
    if cache is None:
        kvb = qdot(rows[..., :r], p["wkv_b"]).reshape(b, s, h, nope + vd)
        k_pe = jnp.broadcast_to(rows[:, :, None, r:],
                                (b, s, h, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kvb[..., :nope], k_pe], -1)
        out = causal_attention(q, k, kvb[..., nope:])
        new = rows
    else:
        with jax.named_scope("kv"):
            new = _write(cache, rows, cache_pos)
        w = _wkv_b(p, x, cfg)
        with jax.named_scope("mla/absorb"):
            q_lat = jnp.einsum("bshn,hnr->bshr", q_nope.astype(jnp.float32),
                               w[:, :nope].astype(jnp.float32))
            scale = math.sqrt(cfg.latent_dim / cfg.qk_head_dim)
            q = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], -1) * scale
        o = decode_attention(q, new, new, valid_len=cache_pos + s)[..., :r]
        with jax.named_scope("mla/absorb"):
            out = jnp.einsum("bshr,hvr->bshv", o,
                             w[:, nope:].astype(jnp.float32)).astype(x.dtype)
    return qdot(out.reshape(b, s, h * vd), p["wo"]), new
