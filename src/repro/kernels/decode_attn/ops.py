"""Public entry point for fused decode attention over a (quantized) KV cache.

``decode_attention(q, k, v, valid_len=...)`` is what the model stack calls
on the decode hot path. ``q`` is a single decode token (B, 1, H, hd) or a
short multi-query verify window (B, K+1, H, hd) — speculative decoding
scores all draft positions in one streaming pass with per-query causal
offset masking (docs/DESIGN.md §11). ``k``/``v`` may be:

* ``quant.kvcache.KVPage``   (int8 / packed int4 / bf16 + per-group scales)
* plain jax.Array            (raw bf16 cache, (B, S, Hkv, hd), or one kv
                              head's flat (B, S, F) rows — MLA's latent)

Backend selection mirrors ``qdot`` (kernels/qmatmul/ops.py):

* ``auto``    — (default) the Pallas kernel on TPU in an unsharded
  trace, else the ``grouped`` jnp fallback (the compiler cannot partition
  a Pallas call over a mesh). Both stream the cache in KV chunks with an online softmax
  and dequantize in-register — no (…, S_max) score tensor is ever
  materialized on the decode path.
* ``pallas``  — force the Pallas kernel (raises off-TPU rather than
  silently degrading).
* ``grouped`` — jnp fallback with the kernel's exact math (chunked online
  softmax; temp memory O(kv_chunk) per step).
* ``simple``  — dequantize-the-cache + dense-softmax oracle (materializes
  the (…, S) scores; parity baseline only).

Set process-wide via ``configure_decode_attn`` (or the
``REPRO_DECODE_ATTN_BACKEND`` / ``REPRO_DECODE_KV_CHUNK`` env vars, read
once at import as initial defaults — NOT the same knob as the
prefill-side ``REPRO_KV_CHUNK`` of models/attention.py). Any chunk width
works for any cache length: a non-dividing final chunk is read
clamped/padded and the extra rows are masked out. Both fallbacks are
validated against ref.py (tests/test_decode_attn.py,
tests/test_spec_decode.py).

``fresh_kv=(fresh_k, fresh_v, base)`` appends a small raw K/V side
buffer — quantized in-call with the page's exact write math — at logical
positions ``base + j`` WITHOUT writing the cache; cache rows at
positions >= base are masked stale. This is what lets the speculative
draft propose k tokens with zero cache writes (docs/DESIGN.md §12).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn.kernel import decode_attn_pallas
from repro.kernels.decode_attn.ref import decode_attn_ref
from repro.quant import paged as paged_ops
from repro.quant.kvcache import (KVPage, PagedKV, dequantize_kv, quantize_kv,
                                 update_page)

BACKENDS = ("auto", "pallas", "grouped", "simple")
NEG_INF = -1e30
_backend = os.environ.get("REPRO_DECODE_ATTN_BACKEND", "auto")
_kv_chunk = int(os.environ.get("REPRO_DECODE_KV_CHUNK", "256"))


def configure_decode_attn(backend: Optional[str] = None,
                          kv_chunk: Optional[int] = None) -> None:
    """Override the decode-attention knobs process-wide (mirrors
    ``models/attention.configure_chunking``). Read at TRACE time — rebuild
    jitted executables, or pass ``backend=`` / ``kv_chunk=`` per call, to
    switch after tracing."""
    global _backend, _kv_chunk
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"unknown decode-attn backend {backend!r}; "
                             f"one of {BACKENDS}")
        _backend = backend
    if kv_chunk is not None:
        if kv_chunk < 1:
            raise ValueError(f"kv chunk must be >= 1, got {kv_chunk}")
        _kv_chunk = kv_chunk


def get_decode_attn_backend() -> str:
    return _backend


def get_decode_kv_chunk() -> int:
    return _kv_chunk


def _use_pallas() -> bool:
    """A TPU, and a trace that is not partitioned over several devices
    (the compiler refuses to partition a Mosaic kernel)."""
    from repro.sharding.ctx import sharded_trace
    return jax.default_backend() == "tpu" and not sharded_trace()


def _page_of(x):
    """Normalize a cache operand to a KVPage (raw arrays become bf16-style
    pages with no scales; a rank-3 (B, S, F) array is one kv head stored
    flat). PagedKV pool views pass through — every backend reads them
    through the slot page table."""
    if isinstance(x, (KVPage, PagedKV)):
        return x
    return KVPage(data=x, scale=None, precision="bf16",
                  head_dim=x.shape[-1], group=x.shape[-1], flat=x.ndim == 3)


def _valid_vec(valid_len, b: int, s: int) -> jax.Array:
    if valid_len is None:
        return jnp.full((b,), s, jnp.int32)
    return jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))


def _fresh_page(raw: jax.Array, like: KVPage) -> KVPage:
    """Quantize fresh rows with the page's EXACT write math (update_page's
    quantize-on-insert), so the fused no-write draft sweep reads values
    bit-identical to what a cache write would have stored."""
    data, scale = quantize_kv(raw, like.precision, like.group)
    return KVPage(data=data.astype(like.data.dtype), scale=scale,
                  precision=like.precision, head_dim=raw.shape[-1],
                  group=like.group)


def _simple(q, kp, vp, valid, causal: bool, fresh=None) -> jax.Array:
    if isinstance(kp, PagedKV):
        # materialize the pool through the page table FIRST so the fresh
        # rows below go through the dense page's write math (`update_page`
        # quantize-on-insert) exactly like `_fresh_page` does in the
        # streaming backends
        kp, vp = paged_ops.gather(kp), paged_ops.gather(vp)
    if fresh is not None:
        # reference semantics: fresh rows behave exactly as if written
        fk, fv, base = fresh
        kp = update_page(kp, fk, base)
        vp = update_page(vp, fv, base)
    return decode_attn_ref(q, dequantize_kv(kp), dequantize_kv(vp), valid,
                           causal=causal)


def _grouped(q, kp, vp, valid, kv_chunk: int,
             causal: bool, fresh=None) -> jax.Array:
    """Chunked online-softmax decode attention — the kernel's exact math in
    jnp. Chunks are carved out of the cache in place with dynamic slices
    (no reshaped/transposed copy of the full cache), so temp memory is
    O(B * Hkv * rep * S * kv_chunk), never O(S_max) — for ANY cache
    length: a non-dividing final chunk is read with a clamped start and
    the re-visited rows are masked out, so every row contributes exactly
    once. Paged pools read the same chunks through the slot page table:
    the chunk width snaps to a whole number of pages and each chunk is a
    (B, pages_per_chunk) table gather instead of a dense slice — identical
    masking arithmetic, so paged/dense outputs match bit-for-bit."""
    b, s, h, d = q.shape
    hkv = kp.num_kv_heads
    rep = h // hkv
    if isinstance(kp, PagedKV):
        p_sz, n_log = kp.page_size, kp.table.shape[-1]
        t = n_log * p_sz
        g = max(1, min(kv_chunk // p_sz, n_log))
        chunk = g * p_sz
    else:
        t = kp.data.shape[1]
        chunk = min(kv_chunk, t)
    nc = -(-t // chunk)                              # ceil-div
    qh = jnp.moveaxis(q.reshape(b, s, hkv, rep, d), 1, 3)  # (B,Hkv,rep,S,d)
    qh = qh.astype(jnp.float32)
    inv_sqrt = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    if causal:
        # query i sees rows < valid - s + 1 + i
        limit = valid[:, None] - s + 1 + jnp.arange(s)[None, :]   # (B, S)
    else:
        limit = jnp.broadcast_to(valid[:, None], (b, s))
    if fresh is not None:
        # cache rows at positions >= base are STALE: the fresh side buffer
        # supersedes them (it holds the rows a cache write would have put
        # there)
        base = fresh[2]
        cache_limit = jnp.minimum(limit, base[:, None])
    else:
        cache_limit = limit

    def take(page, start):
        if isinstance(page, PagedKV):
            npg = chunk // page.page_size
            ids = jax.lax.dynamic_slice(
                page.table, (0, start // page.page_size), (b, npg))

            def gat(x):
                y = x[ids]                           # (B, npg, P, ...)
                return y.reshape(b, chunk, *x.shape[2:])

            return KVPage(
                data=gat(page.data),
                scale=None if page.scale is None else gat(page.scale),
                precision=page.precision, head_dim=page.head_dim,
                group=page.group)
        return jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(
            x, start, chunk, axis=1), page)

    def update(carry, kf, vf, scores_mask):
        m, l, acc = carry
        scores = jnp.einsum("bhrsd,bchd->bhrsc", qh, kf,
                            preferred_element_type=jnp.float32) * inv_sqrt
        scores = jnp.where(scores_mask[:, None, None, :, :], scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhrsc,bchd->bhrsd", p, vf,
                        preferred_element_type=jnp.float32)
        return (m_new, l_new, acc * corr[..., None] + pv)

    def body(ci, carry):
        start = jnp.minimum(ci * chunk, t - chunk)   # clamp the last chunk
        kf = dequantize_kv(take(kp, start))          # (B, C, Hkv, hd) f32
        vf = dequantize_kv(take(vp, start))
        pos = start + jnp.arange(chunk)
        # rows re-read by a clamped start were handled by a prior chunk
        live = pos >= ci * chunk
        mask = (live[None, None, :]
                & (pos[None, None, :] < cache_limit[:, :, None]))  # (B,S,C)
        return update(carry, kf, vf, mask)

    m0 = jnp.full((b, hkv, rep, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, s), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, s, d), jnp.float32)
    carry = jax.lax.fori_loop(0, nc, body, (m0, l0, a0))
    if fresh is not None:
        fk, fv, base = fresh
        kf = dequantize_kv(_fresh_page(fk, kp))       # (B, Sf, Hkv, hd)
        vf = dequantize_kv(_fresh_page(fv, vp))
        pos_f = base[:, None] + jnp.arange(fk.shape[1])[None, :]  # (B, Sf)
        mask = pos_f[:, None, :] < limit[:, :, None]              # (B,S,Sf)
        carry = update(carry, kf, vf, mask)
    m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(b, s, h, d).astype(q.dtype)


def _pallas(q, kp, vp, valid, kv_chunk: int, causal: bool,
            fresh=None, interpret: bool = False) -> jax.Array:
    b, s, h, d = q.shape
    hkv = kp.num_kv_heads
    rep = h // hkv
    paged = isinstance(kp, PagedKV)

    def flat(page):
        # dense: (B, S, ...) -> (B, S, F_store), a no-op for a flat page;
        # paged pool: (N, P, ...) -> (N, P, F_store) — the kernel's
        # scalar-prefetched page table maps grid steps to physical pages
        lead = page.data.shape[:2]
        data = page.data.reshape(*lead, -1)
        if page.scale is None:  # bf16 page: dummy unit scales, never read
            scale = jnp.ones((*lead, 1), jnp.bfloat16)
        else:
            scale = page.scale
        return data, scale

    kd, ks = flat(kp)
    vd, vs = flat(vp)
    qk = jnp.moveaxis(q.reshape(b, s, hkv, rep, d), 1, 3)  # (B,Hkv,rep,S,d)
    fresh_args = {}
    if fresh is not None:
        fk, fv, base = fresh
        sf = fk.shape[1]
        pad = (-sf) % 8                  # sublane-align the tiny row axis
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            fk, fv = jnp.pad(fk, widths), jnp.pad(fv, widths)
        fkd, fks = flat(_fresh_page(fk, kp))
        fvd, fvs = flat(_fresh_page(fv, vp))
        fresh_args = dict(fresh_k_data=fkd, fresh_k_scale=fks,
                          fresh_v_data=fvd, fresh_v_scale=fvs,
                          base=base[:, None])
    out = decode_attn_pallas(
        qk, kd, ks, vd, vs, valid[:, None],
        precision=kp.precision, group=kp.group, head_dim=d,
        kv_chunk=kv_chunk, causal=causal, interpret=interpret,
        page_table=kp.table if paged else None,
        **fresh_args)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, h, d).astype(q.dtype)


def decode_attention(q: jax.Array, k, v, *,
                     valid_len: Optional[jax.Array] = None,
                     causal: bool = True,
                     backend: Optional[str] = None,
                     kv_chunk: Optional[int] = None,
                     fresh_kv=None) -> jax.Array:
    """(Multi-)query GQA attention of q (B, S, H, hd) against a cached
    K/V (KVPage, raw (B, T, Hkv, hd), or one head's raw flat (B, T, hd)).
    ``valid_len`` (scalar or per-slot (B,)) counts valid cache rows
    INCLUDING the S freshly-written query rows; with ``causal=True``
    query i additionally only sees rows
    ``< valid_len - S + 1 + i`` (S=1 reduces to the plain decode mask),
    with ``causal=False`` every query sees all valid rows (cross-attention
    over precomputed encoder K/V). ``backend`` overrides the process-wide
    selection for this call.

    ``fresh_kv=(fresh_k, fresh_v, base)`` — raw (B, Sf, Hkv, hd) side
    buffers plus a per-slot (B,) base position: row j acts exactly as if
    it had been written (quantize-on-insert) at cache position
    ``base + j``, and cache rows at positions >= base are masked stale.
    ``valid_len`` still counts ALL valid rows including the fresh ones.
    Returns (B, S, H, hd) in q's dtype."""
    backend = _backend if backend is None else backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown decode-attn backend {backend!r}; "
                         f"one of {BACKENDS}")
    if kv_chunk is None:
        kv_chunk = _kv_chunk
    elif kv_chunk < 1:
        raise ValueError(f"kv_chunk must be >= 1, got {kv_chunk}")
    kp, vp = _page_of(k), _page_of(v)
    assert kp.precision == vp.precision and kp.group == vp.group, \
        "K and V cache pages must share precision/group"
    b, s, h, d = q.shape
    assert s >= 1, f"decode attention needs at least one query, got s={s}"
    if fresh_kv is not None:
        fk, fv, base = fresh_kv
        assert fk.shape == fv.shape and fk.ndim == 4, (fk.shape, fv.shape)
        fresh_kv = (fk, fv, jnp.broadcast_to(
            jnp.asarray(base, jnp.int32), (b,)))
    t_total = (kp.seq_len if isinstance(kp, PagedKV) else kp.data.shape[1])
    valid = _valid_vec(valid_len, b, t_total)
    if backend == "pallas" or (backend == "auto" and _use_pallas()):
        if backend == "pallas" and not _use_pallas():
            raise ValueError(
                f"decode-attn backend 'pallas' needs a TPU and an "
                f"unsharded trace; running on {jax.default_backend()!r} "
                f"(use 'grouped' for the "
                f"identical-math jnp fallback)")
        return _pallas(q, kp, vp, valid, kv_chunk, causal, fresh_kv)
    if backend == "simple":
        return _simple(q, kp, vp, valid, causal, fresh_kv)
    return _grouped(q, kp, vp, valid, kv_chunk, causal, fresh_kv)
