"""Pallas TPU kernel: fused (multi-)query GQA decode attention over a
quantized KV cache.

Computes, for one decode step (or a short speculative verify window of
``qs`` token positions) per batch slot,

  out[b, h, r, i] = softmax_t( q[b, h, r, i] . K[b, t, h] / sqrt(hd) )
                    . V[b, t, h]

with t masked per query: with ``causal=True`` query i sits at absolute
cache position ``valid_len - qs + i`` and sees rows ``<= valid_len - qs
+ i`` (qs=1 recovers the plain decode mask; qs=K+1 is the speculative
verify window — all draft positions scored in ONE streaming pass,
docs/DESIGN.md §11); ``causal=False`` (cross-attention verify) lets
every query see all ``valid_len`` rows. K/V are stored int8 /
packed-int4 with per-group scales (quant/kvcache.py layout) or bf16.
Design for TPU (validated on CPU via interpret=True and compiled for a
described v5e in tests/test_tpu_compile.py):

* Grid (B, S/C) with the KV-chunk dimension innermost: the online-softmax
  running max / normalizer / accumulator live in VMEM scratch and are
  revisited per chunk — no (…, S) score tensor ever exists; temp memory
  is O(C) per step.
* The cache arrives with heads flattened, data (B, S, F_store) and scales
  (B, S, F/G). One chunk dequantizes in VMEM: int4 is split-half unpacked
  (one lane-aligned concat), and the (C, F/G) scales are spread over
  their groups by one 0/1 matmul on the MXU (exact: one nonzero term per
  output) instead of a lane-splitting reshape. Each head's (C, hd) slab
  then feeds a (rep*qs, hd) x (hd, C) MXU dot. The per-head loop is a
  static python unroll.
* Per-slot scalars — ``valid_len``, the fresh-row ``base`` and the paged
  pool's page table — ride as scalar-prefetch operands in SMEM
  (pltpu.PrefetchScalarGridSpec). Chunk positions are compared against
  each query's causal limit so freshly-admitted slots with short prompts
  never attend to stale cache rows and verify queries never see their
  own future.
* Optional FRESH rows (speculative draft propose with zero cache
  writes, docs/DESIGN.md §12): a small already-quantized side buffer
  (B, Sf, F_store) at logical positions ``base + j`` is swept as an
  epilogue block on the LAST chunk step of the same online softmax —
  the k-round costs one cache sweep, not one per draft write. Cache
  rows at positions >= base are masked stale (the side buffer holds
  what a write would have stored).

VMEM @ C=256, F=Hkv*hd=2304: data 2x256x2304 = 1.2MB (int8) + its f32
dequant, scales 18KB, scratch (Hkv, rep*qs, 128) f32 — well under the
scoped VMEM limit of v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# single source of truth for the nibble order / sign-extension invariant:
# the pack side lives in quant/kvcache.quantize_kv
from repro.quant.kvcache import _unpack_kv_int4

DEFAULT_KV_CHUNK = 256
NEG_INF = -1e30
_LANES = 128          # running max / normalizer are kept lane-replicated
_SUBLANE_ROWS = 32    # int8 sublane tiling: cache chunks align to it
_NT = (((1,), (1,)), ((), ()))


def _spread_scales(scale: jax.Array, group: int, f: int) -> jax.Array:
    """(C, F/G) per-group scales -> (C, F): each scale repeated over its
    ``group`` lanes, via a 0/1 expansion matmul."""
    ng = scale.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (ng, f), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (ng, f), 1)
    spread = ((col >= row * group) & (col < row * group + group)
              ).astype(jnp.float32)
    return jnp.dot(scale.astype(jnp.float32), spread,
                   preferred_element_type=jnp.float32)


def _dequant(data, scale, *, precision: str, group: int) -> jax.Array:
    """One KV chunk (C, F_store) -> (C, F) f32, dequantized in VMEM."""
    if precision == "bf16":
        return data.astype(jnp.float32)
    if precision == "int4":
        data = _unpack_kv_int4(data.astype(jnp.int32))
    vals = data.astype(jnp.float32)
    return vals * _spread_scales(scale, group, vals.shape[1])


def _decode_attn_kernel(*refs, precision: str, group: int,
                        num_kv_heads: int, head_dim: int, qs: int,
                        causal: bool, chunk: int, num_chunks: int,
                        fresh_rows: int, paged: bool):
    valid_ref, refs = refs[0], refs[1:]
    base_ref = None
    if fresh_rows:
        base_ref, refs = refs[0], refs[1:]
    if paged:
        refs = refs[1:]     # the page table is read by the index maps only
    if fresh_rows:
        (q_ref, kd_ref, ks_ref, vd_ref, vs_ref,
         fkd_ref, fks_ref, fvd_ref, fvs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, kd_ref, ks_ref, vd_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    b, ci = pl.program_id(0), pl.program_id(1)
    rq = q_ref.shape[2]                     # rep * qs query rows per head

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[b]
    if causal and qs > 1:
        # row r is query r % qs; query i sees rows < valid - qs + 1 + i
        qi = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (rq, 1), 0), qs)
        limit = valid - qs + 1 + qi
    else:
        limit = jnp.full((rq, 1), valid, jnp.int32)
    inv_sqrt = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)

    def online_update(kf, vf, mask):
        """One masked online-softmax block update over kf/vf's rows."""
        for h in range(num_kv_heads):                 # static unroll
            cols = slice(h * head_dim, (h + 1) * head_dim)
            q_h = q_ref[0, h].astype(jnp.float32)     # (rq, hd)
            s_h = jax.lax.dot_general(
                q_h, kf[:, cols], _NT,
                preferred_element_type=jnp.float32) * inv_sqrt
            s_h = jnp.where(mask, s_h, NEG_INF)       # (rq, rows)
            m_prev = m_ref[h]                         # (rq, LANES)
            m_new = jnp.maximum(m_prev, jnp.max(s_h, axis=-1, keepdims=True))
            p = jnp.exp(s_h - m_new[:, :1])
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr[:, :1] + jnp.dot(
                p, vf[:, cols], preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    kf = _dequant(kd_ref[0], ks_ref[0], precision=precision, group=group)
    vf = _dequant(vd_ref[0], vs_ref[0], precision=precision, group=group)
    start = ci * chunk
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    # cache rows at positions >= base are stale when fresh rows supersede
    cache_limit = (jnp.minimum(limit, base_ref[b]) if fresh_rows
                   else limit)
    # zero invalid V rows: their probability is exactly 0, but a padded
    # tail block (ceil-div grid) may hold NaN/garbage and 0 * NaN = NaN
    row = start + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    vf = jnp.where(row < valid, vf, 0.0)
    online_update(kf, vf, pos < cache_limit)

    if fresh_rows:
        @pl.when(ci == num_chunks - 1)
        def _fresh():
            kff = _dequant(fkd_ref[0], fks_ref[0], precision=precision,
                           group=group)
            vff = _dequant(fvd_ref[0], fvs_ref[0], precision=precision,
                           group=group)
            base = base_ref[b]
            pos_f = base + jax.lax.broadcasted_iota(
                jnp.int32, (1, fresh_rows), 1)
            row_f = base + jax.lax.broadcasted_iota(
                jnp.int32, (fresh_rows, 1), 0)
            online_update(kff, jnp.where(row_f < valid, vff, 0.0),
                          pos_f < limit)

    @pl.when(ci == num_chunks - 1)
    def _finalize():
        for h in range(num_kv_heads):
            l = jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[0, h] = acc_ref[h] / l


def _chunk_rows(kv_chunk: int, s: int) -> int:
    """KV rows per grid step: the requested width, kept a multiple of the
    int8 sublane tiling when it is below the cache length, preferring a
    width that divides the cache so no step reads a padded tail."""
    chunk = min(kv_chunk, s)
    if chunk == s or chunk < _SUBLANE_ROWS:
        return chunk
    chunk -= chunk % _SUBLANE_ROWS
    for c in range(chunk, _SUBLANE_ROWS - 1, -_SUBLANE_ROWS):
        if s % c == 0:
            return c
    return chunk


@functools.partial(jax.jit, static_argnames=("precision", "group",
                                             "head_dim", "kv_chunk",
                                             "causal", "interpret"))
def decode_attn_pallas(q: jax.Array, k_data: jax.Array, k_scale: jax.Array,
                       v_data: jax.Array, v_scale: jax.Array,
                       valid_len: jax.Array, *, precision: str = "int8",
                       group: int = 64, head_dim: int,
                       kv_chunk: int = DEFAULT_KV_CHUNK,
                       causal: bool = True,
                       fresh_k_data: jax.Array | None = None,
                       fresh_k_scale: jax.Array | None = None,
                       fresh_v_data: jax.Array | None = None,
                       fresh_v_scale: jax.Array | None = None,
                       base: jax.Array | None = None,
                       page_table: jax.Array | None = None,
                       interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, rep, Qs, hd) f32/bf16; k/v data: (B, S, F_store) int8 or
    bf16 (F_store = Hkv*hd, int4: Hkv*hd//2); k/v scale: (B, S, F//group)
    bf16; valid_len: (B, 1) int32 rows valid AFTER the Qs query rows were
    written. Optional fresh_* / base: an already-quantized (B, Sf,
    F_store) side buffer swept at logical positions ``base + j`` with
    cache rows >= base masked stale (no-write speculative propose).

    Optional ``page_table`` (B, n_log) int32 switches K/V to a PAGED pool:
    k/v data become (N_phys, P, F_store) page pools and the table maps
    slot i's logical chunk c to its physical page. The table rides as a
    scalar-prefetch operand so the block index maps can read it — grid
    step (i, c) DMAs physical page ``table[i, c]`` while positions stay
    logical (c * P + row), which keeps the kernel body identical to the
    dense path. The KV chunk is pinned to the page size; reads of
    unallocated logical pages hit the dump page and are discarded by the
    validity mask.
    Returns (B, Hkv, rep, Qs, hd) f32."""
    b, hkv, rep, qs, hd = q.shape
    assert hd == head_dim, (q.shape, head_dim)
    paged = page_table is not None
    if paged:
        chunk = k_data.shape[1]              # one physical page per step
        nc = page_table.shape[1]             # n_log logical pages
    else:
        s = k_data.shape[1]
        chunk = _chunk_rows(kv_chunk, s)
        # ceil-div grid: a non-dividing final chunk reads a padded block
        # whose tail rows sit past the cache; their positions are
        # >= s >= valid_len, so the kernel's validity mask discards them
        nc = -(-s // chunk)
    ng = k_scale.shape[-1]
    fresh_rows = 0 if fresh_k_data is None else fresh_k_data.shape[1]

    scalars = [valid_len.reshape(b).astype(jnp.int32)]
    if fresh_rows:
        scalars.append(base.reshape(b).astype(jnp.int32))
    if paged:
        scalars.append(page_table.astype(jnp.int32))
    n_pre = len(scalars)

    def kv_index(i, c, *pre):
        return (pre[-1][i, c], 0, 0) if paged else (i, c, 0)

    def slot_index(i, c, *pre):
        return (i, 0, 0)

    def q_index(i, c, *pre):
        return (i, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, rep * qs, hd), q_index),
        pl.BlockSpec((1, chunk, k_data.shape[-1]), kv_index),
        pl.BlockSpec((1, chunk, ng), kv_index),
        pl.BlockSpec((1, chunk, v_data.shape[-1]), kv_index),
        pl.BlockSpec((1, chunk, ng), kv_index),
    ]
    operands = [q.reshape(b, hkv, rep * qs, hd), k_data, k_scale, v_data,
                v_scale]
    if fresh_rows:
        fng = fresh_k_scale.shape[-1]
        in_specs += [
            pl.BlockSpec((1, fresh_rows, fresh_k_data.shape[-1]),
                         slot_index),
            pl.BlockSpec((1, fresh_rows, fng), slot_index),
            pl.BlockSpec((1, fresh_rows, fresh_v_data.shape[-1]),
                         slot_index),
            pl.BlockSpec((1, fresh_rows, fng), slot_index),
        ]
        operands += [fresh_k_data, fresh_k_scale, fresh_v_data,
                     fresh_v_scale]
    kernel = functools.partial(
        _decode_attn_kernel, precision=precision, group=group,
        num_kv_heads=hkv, head_dim=hd, qs=qs, causal=causal, chunk=chunk,
        num_chunks=nc, fresh_rows=fresh_rows, paged=paged)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=(b, nc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, rep * qs, hd), q_index),
            scratch_shapes=[
                pltpu.VMEM((hkv, rep * qs, _LANES), jnp.float32),
                pltpu.VMEM((hkv, rep * qs, _LANES), jnp.float32),
                pltpu.VMEM((hkv, rep * qs, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep * qs, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*scalars, *operands)
    return out.reshape(b, hkv, rep, qs, hd)
