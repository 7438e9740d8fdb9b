"""Pallas TPU kernel: fused weight-dequant matmul (W8A16 / W4A16 / ternary).

Computes y[m, n] = sum_k x[m, k] * q[n, k] * s[n, k // G]

Design for TPU (target: v5e; validated on CPU via interpret=True and
compiled for a described v5e in tests/test_tpu_compile.py):

* Grid (M/BM, N/BN, K/BK) with the K dimension innermost so each (m, n)
  output tile is revisited and accumulated in-place in VMEM.
* BM/BN/BK are multiples of 128 so MXU matmul dims are hardware aligned and
  the int8 weight tiles respect the (32, 128) int8 VMEM tiling.
* The per-group scale multiplies each group's PARTIAL PRODUCT, not the
  weight: y += (x_g . w_g^T) * s_g for every group g of the K block. The
  integer weight converts exactly to the activation dtype, so the MXU sees
  a plain dot and the scale is one lane-broadcast multiply per group.
* Scales arrive transposed and blocked per K step, (K/BK, BK/G, N): a
  (BK/G, BN) scale tile has N on lanes (it broadcasts over the output
  tile's rows) and its second-minor extent equals the array's, which the
  TPU block-shape rule admits for any BK/G.
* int4 weights arrive packed two-per-byte (BN, BK/2) with the even column
  in the low nibble. The wrapper splits the activation into its even and
  odd columns (two (M, K/2) operands), so the unpacked low / high nibble
  tiles pair with them directly and no in-VMEM interleave is needed; each
  group covers G/2 packed lanes. HBM traffic for the weight is half of
  int8.
* Accumulation is f32 in the output tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512
# v5e has 128 MiB of VMEM per core; the 16 MiB default scoped limit is too
# small for the megakernels' whole-N / whole-K tiles at 4096 widths
VMEM_LIMIT_BYTES = 100 * 2**20
_NT = (((1,), (1,)), ((), ()))      # contract the minor dims: a . b^T


def _params(n_parallel: int, n_arbitrary: int) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=(("parallel",) * n_parallel
                             + ("arbitrary",) * n_arbitrary),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _nibbles(packed: jax.Array, dtype) -> tuple[jax.Array, jax.Array]:
    """(BN, P) packed int8 -> (low, high) nibble tiles in ``dtype``, signed
    in [-8, 7]: the low nibble holds the even column, the high the odd."""
    w = packed.astype(jnp.int32)
    lo = ((w & 0x0F) ^ 8) - 8
    hi = w >> 4                       # arithmetic shift sign-extends
    return lo.astype(dtype), hi.astype(dtype)


def _weight_tiles(w: jax.Array, dtype, packed: bool) -> tuple:
    """A stored weight tile -> the operand tiles that pair with the
    activation halves (one tile for int8/ternary, two for int4)."""
    return _nibbles(w, dtype) if packed else (w.astype(dtype),)


def _group_dots(xs: tuple, ws: tuple, s: jax.Array, *,
                group: int, packed: bool) -> jax.Array:
    """sum_g (x_g . w_g^T) * s[g] over the scale groups of one K block.

    ``xs``/``ws`` are matching tuples of (BM, Kb) activation tiles and
    (BN, Kb) weight tiles (int4: the even/odd halves, Kb = BK/2); ``s`` is
    the (BK/G, BN) f32 scale tile."""
    width = group // 2 if packed else group
    acc = None
    for g in range(ws[0].shape[1] // width):
        sl = slice(g * width, (g + 1) * width)
        part = None
        for x, w in zip(xs, ws):
            d = jax.lax.dot_general(x[:, sl], w[:, sl], _NT,
                                    preferred_element_type=jnp.float32)
            part = d if part is None else part + d
        term = part * s[g:g + 1, :]
        acc = term if acc is None else acc + term
    return acc


def _split_activation(x: jax.Array, packed: bool) -> tuple:
    """int4 pairs the low nibbles with the even activation columns and the
    high nibbles with the odd ones; other precisions use x as is."""
    return (x[:, 0::2], x[:, 1::2]) if packed else (x,)


def _blocked_scales(scale: jax.Array, bk: int, group: int) -> jax.Array:
    """(N, K/G) scales -> (K/BK, BK/G, N): transposed, one slab per K step."""
    n, ng = scale.shape
    return scale.T.reshape(ng * group // bk, bk // group, n)


def _qmatmul_kernel(*refs, group: int, packed: bool):
    *x_refs, w_ref, s_ref, o_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xs = tuple(r[...] for r in x_refs)
    ws = _weight_tiles(w_ref[...], xs[0].dtype, packed)
    o_ref[...] += _group_dots(xs, ws, s_ref[...].astype(jnp.float32),
                              group=group, packed=packed)


@functools.partial(jax.jit, static_argnames=("group", "precision", "bm", "bn",
                                             "bk", "interpret"))
def qmatmul_pallas(x: jax.Array, data: jax.Array, scale: jax.Array, *,
                   group: int = 128, precision: str = "int8",
                   bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                   bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """x: (M, K) bf16/f32; data: (N, K) int8 or (N, K//2) packed int4;
    scale: (N, K//group). Returns (M, N) f32."""
    m, k = x.shape
    packed = precision == "int4"
    n = data.shape[0]
    k_data = data.shape[1] * (2 if packed else 1)
    assert k_data == k, (data.shape, x.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % group == 0
    xs = _split_activation(x, packed)
    bkx = bk // 2 if packed else bk

    kernel = functools.partial(_qmatmul_kernel, group=group, packed=packed)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bkx), lambda i, j, kk: (i, kk))
                  for _ in xs] + [
            pl.BlockSpec((bn, bkx), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((None, bk // group, bn),
                         lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=_params(2, 1),
        interpret=interpret,
    )(*xs, data, _blocked_scales(scale, bk, group))


# ---------------------------------------------------------------------------
# megakernels (docs/DESIGN.md §12): whole quantized sub-blocks in one launch
# ---------------------------------------------------------------------------

def _even_odd(h: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(BM, BF) -> its even and odd columns, (BM, BF/2) each, by two 0/1
    selection matmuls on the MXU (exact: one nonzero term per output)."""
    bf = h.shape[1]
    r = jax.lax.broadcasted_iota(jnp.int32, (bf, bf // 2), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (bf, bf // 2), 1)
    pick = [(r == 2 * c + o).astype(h.dtype) for o in (0, 1)]
    return tuple(jnp.dot(h, p, preferred_element_type=jnp.float32
                         ).astype(h.dtype) for p in pick)


def _qmlp_kernel(*refs, group: int, packed: bool, swiglu: bool):
    nx = 2 if packed else 1
    x_refs, refs = refs[:nx], refs[nx:]
    if swiglu:
        g_ref, gs_ref, u_ref, us_ref, d_ref, ds_ref, o_ref = refs
    else:
        u_ref, us_ref, d_ref, ds_ref, o_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xs = tuple(r[...] for r in x_refs)                      # (BM, K[/2])
    dt = xs[0].dtype

    def proj(w_ref, s_ref):
        return _group_dots(xs, _weight_tiles(w_ref[...], dt, packed),
                           s_ref[...].astype(jnp.float32), group=group,
                           packed=packed)                   # (BM, BF)

    u = proj(u_ref, us_ref)
    if swiglu:
        h = jax.nn.silu(proj(g_ref, gs_ref)) * u
    else:
        h = jax.nn.gelu(u)
    h = h.astype(dt)
    hs = _even_odd(h) if packed else (h,)
    o_ref[...] += _group_dots(hs, _weight_tiles(d_ref[...], dt, packed),
                              ds_ref[...].astype(jnp.float32), group=group,
                              packed=packed)                # (BM, D)


@functools.partial(jax.jit, static_argnames=("group", "precision", "act",
                                             "bm", "bf", "interpret"))
def qmlp_pallas(x: jax.Array, gate_data, gate_scale, up_data, up_scale,
                down_data, down_scale, *, group: int = 128,
                precision: str = "int8", act: str = "swiglu",
                bm: int = DEFAULT_BM, bf: int = DEFAULT_BN,
                interpret: bool = False) -> jax.Array:
    """Fused quantized MLP: y = act-combine(x W_gate^T, x W_up^T) W_down^T
    with EVERY weight dequantized tile-by-tile in VMEM and the (M, FF)
    hidden activation living only as (BM, BF) register tiles — it is never
    written to HBM, and no bf16 copy of any weight ever exists.

    Grid (M/BM, FF/BF) with FF innermost: each FF step computes one hidden
    tile and immediately accumulates its contribution through W_down into
    the (BM, D) output block. x: (M, K); gate/up: (FF, K_store); down:
    (D, FF_store); scales per ``group`` along each contraction. ``act``
    "swiglu" (gate_* used) or "gelu" (gate_* must be None). The hidden
    tile is rounded to x's dtype before the down projection, as the
    unfused qdot sequence rounds it. Returns (M, D) f32."""
    m, k = x.shape
    packed = precision == "int4"
    swiglu = act == "swiglu"
    assert (gate_data is None) == (not swiglu), \
        "gate weights iff act == 'swiglu'"
    ff = up_data.shape[0]
    d = down_data.shape[0]
    bm, bf = min(bm, m), min(bf, ff)
    assert m % bm == 0 and ff % bf == 0, (m, ff, bm, bf)
    assert k % group == 0 and bf % group == 0, (k, bf, group)
    k_store = k // 2 if packed else k
    bf_store = bf // 2 if packed else bf
    assert up_data.shape[1] == k_store and down_data.shape[1] * \
        (2 if packed else 1) == ff, (up_data.shape, down_data.shape)
    xs = _split_activation(x, packed)

    kernel = functools.partial(_qmlp_kernel, group=group, packed=packed,
                               swiglu=swiglu)
    in_specs = [pl.BlockSpec((bm, k_store), lambda i, f: (i, 0))
                for _ in xs]
    operands = list(xs)
    in_proj = [pl.BlockSpec((bf, k_store), lambda i, f: (f, 0)),
               pl.BlockSpec((None, k // group, bf), lambda i, f: (0, 0, f))]
    if swiglu:
        in_specs += in_proj
        operands += [gate_data, _blocked_scales(gate_scale, k, group)]
    in_specs += in_proj + [
        pl.BlockSpec((d, bf_store), lambda i, f: (0, f)),
        pl.BlockSpec((None, bf // group, d), lambda i, f: (f, 0, 0))]
    operands += [up_data, _blocked_scales(up_scale, k, group),
                 down_data, _blocked_scales(down_scale, bf, group)]
    return pl.pallas_call(
        kernel,
        grid=(m // bm, ff // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, d), lambda i, f: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=_params(1, 1),
        interpret=interpret,
    )(*operands)


def _qkv_kernel(*refs, group: int, packed: bool):
    nx = 2 if packed else 1
    x_refs = refs[:nx]
    q_ref, qs_ref, k_ref, ks_ref, v_ref, vs_ref, oq_ref, ok_ref, ov_ref = \
        refs[nx:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        oq_ref[...] = jnp.zeros_like(oq_ref)
        ok_ref[...] = jnp.zeros_like(ok_ref)
        ov_ref[...] = jnp.zeros_like(ov_ref)

    xs = tuple(r[...] for r in x_refs)                      # (BM, BK[/2])
    for w_ref, s_ref, o_ref in ((q_ref, qs_ref, oq_ref),
                                (k_ref, ks_ref, ok_ref),
                                (v_ref, vs_ref, ov_ref)):
        o_ref[...] += _group_dots(
            xs, _weight_tiles(w_ref[...], xs[0].dtype, packed),
            s_ref[...].astype(jnp.float32), group=group, packed=packed)


@functools.partial(jax.jit, static_argnames=("group", "precision", "bm",
                                             "bk", "interpret"))
def qkv_pallas(x: jax.Array, q_data, q_scale, k_data, k_scale, v_data,
               v_scale, *, group: int = 128, precision: str = "int8",
               bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
               interpret: bool = False):
    """Fused quantized QKV projection: the three decode-path projections
    share one sweep of the activation — each (BM, BK) x tile is read from
    HBM once and feeds all three accumulating output blocks, instead of
    three separate kernel launches re-reading x.

    x: (M, K); q/k/v data: (N_*, K_store) int8 (packed int4: K/2); scales
    (N_*, K/group). Grid (M/BM, K/BK), K innermost. Returns a 3-tuple of
    (M, N_*) f32."""
    m, k = x.shape
    packed = precision == "int4"
    nq, nk, nv = q_data.shape[0], k_data.shape[0], v_data.shape[0]
    bm, bk = min(bm, m), min(bk, k)
    assert m % bm == 0 and k % bk == 0 and bk % group == 0, (m, k, bm, bk)
    bkx = bk // 2 if packed else bk
    xs = _split_activation(x, packed)

    def w_spec(n):
        return [pl.BlockSpec((n, bkx), lambda i, kk: (0, kk)),
                pl.BlockSpec((None, bk // group, n),
                             lambda i, kk: (kk, 0, 0))]

    kernel = functools.partial(_qkv_kernel, group=group, packed=packed)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, k // bk),
        in_specs=([pl.BlockSpec((bm, bkx), lambda i, kk: (i, kk))
                   for _ in xs] + w_spec(nq) + w_spec(nk) + w_spec(nv)),
        out_specs=[
            pl.BlockSpec((bm, nq), lambda i, kk: (i, 0)),
            pl.BlockSpec((bm, nk), lambda i, kk: (i, 0)),
            pl.BlockSpec((bm, nv), lambda i, kk: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, nq), jnp.float32),
            jax.ShapeDtypeStruct((m, nk), jnp.float32),
            jax.ShapeDtypeStruct((m, nv), jnp.float32),
        ],
        compiler_params=_params(1, 1),
        interpret=interpret,
    )(*xs, q_data, _blocked_scales(q_scale, bk, group), k_data,
      _blocked_scales(k_scale, bk, group), v_data,
      _blocked_scales(v_scale, bk, group))
