"""Pallas TPU kernel: fused group-wise absmax int8 quantization.

Used at EWQ-apply time: one HBM read of the bf16 weights, one write of the
int8 payload + scales — no intermediate f32 materialization in HBM. The
grid tiles (N, K) into (BN, BK) VMEM blocks with BK a multiple of the
quantization group so each block owns whole groups; absmax reduction and
rounding happen entirely in VMEM registers.

Each group's absmax is taken twice over the same numbers: along lanes of
the tile (a (BN, 1) column that divides the tile's group in place) and
along sublanes of the transposed tile (a (1, BN) row). The rows are what
is stored: scales leave the kernel transposed and blocked per K step,
(K/BK, BK/G, N), so the output tile's lane dimension is BN and the
wrapper transposes the small scale array back to (N, K/G).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 256
DEFAULT_BK = 512


def _quantize_kernel(w_ref, q_ref, s_ref, *, group: int):
    w = w_ref[...].astype(jnp.float32)            # (BN, BK)
    w_t = w.T                                     # (BK, BN)
    for g in range(w.shape[1] // group):
        sl = slice(g * group, (g + 1) * group)
        scale = jnp.max(jnp.abs(w[:, sl]), axis=1, keepdims=True) / 127.0
        safe = jnp.where(scale == 0, 1.0, scale)
        q_ref[:, sl] = jnp.clip(jnp.round(w[:, sl] / safe), -127, 127
                                ).astype(jnp.int8)
        s_ref[g:g + 1, :] = jnp.max(jnp.abs(w_t[sl, :]), axis=0,
                                    keepdims=True) / 127.0


@functools.partial(jax.jit, static_argnames=("group", "bn", "bk", "interpret"))
def quantize_int8_pallas(w: jax.Array, *, group: int = 128,
                         bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                         interpret: bool = False):
    """w: (N, K) -> (q int8 (N, K), scale f32 (N, K//group))."""
    n, k = w.shape
    bn, bk = min(bn, n), min(bk, k)
    assert n % bn == 0 and k % bk == 0 and bk % group == 0
    kernel = functools.partial(_quantize_kernel, group=group)
    q, s_t = pl.pallas_call(
        kernel,
        grid=(n // bn, k // bk),
        in_specs=[pl.BlockSpec((bn, bk), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
            pl.BlockSpec((None, bk // group, bn), lambda i, j: (j, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), jnp.int8),
            jax.ShapeDtypeStruct((k // bk, bk // group, n), jnp.float32),
        ],
        interpret=interpret,
    )(w)
    return q, s_t.reshape(k // group, n).T
