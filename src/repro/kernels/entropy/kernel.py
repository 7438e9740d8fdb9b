"""Pallas TPU kernel: single-pass streaming softmax-entropy of a weight array.

H = lse(w) - E_p[w] with p = softmax(flatten(w)).

The array is viewed as rows of 128 lanes, padded with -inf to whole
(ROWS, 128) blocks, and the grid walks the blocks sequentially. Three
(ROWS, 128) f32 VMEM accumulators carry the online state PER ELEMENT
SLOT (running max m, Z = sum e^{w-m}, S = sum w e^{w-m}) across grid
steps — the standard online-logsumexp merge, done elementwise so no step
needs a cross-lane reduction. The final grid step merges the slots and
writes H = (m + log Z) - S/Z.

This is the TPU-native form of the paper's §3.1 analysis: one HBM read of
the weights, no softmax materialization, O(1) VMEM. Blocks align to the
VPU's (8, 128) vector registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNK = 8 * LANES          # smallest block: one (8, 128) vector register
MAX_ROWS = 512             # rows per grid step for large arrays


def _merge(m, z, s, m_new):
    """Rescale (z, s) accumulated under max ``m`` to max ``m_new``; slots
    that have only seen -inf padding stay empty."""
    r = jnp.where(m == -jnp.inf, 0.0, jnp.exp(m - m_new))
    return z * r, s * r


def _entropy_kernel(w_ref, o_ref, m_ref, z_ref, s_ref, *, n_steps: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        z_ref[...] = jnp.zeros_like(z_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    x = w_ref[...].astype(jnp.float32)            # (ROWS, 128), -inf padded
    m = m_ref[...]
    m_new = jnp.maximum(m, x)
    z, s = _merge(m, z_ref[...], s_ref[...], m_new)
    live = x != -jnp.inf
    e = jnp.where(live, jnp.exp(x - m_new), 0.0)
    m_ref[...] = m_new
    z_ref[...] = z + e
    s_ref[...] = s + jnp.where(live, x * e, 0.0)

    @pl.when(step == n_steps - 1)
    def _finalize():
        m_all = m_ref[...]
        top = jnp.max(m_all)
        z, s = _merge(m_all, z_ref[...], s_ref[...], top)
        z_tot, s_tot = jnp.sum(z), jnp.sum(s)
        o_ref[...] = jnp.full(o_ref.shape, (top + jnp.log(z_tot))
                              - s_tot / z_tot, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def entropy_pallas(w: jax.Array, *, interpret: bool = False) -> jax.Array:
    flat = w.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = -(-n // LANES)
    block_rows = min(MAX_ROWS, -(-rows // 8) * 8)
    n_steps = -(-rows // block_rows)
    pad = n_steps * block_rows * LANES - n
    flat = jnp.pad(flat, (0, pad), constant_values=-jnp.inf)
    tiles = flat.reshape(n_steps * block_rows, LANES)
    acc = pltpu.VMEM((block_rows, LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_entropy_kernel, n_steps=n_steps),
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, LANES), jnp.float32),
        scratch_shapes=[acc, acc, acc],
        interpret=interpret,
    )(tiles)
    return out[0, 0]
