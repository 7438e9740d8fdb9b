"""Shared model building blocks (norms, init, embeddings, quant-aware dense).

Conventions:
* every weight matrix is stored ``(out_features, in_features)`` and applied
  with ``qdot`` (einsum '...k,nk->...n'), so quantization groups along the
  last axis coincide with the contraction axis (fused dequant);
* stacked (scanned) layers carry a leading layer axis;
* activations are bf16 by default, reductions/softmax in f32.
"""

from __future__ import annotations

from collections.abc import Sequence
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.qmatmul.ops import qdot
from repro.quant.qtypes import QTensor
from repro.quant.quantize import dequantize


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


class LayerBlocks(Sequence):
    """A family's ``block_params``: ``head``, then every layer of each
    layer-stacked tree in ``stacks``, then ``tail``. A layer is sliced out
    of its stack only when it is read, so the entropy planner, which walks
    the blocks one at a time, never holds a second copy of a stack (at
    minicpm-2b width that copy is 4.5 GiB of device memory)."""

    def __init__(self, head: Any, stacks: Sequence[Any], tail=()):
        self._items = [(head, None)]
        for stack in stacks:
            n = jax.tree.leaves(stack)[0].shape[0]
            self._items += [(stack, i) for i in range(n)]
        self._items += [(t, None) for t in tail]

    def __len__(self) -> int:
        return len(self._items)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[j] for j in range(*i.indices(len(self)))]
        tree, layer = self._items[i]
        if layer is None:
            return tree
        return jax.tree.map(lambda x: x[layer], tree)


def decode_positions(pos: jax.Array, b: int, s: int) -> jax.Array:
    """(B, S) int32 token positions for a decode step.

    ``pos`` is the cache position — a scalar (whole batch in lockstep) or a
    (B,) vector (slotted continuous batching, one position per slot)."""
    step = jnp.arange(s, dtype=jnp.int32)[None]
    if getattr(pos, "ndim", 0) == 1:
        return pos.astype(jnp.int32)[:, None] + step
    return jnp.broadcast_to(pos.astype(jnp.int32)[None, None] + step, (b, s))


def select_snapshot(snaps: jax.Array, idx: jax.Array,
                    batch_axis: int = 2) -> jax.Array:
    """Per-slot gather over stacked sequential-state snapshots.

    ``snaps`` holds N checkpoints stacked on a new leading axis, so the
    slot/batch dim sits at ``batch_axis`` of ``snaps`` (2 for the usual
    (N, L, B, ...) state stack); ``idx`` is a (B,) per-slot snapshot index
    in [0, N). Returns the un-stacked layout (batch back at
    ``batch_axis - 1``) with each slot's rows taken from its own
    snapshot — the SSM-state rollback primitive for speculative decoding
    (conv/state are O(1) summaries that cannot be rewound by position
    arithmetic, so the verify scan checkpoints them per step and commit
    selects per slot; docs/DESIGN.md §11)."""
    moved = jnp.moveaxis(snaps, batch_axis, 0)       # (B, N, ...)
    out = jax.vmap(lambda sn, i: sn[i])(moved, idx)  # (B, ...)
    return jnp.moveaxis(out, 0, batch_axis - 1)


# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------

def dense_init(key, out_dim: int, in_dim: int, dtype, scale: float = 1.0):
    std = scale / np.sqrt(in_dim)
    return (jax.random.normal(key, (out_dim, in_dim), jnp.float32) * std
            ).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02
            ).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, w, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x: jax.Array, w, eps: float = 1e-5) -> jax.Array:
    """Non-parametric when w is None (OLMo-style)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def norm(x, w, cfg):
    if cfg.nonparametric_norm:
        return layer_norm(x, None, cfg.norm_eps)
    return rms_norm(x, w, cfg.norm_eps)


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    pos = np.arange(seq)[:, None]
    i = np.arange(dim // 2)[None, :]
    ang = pos / (10000 ** (2 * i / dim))
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, jnp.float32)


# --------------------------------------------------------------------------
# Embedding lookup (quant-aware)
# --------------------------------------------------------------------------

@obs.scoped("embed")
def embed_lookup(table, ids: jax.Array, dtype) -> jax.Array:
    if isinstance(table, QTensor):
        rows = jnp.take(table.data, ids, axis=0)
        scales = jnp.take(table.scale, ids, axis=0)
        if table.precision == "int4":
            from repro.quant.quantize import unpack_int4
            rows = unpack_int4(rows)
        k = rows.shape[-1]
        g = rows.astype(jnp.float32).reshape(*rows.shape[:-1], k // table.group,
                                             table.group)
        out = (g * scales.astype(jnp.float32)[..., None]).reshape(
            *rows.shape[:-1], k)
        return out.astype(dtype)
    return jnp.take(table, ids, axis=0).astype(dtype)


@obs.scoped("head")
def lm_head(x: jax.Array, head_w, dtype=jnp.float32) -> jax.Array:
    """Final projection to (padded) vocab logits in f32."""
    return qdot(x, head_w, out_dtype=dtype)


__all__ = ["qdot", "dense_init", "embed_init", "rms_norm", "layer_norm",
           "norm", "rope", "sinusoidal_positions", "embed_lookup", "lm_head",
           "dtype_of", "QTensor", "dequantize"]
