"""Plain reference for the dense decoder configurations (minicpm-2b,
yi-9b): the published llama-style block, in float32 with "highest" matmul
precision, no kernels, no cache, one request at a time.

Block: x + attn(rms(x)), then + mlp(rms(.)); grouped-query attention
with rotary embeddings (half-split rotation, theta from the
configuration) and a causal softmax scaled by 1/sqrt(head_dim); SwiGLU
MLP, silu(x Wg) * (x Wu) Wd; final RMS norm; logits by the (tied)
embedding or the output head. Departure from the published minicpm-2b,
as the program serves it: no muP scalings (scale_emb, scale_depth,
dim_model_base); the configuration file lists them under ``assumed``.

The reference also makes the EWQ plan and the quantized weights itself,
from the raw weights:

* paper-mode entropy of each weight matrix, H = -sum p log(p + 0.01)
  with p = softmax over the flattened matrix (float32), and a block's
  entropy as the size-weighted mean over its matrices (vectors excluded);
  ``8bit-mixed``: a block at or below the mean entropy is int8, else raw;
* the FastEWQ metadata plan: the embedding raw, the trailing
  round(0.41 L) layers int8, the last one int4 under ``4bit/8bit``;
* symmetric per-group absmax quantization along a matrix's last axis
  (groups of 128; int8 scale absmax/127, int4 absmax/7; the scale kept
  in bfloat16), dequantized back to float32.

Blocks: 0 is the embedding table, 1..L the layers. An untied output head
and the final norm belong to no block and stay raw.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS_ENTROPY = 0.01
LOWER = {"raw": "int8", "int8": "int4", "int4": "int4"}
LAYER_MATS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
              ("mlp", "w_gate"), ("mlp", "w_up"), ("mlp", "w_down"))


# -- EWQ plan ---------------------------------------------------------------

def _entropy(w, dtype):
    x = w.reshape(-1).astype(dtype)
    m = jnp.max(x)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e)
    return -jnp.sum((p * jnp.log(p + jnp.asarray(EPS_ENTROPY, dtype))
                     ).astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=1)
def _stack_entropy(w, dtype):
    return jax.lax.map(lambda x: _entropy(x, dtype), w)


def entropies(params, dtype=jnp.float32) -> list:
    """Block entropies: [embedding, layer 1 .. L]."""
    emb = float(jax.jit(_entropy, static_argnums=1)(params["embed"]["tok"],
                                                      dtype))
    layers = params["layers"]
    per = []
    for group, name in LAYER_MATS:
        w = layers[group][name]
        per.append((np.asarray(_stack_entropy(w, dtype), np.float64),
                    int(np.prod(w.shape[1:]))))
    n = sum(s for _, s in per)
    blocks = sum(h * s for h, s in per) / n
    return [emb] + [float(x) for x in blocks]


def plan(params, conf: dict, dtype=jnp.float32) -> dict:
    n = conf["num_layers"]
    if conf["fast"]:
        n_quant = max(1, int(round(n * 0.41)))
        first = 1 + n - n_quant
        prec = ["raw"] * (n + 1)
        for i in range(first, n + 1):
            prec[i] = ("int4" if conf["variant"].startswith("4bit")
                       and i == n else "int8")
        return {"precisions": prec, "entropies": None, "mu": None}
    if conf["variant"] != "8bit-mixed":
        raise ValueError(f"no reference for variant {conf['variant']!r}")
    h = entropies(params, dtype)
    mu = float(np.mean(h))
    return {"precisions": ["int8" if x <= mu else "raw" for x in h],
            "entropies": h, "mu": mu}


# -- quantization -------------------------------------------------------------

def qdq(w, precision: str, group: int):
    """Quantize and dequantize along the last axis; raw passes through
    (as float32)."""
    w = w.astype(jnp.float32)
    if precision == "raw" or w.shape[-1] % group:
        return w
    qmax = {"int8": 127.0, "int4": 7.0}[precision]
    g = w.reshape(*w.shape[:-1], w.shape[-1] // group, group)
    scale = jnp.max(jnp.abs(g), axis=-1, keepdims=True) / qmax
    q = jnp.clip(jnp.round(g / jnp.where(scale == 0, 1.0, scale)),
                 -qmax, qmax)
    s = scale.astype(jnp.bfloat16).astype(jnp.float32)
    return (q * s).reshape(w.shape)


# -- forward ------------------------------------------------------------------

def _rms(x, w, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _rope(x, theta):
    t, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(h, lw, l, conf, precision, q_chunk):
    d, nh, nkv, hd = (conf["d_model"], conf["num_heads"],
                      conf["num_kv_heads"], conf["head_dim"])
    g = conf["quant_group"]
    w = {name: qdq(lw[grp][name][l], precision, g)
         for grp, name in LAYER_MATS}
    t = h.shape[0]
    x = _rms(h, lw["ln1"][l], conf["norm_eps"])
    q = _rope((x @ w["wq"].T).reshape(t, nh, hd), conf["rope_theta"])
    k = _rope((x @ w["wk"].T).reshape(t, nkv, hd), conf["rope_theta"])
    v = (x @ w["wv"].T).reshape(t, nkv, hd)
    rep = nh // nkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for a in range(0, t, q_chunk):
        qc = q[a:a + q_chunk]
        s = jnp.einsum("qhd,khd->hqk", qc, k) / math.sqrt(hd)
        mask = (jnp.arange(a, a + qc.shape[0])[:, None]
                >= jnp.arange(t)[None, :])
        s = jnp.where(mask[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, 0).reshape(t, nh * hd)
    h = h + o @ w["wo"].T
    x = _rms(h, lw["ln2"][l], conf["norm_eps"])
    m = (jax.nn.silu(x @ w["w_gate"].T) * (x @ w["w_up"].T)) @ w["w_down"].T
    return h + m


class Reference:
    """Logits of the plain model over whole sequences."""

    def __init__(self, params, conf: dict, precisions: list,
                 q_chunk: int = 1024):
        self.p, self.conf = params, conf
        self.prec = precisions
        self.q_chunk = q_chunk
        g = conf["quant_group"]
        self._embed = jax.jit(lambda e: qdq(e, precisions[0], g))
        self._layer = jax.jit(
            lambda h, lw, l, precision: _layer(h, lw, l, conf, precision,
                                               q_chunk),
            static_argnums=3)
        self._head = jax.jit(self._head_impl)

    def _head_impl(self, h, norm, head):
        x = _rms(h, norm, self.conf["norm_eps"])
        return (x @ head.T)[:, :self.conf["vocab_size"]]

    def logits(self, tokens: np.ndarray) -> jax.Array:
        """(T, vocab) float32 logits; row i predicts token i + 1."""
        with jax.default_matmul_precision("highest"):
            table = self._embed(self.p["embed"]["tok"])
            h = jnp.take(table, jnp.asarray(tokens), axis=0)
            for l in range(self.conf["num_layers"]):
                h = self._layer(h, self.p["layers"], jnp.int32(l),
                                self.prec[l + 1])
            head = (table if self.conf["tie_embeddings"]
                    else self.p["final"]["head"])
            return self._head(h, self.p["final"]["norm"], head)
