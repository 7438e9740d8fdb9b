"""Plain reference for the DeepSeek-V3 block configurations (moonlight-16b):
the published layer equations in float32 with "highest" matmul precision,
no kernels, no cache, one request at a time. It reads the configuration
file's published keys (``hidden_size``, ``kv_lora_rank``, ...), not the
program's.

Layer l: x + attn(rms(x)), then + ffn(rms(.)); the first
``first_k_dense_replace`` layers have a dense SwiGLU ffn, the others the
MoE ffn. Final RMS norm, then the untied output head.

* Attention (MLA without q-LoRA), as ``DeepseekV3Attention``: q = x Wq
  split per head into q_nope and q_rope; x Wkv_a splits into the latent c
  and one shared k_rope; c is RMS-normalized (epsilon 1e-6, the norm's
  default, as ``kv_a_layernorm``); c Wkv_b gives per-head k_nope and v.
  q_rope and k_rope are roped as ``apply_rotary_pos_emb_interleave``: the
  interleaved pairs are de-interleaved, then rotated by rotate_half with
  inverse frequencies theta^(-2i/rope). Causal softmax of
  (q_nope.k_nope + q_rope.k_rope) * (nope + rope)^-0.5 (no rope scaling),
  then o = p v and o Wo. This is the expanded form; the program decodes in
  the absorbed form, which is algebraically the same.
* MoE, computed densely: router logits x R^T in float32; scores =
  sigmoid(logits); the top ``num_experts_per_tok`` of scores + the
  correction bias are chosen (``n_group`` = ``topk_group`` = 1, so the
  group-limited choice is a plain top-k); their weights are their scores
  without the bias, divided by their sum (+1e-20) under ``norm_topk_prob``,
  times ``routed_scaling_factor``. A (T, E) combine matrix holds those
  weights and zeros elsewhere, and every expert runs on every token:
  y = sum_e combine[:, e] * swiglu_e(x). The shared experts, one SwiGLU MLP
  of width n_shared * moe_intermediate_size, are added.

Departures from ``modeling_deepseek_v3.py``: float32 throughout (the
published model runs in bfloat16); routing ties broken by the lower
expert index (torch's topk order is unspecified); no attention dropout,
rope scaling or MTP layers (the configuration has none).

Near-tied routing. A position whose choice values (scores + bias) of
the k-th and (k+1)-th expert lie within ``ROUTE_MARGIN`` of each other in
some MoE layer has no routing that bfloat16 arithmetic reproduces: the
program's hidden states differ from these float32 ones by bfloat16
rounding, which swaps such experts, and a swapped expert moves the
position's logits as far as one precision step lower does. At this
configuration's random weights (router logits N(0, 1), bias std 1/8) the
smallest margin over four MoE layers has a median of ~0.004, and the
served tokens the program's rounding moves by more than 0.05 all sit at
margins under 0.01 (26,624 positions of 12 seeds on a v5e), so 11-22% of
the positions are compared.
``Reference.logits`` of the reference at its own plan returns such rows
flat (every logit 0), so the check compares no token there (gap 0);
``forward`` gives the unscreened logits and each position's smallest
margin. Any other Reference (the control's stand-in, one precision step
lower) screens nothing: the token it puts first is its own.

EWQ, made here from the raw weights:

* the FastEWQ metadata plan: the embedding raw, the trailing
  round(0.41 L) layers int8, the last one int4 under ``4bit/8bit`` (the
  paper's classifier selects by execution index; its majority rule);
  every other layer raw;
* a quantized layer's matrices (attention, dense MLP, each expert's three
  matrices, the shared MLP) are quantized symmetrically per group of 128
  along their last axis (int8 scale absmax/127, int4 absmax/7, the scale
  kept in bfloat16) and dequantized back to float32. The router, its
  correction bias and the norms stay raw: routing is float32.

Blocks: 0 is the embedding table, 1..L the layers. The output head and
the final norm belong to no block and stay raw.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOWER = {"raw": "int8", "int8": "int4", "int4": "int4"}
ATTN_MATS = ("wq", "wkv_a", "wkv_b", "wo")
MLP_MATS = ("w_gate", "w_up", "w_down")
LATENT_EPS = 1e-6
ROUTE_MARGIN = 0.01   # choice-value gap below which routing is a tie


def plan(params, conf: dict, dtype=jnp.float32) -> dict:
    """The FastEWQ metadata plan (no entropies: ``dtype`` is unused)."""
    if not conf["fast"]:
        raise ValueError("the MLA/MoE reference plans FastEWQ only")
    n = conf["num_layers"]
    n_quant = max(1, int(round(n * 0.41)))
    prec = ["raw"] * (n + 1)
    for i in range(1 + n - n_quant, n + 1):
        prec[i] = ("int4" if conf["variant"].startswith("4bit") and i == n
                   else "int8")
    return {"precisions": prec, "entropies": None, "mu": None}


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


def _rms(x, w, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _rope_interleave(x, theta):
    """x: (T, H, d) with interleaved rope pairs; positions 0..T-1."""
    t, d = x.shape[0], x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"].T) * (x @ w["w_up"].T)) @ w["w_down"].T


def _attention(x, a, conf, q_chunk):
    t = x.shape[0]
    h, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    nope, rope, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                      conf["v_head_dim"])
    q = (x @ a["wq"].T).reshape(t, h, nope + rope)
    kv = x @ a["wkv_a"].T
    c = _rms(kv[:, :r], a["norm"], LATENT_EPS)
    kvb = (c @ a["wkv_b"].T).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_rope = _rope_interleave(q[..., nope:], conf["rope_theta"])
    k_rope = _rope_interleave(kv[:, None, r:], conf["rope_theta"])
    q_nope = q[..., :nope]
    outs = []
    for s0 in range(0, t, q_chunk):
        rows = slice(s0, s0 + q_chunk)
        s = (jnp.einsum("qhd,khd->hqk", q_nope[rows], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_rope[rows], k_rope[:, 0]))
        s = s / math.sqrt(nope + rope)
        n = s.shape[1]
        mask = jnp.arange(s0, s0 + n)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v))
    return jnp.concatenate(outs, 0).reshape(t, h * vd) @ a["wo"].T


def _moe(x, m, shared, conf):
    e, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    logits = x @ m["router"].astype(jnp.float32).T
    if conf["scoring_func"] == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, -1)
    choice = scores + m["e_bias"].astype(jnp.float32)
    vals, top = jax.lax.top_k(choice, k + 1)
    margin, top = vals[:, k - 1] - vals[:, k], top[:, :k]
    w = jnp.take_along_axis(scores, top, -1)
    if conf["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * conf["routed_scaling_factor"]
    combine = jnp.zeros((x.shape[0], e), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], top].set(w)

    def expert(y, i):
        we = {name: m[name][i] for name in MLP_MATS}
        return y + combine[:, i, None] * _swiglu(x, we), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(e))
    return y + _swiglu(x, shared), margin


def _layer(h, lw, l, conf, precision, dense, q_chunk):
    g = conf["quant_group"]
    p = jax.tree.map(lambda x: x[l], lw)
    a = {name: qdq(p["attn"][name], precision, g) for name in ATTN_MATS}
    a["norm"] = p["attn"]["latent"]["norm"]
    eps = conf["rms_norm_eps"]
    h = h + _attention(_rms(h, p["ln1"], eps), a, conf, q_chunk)
    x = _rms(h, p["ln2"], eps)
    if dense:
        mlp = {n: qdq(p["mlp"][n], precision, g) for n in MLP_MATS}
        return h + _swiglu(x, mlp), jnp.full(h.shape[:1], jnp.inf)
    m = dict(p["moe"], **{n: qdq(p["moe"][n], precision, g)
                          for n in MLP_MATS})
    shared = {n: qdq(p["shared_mlp"][n], precision, g) for n in MLP_MATS}
    y, margin = _moe(x, m, shared, conf)
    return h + y, margin


class Reference:
    """Logits of the plain model over whole sequences."""

    def __init__(self, params, conf: dict, precisions: list,
                 q_chunk: int = 1024, screen=None):
        self.p, self.conf = params, conf
        self.prec = precisions
        self.screen = (list(precisions) == plan(params, conf)["precisions"]
                       if screen is None else screen)
        g = conf["quant_group"]
        self._embed = jax.jit(lambda e: qdq(e, precisions[0], g))
        self._layer = jax.jit(
            lambda h, lw, l, precision, dense: _layer(
                h, lw, l, conf, precision, dense, q_chunk),
            static_argnums=(3, 4))
        self._head = jax.jit(self._head_impl)

    def _head_impl(self, h, norm, head):
        x = _rms(h, norm, self.conf["rms_norm_eps"])
        return (x @ head.T)[:, :self.conf["vocab_size"]]

    def forward(self, tokens: np.ndarray) -> tuple:
        """(T, vocab) float32 logits (row i predicts token i + 1) and (T,)
        each position's smallest routing margin over the MoE layers."""
        k = self.conf["first_k_dense_replace"]
        with jax.default_matmul_precision("highest"):
            table = self._embed(self.p["embed"]["tok"])
            h = jnp.take(table, jnp.asarray(tokens), axis=0)
            margin = jnp.full(h.shape[:1], jnp.inf)
            for l in range(self.conf["num_layers"]):
                dense = l < k
                stack = self.p["dense_layers" if dense else "layers"]
                h, m = self._layer(h, stack, jnp.int32(l if dense else l - k),
                                   self.prec[l + 1], dense)
                margin = jnp.minimum(margin, m)
            head = (table if self.conf["tie_word_embeddings"]
                    else self.p["final"]["head"])
            return self._head(h, self.p["final"]["norm"], head), margin

    def logits(self, tokens: np.ndarray) -> jax.Array:
        """(T, vocab) float32 logits; row i predicts token i + 1. Screened
        rows (near-tied routing, see the module's docstring) are flat."""
        lg, margin = self.forward(tokens)
        if not self.screen:
            return lg
        return jnp.where((margin >= ROUTE_MARGIN)[:, None], lg, 0.0)
