"""Operations and bytes of the DeepSeek-V3 block (MLA and routed experts),
from the configuration file's published keys, for the per-layer metrics of
the MLA/MoE cells.

Model FLOPs count the multiply-adds (2 each) a token needs over the
ACTIVE weights: the attention projections, the dense layer's MLP or, in an
MoE layer, the router, ``num_experts_per_tok`` routed experts and the
shared experts, and the output head over the published vocabulary. Decode
counts attention in the absorbed form the program runs (q_nope W_UK and
W_UV per head, then QK over the r + rope latent row and PV over its r
columns); prefill in the expanded form (``wkv_b`` per token, QK over
nope + rope, PV over v). ``decode_attn`` reads each live latent row and
its scales once at the least.
"""

from __future__ import annotations


def _dims(conf: dict) -> tuple:
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["kv_lora_rank"], conf["qk_nope_head_dim"],
            conf["qk_rope_head_dim"], conf["v_head_dim"])


def _ffn_params(conf: dict, moe: bool) -> int:
    d = conf["hidden_size"]
    if not moe:
        return 3 * d * conf["intermediate_size"]
    f = conf["moe_intermediate_size"]
    return (d * conf["n_routed_experts"]
            + 3 * d * f * (conf["num_experts_per_tok"]
                           + conf["n_shared_experts"]))


def _layers_ffn(conf: dict) -> int:
    k = conf["first_k_dense_replace"]
    n = conf["num_layers"]
    return k * _ffn_params(conf, False) + (n - k) * _ffn_params(conf, True)


def _head(conf: dict) -> int:
    return conf["vocab_size"] * conf["hidden_size"]


def decode_attention_flops(conf: dict, rows: float) -> float:
    """QK over the latent rows and PV over their latent columns, every
    head of every layer, for one query against ``rows`` cached rows."""
    _, h, r, _, rope, _ = _dims(conf)
    return 2.0 * conf["num_layers"] * h * (r + rope + r) * rows


def decode_token_flops(conf: dict, rows: int) -> float:
    """One decoded token whose query sees ``rows`` cached rows."""
    d, h, r, nope, rope, vd = _dims(conf)
    proj = (d * h * (nope + rope) + d * (r + rope) + h * nope * r
            + h * r * vd + h * vd * d)
    return (2.0 * (conf["num_layers"] * proj + _layers_ffn(conf)
                   + _head(conf))
            + decode_attention_flops(conf, rows))


def prefill_flops(conf: dict, p: int) -> float:
    """One prompt of ``p`` tokens: every token through every layer (MLA in
    its expanded form), the head for the last token only, causal attention
    (query i sees i + 1 rows)."""
    d, h, r, nope, rope, vd = _dims(conf)
    proj = (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd)
            + h * vd * d)
    n = conf["num_layers"]
    attn = 2.0 * n * h * (nope + rope + vd) * p * (p + 1) / 2
    return 2.0 * p * (n * proj + _layers_ffn(conf)) + 2.0 * _head(conf) \
        + attn


def latent_row_bytes(conf: dict) -> float:
    """int8 latent row plus its bf16 scales, one layer."""
    width = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    return width + width // conf["kv_group"] * 2


def mla_decode_call(conf: dict, rows: list) -> tuple:
    """(flops, bytes) of one decode step's latent attention over all
    layers, for slots attending ``rows`` cached rows each: every live row
    and its scales read once."""
    total = float(sum(rows))
    return (decode_attention_flops(conf, total),
            conf["num_layers"] * latent_row_bytes(conf) * total)
