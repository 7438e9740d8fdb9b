"""The DeepSeek-V3 block (moonlight-16b): the plain reference against
transformers' DeepSeek-V3, the program against the reference (forward,
decode through the latent cache, quantized), routing and dropless
dispatch, and the benchmark configuration against the registry.

Tolerances are relative to the largest reference logit. Float32 runs on
the CPU agree to summation order, ~1e-6; the bounds below leave a decade
or two of room and stay far under what a wrong equation gives (O(1))."""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.qmatmul import ops as qops
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models.model import build
from repro.quant.kvcache import KVPlan, quantize_cache_field
from repro.serving.quantized import plan_for_variant

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import mla_moe as R  # noqa: E402

CFG = dataclasses.replace(get_config("moonlight-16b", smoke=True),
                          dtype="float32")
F32_TOL = 1e-4       # float32 program vs float32 reference


def _conf(cfg, **kw):
    """The reference's view of ``cfg``: published key names."""
    out = dict(num_layers=cfg.num_layers, hidden_size=cfg.d_model,
               num_attention_heads=cfg.num_heads,
               kv_lora_rank=cfg.kv_lora_rank,
               qk_nope_head_dim=cfg.qk_nope_head_dim,
               qk_rope_head_dim=cfg.qk_rope_head_dim,
               v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
               rms_norm_eps=cfg.norm_eps, n_routed_experts=cfg.num_experts,
               num_experts_per_tok=cfg.top_k, scoring_func="sigmoid",
               norm_topk_prob=True, routed_scaling_factor=cfg.routed_scaling,
               first_k_dense_replace=cfg.first_k_dense,
               vocab_size=cfg.vocab_size, tie_word_embeddings=False,
               quant_group=128, fast=True, variant="4bit/8bit")
    out.update(kw)
    return out


def _params(seed=0, bias=None, cfg=CFG):
    """Program-initialized f32 weights with a random correction bias
    (std 1/8, as the benchmark's weight maker), or ``bias`` per expert."""
    p = build(cfg).init(jax.random.PRNGKey(seed))
    n = cfg.num_layers - cfg.first_k_dense
    if bias is None:
        bias = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                 (n, CFG.num_experts)) / 8
    else:
        bias = jnp.broadcast_to(jnp.asarray(bias, jnp.float32),
                                (n, CFG.num_experts))
    p["layers"]["moe"]["e_bias"] = bias
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n).astype(np.int32)


def _close(got, want, rtol, what):
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
    assert err <= rtol * scale, f"{what}: err {err} vs scale {scale}"


def _reference(p, cfg=CFG, precisions=None):
    return R.Reference(p, _conf(cfg), precisions or
                       ["raw"] * (cfg.num_layers + 1))


def test_reference_matches_transformers():
    """The reference is the published DeepSeek-V3 block: transformers'
    own implementation, fed the same weights, gives the same logits."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers")
    c = CFG
    config = hf.DeepseekV3Config(
        vocab_size=c.vocab_size, hidden_size=c.d_model,
        intermediate_size=c.d_ff, moe_intermediate_size=c.moe_d_ff,
        num_hidden_layers=c.num_layers, num_attention_heads=c.num_heads,
        num_key_value_heads=c.num_kv_heads,
        n_shared_experts=c.n_shared_experts, n_routed_experts=c.num_experts,
        routed_scaling_factor=c.routed_scaling,
        kv_lora_rank=c.kv_lora_rank, q_lora_rank=None,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        qk_nope_head_dim=c.qk_nope_head_dim, n_group=1, topk_group=1,
        num_experts_per_tok=c.top_k, first_k_dense_replace=c.first_k_dense,
        norm_topk_prob=True, rope_theta=c.rope_theta,
        rms_norm_eps=c.norm_eps, tie_word_embeddings=False,
        max_position_embeddings=c.max_seq_len, rope_interleave=True,
        attn_implementation="eager")
    model = hf.DeepseekV3ForCausalLM(config).eval()
    p = _params()
    t = lambda x: torch.tensor(np.asarray(x, np.float32))
    sd = {"model.embed_tokens.weight": t(p["embed"]["tok"]),
          "model.norm.weight": t(p["final"]["norm"]),
          "lm_head.weight": t(p["final"]["head"])}
    mlp = {"gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down"}
    for i in range(c.num_layers):
        dense = i < c.first_k_dense
        lw = jax.tree.map(lambda x: x[i if dense else i - c.first_k_dense],
                          p["dense_layers" if dense else "layers"])
        pre = f"model.layers.{i}."
        a = lw["attn"]
        sd.update({pre + "self_attn.q_proj.weight": t(a["wq"]),
                   pre + "self_attn.kv_a_proj_with_mqa.weight": t(a["wkv_a"]),
                   pre + "self_attn.kv_a_layernorm.weight":
                       t(a["latent"]["norm"]),
                   pre + "self_attn.kv_b_proj.weight": t(a["wkv_b"]),
                   pre + "self_attn.o_proj.weight": t(a["wo"]),
                   pre + "input_layernorm.weight": t(lw["ln1"]),
                   pre + "post_attention_layernorm.weight": t(lw["ln2"])})
        if dense:
            sd.update({pre + f"mlp.{h}.weight": t(lw["mlp"][o])
                       for h, o in mlp.items()})
            continue
        sd[pre + "mlp.gate.weight"] = t(lw["moe"]["router"])
        sd[pre + "mlp.gate.e_score_correction_bias"] = t(lw["moe"]["e_bias"])
        for e in range(c.num_experts):
            sd.update({pre + f"mlp.experts.{e}.{h}.weight":
                       t(lw["moe"][o][e]) for h, o in mlp.items()})
        sd.update({pre + f"mlp.shared_experts.{h}.weight":
                   t(lw["shared_mlp"][o]) for h, o in mlp.items()})
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not [k for k in missing
                                   if "rotary" not in k], (missing,
                                                           unexpected)
    toks = _tokens(24)
    with torch.no_grad():
        want = model(torch.tensor(toks[None], dtype=torch.long)).logits[0]
    got = _reference(p).logits(toks)
    _close(got, want.numpy(), F32_TOL, "reference vs transformers")


def test_program_forward_matches_reference():
    p = _params()
    toks = _tokens(32)
    lg, _, _ = T.apply(p, jnp.asarray(toks)[None], CFG, remat=False,
                       return_cache=True)
    _close(lg[0, :, :CFG.vocab_size], _reference(p).logits(toks), F32_TOL,
           "program prefill")


# Decode: every token chooses the same experts (the bias lifts two of them
# a whole score range above the rest), so the comparison reads the latent
# cache path alone; int8 cache noise could otherwise flip a near-tied
# routing choice, which is the router's sensitivity, tested apart below.
FIXED = [4.0, 3.0] + [0.0] * (CFG.num_experts - 2)
# int8 latent rows (groups of 64, absmax/127): ~0.2% per element, a few
# 1e-3 of the logits after five layers
INT8_TOL = 2e-2


@pytest.mark.parametrize("kv", ["raw", "int8"])
def test_decode_through_latent_cache_matches_reference(kv):
    p = _params(bias=FIXED)
    model = build(CFG)
    prompt, steps, max_seq = 12, 10, 32
    toks = _tokens(prompt + steps, seed=3)
    ref = _reference(p).logits(toks)
    lg, _, cache = T.apply(p, jnp.asarray(toks[:prompt])[None], CFG,
                           remat=False, return_cache=True)
    assert cache.c.shape == (CFG.num_layers, 1, prompt, CFG.latent_dim)
    c = jnp.pad(cache.c, ((0, 0), (0, 0), (0, max_seq - prompt), (0, 0)))
    if kv == "int8":
        cuts = [lo for _, lo, _ in T.layer_segments(p)[1:]]
        c = quantize_cache_field(
            c, KVPlan(("int8",) * CFG.num_layers, group=64), cuts)
    cache = cache._replace(c=c)
    got = [lg[0, -1]]
    for i in range(prompt, prompt + steps - 1):
        out, cache = model.decode_step(p, cache, jnp.asarray(toks[i])[None,
                                                                       None])
        got.append(out[0, 0])
    assert int(cache.pos) == prompt + steps - 1
    got = jnp.stack(got)[:, :CFG.vocab_size]
    _close(got, ref[prompt - 1:prompt + steps - 1],
           F32_TOL if kv == "raw" else INT8_TOL,
           f"decode through the {kv} latent cache")


def test_quantized_program_matches_reference_qdq(monkeypatch):
    """The FastEWQ plan of a 5-layer stack (int8 layer 3, int4 layer 4, as
    in the benchmark's stage) compiled by the program against the
    reference's own quantizer; the program's jnp path keeps float32 where
    its matmuls are quantized."""
    cfg = dataclasses.replace(CFG, num_layers=5)
    p = _params(bias=FIXED, cfg=cfg)
    model = build(cfg)
    plan = plan_for_variant(model, p, "4bit/8bit", fast=True)
    conf = _conf(cfg)
    assert plan.precisions() == R.plan(p, conf)["precisions"]
    assert plan.precisions() == ["raw"] * 4 + ["int8", "int4"]
    pq = model.compile_plan(p, plan).params
    toks = _tokens(32, seed=5)
    # the kernel's exact math in place of the dequantize-then-dot fallback
    monkeypatch.setattr(qops, "_dequant_simple", qops._dequant_fused)
    lg, _, _ = T.apply(pq, jnp.asarray(toks)[None], cfg, remat=False,
                       return_cache=True)
    ref, _ = R.Reference(p, conf, plan.precisions()).forward(toks)
    _close(lg[0, :, :cfg.vocab_size], ref, F32_TOL, "quantized program")
    raw = _reference(p, cfg).logits(toks)
    assert float(jnp.max(jnp.abs(ref - raw))) > 100 * F32_TOL * float(
        jnp.max(jnp.abs(raw))), "the plan quantized nothing"


def test_router_stays_raw_in_quantized_layers():
    p = _params()
    model = build(CFG)
    plan = plan_for_variant(model, p, "4bit/8bit", fast=True)
    seg = model.compile_plan(p, plan).params["layers"].segments[-1]
    assert seg.precision == "int4"
    assert isinstance(seg.params["moe"]["router"], jax.Array)
    assert type(seg.params["moe"]["w_gate"]).__name__ == "QTensor"


def test_bias_chooses_but_does_not_weight():
    e, d = 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (5, d))
    router = jax.random.normal(jax.random.PRNGKey(1), (e, d)) / 4
    scores = jax.nn.sigmoid(x @ router.T)
    bias = jnp.zeros((e,)).at[6].set(10.0)          # expert 6 always chosen
    idx, gate, _ = MOE.route({"router": router, "e_bias": bias}, x, 3,
                             "sigmoid", 2.5)
    assert bool(jnp.all(jnp.any(idx == 6, axis=-1)))
    want = jnp.take_along_axis(scores, idx, -1)
    want = want / want.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(gate, want, rtol=1e-5)
    # the same bias on every expert chooses as no bias does
    idx0, gate0, _ = MOE.route({"router": router}, x, 3, "sigmoid", 2.5)
    idx1, gate1, _ = MOE.route({"router": router,
                                "e_bias": jnp.full((e,), 3.0)}, x, 3,
                               "sigmoid", 2.5)
    np.testing.assert_array_equal(idx0, idx1)
    np.testing.assert_allclose(gate0, gate1, rtol=1e-6)


def test_dropless_one_expert_takes_every_token():
    """Every token routes to expert 0 (and 1): dropless, a batch gives
    what each token gives alone; a capacity of 8 of 32 drops most."""
    e, d, f, t = 8, 32, 64, 32
    p = MOE.init_moe_params(jax.random.PRNGKey(0), d, f, e, 1, jnp.float32,
                            router_bias=True)
    p["e_bias"] = jnp.zeros((e,)).at[0].set(5.0).at[1].set(4.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, d))
    kw = dict(num_experts=e, top_k=2, scoring="sigmoid", routed_scaling=2.0)
    y, _ = MOE.moe_block(p, x, capacity_factor=None, **kw)
    alone = jnp.concatenate([MOE.moe_block(p, x[:, i:i + 1],
                                           capacity_factor=None, **kw)[0]
                             for i in range(t)], axis=1)
    np.testing.assert_allclose(y, alone, rtol=1e-5, atol=1e-5)
    dropped, _ = MOE.moe_block(p, x, capacity_factor=1.0, **kw)
    assert MOE.capacity_of(t, e, 2, 1.0) == 8
    np.testing.assert_allclose(dropped[:, :8], y[:, :8], rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(dropped[:, 8:]))) == 0.0


def test_megablox_gmm_matches_ragged_dot():
    """The TPU grouped matmul (interpreted here) against ragged_dot, rows
    unevenly spread over the experts, one expert empty."""
    e, n, k = 4, 256, 128
    sizes = jnp.array([5, 0, 130, 57], jnp.int32)
    m = int(sizes.sum())
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, n, k), jnp.float32)
    got = MOE.gmm(x, w, sizes, interpret=True)
    want = MOE.grouped_matmul(x, w, sizes)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_expert_dequant_is_counted():
    from repro import obs
    from repro.quant.apply import quantize_tree
    p = MOE.init_moe_params(jax.random.PRNGKey(0), 128, 128, 4, 1,
                            jnp.float32)
    q = quantize_tree(p, "int8", 128)
    x = jnp.ones((1, 4, 128), jnp.float32)
    with obs.capture() as (_, reg):
        jax.jit(lambda q, x: MOE.moe_block(q, x, num_experts=4, top_k=2,
                                           capacity_factor=None)[0]).lower(
            q, x)
    c = reg.counter("ewq_qmatmul_calls_total")
    assert c.value(path="dequant", regime="expert") == 3


def test_serving_refuses_paged_and_speculative():
    from repro.serving.engine import ServeEngine
    from repro.serving.spec import SpecConfig
    model = build(get_config("moonlight-16b", smoke=True))
    p = model.init(jax.random.PRNGKey(0))
    for kw in (dict(paged=True), dict(spec=SpecConfig(k=2))):
        with pytest.raises(ValueError, match="latent"):
            ServeEngine(model, p, max_seq=64, **kw)


def test_engine_serves_int8_latent_cache():
    from repro.serving.engine import ServeEngine
    model = build(CFG)
    p = _params(bias=FIXED)
    eng = ServeEngine(model, p, max_seq=48, kv_precision="int8")
    assert eng.kv_bytes_per_slot() == CFG.num_layers * 48 * (
        CFG.latent_dim + CFG.latent_dim // 64 * 2)
    toks = _tokens(12, seed=7)
    res = eng.generate(jnp.asarray(toks)[None], 6)
    out = np.asarray(res.tokens[0])
    ref = _reference(p).logits(out[:18])
    served = ref[np.arange(11, 17), out[12:18]]
    # greedy tokens sit at (or within int8 noise of) the reference's best
    assert float(jnp.max(ref[11:17].max(-1) - served)) < INT8_TOL * float(
        jnp.max(jnp.abs(ref)))


def test_config_file_matches_registry():
    conf = json.loads((ROOT / "bench" / "configs"
                       / "moonlight-16b-5L.json").read_text())
    full = get_config("moonlight-16b")
    keys = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads",
            "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
            "n_routed_experts": "num_experts",
            "num_experts_per_tok": "top_k",
            "n_shared_experts": "n_shared_experts",
            "first_k_dense_replace": "first_k_dense",
            "kv_lora_rank": "kv_lora_rank",
            "qk_nope_head_dim": "qk_nope_head_dim",
            "qk_rope_head_dim": "qk_rope_head_dim",
            "v_head_dim": "v_head_dim",
            "routed_scaling_factor": "routed_scaling",
            "scoring_func": "router_scoring", "vocab_size": "vocab_size",
            "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
            "tie_word_embeddings": "tie_embeddings"}
    for src, field in keys.items():
        assert conf[src] == getattr(full, field), src
    assert conf["published"]["num_hidden_layers"] == full.num_layers
    assert conf["num_hidden_layers"] == conf["num_layers"] == 5
    assert conf["topk_method"] == "noaux_tc" and full.router_bias
    # the program always renormalizes the chosen weights, and always
    # de-interleaves rope dims (rope_interleave, the config's default)
    assert conf["norm_topk_prob"] is True
    assert conf["q_lora_rank"] is None and conf["n_group"] == 1
    assert round(full.param_count() / 1e9, 2) == 15.96
    five = dataclasses.replace(full, num_layers=5)
    assert round(five.param_count() / 1e9, 2) == 3.09
