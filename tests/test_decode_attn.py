"""Entropy-weighted quantized KV cache + fused decode attention
(docs/DESIGN.md §10).

Kernel-level: the ``grouped`` (chunked online-softmax) and ``simple``
fallbacks and the Pallas kernel (interpret mode) must match the dense
ref.py oracle for bf16 / int8 / int4 caches, scalar and per-slot (B,)
positions, GQA ``rep > 1``, and chunk widths that don't divide the cache.

Engine-level: ``serve()`` under ``kv_precision="int8"`` must emit the SAME
greedy tokens as the bf16 cache (logprobs within 1e-2) on all four
families, with KV bytes/slot reduced >= 1.9x; the kv_plan round-trips
through compiled artifacts.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.planner import plan_kv
from repro.kernels.decode_attn.kernel import decode_attn_pallas
from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.decode_attn.ref import decode_attn_ref
from repro.models.model import build
from repro.quant.kvcache import (KVPlan, dequantize_kv, is_kv_page,
                                 make_page, quantize_cache_field)
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import Request

FAMILY_ARCHS = (("dense", "llama3.2-3b"), ("ssm", "mamba2-780m"),
                ("hybrid", "zamba2-2.7b"), ("encdec", "whisper-medium"))


def _qkv(seed, b, s, hkv, rep, hd):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, hkv * rep, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, hd)) * 0.5
    v = jax.random.normal(ks[2], (b, s, hkv, hd)) * 0.5
    return q, k, v


# ---------------------------------------------------------------------------
# backend parity vs the dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("hkv,rep,hd", [(2, 3, 32), (4, 1, 32), (1, 4, 64)])
@pytest.mark.parametrize("vec_pos", [False, True])
def test_fallbacks_match_ref(precision, hkv, rep, hd, vec_pos):
    b, s = 3, 40
    q, k, v = _qkv(hkv * 11 + rep, b, s, hkv, rep, hd)
    kp, vp = make_page(k, precision, 32), make_page(v, precision, 32)
    valid = (jnp.array([5, 40, 13], jnp.int32) if vec_pos
             else jnp.int32(17))
    # oracle runs on the dequantized pages: backends must match its MATH
    # exactly; quantization error is not part of this comparison
    ref = decode_attn_ref(q, dequantize_kv(kp), dequantize_kv(vp), valid)
    for backend in ("simple", "grouped"):
        got = decode_attention(q, kp, vp, valid_len=valid, backend=backend,
                               kv_chunk=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
    # chunk width not dividing S: the final chunk is read with a clamped
    # start and re-visited rows masked — still O(chunk) temps, same math
    got = decode_attention(q, kp, vp, valid_len=valid, backend="grouped",
                           kv_chunk=7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("precision", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("s,kv_chunk", [(32, 8), (40, 16)])
def test_pallas_kernel_matches_ref_interpret(precision, s, kv_chunk):
    b, hkv, rep, hd = 2, 2, 3, 32
    q, k, v = _qkv(5, b, s, hkv, rep, hd)
    kp, vp = make_page(k, precision, 32), make_page(v, precision, 32)
    valid = jnp.array([9, s], jnp.int32)
    ref = decode_attn_ref(q, dequantize_kv(kp), dequantize_kv(vp), valid)

    def flat(p):
        data = p.data.reshape(b, s, -1)
        scale = (jnp.ones((b, s, 1), jnp.bfloat16) if p.scale is None
                 else p.scale)
        return data, scale

    kd, ks = flat(kp)
    vd, vs = flat(vp)
    got = decode_attn_pallas(
        q.reshape(b, hkv, rep, 1, hd), kd, ks, vd, vs, valid[:, None],
        precision=precision, group=kp.group, head_dim=hd, kv_chunk=kv_chunk,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got).reshape(ref.shape),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pallas_backend_raises_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("pallas backend is legal on TPU")
    q, k, v = _qkv(0, 1, 8, 2, 2, 32)
    with pytest.raises(ValueError, match="pallas"):
        decode_attention(q, k, v, backend="pallas")


def test_raw_cache_and_padded_head_shapes():
    """Raw bf16 arrays route through the same fused math, including the
    flat-q-head layout (rep=1 with padded head counts) that
    ``_flatten_gqa_for_sharding`` produces under TP."""
    b, s, hd = 2, 24, 32
    # rep=1, 6 heads (a padded-to-8 variant changes only the head count)
    for h in (6, 8):
        q, k, v = _qkv(h, b, s, h, 1, hd)
        valid = jnp.array([4, 21], jnp.int32)
        ref = decode_attn_ref(q, k.astype(jnp.float32),
                              v.astype(jnp.float32), valid)
        got = decode_attention(q, k, v, valid_len=valid, backend="grouped",
                               kv_chunk=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# page plumbing
# ---------------------------------------------------------------------------

def test_kv_quantize_roundtrip_error_bounds():
    k = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 2, 32))
    absmax = float(jnp.abs(k).max())
    # int8: absmax/254 rounding + bf16 scale rounding; int4: absmax/14
    for precision, tol in (("int8", absmax / 200), ("int4", absmax / 11)):
        page = make_page(k, precision, 64)
        err = float(jnp.abs(dequantize_kv(page) - k).max())
        assert err < tol, (precision, err)
    # int4 payload is genuinely half of int8
    assert make_page(k, "int4", 64).data.nbytes \
        == make_page(k, "int8", 64).data.nbytes // 2


def test_mixed_plan_pages_cut_at_segment_boundaries():
    raw = jnp.zeros((6, 2, 8, 2, 32), jnp.bfloat16)
    plan = KVPlan(precisions=("int8",) * 4 + ("bf16",) * 2, group=64)
    pages = quantize_cache_field(raw, plan, cuts=(2,))
    assert isinstance(pages, tuple) and len(pages) == 3
    assert [p.precision for p in pages] == ["int8", "int8", "bf16"]
    assert [p.data.shape[0] for p in pages] == [2, 2, 2]
    assert pages[2].scale is None
    assert is_kv_page(pages)
    # uniform plan, no cuts -> a single bare page
    uni = quantize_cache_field(raw, KVPlan(precisions=("int8",) * 6))
    assert is_kv_page(uni) and not isinstance(uni, tuple)


def test_plan_kv_entropy_mapping():
    from repro.serving.quantized import explicit_plan
    cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                              num_layers=4)
    wplan = explicit_plan(cfg, ["int4", "int8", "raw", "int8"])
    kv = plan_kv(cfg, wplan, kv_precision="auto")
    assert kv.precisions == ("int4", "int8", "bf16", "int8")
    assert plan_kv(cfg, None, kv_precision="int8").precisions == ("int8",) * 4
    assert plan_kv(cfg, None, kv_precision="bf16") is None
    with pytest.raises(ValueError):
        plan_kv(cfg, None, kv_precision="auto")


# ---------------------------------------------------------------------------
# engine-level parity: int8 KV cache vs bf16, all four families
# ---------------------------------------------------------------------------

def _requests(cfg, n=3, prompt_len=6, max_new=6):
    return [Request(rid=i, prompt=np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (prompt_len,), 0, cfg.vocab_size,
        dtype=jnp.int32)), max_new_tokens=max_new) for i in range(n)]


@pytest.mark.parametrize("family", [f for f, _ in FAMILY_ARCHS])
def test_serve_int8_kv_matches_bf16_cache(trained, family):
    cfg, model, params = trained[family]
    reqs = _requests(cfg)
    ref = ServeEngine(model, params, max_seq=24)
    q8 = ServeEngine(model, params, max_seq=24, kv_precision="int8")
    outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=4)
    outs_q8, _ = q8.serve(reqs, num_slots=2, chunk=4)
    for a, b in zip(outs_q8, outs_ref):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-2)
    if family == "ssm":           # attention-free: the knob is a no-op
        assert q8.kv_bytes_per_slot() == 0.0
    else:
        # f32 smoke cache -> >= 3.8x; (bf16 serving dtype -> >= 1.9x)
        ratio = ref.kv_bytes_per_slot() / q8.kv_bytes_per_slot()
        assert ratio >= 3.8, (family, ratio)


def test_generate_int8_kv_matches_bf16_cache(trained):
    """The generate() path (prefill cache quantized wholesale, vector pos)
    agrees too, and kv bytes at bf16 serving dtype shrink >= 1.9x."""
    cfg, model, params = trained["dense"]
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    ref = ServeEngine(model, params, max_seq=24)
    q8 = ServeEngine(model, params, max_seq=24, kv_precision="int8")
    o_ref = ref.generate(prompts, 8, chunk=3)
    o_q8 = q8.generate(prompts, 8, chunk=3)
    np.testing.assert_array_equal(np.asarray(o_ref.tokens),
                                  np.asarray(o_q8.tokens))
    np.testing.assert_allclose(np.asarray(o_ref.logprobs),
                               np.asarray(o_q8.logprobs), atol=1e-2)
    bf16_cfg = dataclasses.replace(cfg, dtype="bfloat16")
    bmodel = build(bf16_cfg)
    bref = ServeEngine(bmodel, params, max_seq=24)
    bq8 = ServeEngine(bmodel, params, max_seq=24, kv_precision="int8")
    assert bref.kv_bytes_per_slot() / bq8.kv_bytes_per_slot() >= 1.9


def test_int4_kv_cache_serves_and_shrinks(trained):
    cfg, model, params = trained["dense"]
    reqs = _requests(cfg, n=2)
    ref = ServeEngine(model, params, max_seq=24)
    q4 = ServeEngine(model, params, max_seq=24, kv_precision="int4")
    outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=4)
    outs_q4, _ = q4.serve(reqs, num_slots=2, chunk=4)
    agree = np.mean([float(np.mean(np.asarray(a.tokens) ==
                                   np.asarray(b.tokens)))
                     for a, b in zip(outs_q4, outs_ref)])
    assert agree >= 0.75, agree   # int4 is lossier; most tokens still agree
    assert ref.kv_bytes_per_slot() / q4.kv_bytes_per_slot() >= 7.0


def test_auto_kv_with_mixed_weight_plan(trained):
    """Entropy-derived per-layer KV precisions ride a segmented weight
    plan: pages align with the weight segments and serving stays coherent
    with the same quantized weights on a bf16 cache."""
    from repro.serving.quantized import explicit_plan
    cfg, model, params = trained["dense"]
    wplan = explicit_plan(cfg, ["int4", "int8"])
    reqs = _requests(cfg, n=2)
    ref = ServeEngine(model, params, max_seq=24, plan=wplan)
    auto = ServeEngine(model, params, max_seq=24, plan=wplan,
                       kv_precision="auto")
    assert auto.kv_plan.precisions == ("int4", "int8")
    outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=4)
    outs_auto, _ = auto.serve(reqs, num_slots=2, chunk=4)
    for a, b in zip(outs_auto, outs_ref):
        same = np.asarray(a.tokens) == np.asarray(b.tokens)
        assert same.mean() >= 0.75
        np.testing.assert_allclose(a.logprobs[same[len(reqs[0].prompt):]],
                                   b.logprobs[same[len(reqs[0].prompt):]],
                                   atol=0.3)


def test_kv_plan_roundtrips_through_artifact(trained, tmp_path):
    """compile_plan stamps the kv_plan into the manifest; from_artifact
    boots an engine serving with the same quantized cache policy."""
    from repro.quant.compiler import load_artifact, save_artifact
    from repro.serving.quantized import explicit_plan
    cfg, model, params = trained["dense"]
    wplan = explicit_plan(cfg, ["int4", "int8"])
    compiled = model.compile_plan(params, wplan, kv_precision="auto")
    assert compiled.kv_plan is not None
    d = str(tmp_path / "art")
    save_artifact(d, compiled)
    restored = load_artifact(d, model)
    assert restored.kv_plan == compiled.kv_plan
    eng = ServeEngine.from_artifact(model, d, max_seq=24)
    assert eng.kv_plan == compiled.kv_plan
    prompts = jax.random.randint(jax.random.PRNGKey(5), (1, 6), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    mem = ServeEngine(model, compiled.params, max_seq=24,
                      kv_precision=compiled.kv_plan)
    o_mem, o_art = mem.generate(prompts, 5), eng.generate(prompts, 5)
    np.testing.assert_array_equal(np.asarray(o_mem.tokens),
                                  np.asarray(o_art.tokens))


def test_mesh_serve_with_int8_kv_cache_matches_single_device():
    """A 1x8 TP mesh serving a quantized KV cache places KVPage payload +
    scale leaves (cache_specs \"#0\"/\"#1\" branch) and emits the same
    tokens as the single-device engine. Subprocess: XLA_FLAGS must be set
    before jax import (same pattern as tests/test_serving.py)."""
    import subprocess
    import sys
    import textwrap
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import dataclasses, jax, jax.numpy as jnp, numpy as np
            from repro.configs.registry import get_config
            from repro.models.model import build
            from repro.launch.mesh import make_mesh
            from repro.serving.engine import ServeEngine
            from repro.serving.scheduler import Request

            mesh = make_mesh((1, 8), ("data", "model"))
            cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                                      dtype="float32", num_layers=2)
            model = build(cfg)
            params = model.init(jax.random.PRNGKey(0))
            reqs = [Request(rid=i, prompt=np.asarray(jax.random.randint(
                        jax.random.PRNGKey(i), (6,), 0, cfg.vocab_size,
                        dtype=jnp.int32)), max_new_tokens=5)
                    for i in range(3)]
            ref = ServeEngine(model, params, max_seq=24,
                              kv_precision="int8")
            outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=4)
            eng = ServeEngine(model, params, max_seq=24,
                              kv_precision="int8", mesh=mesh)
            outs, _ = eng.serve(reqs, num_slots=2, chunk=4)
            for a, b in zip(outs, outs_ref):
                np.testing.assert_array_equal(a.tokens, b.tokens)
                np.testing.assert_allclose(a.logprobs, b.logprobs,
                                           atol=1e-4)
            print("OK")
        """)],
        capture_output=True, text=True, timeout=560,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout


def test_pallas_aligned_accounts_for_int4_packing():
    from repro.kernels.qmatmul.ops import _pallas_aligned
    assert _pallas_aligned(128, 128, 512, "int8")
    assert not _pallas_aligned(128, 128, 512, "int4")  # packed lane = 256
    assert _pallas_aligned(128, 128, 1024, "int4")


@pytest.mark.parametrize("family,arch", FAMILY_ARCHS)
def test_chunk_widths_never_change_greedy_output(family, arch, monkeypatch):
    """Decode-attention and prefill chunk widths (odd, non-dividing ones
    included) reschedule attention; the greedy tokens stay those of the
    default widths on every family."""
    from repro.kernels.decode_attn import ops as dops
    from repro.models import attention as A
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(
        cfg, num_layers=4 if cfg.family == "hybrid" else 2)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                                 cfg.vocab_size)
    frames = None
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(5),
                                   (2, cfg.encoder_seq, cfg.d_model))

    def greedy():
        jax.clear_caches()   # retrace: the widths are read while tracing
        eng = ServeEngine(model, params, max_seq=24, kv_precision="int8")
        return np.asarray(eng.generate(prompts, 8, frames=frames).tokens)

    base = greedy()
    monkeypatch.setattr(dops, "KV_CHUNK", 3)
    monkeypatch.setattr(A, "CHUNK_THRESHOLD", 4)
    monkeypatch.setattr(A, "Q_CHUNK", 4)
    monkeypatch.setattr(A, "KV_CHUNK", 8)
    try:
        np.testing.assert_array_equal(greedy(), base)
    finally:
        jax.clear_caches()   # no later trace reuses the odd widths
