"""Family-universal plan compiler: segmented mixed-precision execution.

Covers the compiler contract (docs/DESIGN.md §8):
  * mixed "4bit/8bit"-style plans on hybrid and enc-dec yield QUANTIZED
    (QTensor-bearing) segmented stacks — regression for the old silent raw
    fallback — with logits matching the per-block ``apply_plan_blocks``
    reference within quantization tolerance;
  * compile -> save -> restore -> serve produces identical outputs to the
    in-memory plan, including int4-packed and ternary segments;
  * explicit qdot backends (grouped/simple) agree with the ref oracle.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.qmatmul.ops import qdot
from repro.kernels.qmatmul.ref import qmatmul_ref
from repro.models.model import build
from repro.quant.apply import (SegmentedParams, apply_plan_blocks,
                               plan_segments, tree_nbytes)
from repro.quant.compiler import (compile_plan, family_layout, load_artifact,
                                  plan_length, save_artifact)
from repro.quant.qtypes import QTensor
from repro.quant.quantize import dequantize, quantize
from repro.serving.engine import ServeEngine
from repro.serving.quantized import (apply_plan_to_params, explicit_plan,
                                     fastewq_metadata_plan)

KEY = jax.random.PRNGKey(0)


def _model(arch, **over):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32",
                              **over)
    model = build(cfg)
    return cfg, model, model.init(KEY)


def _batch(cfg, b=2, s=16):
    from repro.data.synthetic import synthetic_batch
    return synthetic_batch(cfg, batch=b, seq=s, step=0)


def _dequant_tree(tree):
    """Replace every QTensor with its dequantized weight, carried through
    bf16 exactly like qdot's simple backend so the reference and the
    compiled path see numerically identical weights."""
    return jax.tree.map(
        lambda x: (dequantize(x, jnp.bfloat16).astype(jnp.float32)
                   if isinstance(x, QTensor) else x),
        tree, is_leaf=lambda x: isinstance(x, QTensor))


def _restack(blocks):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def _blockwise_reference(model, params, plan):
    """The per-block reference: quantize each block independently
    (apply_plan_blocks), dequantize, and restack into the raw layout."""
    cfg = model.cfg
    deq = [_dequant_tree(b)
           for b in apply_plan_blocks(model.block_params(params), plan)]
    new = dict(params)
    new["embed"] = deq[0]
    if cfg.family == "encdec":
        ne = cfg.num_encoder_layers
        new["enc_layers"] = _restack(deq[1:1 + ne])
        new["dec_layers"] = _restack(deq[1 + ne:1 + ne + cfg.num_layers])
    else:
        new["layers"] = _restack(deq[1:1 + cfg.num_layers])
        if cfg.family == "hybrid":
            new["shared"] = deq[-1]
    return new


def _stack_qtensors(params, keys):
    return [leaf for k in keys for leaf in
            jax.tree.leaves(params[k], is_leaf=lambda x: isinstance(x, QTensor))
            if isinstance(leaf, QTensor)]


# ---------------------------------------------------------------------------
# segmentation with forced cuts
# ---------------------------------------------------------------------------

def test_plan_segments_with_cuts():
    from repro.core.policy import BlockDecision, QuantPlan
    ds = [BlockDecision(block_index=i, exec_index=i + 1, entropy=0.0,
                        num_parameters=0, precision=p)
          for i, p in enumerate(["int8", "int8", "int8", "int4", "raw",
                                 "raw"])]
    plan = QuantPlan(decisions=ds, mu=0, sigma=0, threshold=0, x_factor=1)
    assert plan_segments(plan, cuts=(2, 4)) == [
        ("int8", 0, 2), ("int8", 2, 3), ("int4", 3, 4), ("raw", 4, 6)]
    # no cuts: unchanged behaviour
    assert plan_segments(plan) == [("int8", 0, 3), ("int4", 3, 4),
                                   ("raw", 4, 6)]


def test_family_layout_covers_all_families():
    for arch in ("llama3.2-3b", "grok-1-314b", "mamba2-780m", "zamba2-2.7b",
                 "whisper-medium"):
        cfg = get_config(arch, smoke=True)
        stacks, extras = family_layout(cfg)
        n = plan_length(cfg)
        covered = set()
        for s in stacks:
            covered |= set(range(s.lo, s.hi))
        covered |= {e.index for e in extras}
        assert covered == set(range(n)), arch
        assert len(fastewq_metadata_plan(cfg).decisions) == n, arch


# ---------------------------------------------------------------------------
# mixed-plan parity vs the blockwise reference (regression: no raw fallback)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,stack_keys", [
    ("zamba2-2.7b", ("layers",)),
    ("whisper-medium", ("enc_layers", "dec_layers")),
])
def test_mixed_plan_parity_and_no_raw_fallback(arch, stack_keys):
    cfg, model, params = _model(arch)
    n = cfg.num_layers + (cfg.num_encoder_layers or 0)
    precs = (["int8", "int4", "raw", "int8"] * n)[:n]
    plan = explicit_plan(cfg, precs, shared_precision="int8")

    pq = apply_plan_to_params(model, params, plan)
    for key in stack_keys:
        assert isinstance(pq[key], SegmentedParams), key
        assert len(pq[key].segments) > 1  # genuinely mixed
    qts = _stack_qtensors(pq, stack_keys)
    assert qts, "mixed plan must quantize layer stacks (old fallback bug)"
    assert {q.precision for q in qts} >= {"int8", "int4"}

    batch = _batch(cfg)
    logits_q, _ = model.apply(pq, batch, remat=False)
    ref = _blockwise_reference(model, params, plan)
    logits_ref, _ = model.apply(ref, batch, remat=False)
    err = float(jnp.max(jnp.abs(logits_q - logits_ref)))
    scale = float(jnp.max(jnp.abs(logits_ref))) + 1e-6
    assert err / scale < 2e-3, f"{arch}: rel err {err/scale}"


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "whisper-medium"])
def test_mixed_plan_weight_bytes_shrink(arch):
    """weight_bytes() must strictly shrink vs raw for the two families the
    old code silently served raw under mixed plans."""
    cfg, model, params = _model(arch)
    n = cfg.num_layers + (cfg.num_encoder_layers or 0)
    plan = explicit_plan(cfg, (["int4", "int8"] * n)[:n],
                         shared_precision="int8")
    raw_engine = ServeEngine(model, params, max_seq=24)
    q_engine = ServeEngine(model, params, max_seq=24, plan=plan)
    assert q_engine.weight_bytes() < 0.7 * raw_engine.weight_bytes()


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "whisper-medium"])
def test_mixed_plan_decode_matches_forward(arch):
    """Segmented cached decode == segmented teacher-forced forward on the
    SAME compiled params (validates the per-unit / per-segment cache
    slicing in the decode paths)."""
    cfg, model, params = _model(arch)
    n = cfg.num_layers + (cfg.num_encoder_layers or 0)
    plan = explicit_plan(cfg, (["raw", "int8", "int4", "int8"] * n)[:n],
                         shared_precision="int8")
    pq = apply_plan_to_params(model, params, plan)
    b, s = 2, 12
    batch = _batch(cfg, b, s)
    logits_tf, _ = model.apply(pq, batch, remat=False)
    cache = model.init_cache(b, s)
    if cfg.family == "encdec":
        from repro.models import encdec
        enc_out = encdec.encode(pq, batch["frames"], cfg, remat=False)
        ck, cv = encdec.precompute_cross_kv(pq, enc_out, cfg)
        cache = cache._replace(cross_k=ck, cross_v=cv)
    outs = []
    for t in range(s):
        lg, cache = model.decode_step(pq, cache, batch["tokens"][:, t:t + 1])
        outs.append(lg[:, 0])
    logits_dec = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(logits_tf - logits_dec)))
    scale = float(jnp.max(jnp.abs(logits_tf))) + 1e-6
    assert err / scale < 5e-5, f"{arch}: rel err {err/scale}"


def test_hybrid_segments_respect_unit_boundaries():
    cfg, model, params = _model("zamba2-2.7b")  # 4 layers, period 2
    plan = explicit_plan(cfg, ["int8", "int8", "int8", "int4"],
                         shared_precision="int8")
    compiled = compile_plan(model, params, plan)
    segs = [(s.precision, s.start, s.stop)
            for s in compiled.params["layers"].segments]
    assert segs == [("int8", 0, 2), ("int8", 2, 3), ("int4", 3, 4)]
    p = cfg.shared_attn_period
    for _, start, stop in segs:
        assert start // p == (stop - 1) // p  # within one unit


# ---------------------------------------------------------------------------
# artifact round-trip: compile -> save -> restore -> serve
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_dense_all_precisions(tmp_path):
    cfg, model, params = _model("llama3.2-3b", num_layers=4)
    plan = explicit_plan(cfg, ["ternary", "int4", "int8", "raw"])
    compiled = compile_plan(model, params, plan)
    save_artifact(str(tmp_path), compiled)
    loaded = load_artifact(str(tmp_path), model)
    mpath = tmp_path / "plan_manifest.json"
    assert mpath.exists()
    # manifests written with the former kernel-tuning stamp still load
    manifest = json.loads(mpath.read_text())
    assert "autotune" not in manifest
    mpath.write_text(json.dumps({**manifest, "autotune": "untuned"}))
    assert load_artifact(str(tmp_path),
                         model).plan.precisions() == plan.precisions()
    assert loaded.plan.precisions() == plan.precisions()
    assert loaded.nbytes_effective() == compiled.nbytes_effective()
    precisions = {s.precision for s in loaded.params["layers"].segments}
    assert precisions == {"ternary", "int4", "int8", "raw"}
    batch = _batch(cfg)
    l1, _ = model.apply(compiled.params, batch, remat=False)
    l2, _ = model.apply(loaded.params, batch, remat=False)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@pytest.mark.parametrize("arch", ["zamba2-2.7b", "whisper-medium"])
def test_artifact_serve_matches_in_memory(arch, tmp_path):
    """Engine booted from the artifact generates token-identical output to
    the engine holding the in-memory compiled plan."""
    cfg, model, params = _model(arch)
    n = cfg.num_layers + (cfg.num_encoder_layers or 0)
    plan = explicit_plan(cfg, (["int4", "ternary", "int8", "raw"] * n)[:n],
                         shared_precision="int8")
    compiled = compile_plan(model, params, plan)
    save_artifact(str(tmp_path), compiled)

    mem = ServeEngine(model, compiled.params, max_seq=20)
    art = ServeEngine.from_artifact(model, str(tmp_path), max_seq=20)
    assert art.plan is not None and art.plan.precisions() == plan.precisions()
    assert art.weight_bytes() == mem.weight_bytes()

    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    out_mem = mem.generate(prompts, 6)
    out_art = art.generate(prompts, 6)
    np.testing.assert_array_equal(np.asarray(out_mem.tokens),
                                  np.asarray(out_art.tokens))
    np.testing.assert_allclose(np.asarray(out_mem.logprobs),
                               np.asarray(out_art.logprobs), atol=1e-5)


def test_artifact_rejects_wrong_model(tmp_path):
    cfg, model, params = _model("llama3.2-3b", num_layers=4)
    plan = explicit_plan(cfg, ["int8"] * 4)
    save_artifact(str(tmp_path), compile_plan(model, params, plan))
    _, other, _ = _model("mamba2-780m")
    with pytest.raises(ValueError, match="compiled for"):
        load_artifact(str(tmp_path), other)


def test_artifact_rejects_layer_count_mismatch(tmp_path):
    """Same config name, different depth: the manifest validator must name
    the plan-length mismatch up front instead of failing deep inside the
    restore shape checks."""
    cfg, model, params = _model("llama3.2-3b", num_layers=4)
    save_artifact(str(tmp_path),
                  compile_plan(model, params, explicit_plan(cfg, ["int8"] * 4)))
    from repro.models.model import build
    deeper = build(dataclasses.replace(cfg, num_layers=6))
    with pytest.raises(ValueError, match="block decisions"):
        load_artifact(str(tmp_path), deeper)


def test_artifact_rejects_tampered_group(tmp_path):
    """A manifest group that quantizes different leaves than the save-time
    compile is rejected with a named leaf-kind ValueError, not a
    stack-trace deep inside restore."""
    import json as _json
    cfg, model, params = _model("llama3.2-3b", num_layers=2)
    save_artifact(str(tmp_path),
                  compile_plan(model, params, explicit_plan(cfg, ["int8"] * 2)))
    mpath = tmp_path / "plan_manifest.json"
    manifest = _json.loads(mpath.read_text())
    manifest["group"] = 100   # divides nothing: skeleton stays raw
    mpath.write_text(_json.dumps(manifest))
    with pytest.raises(ValueError, match="group/plan mismatch"):
        load_artifact(str(tmp_path), model)
    manifest["group"] = 0
    mpath.write_text(_json.dumps(manifest))
    with pytest.raises(ValueError, match="positive integer"):
        load_artifact(str(tmp_path), model)


def test_artifact_roundtrip_with_non_dividing_group(tmp_path):
    """A group that skips some leaves (quantization passes them through
    raw) must still round-trip — validation rejects only genuine
    mismatches, not unusual-but-self-consistent artifacts."""
    cfg, model, params = _model("llama3.2-3b", num_layers=2)
    compiled = compile_plan(model, params, explicit_plan(cfg, ["int8"] * 2),
                            group=33)
    save_artifact(str(tmp_path), compiled)
    loaded = load_artifact(str(tmp_path), model)
    assert loaded.nbytes_effective() == compiled.nbytes_effective()


def test_artifact_records_save_mesh():
    """save_artifact(mesh=...) stamps the save-time layout; artifacts stay
    mesh-portable (restorable without any mesh)."""
    import json as _json
    import tempfile
    from repro.checkpoint.ckpt import load_artifact_manifest
    from repro.launch.mesh import make_mesh
    cfg, model, params = _model("llama3.2-3b", num_layers=2)
    compiled = compile_plan(model, params, explicit_plan(cfg, ["int8", "raw"]))
    d = tempfile.mkdtemp()
    save_artifact(d, compiled, mesh=make_mesh((1, 1), ("data", "model")))
    manifest = load_artifact_manifest(d)
    assert manifest["saved_mesh"] == {"axis_names": ["data", "model"],
                                      "shape": [1, 1]}
    loaded = load_artifact(d, model)   # no mesh: plain single-device boot
    assert loaded.plan.precisions() == ["raw", "int8", "raw"]


# ---------------------------------------------------------------------------
# qdot backend selector (satellite: _dequant_fused wired in, validated)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["int8", "int4", "ternary"])
def test_qdot_backends_match_ref(precision):
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 256))
    w = quantize(jax.random.normal(jax.random.PRNGKey(2), (32, 256)),
                 precision)
    ref = np.asarray(qmatmul_ref(x, w))
    for backend in ("grouped", "simple"):
        y = np.asarray(qdot(x, w, out_dtype=jnp.float32, backend=backend))
        np.testing.assert_allclose(y, ref, rtol=2e-2, atol=2e-2 * np.abs(
            ref).max())


def test_qdot_backend_selection_and_errors():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256))
    w = quantize(jax.random.normal(jax.random.PRNGKey(2), (16, 256)), "int8")
    with pytest.raises(ValueError):
        qdot(x, w, backend="not-a-backend")
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError):  # forced pallas off-TPU: loud, not silent
            qdot(x, w, backend="pallas")
    y = np.asarray(qdot(x, w, out_dtype=jnp.float32, backend="grouped"))
    np.testing.assert_allclose(y, np.asarray(qmatmul_ref(x, w)),
                               rtol=2e-2, atol=1e-2)
