"""Per-arch smoke tests (reduced configs): forward + train step + decode.

One forward/train step on CPU asserting output shapes + no NaNs, per the
assignment; plus decode-vs-teacher-forced parity for one arch per family.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import RunConfig
from repro.configs.registry import ARCHS, get_config
from repro.data.synthetic import synthetic_batch
from repro.launch.steps import make_optimizer
from repro.models.model import build
from repro.train.step import make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=32):
    return synthetic_batch(cfg, batch=b, seq=s, step=0)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    logits, aux = model.apply(params, batch, remat=False)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    if cfg.num_experts:
        assert "moe_aux_loss" in aux


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(KEY)
    run = RunConfig(steps=2, learning_rate=1e-3, warmup_steps=1, remat=False)
    opt = make_optimizer(run)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(model, opt, run))
    params2, opt_state2, metrics = step(params, opt_state, _batch(cfg))
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    # params actually changed
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_shapes(arch):
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(KEY)
    cache = model.init_cache(2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    logits, cache2 = model.decode_step(params, cache, tok)
    assert logits.shape == (2, 1, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    assert int(cache2.pos) == 1


@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-780m",
                                  "zamba2-2.7b", "whisper-medium",
                                  "grok-1-314b"])
def test_decode_matches_teacher_forcing(arch):
    """Step-by-step decode == teacher-forced forward (f32, tight tol).
    MoE needs headroom capacity: prefill routes B*S tokens jointly while
    decode routes B per step, so capacity-drop sets differ at cf=1.25."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32",
                              capacity_factor=8.0)
    model = build(cfg)
    params = model.init(KEY)
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    logits_tf, _ = model.apply(params, batch, remat=False)

    cache = model.init_cache(b, s)
    if cfg.family == "encdec":
        from repro.models import encdec
        enc_out = encdec.encode(params, batch["frames"], cfg, remat=False)
        ck, cv = encdec.precompute_cross_kv(params, enc_out, cfg)
        cache = cache._replace(cross_k=ck, cross_v=cv)
    outs = []
    for t in range(s):
        lg, cache = model.decode_step(params, cache, batch["tokens"][:, t:t+1])
        outs.append(lg[:, 0])
    logits_dec = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(logits_tf - logits_dec)))
    scale = float(jnp.max(jnp.abs(logits_tf))) + 1e-6
    assert err / scale < 5e-5, f"{arch}: rel err {err/scale}"


def test_block_params_counts():
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)
        model = build(cfg)
        blocks = model.block_params(model.init(KEY))
        expected = 1 + cfg.num_layers  # embed + layers
        if cfg.family == "encdec":
            expected += cfg.num_encoder_layers
        if cfg.family == "hybrid":
            expected += 1  # shared block
        assert len(blocks) == expected, arch


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid", "encdec"])
def test_block_params_slice_stacks_on_read(family):
    """Each block read from the lazy list equals the eager per-layer slice
    of its stack, in exec order; slices and negative indices work too."""
    arch = next(a for a in ARCHS
                if get_config(a, smoke=True).family == family)
    params = build(get_config(arch, smoke=True)).init(KEY)
    blocks = build(get_config(arch, smoke=True)).block_params(params)
    stacks = [params[k] for k in ("layers", "enc_layers", "dec_layers")
              if k in params]
    eager = [params["embed"]]
    for stack in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        eager += [jax.tree.map(lambda x, i=i: x[i], stack) for i in range(n)]
    if "shared" in params:
        eager.append(params["shared"])
    assert len(blocks) == len(eager)
    for got, want in zip(blocks, eager):
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.array_equal(a, b)), got, want))
    assert jax.tree.structure(blocks[-1]) == jax.tree.structure(eager[-1])
    assert len(blocks[1:3]) == 2


def test_param_count_matches_init():
    import numpy as np
    for arch in ARCHS:
        cfg = get_config(arch, smoke=True)
        model = build(cfg)
        params = jax.eval_shape(lambda k: model.init(k), KEY)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        assert actual == cfg.param_count(), \
            f"{arch}: analytic {cfg.param_count()} vs init {actual}"
