"""The model step's named scopes and the serving programs' names.

Scopes change only the compiled programs' ``op_name`` metadata, which a
profiler trace carries onto each device op; program names are the
``jit_<function>`` module names the trace's "XLA Modules" line shows.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.kernels.qmatmul.ops import qdot
from repro.models.model import build
from repro.quant.quantize import quantize
from repro.serving.engine import ServeEngine
from repro.serving.pool import PagedConfig
from repro.serving.quantized import fastewq_metadata_plan
from repro.serving.spec import SpecConfig

TOP_SCOPES = ("embed", "attn", "mlp", "kv", "head", "sample")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def dense():
    cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                              num_layers=2)
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _paths(compiled) -> set:
    return set(_OP_NAME.findall(compiled.as_text()))


def _scopes_in(paths: set) -> set:
    return {part for p in paths for part in p.split("/")} & set(TOP_SCOPES)


def _module(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_decode_chunk_and_prefill_carry_every_scope(dense):
    cfg, model, params = dense
    plan = fastewq_metadata_plan(cfg, "8bit-mixed")
    engine = ServeEngine(model, params, max_seq=32, plan=plan,
                         kv_precision="int8", autotune=False)
    progs = engine.compile_programs(8, 2, chunk=2)
    decode, prefill = _paths(progs["decode"]), _paths(progs["prefill"])
    assert _scopes_in(decode) == set(TOP_SCOPES)
    assert _scopes_in(prefill) >= {"embed", "attn", "mlp", "kv", "head"}
    # the quantized layers' jnp matmuls dequantize under ewq/dequant
    for paths in (decode, prefill):
        assert any("/ewq/dequant/" in p for p in paths)


def test_compile_programs_allocates_no_decode_state(dense):
    cfg, model, params = dense
    engine = ServeEngine(model, params, max_seq=32, paged=PagedConfig(
        page_size=8), autotune=False)
    engine.init_decode_state(2)
    pool = engine.pool
    progs = engine.compile_programs(8, 2, chunk=2)
    assert engine.pool is pool                 # the live allocator stays
    assert set(progs) == {"prefill", "decode"}


@pytest.mark.parametrize("backend", ["simple", "grouped"])
def test_qdot_jnp_path_is_scoped_dequant(backend):
    w = quantize(jax.random.normal(jax.random.PRNGKey(1), (256, 256)),
                 "int8", 128)
    x = jnp.ones((4, 256), jnp.bfloat16)
    text = jax.jit(lambda x, w: qdot(x, w, backend=backend)).lower(
        x, w).compile().as_text()
    paths = set(_OP_NAME.findall(text))
    assert any("/ewq/dequant/" in p for p in paths)
    # a raw weight's matmul is not a dequantization
    raw = jax.jit(lambda x, w: qdot(x, w)).lower(
        x, jnp.ones((256, 256), jnp.bfloat16)).compile().as_text()
    assert "ewq/dequant" not in raw


def test_serving_programs_have_distinct_names(dense):
    """The decode chunk alone is ``jit_run``: the trace's per-program
    readings key on the module name."""
    cfg, model, params = dense
    paged = ServeEngine(model, params, max_seq=32, autotune=False,
                        paged=PagedConfig(page_size=8), prefill_chunk=4)
    state = paged.init_decode_state(2)
    pools = {name: getattr(state.cache, name)
             for name in paged._paged_fields}
    row = jnp.zeros(paged.pool.n_log, jnp.int32)
    cache1 = model.init_cache(1, 32)
    toks = jnp.zeros((1, 4), jnp.int32)
    names = {
        "decode": _module(paged._chunk_fn(2).lower(paged.params, state)),
        "seed": _module(paged._seed_fn(4).lower(
            paged.params, pools, row, jnp.int32(8), toks)),
        "chunked": _module(paged._prefill_chunk_fn().lower(
            paged.params, cache1, toks)),
        "gather": _module(paged._pool_gather_fn().lower(
            pools, row, jnp.int32(8))),
    }
    spec = ServeEngine(model, params, max_seq=32, autotune=False,
                       spec=SpecConfig(k=2))
    sstate = spec.init_decode_state(2)
    names["spec"] = _module(spec._spec_fn(2).lower(
        spec.params, spec.draft_params, sstate))
    ecfg = get_config("whisper-medium", smoke=True)
    emodel = build(ecfg)
    enc = ServeEngine(emodel, emodel.init(jax.random.PRNGKey(0)),
                      max_seq=32, autotune=False, prefill_chunk=4)
    frames = enc._default_frames(1)
    enc._encdec_seed(frames)
    names["encdec"] = _module(enc._encdec_seed_fn.lower(enc.params, frames))
    assert names["decode"] == "jit_run"
    assert len(set(names.values())) == len(names), names
    assert names == {"decode": "jit_run", "seed": "jit_seed_prefix",
                     "chunked": "jit_prefill_chunk",
                     "gather": "jit_gather_prefix",
                     "spec": "jit_spec_chunk",
                     "encdec": "jit_encdec_seed"}
