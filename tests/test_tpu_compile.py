"""Compile guards: the main-path Pallas kernels lower for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel body in jnp and
accepts block shapes, slices and VMEM footprints the chip's compiler
refuses. Here each kernel is compiled — not run — for a v5e chip that is
described, not attached, at the published widths of the serving models:
minicpm-2b (d=2304, 36 heads of 64, MHA) for decode attention and yi-9b
(d=4096, 4 KV heads of 128, d_ff=11008) for the weight kernels. A refusal
here is one the chip would raise at the first serve.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
test workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attn.kernel import decode_attn_pallas
from repro.kernels.entropy.kernel import entropy_pallas
from repro.kernels.qmatmul.kernel import (qkv_pallas, qmatmul_pallas,
                                          qmlp_pallas)
from repro.kernels.quantize.kernel import quantize_int8_pallas

# minicpm-2b decode: 8 slots, 576-row cache, int8 KV groups of 64
SLOTS, SEQ, PAGE, KV_GROUP = 8, 576, 64, 64
MINICPM = dict(hkv=36, rep=1, hd=64)
YI = dict(hkv=4, rep=8, hd=128)
# yi-9b weights at prefill M = one 256-token prompt
M, D, FF, G = 256, 4096, 11008, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        # only a missing TPU library (libtpu) skips; any other failure to
        # describe the topology is a failure of these guards
        if not str(e).startswith("JAX TPU support not installed"):
            raise
        pytest.skip(f"no TPU library to compile with: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _weight(sharding, n, k, precision):
    k_store = k // 2 if precision == "int4" else k
    return (_spec(sharding, (n, k_store), jnp.int8),
            _spec(sharding, (n, k // G), jnp.bfloat16))


@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_qmatmul_compiles(one_chip, precision):
    x = _spec(one_chip, (M, D), jnp.bfloat16)
    _compile_has_kernel(
        lambda x, w, s: qmatmul_pallas(x, w, s, precision=precision),
        x, *_weight(one_chip, FF, D, precision))


@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_qkv_compiles(one_chip, precision):
    x = _spec(one_chip, (M, D), jnp.bfloat16)
    kv = YI["hkv"] * YI["hd"]
    _compile_has_kernel(
        lambda x, *w: qkv_pallas(x, *w, precision=precision),
        x, *_weight(one_chip, D, D, precision),
        *_weight(one_chip, kv, D, precision),
        *_weight(one_chip, kv, D, precision))


@pytest.mark.parametrize("precision", ["int8", "int4"])
def test_qmlp_compiles(one_chip, precision):
    x = _spec(one_chip, (M, D), jnp.bfloat16)
    _compile_has_kernel(
        lambda x, *w: qmlp_pallas(x, *w, precision=precision),
        x, *_weight(one_chip, FF, D, precision),
        *_weight(one_chip, FF, D, precision),
        *_weight(one_chip, D, FF, precision))


def _cache(sharding, rows, precision, dims, lead):
    f = dims["hkv"] * dims["hd"]
    if precision == "bf16":
        return (_spec(sharding, (lead, rows, f), jnp.bfloat16),
                _spec(sharding, (lead, rows, 1), jnp.bfloat16))
    f_store = f // 2 if precision == "int4" else f
    return (_spec(sharding, (lead, rows, f_store), jnp.int8),
            _spec(sharding, (lead, rows, f // KV_GROUP), jnp.bfloat16))


def _queries(sharding, dims, qs=1):
    return _spec(sharding, (SLOTS, dims["hkv"], dims["rep"], qs, dims["hd"]),
                 jnp.bfloat16)


@pytest.mark.parametrize("model,precision", [("minicpm", "int8"),
                                             ("minicpm", "bf16"),
                                             ("yi", "int4")])
def test_decode_attn_dense_compiles(one_chip, model, precision):
    dims = MINICPM if model == "minicpm" else YI
    kd, ks = _cache(one_chip, SEQ, precision, dims, SLOTS)
    valid = _spec(one_chip, (SLOTS, 1), jnp.int32)
    _compile_has_kernel(
        lambda q, kd, ks, vd, vs, vl: decode_attn_pallas(
            q, kd, ks, vd, vs, vl, precision=precision, group=KV_GROUP,
            head_dim=dims["hd"]),
        _queries(one_chip, dims), kd, ks, kd, ks, valid)


def test_decode_attn_paged_compiles(one_chip):
    pages = SLOTS * SEQ // PAGE + 1            # + the dump page
    pd, ps = _cache(one_chip, PAGE, "int8", MINICPM, pages)
    valid = _spec(one_chip, (SLOTS, 1), jnp.int32)
    table = _spec(one_chip, (SLOTS, SEQ // PAGE), jnp.int32)
    _compile_has_kernel(
        lambda q, kd, ks, vd, vs, vl, t: decode_attn_pallas(
            q, kd, ks, vd, vs, vl, precision="int8", group=KV_GROUP,
            head_dim=MINICPM["hd"], page_table=t),
        _queries(one_chip, MINICPM), pd, ps, pd, ps, valid, table)


def test_decode_attn_fresh_kv_compiles(one_chip):
    """Speculative verify window (4 queries) plus 8 fresh draft rows."""
    kd, ks = _cache(one_chip, SEQ, "int8", YI, SLOTS)
    fd, fs = _cache(one_chip, 8, "int8", YI, SLOTS)
    valid = _spec(one_chip, (SLOTS, 1), jnp.int32)
    _compile_has_kernel(
        lambda q, kd, ks, vd, vs, vl, fd, fs, base: decode_attn_pallas(
            q, kd, ks, vd, vs, vl, precision="int8", group=KV_GROUP,
            head_dim=YI["hd"], fresh_k_data=fd, fresh_k_scale=fs,
            fresh_v_data=fd, fresh_v_scale=fs, base=base),
        _queries(one_chip, YI, qs=4), kd, ks, kd, ks, valid, fd, fs, valid)


def test_entropy_and_quantize_compile(one_chip):
    """Off the serve path, but dispatched to on TPU by the EWQ analysis
    (kernel mode) and the fused quantizer."""
    _compile_has_kernel(entropy_pallas,
                        _spec(one_chip, (2304, 5760), jnp.bfloat16))
    _compile_has_kernel(quantize_int8_pallas,
                        _spec(one_chip, (D, D), jnp.bfloat16))
