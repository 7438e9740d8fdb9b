"""Compile guards: the main-path Pallas kernels lower for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel body in jnp and
accepts block shapes, slices and VMEM footprints the chip's compiler
refuses. Here each kernel is compiled — not run — for a v5e chip that is
described, not attached, at the published widths of the serving models:
minicpm-2b (d=2304, 36 heads of 64, MHA) for decode attention and yi-9b
(d=4096, 4 KV heads of 128, d_ff=11008) for the weight kernels, at
prefill M and, through the entry points' decode block rule, at the
benchmark's decode slot counts; moonlight-16b (d=2048, MLA latent rows of
512 + 64, 64 experts of 1408) for absorbed latent decode attention, its
quantized projections and the grouped expert matmul. A refusal here is one
the chip would raise at the first serve.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
test workers all import this file.
"""

import collections
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.kernels.decode_attn import ops as attn_ops
from repro.kernels.decode_attn.kernel import decode_attn_pallas
from repro.kernels.entropy.kernel import entropy_pallas
from repro.kernels.qmatmul import ops
from repro.kernels.qmatmul.kernel import (qkv_pallas, qmatmul_pallas,
                                          qmlp_pallas)
from repro.models import moe
from repro.quant.qtypes import QTensor
from repro.kernels.quantize.kernel import quantize_int8_pallas
from repro.models import transformer
from repro.quant import kvcache

# minicpm-2b decode: 8 slots, 576-row cache, int8 KV groups of 64
SLOTS, SEQ, PAGE, KV_GROUP = 8, 576, 64, 64
MINICPM = dict(hkv=36, rep=1, hd=64)
YI = dict(hkv=4, rep=8, hd=128)
# yi-9b weights at prefill M = one 256-token prompt
M, D, FF, G = 256, 4096, 11008, 128
# decode M: the docqa and decode-batch slot counts
DECODE_SLOTS = (16, 64)
# moonlight-16b: 16 heads over one latent kv head of 512 + 64, 64 slots of
# 5120 rows; projections q 16 x 192, kv_b 16 x 256 from 512, o 16 x 128;
# shared experts 2 x 1408, the dense layer 11264; 64 experts of 1408
ML_D, ML_HEADS, ML_LATENT, ML_R = 2048, 16, 576, 512
ML_SLOTS, ML_SEQ, ML_PREFILL = 64, 5120, 1024
ML_EXPERTS, ML_EF = 64, 1408
ML_MATS = {"wq": (16 * 192, ML_D), "wo": (ML_D, 16 * 128),
           "wkv_b": (16 * 256, ML_R)}
ML_MLPS = {"shared": 2 * ML_EF, "dense": 11264}


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


@pytest.mark.parametrize("precision", ["int8", "int4"])
@pytest.mark.parametrize("m", DECODE_SLOTS)
@pytest.mark.parametrize("entry", ["qkv", "qmlp", "qmatmul"])
def test_decode_kernels_compile(one_chip, monkeypatch, entry, m, precision):
    """The entry points at decode M with the decode rule's blocks (qkv:
    4096 -> 4096/512/512; qmlp: 4096 <-> 11008; qmatmul: w_o 4096 x
    4096)."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)

    def q(n, k):
        data, scale = _weight(one_chip, n, k, precision)
        return QTensor(data=data, scale=scale, precision=precision,
                       shape=(n, k), group=G)

    x = _spec(one_chip, (m, 1, D), jnp.bfloat16)
    kv = YI["hkv"] * YI["hd"]
    if entry == "qkv":
        _compile_has_kernel(ops.fused_qkv, x, q(D, D), q(kv, D), q(kv, D))
    elif entry == "qmlp":
        _compile_has_kernel(ops.fused_mlp, x, q(FF, D), q(FF, D), q(D, FF))
    else:
        _compile_has_kernel(ops.qdot, x, q(D, D))


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


def test_mla_decode_attn_compiles(one_chip):
    """Absorbed MLA decode: 16 f32 query heads over one 576-wide latent kv
    head, V the same int8 rows (groups of 64), at 64 slots of 5120."""
    dims = dict(hkv=1, rep=ML_HEADS, hd=ML_LATENT)
    kd, ks = _cache(one_chip, ML_SEQ, "int8", dims, ML_SLOTS)
    q = _spec(one_chip, (ML_SLOTS, 1, ML_HEADS, 1, ML_LATENT), jnp.float32)
    valid = _spec(one_chip, (ML_SLOTS, 1), jnp.int32)
    _compile_has_kernel(
        lambda q, kd, ks, vl: decode_attn_pallas(
            q, kd, ks, kd, ks, vl, precision="int8", group=KV_GROUP,
            head_dim=ML_LATENT),
        q, kd, ks, valid)


def _whole_layer_s8(text, elems):
    """(opcode, dims, fused) of each instruction in compiled HLO ``text``
    that yields an s8 array of ``elems`` elements; ``fused`` marks one
    inside a fusion's computation."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        if not line.startswith(" ") and line.endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = re.search(r"= s8\[([\d,]*)\]\{[^}]*\} ([\w\-]+)\(", line)
        if m:
            dims = tuple(int(d) for d in m.group(1).split(","))
            if math.prod(dims) == elems:
                out.append((m.group(2), dims, comp in fused))
    return out


def test_mla_latent_cache_keeps_its_layout(one_chip, monkeypatch):
    """The int8 latent cache of moonlight-16b-5L's decode (5 layers, 64
    slots of 5120 rows of 576) through two steps of a layer scan that
    carries it as xs/ys, as ``transformer.decode_step`` does: per-slot
    quantize-on-insert, then the decode kernel over the written page. The
    whole layer's s8 array moves at most twice a layer (the scan's slice
    and its stack update), never through a copy, and no array of it
    carries a size-1 axis (the head axis a one-head cache drops)."""
    monkeypatch.setattr(attn_ops, "_use_pallas", lambda: True)
    cfg = get_config("moonlight-16b")
    layers = 5
    raw = jax.eval_shape(lambda: transformer.init_cache(
        cfg, ML_SLOTS, ML_SEQ).c[:layers])
    plan = kvcache.KVPlan(("int8",) * layers, group=KV_GROUP)
    page = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda c: kvcache.quantize_cache_field(c, plan), raw))
    rows = _spec(one_chip, (layers, ML_SLOTS, 1) + raw.shape[3:],
                 jnp.bfloat16)
    q = _spec(one_chip, (ML_SLOTS, 1, ML_HEADS, ML_LATENT), jnp.float32)
    pos = _spec(one_chip, (ML_SLOTS,), jnp.int32)

    def decode(page, q, rows, pos):
        def layer(acc, xs):
            lp, r = xs
            new = kvcache.update_page(lp, r, pos)
            o = attn_ops.decode_attention(q, new, new, valid_len=pos + 1)
            return acc + o.sum(), new

        def step(carry, _):
            page, acc = carry
            acc, page = jax.lax.scan(layer, acc, (page, rows))
            return (page, acc), None

        return jax.lax.scan(step, (page, jnp.float32(0)), None, length=2)[0]

    text = jax.jit(decode, donate_argnums=0).lower(
        page, q, rows, pos).compile().as_text()
    assert "tpu_custom_call" in text
    found = _whole_layer_s8(text, ML_SLOTS * ML_SEQ * ML_LATENT)
    assert found, "no whole-layer s8 array in the compiled decode"
    for op, dims, _ in found:
        minor = dims[next(i for i, d in enumerate(dims) if d != 1):]
        assert 1 not in minor, (op, dims)
    moves = collections.Counter(
        op for op, _, fused in found if not fused
        and op not in ("parameter", "get-tuple-element", "bitcast", "tuple"))
    assert moves["copy"] == 0 and sum(moves.values()) <= 2, moves


# the quantized projections that take a kernel: w_q and w_o everywhere,
# kv_b at prefill in int8 (int4 packs its 512 inputs under the 512-lane
# block), the shared experts' MLP at decode (its 2816 misses the prefill
# lane block), the dense layer's MLP everywhere
ML_CASES = [(e, m, p) for p in ("int8", "int4")
            for m in (ML_SLOTS, ML_PREFILL)
            for e in ("wq", "wo", "wkv_b", "shared", "dense")
            if not (e == "wkv_b" and (m == ML_SLOTS or p == "int4"))
            and not (e == "shared" and m == ML_PREFILL)]


@pytest.mark.parametrize("entry,m,precision", ML_CASES)
def test_mla_projections_compile(one_chip, monkeypatch, entry, m, precision):
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)

    def q(n, k):
        data, scale = _weight(one_chip, n, k, precision)
        return QTensor(data=data, scale=scale, precision=precision,
                       shape=(n, k), group=G)

    if entry in ML_MATS:
        n, k = ML_MATS[entry]
        x = _spec(one_chip, (m, 1, k), jnp.bfloat16)
        _compile_has_kernel(ops.qdot, x, q(n, k))
    else:
        ff = ML_MLPS[entry]
        x = _spec(one_chip, (m, 1, ML_D), jnp.bfloat16)
        _compile_has_kernel(ops.fused_mlp, x, q(ff, ML_D), q(ff, ML_D),
                            q(ML_D, ff))


@pytest.mark.parametrize("rows", [ML_SLOTS * 6, ML_PREFILL * 6])
@pytest.mark.parametrize("mat", ["gate", "down"])
def test_expert_gmm_compiles(one_chip, rows, mat):
    """The grouped expert matmul at decode (64 slots x top-6) and prefill
    rows: gate/up (1408 from 2048) and down (2048 from 1408)."""
    n, k = (ML_EF, ML_D) if mat == "gate" else (ML_D, ML_EF)
    _compile_has_kernel(
        moe.gmm, _spec(one_chip, (rows, k), jnp.bfloat16),
        _spec(one_chip, (ML_EXPERTS, n, k), jnp.bfloat16),
        _spec(one_chip, (ML_EXPERTS,), jnp.int32))
