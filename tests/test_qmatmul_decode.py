"""Decode-shaped quantized matmuls (M < 128) through the Pallas kernels.

Anchor invariants:

* At decode M (1, 16, 64: generate, the docqa and decode-batch slot
  counts) ``qdot`` / ``fused_qkv`` / ``fused_mlp`` pad M to the bf16
  sublane tile, launch ``qmatmul_pallas`` / ``qkv_pallas`` /
  ``qmlp_pallas`` with the decode block rule, and match the jnp
  ``grouped`` sequence (interpret mode on CPU).
* The decode rule admits yi-9b's decode shapes (D 4096, kv 512, FF 11008
  = 43 x 256, group 128, int8 and int4) with blocks that stream the
  weight in large tiles.
* Every M >= 128 decision is the prefill gate's (``_pallas_aligned``)
  with the prefill blocks, unchanged.
* A trace sharded over a mesh never takes a kernel.
* ``ewq_qmatmul_calls_total`` counts each dispatch by path and regime.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels.qmatmul import kernel as K
from repro.kernels.qmatmul import ops
from repro.kernels.qmatmul.kernel import DEFAULT_BK, DEFAULT_BM, DEFAULT_BN
from repro.kernels.qmatmul.ops import fused_mlp, fused_qkv, qdot
from repro.quant.qtypes import QTensor
from repro.quant.quantize import quantize_int4, quantize_int8
from repro.sharding.ctx import activation_sharding

QUANTIZERS = {"int8": quantize_int8, "int4": quantize_int4}
KERNELS = ("qmatmul_pallas", "qkv_pallas", "qmlp_pallas")
DECODE_M = (1, 16, 64)
COUNTER = "ewq_qmatmul_calls_total"


@pytest.fixture
def interpret_kernels(monkeypatch):
    """The kernels' dispatch as on a TPU, the kernels run interpreted."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    for name in KERNELS:
        monkeypatch.setattr(ops, name, functools.partial(
            getattr(K, name), interpret=True))


@pytest.fixture
def recorded_kernels(monkeypatch):
    """The dispatch as on a TPU, each kernel a stub that records its
    call's shape and blocks and returns zeros (for abstract tracing)."""
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    calls = []

    def stub(name, x, *ws, **kw):
        calls.append((name, x.shape, kw))
        m = x.shape[0]
        if name == "qkv_pallas":
            return tuple(jnp.zeros((m, ws[i].shape[0]), jnp.float32)
                         for i in (0, 2, 4))
        n = ws[4].shape[0] if name == "qmlp_pallas" else ws[0].shape[0]
        return jnp.zeros((m, n), jnp.float32)

    for name in KERNELS:
        monkeypatch.setattr(ops, name, functools.partial(stub, name))
    return calls


def _q(precision, n, k, seed, group=128):
    w = jax.random.normal(jax.random.PRNGKey(seed), (n, k)) * 0.2
    return QUANTIZERS[precision](w, group)


def _spec(precision, n, k, group=128):
    """An abstract QTensor of logical shape (n, k)."""
    k_store = k // 2 if precision == "int4" else k
    return QTensor(data=jax.ShapeDtypeStruct((n, k_store), jnp.int8),
                   scale=jax.ShapeDtypeStruct((n, k // group), jnp.bfloat16),
                   precision=precision, shape=(n, k), group=group)


def _x(m, k, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, k),
                             jnp.float32) * 0.5


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# decode M through the padding and block rule (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", ["int8", "int4"])
@pytest.mark.parametrize("m", DECODE_M)
def test_qdot_decode_kernel_matches_grouped(interpret_kernels, precision, m):
    w = _q(precision, 1024, 2560, seed=m)      # blocks 512 x 512: 2 x 5 steps
    x = _x(m, 2560)
    with obs.capture() as (_, mx):
        got = qdot(x, w)
    assert mx.counter(COUNTER).value(path="pallas", regime="decode") == 1
    assert got.shape == (m, 1024)
    _close(got, qdot(x, w, backend="grouped"))


@pytest.mark.parametrize("precision", ["int8", "int4"])
@pytest.mark.parametrize("m", DECODE_M)
def test_fused_qkv_decode_kernel_matches_grouped(interpret_kernels,
                                                 precision, m):
    wq, wk, wv = (_q(precision, n, 2560, seed=s)
                  for s, n in ((1, 256), (2, 128), (3, 128)))
    x = _x(m, 2560).reshape(m, 1, 2560)        # (slots, 1 token, d)
    with obs.capture() as (_, mx):
        got = fused_qkv(x, wq, wk, wv)
    assert mx.counter(COUNTER).value(path="pallas", regime="decode") == 1
    want = fused_qkv(x, wq, wk, wv, backend="grouped")
    for g, w in zip(got, want):
        assert g.shape == w.shape
        _close(g, w)


@pytest.mark.parametrize("precision", ["int8", "int4"])
@pytest.mark.parametrize("m", DECODE_M)
def test_fused_mlp_decode_kernel_matches_grouped(interpret_kernels,
                                                 precision, m):
    """FF 768 = 3 x 256: an FF block of 256 that the prefill gate's
    ``ff % 512`` would refuse."""
    wg, wu = (_q(precision, 768, 256, seed=s) for s in (4, 5))
    wd = _q(precision, 256, 768, seed=6)
    x = _x(m, 256)
    assert ops._decode_blocks("qmlp", m, 256, (768, 256), wu)["bf"] == 256
    assert not ops._pallas_aligned(128, 256, 768, precision)
    with obs.capture() as (_, mx):
        got = fused_mlp(x, wg, wu, wd)
    assert mx.counter(COUNTER).value(path="pallas", regime="decode") == 1
    _close(got, fused_mlp(x, wg, wu, wd, backend="grouped"))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

YI = dict(d=4096, kv=512, ff=11008)


@pytest.mark.parametrize("precision", ["int8", "int4"])
@pytest.mark.parametrize("m", [16, 64])
def test_decode_rule_admits_yi_decode_shapes(recorded_kernels, precision, m):
    d, kv, ff = YI["d"], YI["kv"], YI["ff"]
    x = jax.ShapeDtypeStruct((m, 1, d), jnp.bfloat16)

    def layer(x, wq, wk, wv, wo, wg, wu, wd):
        q, _, _ = fused_qkv(x, wq, wk, wv)
        return qdot(q, wo) + fused_mlp(x, wg, wu, wd)

    with obs.capture() as (_, mx):
        jax.eval_shape(layer, x, _spec(precision, d, d),
                       _spec(precision, kv, d), _spec(precision, kv, d),
                       _spec(precision, d, d), _spec(precision, ff, d),
                       _spec(precision, ff, d), _spec(precision, d, ff))
    qkv_bk = 2048 if precision == "int4" else 1024
    assert recorded_kernels == [
        ("qkv_pallas", (m, d), {"group": 128, "precision": precision,
                                "bm": m, "bk": qkv_bk}),
        ("qmatmul_pallas", (m, d), {"group": 128, "precision": precision,
                                    "bm": m, "bn": 512, "bk": 2048}),
        ("qmlp_pallas", (m, d), {"group": 128, "precision": precision,
                                 "act": "swiglu", "bm": m, "bf": 256}),
    ]
    assert mx.counter(COUNTER).value(path="pallas", regime="decode") == 3
    assert mx.counter(COUNTER).value(path="dequant", regime="decode") is None


def test_decode_rule_pads_m_and_keeps_configured_blocks(recorded_kernels):
    x = jax.ShapeDtypeStruct((3, 1024), jnp.bfloat16)
    out = jax.eval_shape(lambda x, w: qdot(x, w), x,
                         _spec("int8", 1024, 1024))
    assert out.shape == (3, 1024)
    ops.configure_qmatmul(bn=256, bk=256)
    try:
        jax.eval_shape(lambda x, w: qdot(x, w), x, _spec("int8", 1024, 1024))
        # a configured block that does not divide the shape is not used
        ops.configure_qmatmul(bn=384, bk=384)
        jax.eval_shape(lambda x, w: qdot(x, w), x, _spec("int8", 1024, 1024))
    finally:
        ops._blocks.update(bm=None, bn=None, bk=None)
    assert [c[1:] for c in recorded_kernels] == [
        ((16, 1024), {"group": 128, "precision": "int8", "bm": 16,
                      "bn": 512, "bk": 1024}),
        ((16, 1024), {"group": 128, "precision": "int8", "bm": 16,
                      "bn": 256, "bk": 256}),
        ((16, 1024), {"group": 128, "precision": "int8", "bm": 16,
                      "bn": 512, "bk": 1024}),
    ]


@pytest.mark.parametrize("kernel,m,k,ns,precision", [
    ("qmatmul", 16, 1024, (192,), "int8"),       # N not a multiple of 128
    ("qmatmul", 16, 192, (256,), "int8"),        # no K block of 128s
    ("qmatmul", 16, 128, (256,), "int4"),        # packed K block of 64
    ("qkv", 16, 1024, (1024, 192, 192), "int8"),
    ("qmlp", 16, 1024, (640, 1024), "int8"),     # FF not a multiple of 256
    ("qmlp", 16, 1024, (1024, 192), "int8"),     # D not a multiple of 128
])
def test_decode_rule_refuses_shapes_the_kernels_cannot_take(kernel, m, k, ns,
                                                            precision):
    w = _spec(precision, ns[0], k)
    assert ops._decode_blocks(kernel, m, k, ns, w) is None


PREFILL_SHAPES = [(m, n, k, p) for m in (128, 256, 200)
                  for n, k in ((4096, 4096), (512, 4096), (4096, 11008),
                               (640, 1024), (1024, 512))
                  for p in ("int8", "int4")]


@pytest.mark.parametrize("m,n,k,precision", PREFILL_SHAPES)
def test_prefill_decisions_are_the_aligned_gate(recorded_kernels, m, n, k,
                                                precision):
    """M >= 128: the kernel iff ``_pallas_aligned`` (per weight, as the
    prefill gate combines them), with the prefill blocks."""
    aligned = functools.partial(ops._pallas_aligned, precision=precision)
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    w = _spec(precision, n, k)
    jax.eval_shape(lambda x, w: qdot(x, w), x, w)
    jax.eval_shape(lambda x, a, b, c: fused_qkv(x, a, b, c), x, w, w, w)
    ff_w, down = _spec(precision, n, k), _spec(precision, k, n)
    jax.eval_shape(lambda x, a, b, c: fused_mlp(x, a, b, c), x, ff_w, ff_w,
                   down)
    want = []
    if aligned(m, n, k):
        want.append(("qmatmul_pallas", (m, k),
                     {"group": 128, "precision": precision}))
        want.append(("qkv_pallas", (m, k),
                     {"group": 128, "precision": precision,
                      "bm": DEFAULT_BM, "bk": DEFAULT_BK}))
    mlp_aligned = aligned(m, n, k) and k % 128 == 0 and aligned(m, k, n)
    if mlp_aligned:
        want.append(("qmlp_pallas", (m, k),
                     {"group": 128, "precision": precision, "act": "swiglu",
                      "bm": DEFAULT_BM, "bf": DEFAULT_BN}))
    else:   # the fallback's three qdots: gate and up, then down
        want += [("qmatmul_pallas", (m, k),
                  {"group": 128, "precision": precision})] * (
                      2 * aligned(m, n, k))
        if aligned(m, k, n):
            want.append(("qmatmul_pallas", (m, n),
                         {"group": 128, "precision": precision}))
    assert recorded_kernels == want


def test_prefill_takes_configured_blocks(recorded_kernels):
    x = jax.ShapeDtypeStruct((256, 4096), jnp.bfloat16)
    w = _spec("int8", 4096, 4096)
    ops.configure_qmatmul(bm=128, bn=512, bk=1024)
    try:
        jax.eval_shape(lambda x, w: qdot(x, w), x, w)
        jax.eval_shape(lambda x, w: fused_qkv(x, w, w, w), x, w)
        jax.eval_shape(lambda x, w: fused_mlp(x, w, w, w), x, w)
    finally:
        ops._blocks.update(bm=None, bn=None, bk=None)
    base = {"group": 128, "precision": "int8", "bm": 128}
    assert [(c[0], c[2]) for c in recorded_kernels] == [
        ("qmatmul_pallas", dict(base, bn=512, bk=1024)),
        ("qkv_pallas", dict(base, bk=1024)),
        ("qmlp_pallas", dict(base, act="swiglu", bf=512)),
    ]


def test_sharded_trace_never_takes_the_kernel(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in KERNELS:
        monkeypatch.setattr(ops, name, lambda *a, **k: pytest.fail(
            "a sharded trace launched a kernel"))
    assert ops._use_pallas()
    mesh = types.SimpleNamespace(axis_names=("data", "model"),
                                 shape={"data": 1, "model": 2}, size=2)
    x = jax.ShapeDtypeStruct((16, 1, 4096), jnp.bfloat16)
    w, kv = _spec("int8", 4096, 4096), _spec("int8", 512, 4096)
    up, down = _spec("int8", 11008, 4096), _spec("int8", 4096, 11008)
    with obs.capture() as (_, mx), activation_sharding(mesh):
        assert not ops._use_pallas()
        jax.eval_shape(lambda x, w, kv: fused_qkv(x, w, kv, kv), x, w, kv)
        jax.eval_shape(lambda x, w: qdot(x, w), x, w)
        jax.eval_shape(lambda x, u, d: fused_mlp(x, u, u, d), x, up, down)
    # qkv's three qdots, the one qdot, the MLP's three
    assert mx.counter(COUNTER).value(path="dequant", regime="decode") == 7
    assert mx.counter(COUNTER).value(path="pallas", regime="decode") is None


def test_counter_labels_the_jnp_path_and_the_prefill_regime():
    """Off a TPU every quantized dispatch is the jnp path; raw weights
    are not counted."""
    w = _q("int8", 128, 256, seed=8)
    with obs.capture() as (_, mx):
        qdot(_x(4, 256), w)
        qdot(_x(128, 256), w)
        qdot(_x(4, 256), jnp.ones((128, 256)))
    c = mx.counter(COUNTER)
    assert c.value(path="dequant", regime="decode") == 1
    assert c.value(path="dequant", regime="prefill") == 1
    assert c.total() == 2
