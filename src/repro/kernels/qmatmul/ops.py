"""jit'd public wrapper for the fused dequant matmul.

``qdot(x, w)`` is the single entry point the model stack uses for every
weight matmul. ``w`` may be:

* a plain jax.Array (raw / bf16 path)          -> einsum
* a QTensor (int8 / int4 / ternary)            -> fused dequant matmul

Backend selection (explicit, per-call or process-wide):

* ``auto``    — (default) the Pallas kernel on TPU when shapes are tile
  aligned and the trace is not sharded over a mesh (the compiler cannot
  partition a Pallas call), else the ``simple`` jnp fallback. XLA fuses the fallback
  reasonably, keeping HLO byte counts faithful to weight-only quantization
  (int8/int4 weights are read at their quantized width; dequant is a
  flop-cheap broadcast-multiply).
* ``pallas``  — force the Pallas kernel (raises off-TPU / on misaligned
  shapes rather than silently degrading).
* ``grouped`` — jnp fallback with the kernel's exact math: per-group
  partial sums are scaled, never materializing a dequantized weight.
* ``simple``  — dequantize-then-dot fallback.

Set process-wide via ``set_qdot_backend`` or the ``REPRO_QDOT_BACKEND``
env var; both jnp fallbacks are validated against ref.py
(tests/test_compiler.py::test_qdot_backends).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.quant.qtypes import QTensor
from repro.quant.quantize import unpack_int4
from repro.kernels.qmatmul.kernel import (DEFAULT_BK, DEFAULT_BM, DEFAULT_BN,
                                          qkv_pallas, qmatmul_pallas,
                                          qmlp_pallas)

BACKENDS = ("auto", "pallas", "grouped", "simple")
_backend = os.environ.get("REPRO_QDOT_BACKEND", "auto")
# Pallas block-shape overrides (None -> kernel defaults); set by
# configure_qmatmul, swept by kernels/autotune.py. Read at TRACE time.
_blocks: dict = {"bm": None, "bn": None, "bk": None}


def configure_qmatmul(bm: int | None = None, bn: int | None = None,
                      bk: int | None = None,
                      backend: str | None = None) -> None:
    """Override the Pallas qmatmul/megakernel block shapes (and optionally
    the backend) process-wide — the autotuner's hook (kernels/autotune.py).
    Read at TRACE time like ``set_qdot_backend``; blocks that do not divide
    a particular call's shape fall back to the kernel defaults for that
    call."""
    global _blocks
    for name, val in (("bm", bm), ("bn", bn), ("bk", bk)):
        if val is not None:
            if val < 128 or val % 128:
                raise ValueError(f"{name} must be a multiple of 128, "
                                 f"got {val}")
            _blocks[name] = val
    if backend is not None:
        set_qdot_backend(backend)


def get_qmatmul_blocks() -> dict:
    return dict(_blocks)


def _block_kwargs(m: int, n: int, k: int) -> dict:
    """Tuned block overrides that actually divide this call's shape."""
    kw = {}
    for name, dim in (("bm", m), ("bn", n), ("bk", k)):
        v = _blocks[name]
        if v is not None and dim % min(v, dim) == 0:
            kw[name] = v
    return kw


def set_qdot_backend(name: str) -> None:
    """Select the process-wide default qdot backend (see module docstring).

    The selection is read at TRACE time: functions jitted before the call
    (e.g. a ServeEngine's cached decode/prefill executables) keep the
    backend they were traced with — rebuild them (or pass ``backend=`` per
    call) to switch."""
    if name not in BACKENDS:
        raise ValueError(f"unknown qdot backend {name!r}; one of {BACKENDS}")
    global _backend
    _backend = name


def get_qdot_backend() -> str:
    return _backend


def _use_pallas() -> bool:
    """A TPU, and a trace that is not partitioned over several devices
    (the compiler refuses to partition a Mosaic kernel)."""
    from repro.sharding.ctx import sharded_trace
    return jax.default_backend() == "tpu" and not sharded_trace()


def _pallas_aligned(m: int, n: int, k: int, precision: str = "int8") -> bool:
    """Tile alignment for the Pallas kernel.

    ``k`` is the UNPACKED activation contraction dim; int4 payloads pack
    two nibbles per byte, so the weight's physical lane dim is k/2 and must
    itself satisfy the 512-lane block alignment (k % 1024) — checking the
    unpacked k alone would admit shapes whose packed tiles misalign."""
    lane = k // 2 if precision == "int4" else k
    return m % 128 == 0 and n % 128 == 0 and lane % 512 == 0


# The jnp paths run under the ``ewq/dequant`` scope: the dequantization
# and the dot it feeds, which XLA may fuse into one instruction.
@obs.scoped("ewq/dequant")
def _dequant_fused(x2d: jax.Array, w: QTensor) -> jax.Array:
    """jnp fallback with the same math as the kernel: accumulate scaled
    per-group partial sums over a scan of the K/group blocks rather than
    materializing a full dequantized weight — temp memory stays O(M*N)
    (one partial product), never O(M*N*K/group)."""
    data = w.data
    if w.precision == "int4":
        data = unpack_int4(data)
    m = x2d.shape[0]
    n, k = data.shape
    g = w.group
    # (G, M, g) x (G, N, g): one (M, N) partial per group block, scaled.
    xg = jnp.moveaxis(x2d.reshape(m, k // g, g), 1, 0).astype(jnp.float32)
    wg = jnp.moveaxis(data.reshape(n, k // g, g), 1, 0).astype(jnp.float32)
    sg = jnp.moveaxis(w.scale.astype(jnp.float32), -1, 0)  # (G, N)

    def body(acc, xs):
        x_g, w_g, s_g = xs
        part = jnp.einsum("mk,nk->mn", x_g, w_g,
                          preferred_element_type=jnp.float32)
        return acc + part * s_g[None, :], None

    y, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.float32), (xg, wg, sg))
    return y


@obs.scoped("ewq/dequant")
def _dequant_simple(x2d: jax.Array, w: QTensor) -> jax.Array:
    """Dequantize-then-dot fallback (lets XLA fuse convert into the dot)."""
    from repro.quant.quantize import dequantize
    wd = dequantize(w, jnp.bfloat16)
    return jax.lax.dot_general(x2d, wd, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def qdot(x: jax.Array, w, out_dtype=None, backend: str | None = None
         ) -> jax.Array:
    """y[..., n] = sum_k x[..., k] * W[n, k] with W possibly quantized.

    ``backend`` overrides the process-wide selection for this call."""
    backend = backend or _backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown qdot backend {backend!r}; "
                         f"one of {BACKENDS}")
    if out_dtype is None:
        out_dtype = x.dtype
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2d = x.reshape(-1, k)
    if isinstance(w, QTensor):
        m, n = x2d.shape[0], w.data.shape[0]
        aligned = _pallas_aligned(m, n, k, w.precision)
        if backend == "pallas" or (backend == "auto" and _use_pallas()
                                   and aligned):
            if backend == "pallas" and not (_use_pallas() and aligned):
                raise ValueError(
                    f"qdot backend 'pallas' needs a TPU and tile-aligned "
                    f"shapes (m%128, n%128, payload-lane%512 — k%1024 for "
                    f"packed int4); got m={m} n={n} k={k} "
                    f"precision={w.precision!r} on "
                    f"{jax.default_backend()!r}")
            y = qmatmul_pallas(x2d, w.data, w.scale, group=w.group,
                               precision=w.precision,
                               **_block_kwargs(m, n, k))
        elif backend == "grouped":
            y = _dequant_fused(x2d, w)
        else:
            y = _dequant_simple(x2d, w)
        n_out = n
    else:
        y = jax.lax.dot_general(x2d, w, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        n_out = w.shape[0]
    return y.reshape(*lead, n_out).astype(out_dtype)


# ---------------------------------------------------------------------------
# megakernel entry points (docs/DESIGN.md §12)
# ---------------------------------------------------------------------------

def _mega_eligible(ws) -> bool:
    """All operands QTensors of one (precision, group) — the megakernels
    dequantize every tile with a single rule per launch."""
    return (all(isinstance(w, QTensor) for w in ws)
            and len({(w.precision, w.group) for w in ws}) == 1)


def _out_dim(w) -> int:
    return w.data.shape[0] if isinstance(w, QTensor) else w.shape[0]


def fused_mlp(x: jax.Array, w_gate, w_up, w_down, act: str = "swiglu",
              backend: str | None = None) -> jax.Array:
    """Whole quantized MLP block in one call: on TPU with aligned shapes a
    single Pallas launch where the (M, FF) hidden activation never reaches
    HBM and no bf16 weight copy ever exists; everywhere else the EXACT
    qdot sequence of models/mlp.py (bit-identical fallback — greedy serving
    output does not depend on which path ran). ``w_gate`` is None for
    act="gelu"."""
    backend = backend or _backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown qdot backend {backend!r}; "
                         f"one of {BACKENDS}")
    lead, k = x.shape[:-1], x.shape[-1]
    x2d = x.reshape(-1, k)
    m = x2d.shape[0]
    ws = [w for w in (w_gate, w_up, w_down) if w is not None]
    if _mega_eligible(ws):
        w = w_up
        ff, d = _out_dim(w_up), _out_dim(w_down)
        aligned = (_pallas_aligned(m, ff, k, w.precision)
                   and d % 128 == 0
                   and _pallas_aligned(m, d, ff, w.precision))
        if backend == "pallas" or (backend == "auto" and _use_pallas()
                                   and aligned):
            if backend == "pallas" and not (_use_pallas() and aligned):
                raise ValueError(
                    f"fused_mlp backend 'pallas' needs a TPU and aligned "
                    f"shapes; got m={m} ff={ff} d={d} k={k} "
                    f"precision={w.precision!r} on "
                    f"{jax.default_backend()!r}")
            bk = _block_kwargs(m, ff, k)
            y = qmlp_pallas(
                x2d,
                None if w_gate is None else w_gate.data,
                None if w_gate is None else w_gate.scale,
                w_up.data, w_up.scale, w_down.data, w_down.scale,
                group=w.group, precision=w.precision, act=act,
                bm=bk.get("bm", DEFAULT_BM), bf=bk.get("bn", DEFAULT_BN))
            return y.reshape(*lead, d).astype(x.dtype)
    # fallback: models/mlp.py's exact op sequence
    if act == "swiglu":
        g = qdot(x, w_gate, backend=backend)
        u = qdot(x, w_up, backend=backend)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return qdot(h, w_down, backend=backend)
    h = qdot(x, w_up, backend=backend)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return qdot(h, w_down, backend=backend)


def fused_qkv(x: jax.Array, wq, wk, wv, backend: str | None = None):
    """The three attention projections in one launch: each activation tile
    is read from HBM once and feeds all three accumulators. Fallback is
    exactly three ``qdot`` calls (bit-identical). Returns (q, k, v) with
    qdot's dtype convention."""
    backend = backend or _backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown qdot backend {backend!r}; "
                         f"one of {BACKENDS}")
    lead, k = x.shape[:-1], x.shape[-1]
    x2d = x.reshape(-1, k)
    m = x2d.shape[0]
    if _mega_eligible((wq, wk, wv)):
        nq, nkk, nv = _out_dim(wq), _out_dim(wk), _out_dim(wv)
        aligned = all(_pallas_aligned(m, n, k, wq.precision)
                      for n in (nq, nkk, nv))
        if backend == "pallas" or (backend == "auto" and _use_pallas()
                                   and aligned):
            if backend == "pallas" and not (_use_pallas() and aligned):
                raise ValueError(
                    f"fused_qkv backend 'pallas' needs a TPU and aligned "
                    f"shapes; got m={m} n=({nq},{nkk},{nv}) k={k} "
                    f"precision={wq.precision!r} on "
                    f"{jax.default_backend()!r}")
            bk = _block_kwargs(m, nq, k)
            yq, yk, yv = qkv_pallas(
                x2d, wq.data, wq.scale, wk.data, wk.scale, wv.data,
                wv.scale, group=wq.group, precision=wq.precision,
                bm=bk.get("bm", DEFAULT_BM), bk=bk.get("bk", DEFAULT_BK))
            return (yq.reshape(*lead, nq).astype(x.dtype),
                    yk.reshape(*lead, nkk).astype(x.dtype),
                    yv.reshape(*lead, nv).astype(x.dtype))
    return (qdot(x, wq, backend=backend), qdot(x, wk, backend=backend),
            qdot(x, wv, backend=backend))
