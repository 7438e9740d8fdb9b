"""jit'd public wrapper for the fused dequant matmul.

``qdot(x, w)`` is the single entry point the model stack uses for every
weight matmul. ``w`` may be:

* a plain jax.Array (raw / bf16 path)          -> einsum
* a QTensor (int8 / int4 / ternary)            -> fused dequant matmul

Backend selection (explicit, per-call or process-wide):

* ``auto``    — (default) the Pallas kernel on TPU when the shape is one
  the kernel takes and the trace is not sharded over a mesh (the compiler
  cannot partition a Pallas call), else the ``simple`` jnp fallback. XLA
  fuses the fallback reasonably, keeping HLO byte counts faithful to
  weight-only quantization (int8/int4 weights are read at their quantized
  width; dequant is a flop-cheap broadcast-multiply).
* ``pallas``  — force the Pallas kernel (raises off-TPU / on shapes the
  kernel does not take rather than silently degrading).
* ``grouped`` — jnp fallback with the kernel's exact math: per-group
  partial sums are scaled, never materializing a dequantized weight.
* ``simple``  — dequantize-then-dot fallback.

Set process-wide via ``set_qdot_backend`` or the ``REPRO_QDOT_BACKEND``
env var; both jnp fallbacks are validated against ref.py
(tests/test_compiler.py::test_qdot_backends).

Which shapes the kernels take depends on M, the activation rows:

* prefill (M >= ``DECODE_M``): tile-aligned shapes (``_pallas_aligned``)
  with the kernels' default blocks or the ``configure_qmatmul`` ones.
* decode (M < ``DECODE_M``: the slot count, or slots x (k+1) in a
  speculative round): the call is bound by the weight's bytes, so M is
  padded to the bf16 sublane tile (``SUBLANE``) and the blocks stream
  the weight in large tiles (``_decode_blocks``), where the shape
  admits them: N a multiple of 128 and a K block that holds whole scale
  groups on a packed lane dim of 128s (``qdot``/``fused_qkv``), an FF
  block of 256s that divides FF (``fused_mlp``).

Each dispatch of a quantized weight counts once, at trace time, into
``ewq_qmatmul_calls_total{path="pallas"|"dequant",
regime="decode"|"prefill"|"expert"}`` on the installed metrics registry
("expert": a grouped matmul over a quantized expert stack, models/moe.py).
"""

from __future__ import annotations

import functools
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


def _lanes(k: int, precision: str) -> int:
    """Stored width of ``k`` weight columns (int4 packs two a byte)."""
    return k // 2 if precision == "int4" else k


def _pallas_aligned(m: int, n: int, k: int, precision: str = "int8") -> bool:
    """Tile alignment for the Pallas kernel.

    ``k`` is the UNPACKED activation contraction dim; int4 payloads pack
    two nibbles per byte, so the weight's physical lane dim is k/2 and must
    itself satisfy the 512-lane block alignment (k % 1024) — checking the
    unpacked k alone would admit shapes whose packed tiles misalign."""
    return m % 128 == 0 and n % 128 == 0 and _lanes(k, precision) % 512 == 0


# Decode-shaped calls: M under one MXU tile. M pads to the bf16 sublane
# tile; the blocks are the largest candidates that fit the shape, so one
# grid step streams up to DECODE_STEP_BYTES of weight (double-buffered
# inside the kernels' VMEM limit).
DECODE_M = 128
SUBLANE = 16
DECODE_BN = (512, 256, 128)
DECODE_BK = (2048, 1024, 512, 256, 128)
DECODE_BF = (512, 256)
DECODE_STEP_BYTES = 8 * 2**20


def _pick(name: str, candidates: tuple, fits) -> int | None:
    """The ``configure_qmatmul`` override ``name`` where it fits, else the
    first candidate that does."""
    v = _blocks[name]
    return next((c for c in ((v,) if v else ()) + candidates if fits(c)),
                None)


def _decode_bk(k: int, rows: int, w: QTensor) -> int | None:
    """K block of a decode call: divides K, holds whole scale groups, has
    a stored lane dim of 128s, and keeps ``rows`` weight rows of it
    within one step's bytes."""
    return _pick("bk", DECODE_BK, lambda bk: (
        k % bk == 0 and bk % w.group == 0
        and _lanes(bk, w.precision) % 128 == 0
        and rows * _lanes(bk, w.precision) <= DECODE_STEP_BYTES))


def _decode_blocks(kernel: str, m: int, k: int, ns: tuple,
                   w: QTensor) -> dict | None:
    """Blocks of a decode-shaped call of ``kernel`` ("qmatmul": ns = (N,);
    "qkv": (Nq, Nk, Nv); "qmlp": (FF, D)), or None where the kernel does
    not take the shape."""
    bm = -(-m // SUBLANE) * SUBLANE
    if kernel == "qmlp":
        ff, d = ns
        if k % w.group or _lanes(k, w.precision) % 128 or d % 128:
            return None
        bf = _pick("bn", DECODE_BF, lambda bf: (
            ff % bf == 0 and bf % w.group == 0
            and _lanes(bf, w.precision) % 128 == 0))
        return None if bf is None else {"bm": bm, "bf": bf}
    if any(n % 128 for n in ns):
        return None
    if kernel == "qkv":
        bk = _decode_bk(k, sum(ns), w)
        return None if bk is None else {"bm": bm, "bk": bk}
    bn = _pick("bn", DECODE_BN, lambda bn: ns[0] % bn == 0)
    bk = _decode_bk(k, bn, w)
    return None if bk is None else {"bm": bm, "bn": bn, "bk": bk}


def _padded(x2d: jax.Array) -> jax.Array:
    """``x2d`` with zero rows up to a multiple of ``SUBLANE`` (a no-op at
    prefill, whose M is a multiple of 128)."""
    pad = -x2d.shape[0] % SUBLANE
    return jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d


def count_dispatch(path: str, m: int, regime: str | None = None) -> None:
    """Count one dispatch of a quantized weight over ``m`` activation rows
    (``regime`` from ``m`` unless given: "expert" for a grouped expert
    matmul)."""
    obs.count("ewq_qmatmul_calls_total", 1,
              "quantized matmul dispatches per traced program",
              path=path,
              regime=regime or ("decode" if m < DECODE_M else "prefill"))


def _prefill_blocks(kernel: str, m: int, k: int, ns: tuple,
                    w: QTensor) -> dict | None:
    """Blocks of an M >= 128 call (``ns`` as in ``_decode_blocks``): the
    kernel defaults or the ``configure_qmatmul`` ones, where every weight
    it reads is tile aligned."""
    aligned = functools.partial(_pallas_aligned, m, precision=w.precision)
    if kernel == "qmlp":
        ff, d = ns
        if not (aligned(ff, k) and d % 128 == 0 and aligned(d, ff)):
            return None
        bk = _block_kwargs(m, ff, k)
        return {"bm": bk.get("bm", DEFAULT_BM), "bf": bk.get("bn", DEFAULT_BN)}
    if not all(aligned(n, k) for n in ns):
        return None
    bk = _block_kwargs(m, ns[0], k)
    if kernel == "qkv":
        return {"bm": bk.get("bm", DEFAULT_BM), "bk": bk.get("bk", DEFAULT_BK)}
    return bk


def _kernel_blocks(backend: str, kernel: str, m: int, k: int, ns: tuple,
                   w: QTensor) -> dict | None:
    """The blocks this call launches ``kernel`` with, or None where it takes
    the jnp path; a forced ``pallas`` backend raises where it cannot."""
    blocks = (_decode_blocks if m < DECODE_M else _prefill_blocks)(
        kernel, m, k, ns, w)
    if backend == "pallas" and not (_use_pallas() and blocks is not None):
        raise ValueError(
            f"{kernel} m={m} k={k} n={ns} precision={w.precision!r}: "
            f"backend 'pallas' needs a TPU, an unsharded trace and a shape "
            f"the kernel takes (prefill: m%128, n%128, payload-lane%512 — "
            f"k%1024 for packed int4; decode m<128: see _decode_blocks) on "
            f"{jax.default_backend()!r}")
    take = backend == "pallas" or (backend == "auto" and _use_pallas())
    return blocks if take else None


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
        blocks = _kernel_blocks(backend, "qmatmul", m, k, (n,), w)
        if blocks is not None:
            count_dispatch("pallas", m)
            y = qmatmul_pallas(_padded(x2d), w.data,
                               w.scale, group=w.group,
                               precision=w.precision, **blocks)[:m]
        else:
            count_dispatch("dequant", m)
            y = (_dequant_fused if backend == "grouped"
                 else _dequant_simple)(x2d, w)
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
    """Whole quantized MLP block in one call: on TPU with a shape the
    kernel takes, a single Pallas launch where the (M, FF) hidden
    activation never reaches HBM and no bf16 weight copy ever exists;
    everywhere else the EXACT qdot sequence of models/mlp.py
    (bit-identical fallback — greedy serving output does not depend on
    which path ran). ``w_gate`` is None for act="gelu"."""
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
        blocks = _kernel_blocks(backend, "qmlp", m, k, (ff, d), w)
        if blocks is not None:
            count_dispatch("pallas", m)
            y = qmlp_pallas(
                _padded(x2d),
                None if w_gate is None else w_gate.data,
                None if w_gate is None else w_gate.scale,
                w_up.data, w_up.scale, w_down.data, w_down.scale,
                group=w.group, precision=w.precision, act=act, **blocks)
            return y[:m].reshape(*lead, d).astype(x.dtype)
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
        ns = tuple(_out_dim(w) for w in (wq, wk, wv))
        blocks = _kernel_blocks(backend, "qkv", m, k, ns, wq)
        if blocks is not None:
            count_dispatch("pallas", m)
            yq, yk, yv = qkv_pallas(
                _padded(x2d), wq.data, wq.scale, wk.data,
                wk.scale, wv.data, wv.scale, group=wq.group,
                precision=wq.precision, **blocks)
            return tuple(y[:m].reshape(*lead, n).astype(x.dtype)
                         for y, n in zip((yq, yk, yv), ns))
    return (qdot(x, wq, backend=backend), qdot(x, wk, backend=backend),
            qdot(x, wv, backend=backend))
