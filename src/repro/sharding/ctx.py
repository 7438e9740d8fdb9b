"""Activation-sharding context: logical-dim constraints inside model code.

GSPMD propagation alone loses the batch sharding through scan carries (the
embed table's conflicting dims win), which replicates attention scores and
logits. Model code therefore marks activations with *logical* dims via
``constrain(x, ("batch", None, "model"))``; the mapping to mesh axes is
installed by ``activation_sharding(mesh)`` in the launch drivers. Outside
the context (single-device smoke tests) ``constrain`` is a no-op.

Logical dims:
  "batch"  -> ("pod", "data") / "data"   (the FSDP/DP axes)
  "model"  -> "model"                     (TP/EP axis)
  "expert" -> "model"
A dim is only sharded when its size divides the axis size.

Two more facilities live here because they must be visible inside model
code:

* ``unshard_fsdp(tree)`` — FSDP materialization point. Layer bodies call it
  on their (scan-sliced) parameters; each weight leaf is constrained to its
  TP-only spec (fsdp dims -> replicated), which makes GSPMD emit the
  per-layer all-gather in the forward and the matching reduce-scatter for
  the gradients — ZeRO-3 semantics with remat-aware re-gathering.

* ``cost_mode()`` / ``unroll_flag()`` — XLA's HloCostAnalysis counts a
  while-loop body ONCE regardless of trip count, so scans hide depth from
  cost_analysis. The dry-run's cost lowering enters ``cost_mode()``, which
  makes every model scan fully unroll (models pass ``unroll=unroll_flag()``
  to lax.scan); the dry-run lowers reduced-depth variants and extrapolates
  affinely in depth (see launch/dryrun.py).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

_STATE = threading.local()


def _rules():
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def activation_sharding(mesh):
    """Install the logical-dim -> mesh-axis mapping. Tolerates meshes
    missing an axis (pure-DP serving mesh has no "model"; pure-TP no
    "data"): the absent logical dim maps to no axis (size 1 — always
    divides, always replicated)."""
    fsdp = ("pod", "data") if "pod" in mesh.axis_names else "data"
    fsdp_t = fsdp if isinstance(fsdp, tuple) else (fsdp,)
    if not all(a in mesh.axis_names for a in fsdp_t):
        fsdp, fsdp_t = None, ()
    model_ax = "model" if "model" in mesh.axis_names else None
    model_sz = mesh.shape["model"] if model_ax else 1
    sizes = {
        "batch": int(np.prod([mesh.shape[a] for a in fsdp_t] or [1])),
        "model": model_sz,
        "expert": model_sz,
        "seq": model_sz,
    }
    axes = {"batch": fsdp, "model": model_ax, "expert": model_ax,
            "seq": model_ax}
    old = _rules()
    _STATE.rules = {"axes": axes, "sizes": sizes, "mesh": mesh}
    try:
        yield
    finally:
        _STATE.rules = old


def constrain(x: jax.Array, dims: Sequence[Optional[str]]) -> jax.Array:
    rules = _rules()
    if rules is None:
        return x
    assert len(dims) == x.ndim, (dims, x.shape)
    parts = []
    for name, size in zip(dims, x.shape):
        if name is None:
            parts.append(None)
        elif size % rules["sizes"][name] == 0:
            parts.append(rules["axes"][name])
        else:
            parts.append(None)
    return jax.lax.with_sharding_constraint(x, P(*parts))


def data_shards() -> int:
    """Size of the data (batch) axes, 1 outside the context — used by the
    MoE grouped-local dispatch to align groups with data shards."""
    rules = _rules()
    return rules["sizes"]["batch"] if rules else 1


def sharded_trace() -> bool:
    """True inside an ``activation_sharding`` context over more than one
    device. The compiler cannot partition a Pallas (Mosaic) call, so the
    kernel entry points take their jnp paths in such a trace."""
    rules = _rules()
    return rules is not None and rules["mesh"].size > 1


def model_shards() -> int:
    rules = _rules()
    return rules["sizes"]["model"] if rules else 1


def unshard_fsdp(tree):
    """FSDP materialization: constrain each weight leaf to its TP-only spec
    (fsdp dims replicated). No-op outside the activation_sharding context."""
    rules = _rules()
    if rules is None:
        return tree
    mesh = rules["mesh"]
    from repro.sharding.specs import fsdp_axes, param_specs

    fsdp = fsdp_axes(mesh)
    fsdp_set = set(fsdp) if isinstance(fsdp, tuple) else {fsdp}
    specs = param_specs(tree, mesh)

    def strip(spec):
        parts = []
        for ax in spec:
            if ax is None or ax in fsdp_set or (
                    isinstance(ax, tuple) and set(ax) & fsdp_set):
                parts.append(None)
            else:
                parts.append(ax)
        return P(*parts)

    def apply(leaf, spec):
        return jax.lax.with_sharding_constraint(leaf, strip(spec))

    return jax.tree.map(apply, tree, specs,
                        is_leaf=lambda x: isinstance(x, P))


@contextlib.contextmanager
def cost_mode():
    old = getattr(_STATE, "cost_mode", False)
    _STATE.cost_mode = True
    try:
        yield
    finally:
        _STATE.cost_mode = old


def in_cost_mode() -> bool:
    return getattr(_STATE, "cost_mode", False)


def unroll_flag():
    """Pass as lax.scan(..., unroll=unroll_flag())."""
    return True if in_cost_mode() else 1
