"""Apply a QuantPlan to model parameters.

Two layouts are supported:

* ``apply_plan_blocks``   — params as an ordered list of per-block dicts
  (serving engine / small models / tests).  Each >=2D leaf of a block whose
  decision is quantized becomes a QTensor.
* ``apply_plan_stacked``  — params with leaves stacked over a leading layer
  axis (the scan layout used by every model here).  The layer stack is
  partitioned into maximal contiguous *segments* of equal precision; each
  segment keeps its stacked layout (quantized per segment precision), so the
  model can scan each segment separately.  A uniform plan degenerates to one
  segment (the fast path).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import QuantPlan
from repro.quant.qtypes import QTensor
from repro.quant.quantize import quantize


@functools.partial(jax.jit, static_argnums=(1, 2))
def _quantize(x, precision: str, group: int):
    """One fused program per leaf shape: eager quantization of a
    layer-stacked leaf would hold several f32 copies of it at once."""
    return quantize(x, precision, group)


def _quantizable(x: Any, group: int, min_ndim: int) -> bool:
    return (hasattr(x, "ndim") and x.ndim >= min_ndim
            and x.shape[-1] % group == 0 and x.shape[-1] % 2 == 0)


def quantize_tree(tree: Any, precision: str, group: int = 128,
                  min_ndim: int = 2) -> Any:
    """Quantize every eligible leaf of a pytree; ineligible leaves pass
    through. ``min_ndim=3`` for layer-stacked trees, where 1D per-layer
    vectors (norm scales, biases, A_log) appear as 2D (L, D) leaves and must
    stay raw (the paper quantizes Linear/Embedding weights only)."""
    if precision == "raw":
        return tree

    def leaf(x):
        if _quantizable(x, group, min_ndim):
            return _quantize(x, precision, group)
        return x

    return jax.tree.map(leaf, tree)


def apply_plan_blocks(blocks: list[Mapping[str, Any]], plan: QuantPlan,
                      group: int = 128) -> list[Any]:
    assert len(blocks) == len(plan.decisions), \
        f"{len(blocks)} blocks vs {len(plan.decisions)} decisions"
    return [quantize_tree(b, d.precision, group)
            for b, d in zip(blocks, plan.decisions)]


# ---------------------------------------------------------------------------
# Stacked (scan) layout
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Segment:
    precision: str
    start: int          # first layer index (inclusive)
    stop: int           # last layer index (exclusive)
    params: Any         # stacked over [start:stop), quantized unless raw

    def tree_flatten(self):
        return (self.params,), (self.precision, self.start, self.stop)

    @classmethod
    def tree_unflatten(cls, aux, children):
        precision, start, stop = aux
        return cls(precision=precision, start=start, stop=stop,
                   params=children[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SegmentedParams:
    segments: list[Segment]
    num_layers: int

    def tree_flatten(self):
        return (self.segments,), (self.num_layers,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(segments=children[0], num_layers=aux[0])

    def nbytes_effective(self) -> float:
        total = 0.0
        for seg in self.segments:
            for leaf in jax.tree.leaves(
                    seg.params, is_leaf=lambda x: isinstance(x, QTensor)):
                if isinstance(leaf, QTensor):
                    total += leaf.nbytes_effective()
                else:
                    total += leaf.size * leaf.dtype.itemsize
        return total


def plan_segments(plan: QuantPlan,
                  cuts: Sequence[int] = ()) -> list[tuple[str, int, int]]:
    """Maximal runs of equal precision over block_index order.

    ``cuts`` forces additional segment boundaries at the given layer indices
    (the hybrid family cuts at shared-attention unit boundaries so every
    segment executes inside exactly one unit — docs/DESIGN.md §8)."""
    precisions = plan.precisions()
    cutset = set(cuts)
    runs: list[tuple[str, int, int]] = []
    start = 0
    for i in range(1, len(precisions) + 1):
        if (i == len(precisions) or precisions[i] != precisions[start]
                or i in cutset):
            runs.append((precisions[start], start, i))
            start = i
    return runs


def apply_plan_stacked(stacked: Any, plan: QuantPlan, group: int = 128,
                       cuts: Sequence[int] = ()) -> SegmentedParams:
    """``stacked`` leaves have a leading layer axis of length == len(plan).

    The plan here must cover exactly the stacked layers (embedding / final
    params are handled separately by the caller).
    """
    num_layers = len(plan.decisions)
    segs = []
    for precision, start, stop in plan_segments(plan, cuts):
        sliced = jax.tree.map(lambda x: x[start:stop], stacked)
        segs.append(Segment(precision=precision, start=start, stop=stop,
                            params=quantize_tree(sliced, precision, group,
                                                 min_ndim=3)))
    return SegmentedParams(segments=segs, num_layers=num_layers)


def segment_slices(layers: Any) -> list[tuple[Any, int, int]]:
    """Uniform iteration over a layer stack that may or may not be segmented.

    Returns ``[(stacked_params, start, stop), ...]`` — one entry per segment
    for a ``SegmentedParams``, or a single full-range entry for a plain
    stacked tree. Model scan bodies use this to run each segment (and its
    cache slice ``[start:stop]``) through one ``lax.scan`` without branching
    on the parameter layout."""
    if isinstance(layers, SegmentedParams):
        return [(s.params, s.start, s.stop) for s in layers.segments]
    n = jax.tree.leaves(layers)[0].shape[0]
    return [(layers, 0, n)]


def tree_nbytes(tree: Any) -> float:
    """Effective byte count of a tree that may contain QTensors."""
    total = 0.0
    for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes_effective()
        elif hasattr(leaf, "size"):
            total += leaf.size * np.dtype(leaf.dtype).itemsize
    return total
