"""Family-universal plan compiler: QuantPlan -> CompiledPlan.

Lowers an EWQ/FastEWQ ``QuantPlan`` (one precision decision per block in
``Model.block_params`` order) onto a model family's concrete parameter
layout, replacing the previous per-family branching in serving/quantized.py
(which silently fell back to RAW weights for hybrid and enc-dec mixed
plans). Every family now yields quantized segmented stacks:

* dense / moe / ssm — one layer stack, segmented into maximal runs of equal
  precision (``SegmentedParams``); an MoE model's leading dense layers
  (``first_k_dense``) are a second stack ahead of it;
* hybrid — the Mamba2 layer stack is additionally cut at shared-attention
  unit boundaries when the plan is mixed, so each segment executes inside
  exactly one unit of the unit-scan (models/hybrid.py); the shared block is
  a per-block extra quantized at its own decision;
* encdec — independent segmented encoder and decoder stacks.

The result carries a serializable manifest (family, plan, segment layout,
group, effective bytes), and ``save_artifact``/``load_artifact`` persist the
quantized parameters + manifest as a bootable checkpoint so a server cold
start skips raw-weight loading AND entropy analysis entirely
(``launch/serve.py --plan-artifact``). Contract details: docs/DESIGN.md §8.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.policy import QuantPlan
from repro.quant.apply import (Segment, SegmentedParams, _quantizable,
                               apply_plan_stacked, quantize_tree, tree_nbytes)
from repro.quant.kvcache import DEFAULT_KV_GROUP, KVPlan

ARTIFACT_VERSION = 1

# Block decisions at (or below) these precisions already carry int4-or-lower
# payloads — a self-speculative draft (docs/DESIGN.md §11) shares them
# byte-for-byte with the target instead of storing a copy.
DRAFT_SHARED = ("int4", "int3", "ternary")

# Entropy-weighted weight decision -> KV-cache precision (docs/DESIGN.md
# §10): layers whose weights tolerate aggressive quantization (low entropy)
# also take the int4 cache; sensitive (raw-weight) layers keep bf16 K/V.
KV_OF_WEIGHT = {"ternary": "int4", "int3": "int4", "int4": "int4",
                "int8": "int8", "raw": "bf16"}


@dataclasses.dataclass(frozen=True)
class StackSpec:
    """One scanned layer stack: param key + the plan slice covering it."""
    key: str                        # params dict key ("layers", "enc_layers", ...)
    lo: int                         # first plan decision index (inclusive)
    hi: int                         # last plan decision index (exclusive)
    cut_period: Optional[int] = None  # forced segment cuts every N layers


@dataclasses.dataclass(frozen=True)
class ExtraSpec:
    """One non-stacked block quantized whole (embedding, hybrid shared)."""
    key: str
    index: int                      # plan decision index


def family_layout(cfg: ModelConfig) -> tuple[list[StackSpec], list[ExtraSpec]]:
    """Map a family's ``block_params`` order onto its param-dict layout.

    The decision order is [embed] + stacked layers (+ family extras), matching
    ``Model.block_params`` / the planner's exec_index convention.
    """
    n = cfg.num_layers
    k = cfg.first_k_dense if cfg.family == "moe" else 0
    if k:
        # leading dense layers: a stack of their own ahead of the MoE stack
        return ([StackSpec("dense_layers", 1, 1 + k),
                 StackSpec("layers", 1 + k, 1 + n)], [ExtraSpec("embed", 0)])
    if cfg.family in ("dense", "moe", "ssm"):
        return [StackSpec("layers", 1, 1 + n)], [ExtraSpec("embed", 0)]
    if cfg.family == "hybrid":
        # Mixed plans must not let a segment span a shared-attention site:
        # cut at unit boundaries so execution stays a per-unit inner scan.
        return ([StackSpec("layers", 1, 1 + n,
                           cut_period=cfg.shared_attn_period)],
                [ExtraSpec("embed", 0), ExtraSpec("shared", 1 + n)])
    if cfg.family == "encdec":
        ne = cfg.num_encoder_layers
        return ([StackSpec("enc_layers", 1, 1 + ne),
                 StackSpec("dec_layers", 1 + ne, 1 + ne + n)],
                [ExtraSpec("embed", 0)])
    raise ValueError(f"unknown family {cfg.family!r}")


def plan_length(cfg: ModelConfig) -> int:
    """Number of block decisions a plan for ``cfg`` must carry."""
    stacks, extras = family_layout(cfg)
    return max([s.hi for s in stacks] + [e.index + 1 for e in extras])


def _subplan(plan: QuantPlan, lo: int, hi: int) -> QuantPlan:
    return dataclasses.replace(plan, decisions=plan.decisions[lo:hi])


def kv_cache_layers(cfg: ModelConfig) -> int:
    """Leading-axis length of the family's attention cache (0: no cache)."""
    if cfg.family in ("dense", "moe"):
        return cfg.num_layers
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.shared_attn_period  # U shared sites
    if cfg.family == "encdec":
        return cfg.num_layers                            # decoder stack
    return 0                                             # ssm


def compile_kv_plan(cfg: ModelConfig, plan: Optional[QuantPlan],
                    kv_precision: str = "auto",
                    group: int = DEFAULT_KV_GROUP) -> Optional[KVPlan]:
    """Lower a KV-cache precision policy onto a family's cache layout.

    ``kv_precision``:
      "bf16"          — no quantized cache (None)
      "int8" / "int4" — uniform across all cache layers
      "auto"          — entropy-weighted: each cache layer inherits its
        block's weight decision via ``KV_OF_WEIGHT`` (hybrid's shared-site
        cache follows the shared block's single decision; enc-dec follows
        the decoder stack). Requires ``plan``.
    """
    if kv_precision in (None, "bf16"):
        return None
    n = kv_cache_layers(cfg)
    if n == 0:          # attention-free (ssm): nothing to plan
        return None
    if kv_precision in ("int8", "int4"):
        return KVPlan(precisions=(kv_precision,) * n, group=group)
    if kv_precision != "auto":
        raise ValueError(f"unknown kv_precision {kv_precision!r}; one of "
                         f"('bf16', 'int8', 'int4', 'auto')")
    if plan is None:
        raise ValueError("kv_precision='auto' derives per-layer cache "
                         "precision from the weight plan's entropy "
                         "decisions — pass a QuantPlan")
    if cfg.family == "hybrid":
        shared = plan.decisions[1 + cfg.num_layers].precision
        prec = (KV_OF_WEIGHT[shared],) * n
    elif cfg.family == "encdec":
        ne = cfg.num_encoder_layers
        prec = tuple(KV_OF_WEIGHT[d.precision]
                     for d in plan.decisions[1 + ne:1 + ne + cfg.num_layers])
    else:
        prec = tuple(KV_OF_WEIGHT[d.precision]
                     for d in plan.decisions[1:1 + cfg.num_layers])
    return KVPlan(precisions=prec, group=group)


_KV_DOWN = {"bf16": "int8", "int8": "int4", "int4": "int4"}


def degrade_kv_ladder(cfg: ModelConfig, plan: Optional[QuantPlan],
                      base: Optional[KVPlan],
                      group: int = DEFAULT_KV_GROUP, *,
                      fastewq=None, block_sizes=None,
                      cuts: Sequence[int] = ()) -> list:
    """Entropy-ordered KV degradation tiers (DESIGN.md §15).

    Tier 0 is the serving policy (``base``; None = bf16). Deeper tiers
    spill cache precision down bf16→int8→int4 in the order the layer-
    level entropy signal dictates: layers whose weight blocks the plan
    marked quantizable (or that a FastEWQ classifier predicts quantizable
    from metadata alone, O(1) per block) spill FIRST; entropy-sensitive
    layers follow one tier later; the final tier is all-int4. Lowering
    precision at constant byte budget buys proportionally more pool
    pages, which is what relieves ``OutOfPages`` pressure — see
    ``ServeEngine.apply_kv_plan``.
    """
    n = kv_cache_layers(cfg)
    if n == 0:
        return []
    base_prec = list(base.precisions) if base is not None else ["bf16"] * n
    if base is not None:
        group = base.group
    if plan is not None:
        if cfg.family == "hybrid":
            spill = [plan.decisions[1 + cfg.num_layers].quantized] * n
        elif cfg.family == "encdec":
            ne = cfg.num_encoder_layers
            spill = [d.quantized
                     for d in plan.decisions[1 + ne:1 + ne + cfg.num_layers]]
        else:
            spill = [d.quantized for d in plan.decisions[1:1 + cfg.num_layers]]
    elif fastewq is not None and block_sizes is not None:
        order = fastewq.kv_spill_order(block_sizes)
        first = set(order[:max(1, len(order) // 2)])
        spill = [i in first for i in range(n)]
    else:
        # no entropy signal: deepest layers spill first (paper §6.3 —
        # the highest exec-index quantized block is first to drop a tier)
        spill = [i >= n // 2 for i in range(n)]
    # decode scans the cache one pool run per parameter segment
    # (kvcache.kv_segment), so a tier's precision must be uniform within
    # each segment (no cuts = ONE segment spanning the stack): a segment
    # spills when at least half of its layers' entropy decisions say spill
    bounds = [0] + [c for c in sorted(set(cuts)) if 0 < c < n] + [n]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        seg = sum(spill[lo:hi]) * 2 >= (hi - lo)
        spill[lo:hi] = [seg] * (hi - lo)
    if not any(spill):
        spill = [True] * n
    t1 = [_KV_DOWN[p] if s else p for p, s in zip(base_prec, spill)]
    t2 = [_KV_DOWN[_KV_DOWN[p]] if s else _KV_DOWN[p]
          for p, s in zip(base_prec, spill)]
    t3 = ["int4"] * n
    tiers = [base]
    last = base_prec
    for t in (t1, t2, t3):
        if t != last:
            tiers.append(KVPlan(precisions=tuple(t), group=group))
            last = t
    return tiers


def kv_tier_labels(ladder: Sequence[Optional[KVPlan]]) -> list[str]:
    """Human precision label per degradation tier ("bf16" / "int8" /
    "mixed" / ...), used as the ``precision`` metric label on
    ``serve_kv_tier_steps_total`` so a dashboard shows which cache
    precision the degraded steps actually ran at."""
    labels = []
    for kv in ladder:
        if kv is None:
            labels.append("bf16")
            continue
        uniq = sorted(set(kv.precisions))
        labels.append(uniq[0] if len(uniq) == 1 else "mixed")
    return labels


@dataclasses.dataclass
class CompiledPlan:
    """A QuantPlan lowered onto one model's parameters.

    ``params`` is the full parameter tree ready for the model/serving stack:
    every scanned stack is a ``SegmentedParams`` (even uniform/raw plans —
    one segment), per-block extras are quantized trees, and untouched keys
    ("final", ...) pass through. ``kv_plan`` (optional) records the
    KV-cache precision policy compiled alongside the weights; it is
    stamped into the artifact manifest so a cold boot serves with the same
    cache quantization without re-analysis.
    """
    family: str
    config_name: str
    group: int
    plan: QuantPlan
    params: Any
    kv_plan: Optional[KVPlan] = None
    # self-speculative draft stamp (DraftPlan.to_manifest()): recorded so a
    # cold boot re-derives the identical draft without re-deciding anything
    # (the derivation is deterministic given plan + params); the stamped
    # overhead_bytes is the deployment-memory number (docs/DESIGN.md §11)
    draft: Optional[dict] = None

    def stack_keys(self) -> list[str]:
        return [k for k, v in self.params.items()
                if isinstance(v, SegmentedParams)]

    def nbytes_effective(self) -> float:
        total = 0.0
        for v in self.params.values():
            total += (v.nbytes_effective() if isinstance(v, SegmentedParams)
                      else tree_nbytes(v))
        return total

    def manifest(self) -> dict:
        stacks = {}
        for key in self.stack_keys():
            seg = self.params[key]
            stacks[key] = [{"precision": s.precision, "start": s.start,
                            "stop": s.stop} for s in seg.segments]
        out = {
            "version": ARTIFACT_VERSION,
            "family": self.family,
            "config_name": self.config_name,
            "group": self.group,
            "plan": json.loads(self.plan.to_json()),
            "stacks": stacks,
            "effective_bytes": float(self.nbytes_effective()),
        }
        if self.kv_plan is not None:
            out["kv_plan"] = self.kv_plan.to_dict()
        if self.draft is not None:
            out["draft"] = self.draft
        return out


def compile_plan(model, params, plan: QuantPlan, group: int = 128,
                 kv_precision: str = "bf16",
                 kv_group: int = DEFAULT_KV_GROUP) -> CompiledPlan:
    """Lower ``plan`` onto ``params`` for any model family.

    ``kv_precision`` additionally compiles a KV-cache plan
    (``compile_kv_plan``) carried on the result and stamped into artifact
    manifests. Traceable (pure jnp + static python control flow), so it
    runs under ``jax.eval_shape`` for abstract/dry-run inputs.
    """
    cfg = model.cfg
    expected = plan_length(cfg)
    assert len(plan.decisions) == expected, \
        (f"plan has {len(plan.decisions)} decisions; family {cfg.family!r} "
         f"needs {expected}")
    stacks, extras = family_layout(cfg)
    new = dict(params)
    for spec in stacks:
        sub = _subplan(plan, spec.lo, spec.hi)
        cuts: Sequence[int] = ()
        if spec.cut_period and len(set(sub.precisions())) > 1:
            cuts = range(spec.cut_period, spec.hi - spec.lo, spec.cut_period)
        new[spec.key] = apply_plan_stacked(params[spec.key], sub, group,
                                           cuts=cuts)
    for spec in extras:
        new[spec.key] = quantize_tree(
            params[spec.key], plan.decisions[spec.index].precision, group)
    return CompiledPlan(family=cfg.family, config_name=cfg.name, group=group,
                        plan=plan, params=new,
                        kv_plan=compile_kv_plan(cfg, plan, kv_precision,
                                                kv_group))


# ---------------------------------------------------------------------------
# self-speculative draft plans (docs/DESIGN.md §11)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DraftPlan:
    """An entropy-ordered all-int4 draft derived from a compiled target.

    ``params`` is a full parameter tree executable by the same model code
    as the target: blocks the entropy plan already pushed to int4 (or
    lower) REFERENCE the target's QTensor payloads — the same jax.Arrays,
    zero extra HBM — while raw/int8 blocks carry a draft-only int4
    requantization. ``overhead_bytes`` is exactly those draft-only
    payloads (the manifest number; by construction it is bounded by the
    int4 size of the blocks it re-quantizes)."""
    params: Any
    precisions: tuple[str, ...]     # per-block draft decision (plan order;
                                    # "skip" = truncated away, not executed)
    shared_blocks: int              # decisions sharing target payloads
    requantized_blocks: int         # decisions with a draft-only int4 copy
    overhead_bytes: float
    group: int
    draft_layers: Optional[int] = None  # truncated layer count (None: full)

    def to_manifest(self) -> dict:
        return {"precisions": list(self.precisions),
                "shared_blocks": self.shared_blocks,
                "requantized_blocks": self.requantized_blocks,
                "overhead_bytes": float(self.overhead_bytes),
                "group": self.group,
                "draft_layers": self.draft_layers}


def _draft_tree(tree: Any, group: int, min_ndim: int) -> tuple[Any, float]:
    """Requantize one block's tree to int4, dequantizing int8 QTensors
    first; already-aggressive QTensors and ineligible leaves are shared.
    Returns (draft_tree, draft_only_bytes)."""
    from repro.quant.qtypes import QTensor
    from repro.quant.quantize import dequantize, quantize
    overhead = [0.0]

    def leaf(x):
        if isinstance(x, QTensor):
            if x.precision in DRAFT_SHARED:
                return x                       # shared payload, zero bytes
            q = quantize(dequantize(x, jnp.float32), "int4", x.group)
            overhead[0] += q.nbytes_effective()
            return q
        if _quantizable(x, group, min_ndim):
            q = quantize(x, "int4", group)
            overhead[0] += q.nbytes_effective()
            return q
        return x                               # norms/biases: shared raw

    out = jax.tree.map(leaf, tree,
                       is_leaf=lambda x: isinstance(x, QTensor))
    return out, overhead[0]


def _slice_stack_layers(tree: Any, take: int) -> Any:
    """Slice the leading (stacked-layer) axis of every leaf to [0, take),
    rebuilding the STATIC logical shape QTensors carry (a plain tree.map
    would slice data/scale but leave ``shape`` stale)."""
    from repro.quant.qtypes import QTensor

    def leaf(x):
        if isinstance(x, QTensor):
            return QTensor(data=x.data[:take], scale=x.scale[:take],
                           precision=x.precision,
                           shape=(take,) + tuple(x.shape[1:]),
                           group=x.group)
        return x[:take]

    return jax.tree.map(leaf, tree,
                        is_leaf=lambda x: isinstance(x, QTensor))


def compile_draft_plan(model, params, plan: Optional[QuantPlan],
                       group: int = 128,
                       draft_layers: Optional[int] = None) -> DraftPlan:
    """Derive the self-speculative all-int4 draft from a served model.

    ``params`` is the tree the engine serves (compiled: segmented stacks +
    quantized extras; or raw when ``plan`` is None). The draft derivation
    rule follows the plan's entropy ordering: every block decision maps to
    ``min(decision, int4)`` — blocks the entropy analysis already marked
    aggressive keep their exact payloads (shared, no copy), higher-entropy
    raw/int8 blocks get a draft-only int4 requantization. With no plan
    (raw serving) the draft is a uniform int4 copy of every eligible
    block. Segment boundaries are preserved 1:1 with the target, so the
    draft executes through the identical segmented scan paths (hybrid unit
    cuts included) and shares the target's KV-cache layout.

    ``draft_layers=N`` truncates the draft to the first N layers of the
    stack (early-exit drafting, fused-propose families only — the target's
    verification keeps greedy output exact regardless of draft depth). A
    segment the cut lands inside is sliced; slicing materializes a copy,
    so sliced segments count toward ``overhead_bytes`` even when their
    precision would otherwise share the target payload. Truncated-away
    blocks are stamped ``"skip"`` in ``precisions``."""
    cfg = model.cfg
    if draft_layers is not None:
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"draft_layers needs the fused propose path (dense/moe "
                f"families); family is {cfg.family!r}")
        if not 1 <= draft_layers <= cfg.num_layers:
            raise ValueError(
                f"draft_layers must be in [1, {cfg.num_layers}], got "
                f"{draft_layers}")
    new = dict(params)
    stacks, extras = family_layout(cfg)
    overhead = 0.0
    shared = requant = 0
    n_blocks = plan_length(cfg)
    precisions = ["int4"] * n_blocks

    def mark_skipped():
        if draft_layers is None:
            return
        for spec in stacks:                    # dense/moe: one "layers" stack
            for i in range(draft_layers, spec.hi - spec.lo):
                precisions[spec.lo + i] = "skip"

    if plan is None:
        for key, val in params.items():
            n = draft_layers if key == "layers" else None
            if isinstance(val, SegmentedParams):
                segs = []
                for seg in val.segments:
                    if n is not None and seg.start >= n:
                        break
                    stop = min(seg.stop, n) if n is not None else seg.stop
                    src = (_slice_stack_layers(seg.params, stop - seg.start)
                           if stop < seg.stop else seg.params)
                    t, ob = _draft_tree(src, group, min_ndim=3)
                    segs.append(Segment(precision="int4", start=seg.start,
                                        stop=stop, params=t))
                    overhead += ob
                new[key] = SegmentedParams(
                    segments=segs,
                    num_layers=n if n is not None else val.num_layers)
            elif key in ("embed", "shared") or any(s.key == key
                                                   for s in stacks):
                if n is not None:
                    val = _slice_stack_layers(val, n)
                new[key], ob = _draft_tree(val, group,
                                           min_ndim=3 if any(
                                               s.key == key for s in stacks)
                                           else 2)
                overhead += ob
        mark_skipped()
        requant = sum(1 for p in precisions if p != "skip")
        return DraftPlan(params=new, precisions=tuple(precisions),
                         shared_blocks=0, requantized_blocks=requant,
                         overhead_bytes=overhead, group=group,
                         draft_layers=draft_layers)

    assert len(plan.decisions) == n_blocks, \
        (f"plan has {len(plan.decisions)} decisions; family {cfg.family!r} "
         f"needs {n_blocks}")
    for spec in stacks:
        layers = params[spec.key]
        assert isinstance(layers, SegmentedParams), \
            (f"draft derivation expects compiled (segmented) stacks; "
             f"{spec.key!r} is {type(layers).__name__} — compile the plan "
             f"first (quant/compiler.compile_plan)")
        n = draft_layers if spec.key == "layers" else None
        segs = []
        for seg in layers.segments:
            if n is not None and seg.start >= n:
                break
            sliced = n is not None and seg.stop > n
            stop = n if sliced else seg.stop
            if seg.precision in DRAFT_SHARED and not sliced:
                segs.append(seg)               # payloads shared verbatim
                shared += stop - seg.start
                for i in range(seg.start, stop):
                    precisions[spec.lo + i] = seg.precision
            elif seg.precision in DRAFT_SHARED:
                # the slice materializes a draft-only copy of an
                # already-aggressive payload — same precision, real bytes
                t = _slice_stack_layers(seg.params, stop - seg.start)
                segs.append(Segment(precision=seg.precision,
                                    start=seg.start, stop=stop, params=t))
                overhead += tree_nbytes(t)
                shared += stop - seg.start
                for i in range(seg.start, stop):
                    precisions[spec.lo + i] = seg.precision
            else:
                src = (_slice_stack_layers(seg.params, stop - seg.start)
                       if sliced else seg.params)
                t, ob = _draft_tree(src, group, min_ndim=3)
                segs.append(Segment(precision="int4", start=seg.start,
                                    stop=stop, params=t))
                overhead += ob
                requant += stop - seg.start
        new[spec.key] = SegmentedParams(
            segments=segs,
            num_layers=n if n is not None else layers.num_layers)
    mark_skipped()
    for spec in extras:
        prec = plan.decisions[spec.index].precision
        if prec in DRAFT_SHARED:
            shared += 1
            precisions[spec.index] = prec
        else:
            new[spec.key], ob = _draft_tree(params[spec.key], group,
                                            min_ndim=2)
            overhead += ob
            requant += 1
    return DraftPlan(params=new, precisions=tuple(precisions),
                     shared_blocks=shared, requantized_blocks=requant,
                     overhead_bytes=overhead, group=group,
                     draft_layers=draft_layers)


# ---------------------------------------------------------------------------
# persisted artifacts (compile once, serve many)
# ---------------------------------------------------------------------------

def validate_manifest(manifest: dict, cfg: ModelConfig) -> None:
    """Check an artifact manifest against a target model config up front.

    Raises a ``ValueError`` naming the mismatch (family, config, plan
    length, stack layout, group size) instead of letting the restore fail
    deep inside per-leaf shape checks.
    """
    def bail(msg):
        raise ValueError(f"artifact/model mismatch: {msg}")

    if manifest.get("version") != ARTIFACT_VERSION:
        bail(f"manifest version {manifest.get('version')!r}, this build "
             f"reads version {ARTIFACT_VERSION}")
    if manifest["family"] != cfg.family or manifest["config_name"] != cfg.name:
        bail(f"artifact was compiled for {manifest['config_name']!r} "
             f"({manifest['family']}); model is {cfg.name!r} ({cfg.family})")
    expected = plan_length(cfg)
    got = len(manifest["plan"]["decisions"])
    if got != expected:
        bail(f"plan carries {got} block decisions; family {cfg.family!r} "
             f"config {cfg.name!r} needs {expected} (layer counts differ?)")
    stacks, _ = family_layout(cfg)
    want_stacks = {s.key: s.hi - s.lo for s in stacks}
    got_stacks = manifest.get("stacks", {})
    if set(got_stacks) != set(want_stacks):
        bail(f"stack keys {sorted(got_stacks)} != expected "
             f"{sorted(want_stacks)}")
    for key, segs in got_stacks.items():
        covered = sum(s["stop"] - s["start"] for s in segs)
        if covered != want_stacks[key]:
            bail(f"stack {key!r} segments cover {covered} layers; config "
                 f"has {want_stacks[key]}")
    group = manifest["group"]
    if not isinstance(group, int) or group < 1:
        bail(f"group size {group!r} is not a positive integer")
    # A group that quantizes different leaves than the save-time compile
    # (e.g. a tampered manifest) surfaces as a leaf-KIND mismatch between
    # the rebuilt skeleton and the checkpoint — ckpt.restore names it.


def save_artifact(directory: str, compiled: CompiledPlan,
                  mesh=None) -> str:
    """Persist a compiled plan: quantized params checkpoint + manifest.

    Arrays are stored logically (shards are gathered to host buffers), so
    the artifact is mesh-portable: it can be restored onto any mesh — or
    none. ``mesh`` only stamps the save-time layout into the manifest for
    provenance."""
    from repro.checkpoint import ckpt
    from repro.kernels.autotune import current_stamp
    manifest = compiled.manifest()
    # which kernel-tuning config (kernels/autotune.py) was live when the
    # artifact was produced — "untuned" for library defaults. Cold-booted
    # replicas re-resolve against their own device's cache; this records
    # provenance for the numbers benchmarked at save time.
    manifest["autotune"] = current_stamp()
    if mesh is not None:
        manifest["saved_mesh"] = {
            "axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names]}
    return ckpt.save_artifact(directory, compiled.params, manifest)


def load_artifact(directory: str, model, *, mesh=None) -> CompiledPlan:
    """Boot a CompiledPlan from disk without raw weights or entropy analysis.

    The manifest's plan is re-lowered through ``compile_plan`` under
    ``eval_shape`` to rebuild the exact (segmented, quantized) tree skeleton,
    then the checkpointed leaves are restored into it. With ``mesh``, every
    leaf is device_put to its TP-only serving NamedSharding
    (``param_specs(serving=True)``) straight from the checkpoint file —
    weights land sharded, never materialized replicated.
    """
    from repro.checkpoint import ckpt
    manifest = ckpt.load_artifact_manifest(directory)
    cfg = model.cfg
    validate_manifest(manifest, cfg)
    plan = QuantPlan.from_json(json.dumps(manifest["plan"]))
    group = manifest["group"]
    skeleton = jax.eval_shape(
        lambda p: compile_plan(model, p, plan, group).params,
        model.abstract_params())
    if mesh is not None:
        from repro.sharding.specs import param_specs
        specs = param_specs(skeleton, mesh, serving=True)
        # specs mirrors the skeleton leaf-for-leaf, so restore device_puts
        # every leaf to its NamedSharding — already committed jax.Arrays.
        params = ckpt.restore_artifact(directory, skeleton, mesh=mesh,
                                       specs=specs)
    else:
        params = ckpt.restore_artifact(directory, skeleton)
        params = jax.tree.map(jnp.asarray, params)
    kv_plan = (KVPlan.from_dict(manifest["kv_plan"])
               if manifest.get("kv_plan") else None)
    return CompiledPlan(family=cfg.family, config_name=cfg.name, group=group,
                        plan=plan, params=params, kv_plan=kv_plan,
                        draft=manifest.get("draft"))
