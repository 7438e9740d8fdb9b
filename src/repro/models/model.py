"""Model registry: uniform interface over the four family modules."""

from __future__ import annotations

import dataclasses
from collections.abc import Sequence
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, ssm_lm, transformer

_FAMILIES = {
    "dense": transformer,
    "moe": transformer,
    "encdec": encdec,
    "hybrid": hybrid,
    "ssm": ssm_lm,
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def module(self):
        return _FAMILIES[self.cfg.family]

    # ---- parameters -------------------------------------------------------
    def init(self, key) -> Any:
        return self.module.init(key, self.cfg)

    def abstract_params(self, key=None) -> Any:
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(lambda k: self.module.init(k, self.cfg), key)

    # ---- forward ----------------------------------------------------------
    def apply(self, params, batch: dict, *, remat: bool = True,
              last_only: bool = False):
        """batch: {"tokens": (B,S)} (+ "frames" for enc-dec). -> (logits, aux)."""
        if self.cfg.family == "encdec":
            return self.module.apply(params, batch["tokens"], batch["frames"],
                                     self.cfg, remat=remat,
                                     last_only=last_only)
        return self.module.apply(params, batch["tokens"], self.cfg,
                                 remat=remat, last_only=last_only)

    # ---- decode -----------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int):
        return self.module.init_cache(self.cfg, batch, max_seq)

    def decode_step(self, params, cache, tokens):
        return self.module.decode_step(params, cache, tokens, self.cfg)

    # ---- speculative propose (fused, docs/DESIGN.md §12) -------------------
    @property
    def supports_fused_propose(self) -> bool:
        """True when the family has a read-only draft decode step (dense /
        MoE — the transformer module); other families fall back to the
        two-pass throwaway-cache propose."""
        return hasattr(self.module, "draft_propose_step")

    def draft_propose_step(self, params, cache, fresh_k, fresh_v, count,
                           tokens):
        """One read-only draft decode step: k/v go to row ``count`` of the
        (L_draft, B, K, Hkv, hd) side buffers, never to the cache. Returns
        (logits, fresh_k, fresh_v)."""
        return self.module.draft_propose_step(params, cache, fresh_k,
                                              fresh_v, count, tokens,
                                              self.cfg)

    # ---- speculative verify (docs/DESIGN.md §11) ---------------------------
    def spec_verify(self, params, cache, tokens):
        """Score a (B, K+1) verify window against the cache: attention
        families run ONE fused multi-query decode pass; SSM/hybrid scan
        single-token steps while checkpointing their sequential state.
        Returns (logits (B, K+1, V_pad), snap) — pass the snap plus the
        per-slot committed length to ``spec_commit`` to roll the cache
        back (position arithmetic over KV rows, snapshot selection over
        SSM summaries)."""
        return self.module.spec_verify(params, cache, tokens, self.cfg)

    def spec_commit(self, snap, committed):
        """Commit ``committed`` (B,) tokens out of a verify window; 0 rolls
        a slot fully back to its pre-verify cache."""
        return self.module.spec_commit(snap, committed)

    # ---- slotted decode (continuous batching) -----------------------------
    @property
    def cache_batch_axes(self):
        """Cache NamedTuple of ints: batch axis per field in the slotted
        layout (``pos`` held as a (B,) per-slot vector)."""
        return self.module.CACHE_BATCH_AXES

    @property
    def kv_cache_fields(self) -> tuple:
        """Cache fields the engine may replace with quantized KVPages."""
        return getattr(self.module, "KV_CACHE_FIELDS", ())

    def slotted_cache(self, num_slots: int, max_seq: int):
        """init_cache with per-slot positions — serving/batch.py layout."""
        cache = self.init_cache(num_slots, max_seq)
        return cache._replace(pos=jnp.zeros((num_slots,), jnp.int32))

    def insert_cache_slot(self, cache, one, slot, page_rows=None):
        """Write a single-request cache (batch=1 leaves, scalar or (1,) pos)
        into slot ``slot`` of a slotted batch cache. Traceable (``slot`` may
        be a traced index).

        Prefill always produces a raw bf16 cache; when the destination
        field holds quantized KVPages the prompt K/V are quantized here, at
        admission — the decode scan's steady-state carry never sees a raw
        copy (quantize-on-insert, docs/DESIGN.md §10). Paged-pool fields
        (quant/kvcache.PagedKV) additionally need ``page_rows=(row, wrow)``,
        the slot's page-table rows from the host allocator
        (serving/pool.py): ``row`` maps logical pages to physical,
        ``wrow`` redirects shared read-only prefix pages to the dump page
        so this insert cannot overwrite them (docs/DESIGN.md §13)."""
        from repro.quant import kvcache as KV

        def leaf(dst, src, axis):
            if KV.is_kv_page(dst):
                first = dst[0] if isinstance(dst, tuple) else dst
                if isinstance(first, KV.PagedKV):
                    from repro.quant import paged
                    assert page_rows is not None, \
                        "inserting into a paged cache needs page_rows"
                    return paged.insert_slot_paged(dst, jnp.asarray(src),
                                                   slot, *page_rows)
                return KV.insert_slot(dst, jnp.asarray(src), slot)
            src = jnp.asarray(src)
            if src.ndim < dst.ndim:           # scalar pos -> (1,) vector
                src = src[None]
            start = [0] * dst.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                                tuple(start))

        axes = self.cache_batch_axes
        return type(cache)(*(leaf(d, s, a)
                             for d, s, a in zip(cache, one, axes)))

    # ---- EWQ --------------------------------------------------------------
    def block_params(self, params) -> Sequence:
        return self.module.block_params(params)

    def compile_plan(self, params, plan, group: int = 128, **kw):
        """Lower a QuantPlan onto this model's parameter layout — segmented
        quantized stacks for every family (quant/compiler.py,
        docs/DESIGN.md §8). Returns a CompiledPlan; its ``.params`` slot in
        for raw params everywhere (apply / decode_step / serving).
        ``kv_precision=``/``kv_group=`` additionally compile a KV-cache
        plan (docs/DESIGN.md §10)."""
        from repro.quant.compiler import compile_plan
        return compile_plan(self, params, plan, group, **kw)


def build(cfg: ModelConfig) -> Model:
    if cfg.family not in _FAMILIES:
        raise ValueError(f"unknown family {cfg.family!r}")
    return Model(cfg=cfg)
