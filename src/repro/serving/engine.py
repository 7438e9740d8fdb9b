"""Continuous-batching serving engine with EWQ/FastEWQ-quantized weights.

Deployment story (the paper's §3.4/§4 pipeline, end-to-end):
  1. at startup, pick a QuantPlan — full EWQ (weights analyzed), FastEWQ
     (O(1), metadata only), or resource-fitted via cluster.fit_plan_to_hbm;
  2. quantize params per plan (block-granular mixed precision);
  3. serve: prefill fills the KV/SSM cache, decode runs against quantized
     weights (decode is weight-bytes-bound — exactly where int8/int4
     payloads pay off; see README.md §Serving and
     benchmarks/serve_throughput.py).

Engine structure:
  * the decode loop is ONE jitted ``lax.scan`` over a chunk of token steps
    (``_make_chunk_fn``): masked sampling, per-slot stop conditions (EOS /
    max-new-tokens), per-slot cache positions. No per-token Python
    dispatch; one compile per (chunk, num_slots) — sampling controls
    (temperature / top-k / top-p) are traced per-slot state, never
    compile keys.
  * ``serve`` runs continuous batching: between chunks the host-side
    Scheduler admits queued requests into freed slots (each admission is a
    batch=1 prefill + jitted slot insert) and harvests finished ones.
  * ``generate`` is a thin compatibility wrapper — a single fixed batch is
    one scheduler-free drain of the same chunked loop.

Prefill paths: transformer families use the fused apply(return_cache=True)
pass (works for segmented/quantized stacks too); SSM/hybrid prefill by
scanning decode steps over the prompt (their decode matches teacher-forced
forward exactly — tests/test_models_parity); enc-dec prefill additionally
encodes the request's frames and precomputes per-decoder-layer cross K/V
first (zero frames when a request carries none). The jitted prefill is
built once per engine and cached across calls.

Quantized weights come either from an in-memory plan (compiled at engine
construction via quant/compiler.py) or from a persisted artifact
(``ServeEngine.from_artifact`` — cold start with no raw weights and no
entropy analysis; docs/DESIGN.md §8).

Mesh-parallel serving (docs/DESIGN.md §9): pass ``mesh=`` and the engine
places the (quantized) weights with the TP-only serving specs
(``param_specs(serving=True)`` — QTensor payload/scale leaves included),
places the slotted decode caches with ``cache_specs`` (KV-head sharding or
the GQA sequence-shard fallback), and traces every jitted path (fused
prefill, chunked decode scan, slot insert/evict) under
``activation_sharding(mesh)`` so the model-code constraints resolve. A
mesh-less engine is byte-for-byte the old single-device path.

Self-speculative decoding (docs/DESIGN.md §11): pass
``spec=SpecConfig(k=...)`` and decode runs draft-propose / target-verify
rounds instead of single-token steps — the entropy-ordered all-int4 draft
(compile_draft_plan; payloads shared with the target for blocks the plan
already quantized aggressively) proposes k tokens, the target scores the
whole window in one fused multi-query pass, and the per-slot cache
position rolls back to the accepted prefix inside the jitted scan. Greedy
spec serving is token-identical to the non-spec engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.policy import QuantPlan
from repro.models.model import Model
from repro.serving import batch as B
from repro.serving import sampling as S
from repro.serving.pool import (OutOfPages, PagedConfig, PoolSession,
                                PrefixMatch)
from repro.serving.quantized import apply_plan_to_params
from repro.serving.scheduler import (Request, RequestOutput, Scheduler,
                                     SLOConfig)
from repro.serving.spec import SpecConfig

DEFAULT_CHUNK = 8


@dataclasses.dataclass
class GenerateResult:
    tokens: jax.Array          # (B, prompt+new)
    logprobs: jax.Array        # (B, new) chosen-token logprobs
    steps: int


@dataclasses.dataclass
class Prefill:
    """One request's prefill result — everything ``insert`` needs to admit
    it into a decode slot (the disaggregated prefill/insert/generate API,
    docs/DESIGN.md §13)."""
    prompt: np.ndarray           # (P,) int32 host tokens
    cache: object                # batch=1 prefilled family cache (raw bf16)
    last_logits: jax.Array       # (1, V_pad) logits after the last token
    match: Optional[PrefixMatch] = None  # pinned prefix-cache match (paged)


@dataclasses.dataclass
class ChunkedPrefill:
    """An in-flight chunked prefill (docs/DESIGN.md §14): the request holds
    a reserved slot while its prompt enters the batch=1 prefill cache one
    ``prefill_chunk``-token slice per serve tick, interleaved between
    decode chunks so a long prompt never monopolizes the device. Becomes a
    plain ``Prefill`` (and is inserted) once ``pos`` covers the prompt."""
    prompt: np.ndarray           # (P,) int32 host tokens
    cache: object                # batch=1 family cache, filled to ``pos``
    last_logits: Optional[jax.Array]  # (1, V_pad) after the last chunk
    pos: int                     # prompt tokens already in the cache
    match: Optional[PrefixMatch] = None  # pinned prefix-cache match (paged)

    @property
    def done(self) -> bool:
        return self.pos >= len(self.prompt)

    def as_prefill(self) -> "Prefill":
        assert self.done and self.last_logits is not None
        return Prefill(prompt=self.prompt, cache=self.cache,
                       last_logits=self.last_logits, match=self.match)


@dataclasses.dataclass
class ServeStats:
    """Continuous-batching run statistics (benchmarks/serve_throughput.py)."""
    decode_steps: int          # jitted decode steps executed (chunks * chunk)
    generated_tokens: int      # tokens actually emitted across all requests
    occupancy: float           # mean fraction of active slots per chunk
    num_chunks: int
    admissions: int            # continuous-batching refills: requests
                               # admitted while others were mid-decode
    # request latency (wall-clock; chunk-granular attribution)
    ttft_p50_s: float = 0.0    # time to first token, admission -> first chunk
    ttft_p95_s: float = 0.0    #   that contains a generated token
    tpot_p50_s: float = 0.0    # per-output-token latency after the first
    tpot_p95_s: float = 0.0
    # open-loop queueing + SLO scheduling (docs/DESIGN.md §14)
    queue_delay_p50_s: float = 0.0  # ready -> dequeue wait, SEPARATE from
    queue_delay_p95_s: float = 0.0  #   ttft (which starts at dequeue)
    preemptions: int = 0       # restart-style evictions for higher priority
    timeouts: int = 0          # requests dropped by queue timeout
    cancelled: int = 0         # requests cancelled (queued or running)
    prefill_chunks: int = 0    # chunked-prefill advances interleaved
    # per-decode-chunk wall-clock gaps while slots were running: monolithic
    # prefill of a long prompt shows up as a multi-x spike in gap_max
    decode_gap_p50_s: float = 0.0
    decode_gap_p95_s: float = 0.0
    decode_gap_max_s: float = 0.0
    # speculative decoding (spec=SpecConfig(...) engines only)
    spec_rounds: int = 0       # draft-propose/verify rounds executed
    draft_proposed: int = 0    # draft tokens proposed to live slots
    draft_accepted: int = 0    # draft tokens verified AND committed
    acceptance_rate: float = 0.0   # accepted / proposed (realized uplift)
    tokens_per_round: float = 0.0  # committed tokens per live round
    # paged KV pool (paged=... engines only; docs/DESIGN.md §13)
    pool_pages_total: int = 0      # allocatable physical pages in the pool
    pool_pages_peak: int = 0       # high-water mark of pages in use
    pool_page_size: int = 0        # tokens per page
    prefix_hits: int = 0           # admissions that reused shared pages
    prefix_hit_tokens: int = 0     # prompt tokens served from shared pages
    prefix_hit_rate: float = 0.0   # hit tokens / total prompt tokens
    cow_copies: int = 0            # COW boundary pages materialized
    kv_bytes_peak: float = 0.0     # peak physical KV bytes actually held
    # kernels/autotune.py provenance: the tune-cache key whose config the
    # engine's executables were traced under, or "untuned"
    tuned: str = "untuned"
    # fault tolerance + graceful degradation (docs/DESIGN.md §15)
    replica_restarts: int = 0      # replicas quarantined and failed over
    redriven_requests: int = 0     # in-flight requests re-driven to survivors
    recovery_p95_s: float = 0.0    # p95 wall s, failure -> survivors resumed
    watchdog_trips: int = 0        # dispatch->harvest deadline overruns
    degraded_steps: int = 0        # decode steps run below tier 0
    degrade_transitions: int = 0   # KV tier changes (spills + promotions)
    kv_tier_steps: tuple = ()      # decode steps per degradation tier
    # the registry this snapshot was reconstructed from (docs/DESIGN.md
    # §16): carries the per-priority/per-tier label breakdowns the flat
    # fields above aggregate away. Excluded from ==/repr so stats stay
    # comparable across runs.
    registry: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)

    @classmethod
    def from_registry(cls, reg) -> "ServeStats":
        """Snapshot VIEW over a published metrics registry — the field
        mapping lives in ``obs/serve_metrics.py`` (single source of
        truth; the obs tests assert two-way coverage)."""
        from repro.obs.serve_metrics import stats_fields
        return cls(registry=reg, **stats_fields(reg))


class ServeEngine:
    def __init__(self, model: Model, params, *, max_seq: int,
                 plan: Optional[QuantPlan] = None, group: int = 128,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 mesh=None, kv_precision="bf16",
                 kv_group: Optional[int] = None,
                 spec: Optional[SpecConfig] = None,
                 autotune: bool = True,
                 paged=None,
                 prefill_chunk: Optional[int] = None):
        self.model = model
        self.cfg = model.cfg
        self.max_seq = max_seq
        self.plan = plan
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.mesh = mesh
        self.spec = spec
        # chunked prefill interleaving (docs/DESIGN.md §14): serve() splits
        # prompts into prefill_chunk-token slices scheduled between decode
        # chunks. None/0 keeps the monolithic whole-prompt prefill.
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1 or None, got "
                             f"{prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        if self.cfg.is_mla and (paged or spec is not None):
            raise ValueError(
                f"{self.cfg.name}: multi-head latent attention serves from "
                f"dense slots only; the paged pool, its prefix cache and "
                f"speculative decoding do not hold its latent cache")
        # paged KV pool (docs/DESIGN.md §13): True -> defaults, or a
        # PagedConfig. Only plain K/V participates — enc-dec cross K/V is
        # per-request (frames-dependent, nothing to share) and stays in the
        # dense quantized layout; SSM families have no KV at all, so the
        # pool is inert there and the API still works.
        self.paged = (PagedConfig() if paged is True else paged) or None
        self._paged_fields = (tuple(f for f in model.kv_cache_fields
                                    if f in ("k", "v"))
                              if self.paged is not None else ())
        self.pool: Optional[PoolSession] = None  # built by init_decode_state
        self._page_bytes = 0.0
        self._seed_fns: dict = {}
        self._draft = None         # compiled lazily (plan may be set late)
        self._draft_stamp = None   # artifact manifest "draft" (from_artifact)
        if plan is not None:
            params = apply_plan_to_params(model, params, plan, group)
        if mesh is not None:
            from repro.sharding.specs import serving_param_shardings
            # TP-only placement (a no-op resharding when the params already
            # arrived sharded, e.g. from_artifact(mesh=...)).
            params = jax.device_put(params,
                                    serving_param_shardings(params, mesh))
        self.params = params
        self.kv_plan = self._resolve_kv_plan(kv_precision, kv_group)
        # kernels/autotune.py: swap in the tuned chunk/tile config (if one
        # is cached for this device/family/precision) BEFORE the jitted
        # paths below trace — every knob is read at trace time. "untuned"
        # means library defaults; the stamp lands in ServeStats and saved
        # artifact manifests for provenance.
        self.tuned = "untuned"
        if autotune:
            from repro.kernels.autotune import kv_label, maybe_apply_tuned
            self.tuned = maybe_apply_tuned(self.cfg.family,
                                           kv_label(self.kv_plan))
        self._decode = self._traced(jax.jit(model.decode_step))
        # built once, cached (enc-dec prefill also takes encoder frames)
        self._prefill = self._traced(jax.jit(self._prefill_encdec
                                             if self.cfg.family == "encdec"
                                             else self._prefill_impl))
        self._insert = self._traced(jax.jit(self._insert_impl))
        self._release = self._traced(jax.jit(self._release_impl))
        self._kv_wrap = self._traced(jax.jit(self._wrap_cache))
        self._chunk_fns: dict = {}
        self._pchunk_fn = None     # chunked-prefill advance (built lazily)
        self._gather_fn = None     # pool-rows -> dense cache seed (paged)
        self._encdec_seed_fn = None

    # -- quantized KV cache (docs/DESIGN.md §10) -----------------------------
    def _resolve_kv_plan(self, kv_precision, kv_group):
        from repro.quant.kvcache import DEFAULT_KV_GROUP, KVPlan
        if isinstance(kv_precision, KVPlan):
            if kv_group is not None and kv_group != kv_precision.group:
                raise ValueError(
                    f"kv_group={kv_group} conflicts with the provided "
                    f"KVPlan's group={kv_precision.group}; the plan's "
                    f"group is part of the (possibly artifact-stamped) "
                    f"policy — rebuild the plan to change it")
            return kv_precision
        from repro.quant.compiler import compile_kv_plan
        return compile_kv_plan(self.cfg, self.plan, kv_precision,
                               kv_group or DEFAULT_KV_GROUP)

    def _kv_cuts(self) -> tuple:
        """Page boundaries = the weight stacks' segment boundaries, so each
        cache page aligns 1:1 with a model scan segment."""
        if self.cfg.family in ("dense", "moe"):
            from repro.models.transformer import layer_segments
            return tuple(lo for _, lo, _ in layer_segments(self.params)[1:])
        if self.cfg.family != "encdec":
            return ()
        from repro.quant.apply import segment_slices
        return tuple(lo for _, lo, _ in
                     segment_slices(self.params["dec_layers"])[1:])

    def _wrap_cache(self, cache):
        """Raw (bf16) family cache -> quantized-page layout per the KV
        plan; identity when serving with a bf16 cache. Traceable — the
        engine jits it once per cache shape (``self._kv_wrap``)."""
        if self.kv_plan is None:
            return cache
        from repro.quant.kvcache import quantize_model_cache
        return quantize_model_cache(cache, self.kv_plan, self._kv_cuts(),
                                    self.model.kv_cache_fields)

    # -- mesh plumbing -------------------------------------------------------
    def _ctx(self):
        """Mesh + activation-sharding context every jitted path traces (and
        runs) under; a null context without a mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.sharding.ctx import activation_sharding
        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(activation_sharding(self.mesh))
        return stack

    def _traced(self, fn):
        """Wrap a jitted callable so tracing happens inside ``_ctx()``."""
        if self.mesh is None:
            return fn

        def wrapped(*args, **kw):
            with self._ctx():
                return fn(*args, **kw)

        def lower(*args, **kw):
            with self._ctx():
                return fn.lower(*args, **kw)

        wrapped.lower = lower
        return wrapped

    def _shard_state(self, state: B.DecodeState) -> B.DecodeState:
        return B.shard_state(state, self.mesh) if self.mesh is not None \
            else state

    @classmethod
    def from_artifact(cls, model: Model, directory: str, *, max_seq: int,
                      mesh=None, **kw) -> "ServeEngine":
        """Boot from a persisted compiled-plan artifact: quantized weights
        are restored directly — no raw weight loading, no entropy analysis,
        no re-quantization (quant/compiler.py). With ``mesh``, every leaf is
        device_put to its serving NamedSharding straight from the checkpoint
        file — a cold boot lands sharded without ever materializing a
        replicated copy."""
        from repro.quant.compiler import compile_kv_plan, load_artifact
        from repro.quant.kvcache import DEFAULT_KV_GROUP
        compiled = load_artifact(directory, model, mesh=mesh)
        if compiled.kv_plan is not None:
            # serve with the KV-cache policy stamped at compile time unless
            # the caller explicitly overrides it
            kw.setdefault("kv_precision", compiled.kv_plan)
        if kw.get("kv_precision") == "auto":
            # entropy-weighted selection needs the weight plan, which the
            # engine ctor doesn't see on this path (params arrive compiled)
            kw["kv_precision"] = compile_kv_plan(
                model.cfg, compiled.plan, "auto",
                kw.pop("kv_group", None) or DEFAULT_KV_GROUP)
        engine = cls(model, compiled.params, max_seq=max_seq, plan=None,
                     mesh=mesh, **kw)
        engine.plan = compiled.plan
        engine._draft_stamp = compiled.draft   # validated by _ensure_draft
        obs.instant("engine/from_artifact",
                    args={"directory": directory,
                          "family": model.cfg.family})
        return engine

    # -- prefill -------------------------------------------------------------
    # the prefill paths take ``params`` as an argument: a jitted function
    # that closed over them would embed every weight as a program constant
    def _prefill_scan(self, params, prompts: jax.Array, cache):
        """Universal prefill: scan decode steps over prompt tokens."""

        def body(cache, tok):
            logits, cache = self.model.decode_step(params, cache,
                                                   tok[:, None])
            return cache, logits[:, 0]

        cache, logits = jax.lax.scan(body, cache, prompts.T)
        return cache, logits[-1]  # logits after last prompt token

    def _prefill_fused(self, params, prompts: jax.Array):
        """Transformer prefill: one fused forward emitting the KV cache."""
        from repro.models import transformer
        b, s = prompts.shape
        logits, _, cache = transformer.apply(
            params, prompts, self.cfg, remat=False, return_cache=True,
            last_only=True)
        # (L, 1, s, ...) -> (L, 1, max_seq, ...): K/V (..., Hkv, hd), or
        # MLA's flat latent rows (..., r + rope)
        with jax.named_scope("kv"):
            padded = {}
            for f in self.model.kv_cache_fields:
                x = getattr(cache, f)
                pad = [(0, 0)] * x.ndim
                pad[2] = (0, self.max_seq - s)
                padded[f] = jnp.pad(x, pad)
        return cache._replace(**padded), logits[:, 0]

    def _prefill_impl(self, params, prompts: jax.Array):
        if self.cfg.family in ("dense", "moe"):
            return self._prefill_fused(params, prompts)
        return self._prefill_scan(params, prompts,
                                  self.model.init_cache(prompts.shape[0],
                                                        self.max_seq))

    def _prefill_encdec(self, params, prompts: jax.Array,
                        frames: jax.Array):
        """Enc-dec prefill: encode frames, precompute per-decoder-layer
        cross K/V, then scan decode steps over the prompt."""
        from repro.models import encdec
        cache = self.model.init_cache(prompts.shape[0], self.max_seq)
        enc_out = encdec.encode(params, frames, self.cfg, remat=False)
        ck, cv = encdec.precompute_cross_kv(params, enc_out, self.cfg)
        cache = cache._replace(cross_k=ck, cross_v=cv)
        return self._prefill_scan(params, prompts, cache)

    def _default_frames(self, batch: int) -> jax.Array:
        from repro.models.common import dtype_of
        return jnp.zeros((batch, self.cfg.encoder_seq, self.cfg.d_model),
                         dtype_of(self.cfg))

    def prefill(self, prompts: jax.Array, frames=None):
        assert prompts.shape[1] <= self.max_seq
        if self.cfg.family == "encdec":
            if frames is None:
                frames = self._default_frames(prompts.shape[0])
            assert frames.shape[1] == self.cfg.encoder_seq
            return self._prefill(self.params, prompts, frames)
        assert frames is None, "frames only apply to enc-dec models"
        return self._prefill(self.params, prompts)

    # -- paged KV pool + disaggregated API (docs/DESIGN.md §13) --------------
    def _pool_runs(self, raw) -> list:
        """Per-precision layer runs for a pool, aligned with the KV plan's
        page cuts (a single raw-dtype run when serving bf16 caches)."""
        l_total = raw.shape[0]
        if self.kv_plan is None:
            # bf16 pools still split at the weight stack's segment cuts:
            # decode scans per segment, and kv_segment hands each scan its
            # own pool (a full-stack pool would mismatch the leading axis)
            cuts = (0,) + tuple(c for c in self._kv_cuts()
                                if 0 < c < l_total) + (l_total,)
            return [("bf16", lo, hi) for lo, hi in zip(cuts[:-1], cuts[1:])]
        runs = self.kv_plan.pages(self._kv_cuts())
        assert runs[-1][2] == l_total, (runs, l_total)
        return runs

    def _paged_cache(self, num_slots: int, pool_pages: int):
        """Slotted family cache with the paged fields replaced by pools."""
        from repro.quant import paged as PG
        from repro.quant.kvcache import DEFAULT_KV_GROUP
        cache = self.model.slotted_cache(num_slots, self.max_seq)
        group = (self.kv_plan.group if self.kv_plan is not None
                 else DEFAULT_KV_GROUP)
        reps = {}
        for name in self._paged_fields:
            raw = getattr(cache, name)
            reps[name] = PG.init_pool_field(
                raw, self._pool_runs(raw), num_pages=pool_pages,
                page_size=self.paged.page_size, num_slots=num_slots,
                group=group)
        return cache._replace(**reps)

    def init_decode_state(self, num_slots: int,
                          key: Optional[jax.Array] = None) -> B.DecodeState:
        """Empty slotted decode state — the disaggregated API's entry
        point. Paged engines also (re)build the page pool and its host
        allocator here: one ``PoolSession`` per decode state, sized (by
        default) to the dense engine's reservation of
        ``num_slots * ceil(max_seq / page_size)`` pages — equal memory."""
        key = key if key is not None else jax.random.PRNGKey(0)
        cache = None
        if self._paged_fields:
            from repro.quant import paged as PG
            n_log = PG.logical_pages(self.max_seq, self.paged.page_size)
            pool_pages = self.paged.pool_pages or num_slots * n_log
            self.pool = PoolSession(pool_pages, self.paged.page_size, n_log,
                                    prefix_sharing=self.paged.prefix_sharing)
            cache = self._paged_cache(num_slots, pool_pages)
            self._page_bytes = sum(PG.page_nbytes(getattr(cache, name))
                                   for name in self._paged_fields)
        state = B.init_state(self.model, num_slots, self.max_seq, key,
                             cache=cache)
        # quantize any NON-paged KV fields (enc-dec cross K/V); pools pass
        # through untouched (quantize_model_cache skips page fields)
        state = state._replace(cache=self._kv_wrap(state.cache))
        return self._shard_state(state)

    # -- graceful degradation (docs/DESIGN.md §15) ---------------------------
    def degrade_ladder(self) -> list:
        """Entropy-ordered KV degradation tiers for this engine: tier 0 is
        the serving policy; deeper tiers spill cache precision down
        bf16→int8→int4 in the order the weight plan's entropy decisions
        (or FastEWQ, via the compiler) dictate. Empty for unpaged
        engines — degradation trades precision for pool pages."""
        if not self._paged_fields:
            return []
        from repro.quant.compiler import degrade_kv_ladder
        from repro.quant.kvcache import DEFAULT_KV_GROUP
        group = (self.kv_plan.group if self.kv_plan is not None
                 else DEFAULT_KV_GROUP)
        return degrade_kv_ladder(self.cfg, self.plan, self.kv_plan, group,
                                 cuts=self._kv_cuts())

    def apply_kv_plan(self, state: B.DecodeState, new_plan
                      ) -> Optional[B.DecodeState]:
        """Live engine-wide KV-precision transition at CONSTANT byte
        budget. Demoting (bf16→int8→int4) shrinks the page and buys
        proportionally more pages in the same bytes — exactly what
        relieves ``OutOfPages`` pressure; promoting shrinks the pool and
        is refused (returns None) while the live pages would not fit
        (cache-only prefix pages are flushed first). Every live page's
        payload is requantized in place — a demoted page holds the same
        values as if its request had been admitted at the lower tier —
        and the host allocator is rebuilt with refcounts, slot maps and
        the prefix cache remapped. Decode fns re-trace automatically on
        the new pool pytree structure."""
        from repro.quant import paged as PG
        from repro.quant.kvcache import DEFAULT_KV_GROUP
        pool = self.pool
        if pool is None or new_plan is self.kv_plan:
            return None
        num_slots = state.tokens.shape[0]
        old_plan, old_pages = self.kv_plan, pool.num_pages
        budget = old_pages * self._page_bytes
        self.kv_plan = new_plan
        try:
            proto = jax.eval_shape(
                lambda: self.model.slotted_cache(num_slots, self.max_seq))
            group = (new_plan.group if new_plan is not None
                     else DEFAULT_KV_GROUP)
            new_runs, raw_dtypes, page_bytes_new = {}, {}, 0.0
            for name in self._paged_fields:
                raw = getattr(proto, name)
                new_runs[name] = self._pool_runs(raw)
                raw_dtypes[name] = raw.dtype
                f = jax.eval_shape(
                    lambda r=raw, rs=new_runs[name]: PG.init_pool_field(
                        r, rs, num_pages=1,
                        page_size=self.paged.page_size,
                        num_slots=num_slots, group=group))
                page_bytes_new += PG.page_nbytes(f)
            new_pages = int(budget // page_bytes_new)

            def alive():
                return [pid for pid in range(1, old_pages + 1)
                        if pool._ref[pid] > 0]

            live = alive()
            if len(live) > new_pages and pool.prefix is not None:
                pool.flush_prefix()
                live = alive()
            if new_pages < 1 or len(live) > new_pages:
                self.kv_plan = old_plan
                return None
            perm = np.zeros(old_pages + 1, np.int32)
            if new_pages >= old_pages:
                perm[live] = live                      # growth: in place
            else:
                perm[live] = np.arange(1, len(live) + 1)  # compaction
            inv = np.zeros(new_pages + 1, np.int32)
            inv[perm[live]] = live

            def repack(cache):
                reps = {
                    name: PG.repack_pool_field(
                        getattr(cache, name), new_runs[name], perm=perm,
                        inv=inv, group=group, raw_dtype=raw_dtypes[name])
                    for name in self._paged_fields}
                return cache._replace(**reps)

            state = state._replace(
                cache=self._traced(jax.jit(repack))(state.cache))
        except Exception:
            self.kv_plan = old_plan
            raise
        self.pool = pool.rebuild(perm, new_pages)
        self._page_bytes = page_bytes_new
        return self._shard_state(state)

    def _slot_seq_budget(self, prompt_len: int, max_new: int) -> int:
        """Deepest cache row a request can write + 1 (spec verify probes
        ``k`` rows past the last committed token)."""
        k = self.spec.k if self.spec is not None else 0
        return min(self.max_seq, prompt_len + max_new + k)

    def _seed_fn(self, suffix_len: int):
        """Jitted prefix-hit prefill: gather the shared rows from the pool
        into a dense bf16 cache positioned at ``hit`` and scan decode steps
        over ONLY the suffix. One compile per suffix length."""
        if suffix_len not in self._seed_fns:
            model, max_seq = self.model, self.max_seq
            fields = self._paged_fields

            def seed_prefix(params, pools, row, hit, suffix):
                from repro.quant import paged as PG
                from repro.quant.kvcache import dequantize_kv
                cache = model.init_cache(1, max_seq)
                reps = {}
                for name in fields:
                    field = pools[name]
                    parts = [dequantize_kv(PG.gather_rows(pg, row),
                                           getattr(cache, name).dtype)
                             for pg in (field if isinstance(field, tuple)
                                        else (field,))]
                    full = (jnp.concatenate(parts, 0) if len(parts) > 1
                            else parts[0])
                    reps[name] = full[:, :, :max_seq]
                cache = cache._replace(pos=jnp.asarray(hit, jnp.int32),
                                       **reps)

                def body(c, tok):
                    logits, c = model.decode_step(params, c, tok[:, None])
                    return c, logits[:, 0]

                cache, logits = jax.lax.scan(body, cache, suffix.T)
                return cache, logits[-1]

            self._seed_fns[suffix_len] = self._traced(jax.jit(seed_prefix))
        return self._seed_fns[suffix_len]

    def _seed_prefill(self, prompt: np.ndarray, m: PrefixMatch, state):
        row = np.zeros(self.pool.n_log, np.int32)
        row[:len(m.full_ids)] = m.full_ids
        if m.donor is not None:
            row[len(m.full_ids)] = m.donor
        pools = {name: getattr(state.cache, name)
                 for name in self._paged_fields}
        suffix = jnp.asarray(prompt[m.hit:], jnp.int32)[None]
        fn = self._seed_fn(int(prompt.size) - m.hit)
        return fn(self.params, pools, jnp.asarray(row), jnp.int32(m.hit),
                  suffix)

    def prefill_request(self, prompt, frames=None, state=None) -> Prefill:
        """Disaggregated prefill of ONE request (1-D prompt).

        Paged engines with prefix sharing first match the prompt against
        the pool's prefix cache, PINNING any matched pages. On a hit,
        dense/MoE text requests skip the shared tokens outright: the
        seeded prefill (needs ``state`` for the pool arrays) reads the
        shared K/V back from the pool and only runs the model over the
        suffix. Other families still prefill in full (hybrid needs its
        conv/SSM state, enc-dec its frames) but the matched pages are
        still mapped — causal K/V depends only on the preceding tokens,
        so page sharing is valid for every attention family."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        match = None
        if self.pool is not None and self.pool.prefix is not None:
            match = self.pool.match(prompt)
            if (match.hit > 0 and frames is None and state is not None
                    and self.cfg.family in ("dense", "moe")):
                cache1, logits1 = self._seed_prefill(prompt, match, state)
                return Prefill(prompt=prompt, cache=cache1,
                               last_logits=logits1, match=match)
        frames_b = (jnp.asarray(frames)[None]
                    if frames is not None else None)
        cache1, logits1 = self.prefill(jnp.asarray(prompt)[None], frames_b)
        return Prefill(prompt=prompt, cache=cache1, last_logits=logits1,
                       match=match)

    # -- chunked prefill interleaving (docs/DESIGN.md §14) -------------------
    def _prefill_chunk_fn(self):
        """Jitted one-chunk prefill advance: extend a batch=1 cache by the
        chunk's tokens. Transformer/enc-dec families score the whole chunk
        in ONE multi-query decode_step (the same per-query causal-offset
        masking the spec verify window uses), so a c-token chunk costs one
        kernel launch, not c; SSM/hybrid scan single-token steps — bit-
        identical to their monolithic scan prefill (their recurrent state
        has no fused multi-token form). jit recompiles per distinct chunk
        length, which is bounded: prefill_chunk plus per-prompt remainders.
        """
        if self._pchunk_fn is None:
            model = self.model
            if self.cfg.family in ("dense", "moe", "encdec"):
                def prefill_chunk(params, cache, toks):
                    logits, cache = model.decode_step(params, cache, toks)
                    return cache, logits[:, -1]
            else:
                def prefill_chunk(params, cache, toks):
                    def body(c, tok):
                        logits, c = model.decode_step(params, c, tok[:, None])
                        return c, logits[:, 0]
                    cache, logits = jax.lax.scan(body, cache, toks.T)
                    return cache, logits[-1]
            self._pchunk_fn = self._traced(jax.jit(prefill_chunk))
        return self._pchunk_fn

    def _pool_gather_fn(self):
        """Jitted prefix-hit seed: gather the matched shared rows from the
        pool into a dense bf16 batch=1 cache positioned at ``hit`` — the
        chunked twin of ``_seed_fn``, minus the suffix scan (the chunk loop
        covers the suffix)."""
        if self._gather_fn is None:
            model, max_seq = self.model, self.max_seq
            fields = self._paged_fields

            def gather_prefix(pools, row, hit):
                from repro.quant import paged as PG
                from repro.quant.kvcache import dequantize_kv
                cache = model.init_cache(1, max_seq)
                reps = {}
                for name in fields:
                    field = pools[name]
                    parts = [dequantize_kv(PG.gather_rows(pg, row),
                                           getattr(cache, name).dtype)
                             for pg in (field if isinstance(field, tuple)
                                        else (field,))]
                    full = (jnp.concatenate(parts, 0) if len(parts) > 1
                            else parts[0])
                    reps[name] = full[:, :, :max_seq]
                return cache._replace(pos=jnp.asarray(hit, jnp.int32),
                                      **reps)

            self._gather_fn = self._traced(jax.jit(gather_prefix))
        return self._gather_fn

    def _encdec_seed(self, frames_b: jax.Array):
        """Jitted enc-dec seed for a chunked prefill: encode the frames and
        precompute the per-decoder-layer cross K/V once; the decoder-side
        prompt then enters chunk by chunk."""
        if self._encdec_seed_fn is None:
            model, max_seq = self.model, self.max_seq

            def encdec_seed(params, frames):
                from repro.models import encdec
                cache = model.init_cache(1, max_seq)
                enc_out = encdec.encode(params, frames, self.cfg,
                                        remat=False)
                ck, cv = encdec.precompute_cross_kv(params, enc_out,
                                                    self.cfg)
                return cache._replace(cross_k=ck, cross_v=cv)

            self._encdec_seed_fn = self._traced(jax.jit(encdec_seed))
        return self._encdec_seed_fn(self.params, frames_b)

    def begin_prefill(self, prompt, frames=None, state=None
                      ) -> ChunkedPrefill:
        """Start a chunked prefill (disaggregated API): returns the
        ChunkedPrefill task to be advanced with ``advance_prefill`` between
        decode chunks. Prefix-cache hits (paged dense/MoE, like
        ``prefill_request``) seed the cache from the pool's shared rows and
        only the suffix runs through the model — the match's pages stay
        PINNED for the task's lifetime (``insert`` transfers the pins;
        abandon via ``pool.unpin`` on cancellation)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        match = None
        if self.pool is not None and self.pool.prefix is not None:
            match = self.pool.match(prompt)
            if (match.hit > 0 and frames is None and state is not None
                    and self.cfg.family in ("dense", "moe")):
                row = np.zeros(self.pool.n_log, np.int32)
                row[:len(match.full_ids)] = match.full_ids
                if match.donor is not None:
                    row[len(match.full_ids)] = match.donor
                pools = {name: getattr(state.cache, name)
                         for name in self._paged_fields}
                cache = self._pool_gather_fn()(pools, jnp.asarray(row),
                                               jnp.int32(match.hit))
                return ChunkedPrefill(prompt=prompt, cache=cache,
                                      last_logits=None, pos=match.hit,
                                      match=match)
        if self.cfg.family == "encdec":
            frames_b = (jnp.asarray(frames)[None] if frames is not None
                        else self._default_frames(1))
            assert frames_b.shape[1] == self.cfg.encoder_seq
            cache = self._encdec_seed(frames_b)
        else:
            assert frames is None, "frames only apply to enc-dec models"
            cache = self.model.init_cache(1, self.max_seq)
        return ChunkedPrefill(prompt=prompt, cache=cache, last_logits=None,
                              pos=0, match=match)

    def advance_prefill(self, cp: ChunkedPrefill,
                        budget: int) -> ChunkedPrefill:
        """Run ONE prefill chunk of up to ``budget`` prompt tokens (called
        between decode chunks). Mutates and returns ``cp``."""
        assert not cp.done
        c = min(int(budget), len(cp.prompt) - cp.pos)
        toks = jnp.asarray(cp.prompt[cp.pos:cp.pos + c], jnp.int32)[None]
        cache, last = self._prefill_chunk_fn()(self.params, cp.cache, toks)
        cp.cache, cp.last_logits = cache, last
        cp.pos += c
        return cp

    def insert(self, state: B.DecodeState, slot: int, pf: Prefill,
               max_new: int, *, temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0) -> B.DecodeState:
        """Admit a prefilled request into ``slot`` (disaggregated API).

        Paged engines allocate the slot's pages here (shared prefix pages
        are mapped, not copied; the COW boundary page is materialized by
        the insert scatter) and raise ``OutOfPages`` — with the match's
        pins released and nothing leaked — when the pool cannot serve the
        request; callers should ``Scheduler.requeue`` and retry after a
        slot drains."""
        page_rows = None
        p = int(pf.prompt.size)
        if self.pool is not None:
            need = self.pool.pages_for(self._slot_seq_budget(p, max_new))
            row, wrow = self.pool.admit(slot, pf.prompt, need, pf.match)
            page_rows = (jnp.asarray(row), jnp.asarray(wrow))
        state = self._insert(state, jnp.int32(slot),
                             jnp.asarray(pf.prompt, jnp.int32), pf.cache,
                             pf.last_logits, jnp.int32(max_new),
                             jnp.float32(temperature), jnp.int32(top_k),
                             jnp.float32(top_p), page_rows)
        if self.pool is not None:
            self.pool.register(slot, pf.prompt, p)
        return state

    def decode_chunk(self, state: B.DecodeState, steps: int = DEFAULT_CHUNK):
        """Run ``steps`` jitted decode steps over every active slot
        (disaggregated API). Spec engines run ``steps`` propose/verify
        rounds and return ``(state, round_metrics)``; plain engines return
        the new state."""
        if self.spec is not None:
            return self._spec_fn(steps)(self.params, self.draft_params,
                                        state)
        return self._chunk_fn(steps)(self.params, state)

    def release(self, state: B.DecodeState, slot: int) -> B.DecodeState:
        """Evict a finished request and return its pages to the pool
        (shared pages survive while the prefix cache or other slots still
        reference them)."""
        state = self._release(state, jnp.int32(slot))
        if self.pool is not None:
            self.pool.release(int(slot))
        return state

    # -- fused chunked decode loop -------------------------------------------
    def _make_chunk_fn(self, steps: int):
        """One jitted scan over ``steps`` token positions.

        Per step: masked sampling from each slot's last logits (done or
        empty slots emit pad and do not advance), scatter the chosen token
        and its logprob at ``lengths[slot]``, update per-slot stop
        conditions, then one batched decode_step for the next logits.

        Sampling controls (temperature / top-k / top-p) ride in the state
        as TRACED per-slot vectors (serving/sampling.py), so there is
        exactly one compile per (chunk, num_slots) — changing sampling
        params never retriggers XLA compilation.
        """
        vocab = self.cfg.vocab_size
        eos_id, pad_id = self.eos_id, self.pad_id
        model = self.model

        def step(params, st, _):
            with jax.named_scope("sample"):
                lp = jax.nn.log_softmax(
                    st.last_logits[:, :vocab].astype(jnp.float32), -1)
                key, sub = jax.random.split(st.key)
                dist = S.masked_dist(lp, st.temperature, st.top_k, st.top_p)
                nxt = S.sample(sub, dist, st.temperature)
                chosen_lp = jnp.take_along_axis(lp, nxt[:, None], 1)[:, 0]
                advance = st.active & ~st.done
                nxt = jnp.where(advance, nxt, pad_id).astype(jnp.int32)
                at = (jnp.arange(st.tokens.shape[1])[None, :]
                      == st.lengths[:, None])
                write = at & advance[:, None]
                tokens = jnp.where(write, nxt[:, None], st.tokens)
                logprobs = jnp.where(write, chosen_lp[:, None], st.logprobs)
                lengths = st.lengths + advance.astype(jnp.int32)
                done = st.done | (advance & (lengths >= st.max_len))
                if eos_id is not None:
                    done = done | (advance & (nxt == eos_id))
            logits, cache = model.decode_step(params, st.cache, nxt[:, None])
            return st._replace(
                cache=cache, last_logits=logits[:, 0].astype(jnp.float32),
                tokens=tokens, lengths=lengths, done=done,
                logprobs=logprobs, key=key), None

        mesh = self.mesh

        def run(params, state):
            state, _ = jax.lax.scan(
                lambda st, x: step(params, st, x), state, None, length=steps)
            if mesh is not None:
                # pin the carry layout so chunk N+1 reuses chunk N's compile
                state = B.constrain_state(state, mesh)
            return state

        return self._traced(jax.jit(run))

    def _chunk_fn(self, steps: int):
        if steps not in self._chunk_fns:
            self._chunk_fns[steps] = self._make_chunk_fn(steps)
        return self._chunk_fns[steps]

    # -- self-speculative decoding (docs/DESIGN.md §11) ----------------------
    def _ensure_draft(self):
        """Compile the all-int4 draft lazily (engine.plan may be assigned
        after construction, e.g. ``from_artifact``)."""
        if self._draft is None:
            from repro.quant.compiler import compile_draft_plan
            draft = compile_draft_plan(self.model, self.params, self.plan,
                                       self.spec.draft_group,
                                       draft_layers=self.spec.draft_layers)
            stamp = self._draft_stamp
            if (stamp and stamp.get("group") == self.spec.draft_group
                    and stamp.get("draft_layers") == self.spec.draft_layers):
                # cold boot must re-derive the exact stamped draft; a
                # different draft_group is an explicit operator override
                if list(draft.precisions) != stamp.get("precisions"):
                    raise ValueError(
                        "artifact draft stamp mismatch: re-derived draft "
                        f"precisions {list(draft.precisions)} != stamped "
                        f"{stamp.get('precisions')} — the artifact's plan "
                        "and the serving engine's plan disagree")
            if self.mesh is not None:
                from repro.sharding.specs import serving_param_shardings
                # shared leaves are already placed (no-op); only the
                # draft-only int4 copies actually move
                draft.params = jax.device_put(
                    draft.params,
                    serving_param_shardings(draft.params, self.mesh))
            self._draft = draft
        return self._draft

    @property
    def draft_params(self):
        # the ngram draft proposes from committed context — no draft model
        # exists; the round's propose branch never reads these params
        if self.spec is not None and self.spec.draft_source == "ngram":
            return self.params
        return self._ensure_draft().params

    def draft_overhead_bytes(self) -> float:
        """Draft-only weight bytes (blocks the plan left raw/int8, re-
        quantized to int4 for the draft); everything else is shared with
        the target byte-for-byte."""
        if self.spec is not None and self.spec.draft_source == "ngram":
            return 0.0
        return float(self._ensure_draft().overhead_bytes)

    def _spec_fn(self, rounds: int):
        key = ("spec", rounds)
        if key not in self._chunk_fns:
            from repro.serving.spec import make_spec_round
            fused = (self.spec.fused_propose
                     and self.model.supports_fused_propose)
            if self.spec.draft_layers is not None and not fused:
                raise ValueError(
                    f"spec draft_layers needs the fused propose path; "
                    f"family {self.model.cfg.family!r} does not support it")
            spec_chunk = make_spec_round(
                self.model, self.spec.k, rounds, self.eos_id, self.mesh,
                fused_propose=fused, draft_source=self.spec.draft_source)
            self._chunk_fns[key] = self._traced(jax.jit(spec_chunk))
        return self._chunk_fns[key]

    def _spec_budget_check(self, prompt_len: int, max_new: int):
        """Spec verify writes k+1 cache rows starting at each slot's
        position; the deepest speculative write is ``max_len - 1 + k``,
        which must stay inside the cache."""
        need = prompt_len + max_new + self.spec.k
        assert need <= self.max_seq, \
            (f"speculative serving needs max_seq >= prompt + max_new + k "
             f"= {need} (k={self.spec.k} verify headroom); max_seq is "
             f"{self.max_seq}")

    def _insert_impl(self, state, slot, prompt, prompt_cache, last_logits,
                     max_new, temperature, top_k, top_p, page_rows=None):
        state = B.insert_request(self.model, state, slot, prompt,
                                 prompt_cache, last_logits, max_new,
                                 temperature, top_k, top_p,
                                 page_rows=page_rows)
        if self.mesh is not None:
            state = B.constrain_state(state, self.mesh)
        return state

    def _release_impl(self, state, slot):
        state = B.release_slot(state, slot)
        if self._paged_fields:
            from repro.quant import paged as PG
            reps = {name: PG.release_slot_pages(getattr(state.cache, name),
                                                slot)
                    for name in self._paged_fields}
            state = state._replace(cache=state.cache._replace(**reps))
        if self.mesh is not None:
            state = B.constrain_state(state, self.mesh)
        return state

    # -- generation (compat wrapper: single batch == one drain) ---------------
    def _batch_state(self, prompts, frames, max_new_tokens, temperature,
                     top_k, top_p, key) -> B.DecodeState:
        """Fixed-batch DecodeState for generate()'s decode modes (identical
        for spec and baseline: full-prompt prefill, ``pos == lengths`` —
        the spec loop recognizes that as a *fresh* slot)."""
        b, p = prompts.shape
        cache, last_logits = self.prefill(prompts, frames)
        cache = cache._replace(pos=jnp.full((b,), p, jnp.int32))
        # quantize-on-insert: prefill ran bf16; the decode carry is pages
        cache = self._kv_wrap(cache)
        tokens = jnp.zeros((b, self.max_seq), jnp.int32)
        tokens = jax.lax.dynamic_update_slice(
            tokens, prompts.astype(jnp.int32), (0, 0))
        return B.DecodeState(
            cache=cache, last_logits=last_logits.astype(jnp.float32),
            tokens=tokens,
            lengths=jnp.full((b,), p, jnp.int32),
            max_len=jnp.full((b,), p + max_new_tokens, jnp.int32),
            done=jnp.zeros((b,), bool),
            active=jnp.ones((b,), bool),
            logprobs=jnp.zeros((b, self.max_seq), jnp.float32),
            key=key if key is not None else jax.random.PRNGKey(0),
            temperature=jnp.full((b,), temperature, jnp.float32),
            top_k=jnp.full((b,), top_k, jnp.int32),
            top_p=jnp.full((b,), top_p, jnp.float32))

    def _slice_prefill(self, cache, i: int):
        """Batch prefill cache -> the batch=1 slice ``insert`` expects."""
        axes = self.model.cache_batch_axes

        def one(leaf, axis):
            leaf = jnp.asarray(leaf)
            if leaf.ndim == 0:      # scalar pos is shared across the batch
                return leaf
            return jax.lax.dynamic_slice_in_dim(leaf, i, 1, axis=axis)

        return type(cache)(*(one(l, a) for l, a in zip(cache, axes)))

    def _batch_state_paged(self, prompts, frames, max_new_tokens,
                           temperature, top_k, top_p, key) -> B.DecodeState:
        """generate()'s paged twin of ``_batch_state``: the SAME batched
        prefill (numerics identical to dense), then each row is admitted
        through the pool so the decode carry reads/writes pages."""
        b = prompts.shape[0]
        state = self.init_decode_state(b, key)
        cache, last_logits = self.prefill(prompts, frames)
        prompts_np = np.asarray(prompts).astype(np.int32)
        for i in range(b):
            pf = Prefill(prompt=prompts_np[i],
                         cache=self._slice_prefill(cache, i),
                         last_logits=last_logits[i:i + 1])
            state = self.insert(state, i, pf, max_new_tokens,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p)
        return state

    def generate(self, prompts: jax.Array, max_new_tokens: int,
                 temperature: float = 0.0,
                 key: Optional[jax.Array] = None,
                 chunk: Optional[int] = None,
                 frames: Optional[jax.Array] = None,
                 top_k: int = 0, top_p: float = 1.0) -> GenerateResult:
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if chunk is not None and chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        b, p = prompts.shape
        total = p + max_new_tokens
        spec = self.spec is not None
        if spec:
            self._spec_budget_check(p, max_new_tokens)
        else:
            assert total <= self.max_seq, (total, self.max_seq)
        if self._paged_fields:
            state = self._batch_state_paged(prompts, frames, max_new_tokens,
                                            temperature, top_k, top_p, key)
        else:
            state = self._batch_state(prompts, frames, max_new_tokens,
                                      temperature, top_k, top_p, key)
        state = self._shard_state(state)
        chunk = max_new_tokens if chunk is None else min(chunk, max_new_tokens)
        if spec:
            # each live round commits >= 1 token, so max_new rounds suffice
            fn = self._spec_fn(chunk)
            draft_params = self.draft_params
            rounds = 0
            while True:
                state, m = fn(self.params, draft_params, state)
                rounds += chunk
                if bool(state.done.all()) or rounds >= max_new_tokens:
                    break
            steps = rounds
        else:
            fn = self._chunk_fn(chunk)
            steps = 0
            while True:
                state = fn(self.params, state)
                steps += chunk
                if steps >= max_new_tokens or bool(state.done.all()):
                    break
        return GenerateResult(tokens=state.tokens[:, :total],
                              logprobs=state.logprobs[:, p:total],
                              steps=steps)

    def generate_stepwise(self, prompts: jax.Array, max_new_tokens: int,
                          temperature: float = 0.0,
                          key: Optional[jax.Array] = None,
                          frames: Optional[jax.Array] = None
                          ) -> GenerateResult:
        """Legacy per-token Python dispatch loop.

        Kept as the benchmark baseline (benchmarks/serve_throughput.py):
        identical math to ``generate``, but every token pays Python-side
        sampling-op dispatch plus a separate jitted decode dispatch.
        """
        b = prompts.shape[0]
        cache, last_logits = self.prefill(prompts, frames)
        toks = [prompts]
        logprobs = []
        logits = last_logits
        key = key if key is not None else jax.random.PRNGKey(0)
        for _ in range(max_new_tokens):
            lp = jax.nn.log_softmax(
                logits[:, :self.cfg.vocab_size].astype(jnp.float32), -1)
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, lp / temperature, axis=-1)
            else:
                nxt = jnp.argmax(lp, axis=-1)
            logprobs.append(jnp.take_along_axis(lp, nxt[:, None], 1)[:, 0])
            nxt = nxt[:, None].astype(jnp.int32)
            toks.append(nxt)
            step_logits, cache = self._decode(self.params, cache, nxt)
            logits = step_logits[:, 0]
        return GenerateResult(tokens=jnp.concatenate(toks, axis=1),
                              logprobs=jnp.stack(logprobs, axis=1),
                              steps=max_new_tokens)

    # -- continuous batching ---------------------------------------------------
    def serve(self, requests: Sequence[Request], *, num_slots: int = 8,
              chunk: int = DEFAULT_CHUNK, temperature: float = 0.0,
              key: Optional[jax.Array] = None,
              prefill_chunk: Optional[int] = None,
              slo: Optional["SLOConfig"] = None,
              degrade=None
              ) -> tuple[list[RequestOutput], ServeStats]:
        """Drain a request stream with continuous batching.

        Between decode chunks, finished slots are harvested and queued
        requests (arrival_step <= clock) are admitted into freed slots —
        highest priority first, FIFO within a class. Returns outputs
        ordered by request id plus occupancy/latency statistics.

        ``prefill_chunk`` (or the engine-level knob) turns on chunked
        prefill interleaving: prompts enter the cache in prefill_chunk-
        token slices scheduled between decode chunks, so a long prompt
        never stalls the running slots for its whole prefill (greedy
        output is token-identical to monolithic prefill). ``slo`` adds
        TPOT-gated admission and priority preemption (docs/DESIGN.md §14);
        request-level deadlines / timeouts / cancellation are honored
        either way.

        Per-request sampling controls (``Request.temperature/top_k/top_p``)
        override the call-level ``temperature`` default; they are traced,
        so a stream mixing greedy and nucleus requests still compiles one
        chunk fn. With ``spec=SpecConfig(...)`` each chunk runs ``chunk``
        draft-propose/verify ROUNDS (1..k+1 tokens committed per live
        round) and the stats report acceptance counters.
        """
        from repro.serving.session import ServeSession
        return ServeSession(self, requests, num_slots=num_slots,
                            chunk=chunk, temperature=temperature, key=key,
                            prefill_chunk=prefill_chunk, slo=slo,
                            degrade=degrade).run()

    # -- diagnostics -----------------------------------------------------------
    def compile_programs(self, prompt_len: int, num_slots: int,
                         chunk: int = DEFAULT_CHUNK) -> dict:
        """The engine's prefill (one ``prompt_len`` request) and plain
        decode-chunk programs, compiled for these shapes — for inspecting
        what the device runs (``as_text()``, ``memory_analysis()``). The
        decode state is abstract: nothing is allocated, and a paged
        engine's pool allocator is left as it was."""
        prompts = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
        pool, page_bytes = self.pool, self._page_bytes
        try:
            state = jax.eval_shape(lambda: self.init_decode_state(num_slots))
        finally:
            self.pool, self._page_bytes = pool, page_bytes
        prefill = self._prefill.lower(self.params, prompts)
        decode = self._make_chunk_fn(chunk).lower(self.params, state)
        return {"prefill": prefill.compile(), "decode": decode.compile()}

    def kv_bytes_per_slot(self) -> float:
        """Physical attention-cache bytes one decode slot holds at
        ``max_seq`` (K/V payloads + per-group scales; enc-dec includes the
        cross-attention cache; 0.0 for attention-free families).

        This is the per-request HBM cost that scales with
        ``num_slots x max_seq`` — the number the KV-cache quantization
        shrinks (docs/DESIGN.md §10)."""
        from repro.quant.kvcache import kv_field_nbytes
        cache = jax.eval_shape(
            lambda: self._wrap_cache(self.model.slotted_cache(1,
                                                              self.max_seq)))
        return float(sum(kv_field_nbytes(getattr(cache, name))
                         for name in self.model.kv_cache_fields))

    def _nonpaged_bytes_per_slot(self) -> float:
        """Per-slot bytes of KV fields NOT served from the pool (enc-dec
        cross K/V); 0.0 when everything is paged or there is no KV."""
        from repro.quant.kvcache import kv_field_nbytes
        names = [n for n in self.model.kv_cache_fields
                 if n not in self._paged_fields]
        if not names:
            return 0.0
        cache = jax.eval_shape(
            lambda: self._wrap_cache(self.model.slotted_cache(1,
                                                              self.max_seq)))
        return float(sum(kv_field_nbytes(getattr(cache, n)) for n in names))

    def kv_bytes_allocated(self, num_slots: int = 1) -> float:
        """Physical attention-cache bytes actually held right now.

        Dense engines reserve every slot at full depth up front, so this
        is just ``num_slots * kv_bytes_per_slot()``. Paged engines charge
        only the pool pages currently referenced (shared prefix pages
        counted ONCE — that is the whole point) plus the dense reservation
        of any non-paged KV fields (enc-dec cross K/V)."""
        if self.pool is None:
            return num_slots * self.kv_bytes_per_slot()
        return (self.pool.pages_in_use * self._page_bytes
                + num_slots * self._nonpaged_bytes_per_slot())

    @staticmethod
    def _tree_weight_bytes(params) -> float:
        from repro.quant.apply import tree_nbytes
        from repro.quant.apply import SegmentedParams
        total = 0.0
        for v in jax.tree.leaves(
                params,
                is_leaf=lambda x: isinstance(x, SegmentedParams)):
            if isinstance(v, SegmentedParams):
                total += v.nbytes_effective()
            else:
                total += tree_nbytes(v)
        return total

    def weight_bytes(self) -> float:
        return self._tree_weight_bytes(self.params)

    def draft_weight_bytes(self) -> float:
        """Effective bytes ONE draft decode step reads (shared int4
        payloads + draft-only copies) — the numerator of the
        weight-bytes-per-committed-token uplift estimate: decode is
        weight-bytes-bound, so spec serving reads
        ``(target + k * draft) / tokens_per_round`` bytes per token vs
        ``target`` for the baseline."""
        if self.spec is not None and self.spec.draft_source == "ngram":
            return 0.0
        return self._tree_weight_bytes(self.draft_params)

    def weight_bytes_per_device(self) -> float:
        """Max physical weight bytes resident on any single device.

        Counts each leaf's addressable shards per device (a replicated leaf
        contributes its full size to every device; a TP-sharded one only its
        slice), so on a 1xN TP mesh this is what actually bounds HBM —
        the deployment-memory number the mesh benchmark rows report."""
        per_device: dict = {}
        for leaf in jax.tree.leaves(self.params):
            if isinstance(leaf, jax.Array):
                for s in leaf.addressable_shards:
                    dev = s.device.id
                    per_device[dev] = per_device.get(dev, 0.0) + s.data.nbytes
            else:
                arr = np.asarray(leaf)
                per_device[-1] = per_device.get(-1, 0.0) + arr.nbytes
        return max(per_device.values()) if per_device else 0.0
