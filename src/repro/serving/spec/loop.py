"""Self-speculative decode loop: draft-propose / target-verify rounds.

One spec round, entirely on device (no host round-trips mid-chunk; the
engine scans ``chunk`` rounds inside ONE jitted call):

1. **Propose** — the all-int4 draft (shares payloads with the target for
   already-aggressive blocks) runs K single-token decode steps starting
   from each slot's *pending* token, sampling K proposals from its masked
   distribution q. The draft reads and writes a throwaway functional copy
   of the SAME cache — its writes are discarded, so no draft-side KV
   memory, no draft prefill, no cache-sync protocol. (With
   ``draft_source="ngram"`` the proposals instead come from prompt
   lookup — no draft model runs at all; q becomes the one-hot of the
   copied tokens.)
2. **Verify** — the target scores the (K+1)-token window
   ``[pending, x_1..x_K]`` in one multi-query decode pass
   (``Model.spec_verify`` — fused causal-offset attention for
   transformer/enc-dec; a checkpointing scan for SSM/hybrid), yielding the
   target distribution p_i for every draft position plus the bonus
   position.
3. **Accept** — greedy slots accept the longest prefix with
   ``x_i == argmax p_i`` (token-identical to the non-spec engine by
   construction); sampling slots run standard speculative rejection
   sampling (accept w.p. min(1, p_i(x)/q_i(x)); on first rejection
   resample from the normalized residual ``max(p - q, 0)``; bonus token
   from p_{K+1} when everything is accepted). Each live slot commits
   between 1 and K+1 tokens per round — never fewer than the baseline.
4. **Rollback/commit** — ``Model.spec_commit`` moves each slot's
   ``cache_pos`` to its committed length (rows past it stay in memory,
   masked invalid — position arithmetic over raw or quantized KVPages)
   and selects the per-slot SSM state snapshot where the family carries
   sequential summaries.

Invariant between rounds (per slot): ``cache_pos == lengths - 1`` and the
*pending* token ``tokens[lengths - 1]`` — the newest committed token —
has no cache row yet; the next round's verify writes it. Admission is
EXACTLY the baseline's (full-prompt prefill, ``cache_pos == lengths``):
such *fresh* slots have no row gap but no post-pending distribution
either, so their first round takes the candidate-0 distribution from the
slot's ``last_logits`` (the bf16 prefill logits — bit-identical to what
the baseline samples its first token from, which is what makes greedy
parity EXACT even over a quantized KV cache) and verifies the window
``[x_1..x_K]`` instead of ``[pending, x_1..x_K]``. Freshness is derived,
not stored: ``cache_pos == lengths`` iff the slot was admitted and has
not committed a spec round yet.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.serving import batch as B
from repro.serving.sampling import masked_dist, sample

NEG_INF = -1e30
_TINY = 1e-38


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Self-speculative serving knobs.

    ``k`` — draft tokens proposed per round (the verify window is k+1
    positions wide). ``draft_group`` — quantization group for the
    draft-only int4 copies of raw/int8 blocks. ``fused_propose`` — run the
    draft through the read-only fused propose path (zero draft-side cache
    writes, docs/DESIGN.md §12) on families that support it; the two-pass
    throwaway-cache propose is the fallback (and the parity oracle).
    ``draft_layers`` — truncate the draft to the first N layers (early-exit
    drafting; verification keeps greedy output exact regardless of draft
    quality). Requires ``fused_propose`` and a dense/MoE family.
    ``draft_source`` — "model" runs the int4 self-draft; "ngram" proposes
    by prompt lookup (match the context's trailing bigram, copy the k
    tokens that followed it): zero draft-side model calls, so a round
    costs ~one fused multi-query verify step — the regime where spec pays
    off even on a FLOPs-bound backend. Verification is identical either
    way, so greedy output never depends on the draft source."""
    k: int = 4
    draft_group: int = 128
    fused_propose: bool = True
    draft_layers: int | None = None
    draft_source: str = "model"

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        if self.draft_source not in ("model", "ngram"):
            raise ValueError(f"draft_source must be 'model' or 'ngram', "
                             f"got {self.draft_source!r}")
        if self.draft_source == "ngram" and self.draft_layers is not None:
            raise ValueError("draft_layers only applies to the model "
                             "draft; the ngram draft runs no model")
        if self.draft_layers is not None:
            if self.draft_layers < 1:
                raise ValueError(f"draft_layers must be >= 1, got "
                                 f"{self.draft_layers}")
            if not self.fused_propose:
                raise ValueError(
                    "draft_layers needs fused_propose=True: the two-pass "
                    "propose runs the draft through decode_step, whose "
                    "cache segmentation must match the full target stack")


def obs_labels(cfg: SpecConfig) -> dict:
    """Metric labels for the spec counters (obs/serve_metrics.py): the
    two knobs that change the acceptance/throughput trade-off."""
    return {"k": str(cfg.k), "source": cfg.draft_source}


class SpecMetrics(NamedTuple):
    """Per-chunk device-side counters (summed over rounds and slots)."""
    proposed: jax.Array    # draft tokens proposed to live slots
    accepted: jax.Array    # draft tokens verified AND committed
    committed: jax.Array   # tokens committed (incl. bonus/correction)
    rounds: jax.Array      # rounds with at least one live slot

    @staticmethod
    def zeros() -> "SpecMetrics":
        z = jnp.zeros((), jnp.int32)
        return SpecMetrics(z, z, z, z)


def spec_round(model, params, draft_params, state: B.DecodeState, k: int,
               eos_id, *, fused_propose: bool = False,
               draft_source: str = "model"
               ) -> tuple[B.DecodeState, SpecMetrics]:
    """One draft-propose / target-verify / accept / rollback round."""
    vocab = model.cfg.vocab_size
    b = state.num_slots
    live = state.active & ~state.done
    # fresh = just admitted (baseline-style full prefill): no pending row
    # gap, candidate-0 dist comes from the slot's prefill last_logits
    fresh = state.cache.pos == state.lengths
    pend_idx = jnp.clip(state.lengths - 1, 0, None)
    pending = jnp.take_along_axis(state.tokens, pend_idx[:, None], 1)[:, 0]
    key, pkey, ukey, zkey = jax.random.split(state.key, 4)

    # -- 1) draft propose: K single-token steps ----------------------------
    # (fresh slots process their last prompt token once more, at pos ==
    # lengths — a slightly stale q on the admission round only; q is the
    # proposal distribution, so this affects acceptance, never correctness)
    def draft_dist(logits):
        lp = jax.nn.log_softmax(
            logits[:, 0, :vocab].astype(jnp.float32), -1)
        return masked_dist(lp, state.temperature, state.top_k, state.top_p)

    if draft_source == "ngram":
        # prompt-lookup propose: match the trailing bigram [prev, pending]
        # against earlier committed context and copy the k tokens that
        # followed the latest match. The proposal is a contiguous slice of
        # tokens that already exist — no sequential draft dependency, no
        # model call — so the whole round costs ~one multi-query verify.
        # q is the one-hot of the copied tokens: stochastic slots accept
        # x_i w.p. p_i(x_i) and resample from clip(p - onehot, 0) on
        # rejection — exact speculative sampling with a deterministic q.
        toks = state.tokens
        L = toks.shape[1]
        prev_idx = jnp.clip(state.lengths - 2, 0, None)
        prev = jnp.take_along_axis(toks, prev_idx[:, None], 1)[:, 0]
        pos = jnp.arange(L)[None, :]
        shifted = jnp.concatenate([toks[:, :1], toks[:, :-1]], axis=1)
        hit = ((toks == pending[:, None]) & (shifted == prev[:, None])
               & (pos >= 1) & (pos < (state.lengths - 1)[:, None]))
        j = jnp.max(jnp.where(hit, pos, -1), axis=1)       # (B,) -1 = miss
        src = j[:, None] + 1 + jnp.arange(k)[None, :]      # (B, K)
        x = jnp.take_along_axis(toks, jnp.clip(src, 0, L - 1), 1)
        # miss, or the match runs off the committed context: fall back to
        # re-proposing the pending token — verification rejects a bad
        # proposal for free and the round still commits >= 1 token
        valid = (j[:, None] >= 0) & (src < state.lengths[:, None])
        x = jnp.where(valid, x, pending[:, None]).astype(jnp.int32)
        q_bt = jnp.where(jax.nn.one_hot(x, vocab, dtype=bool),
                         0.0, NEG_INF).astype(jnp.float32)
    elif fused_propose:
        # fused path (docs/DESIGN.md §12): the draft reads the cache and
        # writes each step's k/v into small raw side buffers swept by the
        # SAME online softmax — no throwaway cache copy, no k*L
        # quantize-and-scatter writes. The buffers span the draft's layer
        # count, which may be a truncated prefix of the target's.
        from repro.models.common import dtype_of
        from repro.quant.apply import segment_slices
        cfg = model.cfg
        n_draft = segment_slices(draft_params["layers"])[-1][2]
        buf_shape = (n_draft, b, k, cfg.num_kv_heads, cfg.head_dim)
        fk0 = jnp.zeros(buf_shape, dtype_of(cfg))
        fv0 = jnp.zeros(buf_shape, dtype_of(cfg))

        def propose_body(carry, sub):
            fk, fv, cnt, tok = carry
            logits, fk, fv = model.draft_propose_step(
                draft_params, state.cache, fk, fv, cnt, tok[:, None])
            q = draft_dist(logits)
            nxt = sample(sub, q, state.temperature)
            return (fk, fv, cnt + 1, nxt), (nxt, q)

        _, (xs, qlps) = jax.lax.scan(
            propose_body, (fk0, fv0, jnp.int32(0), pending),
            jax.random.split(pkey, k))
    else:
        def propose_body(carry, sub):
            dcache, tok = carry
            logits, dcache = model.decode_step(draft_params, dcache,
                                               tok[:, None])
            q = draft_dist(logits)
            nxt = sample(sub, q, state.temperature)
            return (dcache, nxt), (nxt, q)

        _, (xs, qlps) = jax.lax.scan(propose_body, (state.cache, pending),
                                     jax.random.split(pkey, k))
    if draft_source != "ngram":
        x = xs.T                                          # (B, K)
        q_bt = jnp.moveaxis(qlps, 0, 1)                   # (B, K, V)

    # -- 2) target verify: one multi-query pass over the window ------------
    # stale slots rewrite their pending row first; fresh slots start at x_1
    # (their trailing window slot is a duplicate whose row/dist are unused)
    stale_q = jnp.concatenate([pending[:, None], x], axis=1)
    fresh_q = jnp.concatenate([x, x[:, -1:]], axis=1)
    qtoks = jnp.where(fresh[:, None], fresh_q, stale_q).astype(jnp.int32)
    logits, snap = model.spec_verify(params, state.cache, qtoks)
    lv = jax.nn.log_softmax(
        logits[:, :, :vocab].astype(jnp.float32), -1)     # (B, K+1, V)
    # candidate-j dist: stale = after qtoks[j]; fresh = prefill last_logits
    # for j=0 (EXACTLY what the baseline samples its first token from —
    # greedy parity over quantized caches hinges on this), then after x_j
    lp0 = jax.nn.log_softmax(
        state.last_logits[:, :vocab].astype(jnp.float32), -1)
    lp_raw = jnp.where(
        fresh[:, None, None],
        jnp.concatenate([lp0[:, None], lv[:, :k]], axis=1), lv)
    p = masked_dist(lp_raw, state.temperature[:, None],
                    state.top_k[:, None], state.top_p[:, None])

    # -- 3) longest-prefix acceptance + rejection resampling --------------
    y = jnp.argmax(p, axis=-1).astype(jnp.int32)          # (B, K+1)
    px = jnp.take_along_axis(p[:, :k], x[..., None], -1)[..., 0]
    qx = jnp.take_along_axis(q_bt, x[..., None], -1)[..., 0]
    u = jax.random.uniform(ukey, x.shape)
    stoch_acc = jnp.log(jnp.maximum(u, _TINY)) < (px - qx)  # u < p/q
    greedy_acc = x == y[:, :k]
    acc = jnp.where((state.temperature > 0)[:, None], stoch_acc, greedy_acc)
    a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)  # (B,)

    # correction (a < K) / bonus (a == K) token from the residual at a
    pa = jnp.take_along_axis(p, a[:, None, None], 1)[:, 0]          # (B, V)
    q_ext = jnp.concatenate(
        [q_bt, jnp.full((b, 1, vocab), NEG_INF, q_bt.dtype)], axis=1)
    qa = jnp.take_along_axis(q_ext, a[:, None, None], 1)[:, 0]
    resid = jnp.clip(jnp.exp(pa) - jnp.exp(qa), 0.0, None)
    rsum = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(rsum > 0, resid / jnp.maximum(rsum, _TINY),
                      jnp.exp(pa))
    z_st = jax.random.categorical(zkey, jnp.log(resid + _TINY), axis=-1)
    z_gr = jnp.take_along_axis(y, a[:, None], 1)[:, 0]
    z = jnp.where(state.temperature > 0, z_st, z_gr).astype(jnp.int32)

    # committed candidates: x_1..x_a then the correction/bonus z
    jidx = jnp.arange(k + 1)[None, :]
    x_pad = jnp.concatenate([x, x[:, -1:]], axis=1)
    cand = jnp.where(jidx == a[:, None], z[:, None], x_pad)
    # chosen-token logprobs under the UNMASKED target dist — exactly what
    # the baseline chunk loop records
    cand_lp = jnp.take_along_axis(lp_raw, cand[..., None], -1)[..., 0]

    # -- 4) commit count: acceptance, token budget, first EOS -------------
    budget = jnp.clip(state.max_len - state.lengths, 0, None)
    c = jnp.minimum(a + 1, budget)
    if eos_id is not None:
        is_eos = cand == eos_id
        eos_cut = jnp.where(is_eos.any(1),
                            jnp.argmax(is_eos, axis=1) + 1, k + 1)
        c = jnp.minimum(c, eos_cut)
    c = jnp.where(live, c, 0).astype(jnp.int32)

    state2 = B.commit_tokens(state, cand, cand_lp, c)
    done = state.done | (live & (state2.lengths >= state.max_len))
    if eos_id is not None:
        done = done | (live & (is_eos & (jidx < c[:, None])).any(1))

    # rows/state to keep: fresh slots never fed their pending token, so the
    # cache advances one row less than the commit count (the last committed
    # token becomes the next round's pending — invariant pos = lengths - 1)
    rows = jnp.maximum(c - fresh.astype(jnp.int32), 0)
    cache2 = model.spec_commit(snap, rows)
    state2 = state2._replace(cache=cache2, done=done, key=key)

    live32 = live.astype(jnp.int32)
    # draft tokens actually COMMITTED: the last committed candidate is the
    # correction/bonus (not a draft token) only when nothing cut the window
    # short (c == a+1); acceptance_rate therefore predicts the realized
    # bytes-per-token uplift, not the pre-truncation verifier verdicts
    drafts_committed = c - (c > a).astype(jnp.int32)
    metrics = SpecMetrics(
        proposed=jnp.sum(live32) * k,
        accepted=jnp.sum(jnp.where(live, drafts_committed, 0)),
        committed=jnp.sum(c),
        rounds=jnp.any(live).astype(jnp.int32))
    return state2, metrics


def make_spec_round(model, k: int, rounds: int, eos_id, mesh=None,
                    fused_propose: bool = False,
                    draft_source: str = "model"):
    """Build the body the engine jits: ``rounds`` spec rounds in one scan
    (per-slot rollback stays inside the scan — no host sync mid-chunk)."""

    def spec_chunk(params, draft_params, state: B.DecodeState):
        def body(carry, _):
            st, m = carry
            st2, m2 = spec_round(model, params, draft_params, st, k, eos_id,
                                 fused_propose=fused_propose,
                                 draft_source=draft_source)
            return (st2, jax.tree.map(jnp.add, m, m2)), None

        (state, metrics), _ = jax.lax.scan(
            body, (state, SpecMetrics.zeros()), None, length=rounds)
        if mesh is not None:
            state = B.constrain_state(state, mesh)
        return state, metrics

    return spec_chunk
