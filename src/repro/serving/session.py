"""Serve-loop state machine: continuous batching with chunked prefill
interleaving and SLO-aware scheduling (docs/DESIGN.md §14).

``ServeSession`` owns everything one ``ServeEngine.serve`` run carries
between decode chunks — the scheduler, the slotted DecodeState, in-flight
chunked prefills, the decode-step clock and the latency accounting. One
serve *tick* is split into two phases:

* ``dispatch()``: host-side policy + device-side launches, NO blocking
  reads — expire/cancel/deadline sweeps, SLO preemption, admissions
  (monolithic prefill+insert, or reserve + chunked-prefill start),
  advancing one interleaved prefill chunk, then launching the next jitted
  decode chunk (JAX dispatch is async, so the chunk runs while the host
  moves on);
* ``harvest()``: the only device_get — read done/lengths from the chunk
  ``dispatch`` launched, mark first tokens, complete finished slots.

The split exists for DP replica serving (serving/replica.py): a router
dispatches EVERY replica's chunk before harvesting ANY of them, so the
replicas' device work overlaps instead of serializing behind each
other's blocking reads. Single-engine ``serve()`` just calls both phases
back to back — byte-identical behavior to the old inline loop.

Chunked prefill (Sarathi/SplitFuse-style): with ``prefill_chunk`` set,
an admitted request first RESERVES its slot and its prompt enters the
batch=1 prefill cache one chunk per tick, interleaved between decode
chunks, so a 2048-token prompt no longer stalls 15 running slots for its
whole prefill. The decode-step clock does NOT advance on prefill-only
ticks, keeping arrival_step semantics identical to monolithic serving.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro import obs
from repro.serving import chaos
from repro.serving.pool import OutOfPages
from repro.serving.scheduler import Request, Scheduler, SLOConfig


@dataclasses.dataclass(frozen=True)
class DegradeConfig:
    """Graceful-degradation policy under pool pressure (DESIGN.md §15).

    ``policy="ewq"`` spills the engine's KV precision down its entropy-
    ordered tier ladder (``ServeEngine.degrade_ladder``, grounded in the
    weight plan's / FastEWQ's layer-level decisions) when admission
    backpressure persists for ``patience`` consecutive ticks — each tier
    repacks the pool at constant bytes, so lower precision buys more
    pages — and promotes one tier back after ``cooldown`` stall-free
    ticks with at least ``headroom`` of the pool free. ``shrink_spec``
    additionally drops speculative decoding while degraded (draft rounds
    probe extra cache rows per slot)."""
    policy: str = "ewq"
    patience: int = 2
    cooldown: int = 16
    headroom: float = 0.5
    shrink_spec: bool = True


class ServeSession:
    """One continuous-batching run over a fixed request list."""

    def __init__(self, engine, requests, *, num_slots: int, chunk: int,
                 temperature: float = 0.0, key=None,
                 prefill_chunk: Optional[int] = None,
                 slo: Optional[SLOConfig] = None, replica_id: int = 0,
                 degrade: Optional[DegradeConfig] = None,
                 watchdog_s: Optional[float] = None):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if prefill_chunk is None:
            prefill_chunk = engine.prefill_chunk
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1 or None, got "
                             f"{prefill_chunk}")
        self.engine = engine
        self.chunk = chunk
        self.num_slots = num_slots
        self.temperature = temperature
        self.prefill_chunk = prefill_chunk
        self.slo = slo
        self.spec = engine.spec is not None
        self.sched = Scheduler(num_slots)
        # telemetry (docs/DESIGN.md §16): stamp the replica id on every
        # emitter BEFORE the first submit so request spans / pool instants
        # land on this replica's trace process from the start
        self.replica_id = replica_id
        self.sched.pid = replica_id
        if engine.pool is not None:
            engine.pool.pid = replica_id
        _tr = obs.tracer()
        if _tr is not None:
            _tr.set_process_name(replica_id, f"replica{replica_id}")
        for r in requests:
            if self.spec:
                engine._spec_budget_check(len(r.prompt), r.max_new_tokens)
            else:
                assert len(r.prompt) + r.max_new_tokens <= engine.max_seq, \
                    r.rid
            self.sched.submit(r)
        self.state = engine.init_decode_state(
            num_slots, key if key is not None else jax.random.PRNGKey(0))
        if self.spec:
            self.fn = engine._spec_fn(chunk)
            self.draft_params = engine.draft_params
        else:
            self.fn = engine._chunk_fn(chunk)
        self.clock = 0
        self.occupancy: list[float] = []
        self.admissions = 0
        self.generated = 0
        self.prefill_chunks = 0
        self.spec_m = {"proposed": 0, "accepted": 0, "committed": 0,
                       "rounds": 0}
        self.tasks: dict = {}          # slot -> ChunkedPrefill (reserved)
        self.gaps: list[float] = []    # wall seconds per decode chunk
        self._chunk_t0: Optional[float] = None
        self._pending_spec = None
        self._dispatched = False
        # fault tolerance + graceful degradation (docs/DESIGN.md §15)
        self.watchdog_s = watchdog_s
        self.watchdog_trips = 0
        self.degrade = degrade if engine.pool is not None else None
        self._ladder = (engine.degrade_ladder() if self.degrade is not None
                        else [engine.kv_plan])
        self.tier = 0
        self.tier_steps = [0] * max(1, len(self._ladder))
        self.degraded_steps = 0
        self.transitions: list = []    # (clock, from_tier, to_tier)
        self._stall_ticks = 0
        self._calm_ticks = 0

    # -- progress ------------------------------------------------------------
    @property
    def done(self) -> bool:
        return self.sched.all_done()

    # -- tick phase 1: policy + launches --------------------------------------
    def dispatch(self) -> None:
        """Admissions, SLO enforcement, one interleaved prefill chunk, and
        the next decode-chunk launch. Never blocks on device results."""
        pf = obs.profile()
        if pf is not None:
            pf.tick(self.clock)
        with obs.span("serve/dispatch", self.replica_id):
            self._dispatch()

    def _dispatch(self) -> None:
        eng, sched = self.engine, self.sched
        self._dispatched = False
        # chaos sites fire BEFORE any state mutation, so a transient fault
        # can retry this tick in place (serving/chaos.py)
        chaos.fire("replica.dispatch", tag=self.replica_id)
        chaos.fire("device.stall", tag=self.replica_id)
        now = time.perf_counter()
        with obs.span("serve/policy", self.replica_id):
            sched.poll(self.clock, now)
            sched.expire(self.clock)
            self._enforce_running_drops()
            self._preempt_for_priority()
        stalled = self._admit(now)
        with obs.span("serve/policy", self.replica_id):
            degraded = self._degrade_tick(stalled)
        if degraded and stalled:
            stalled = self._admit(now)   # lower tier freed pages: retry now
        self._advance_prefills()
        if sched.num_active == 0:
            if self.tasks:
                return                 # prefill-only tick; clock frozen
            if stalled:
                if (self.degrade is not None
                        and self.tier + 1 < len(self._ladder)
                        and self._transition(self.tier + 1)):
                    return             # spilled a tier: re-admit next tick
                raise OutOfPages(
                    "admission deadlock: no active slots and the pool "
                    "cannot supply the next request's pages "
                    f"({eng.pool.num_pages} pages of "
                    f"{eng.pool.page_size} tokens) — size pool_pages "
                    "for the longest request")
            nxt = sched.next_arrival()
            if nxt is not None:
                self.clock = max(self.clock + 1, nxt)  # idle: fast-forward
            return
        self.occupancy.append(sched.num_active / self.num_slots)
        self._chunk_t0 = time.perf_counter()
        use_spec = self.spec and not (
            self.tier > 0 and self.degrade is not None
            and self.degrade.shrink_spec)
        with obs.span("serve/launch", self.replica_id):
            if use_spec:
                self.state, self._pending_spec = self.fn(
                    eng.params, self.draft_params, self.state)
            else:
                fn = self.fn if not self.spec else eng._chunk_fn(self.chunk)
                self.state = fn(eng.params, self.state)
        self.clock += self.chunk
        self.tier_steps[self.tier] += self.chunk
        if self.tier:
            self.degraded_steps += self.chunk
        self._dispatched = True

    # -- graceful degradation (docs/DESIGN.md §15) ----------------------------
    def _degrade_tick(self, stalled: bool) -> bool:
        """Tier policy, one decision per tick: persistent backpressure
        spills down the ladder, sustained headroom promotes back up.
        Returns True when a transition happened."""
        if self.degrade is None or len(self._ladder) < 2:
            return False
        if stalled:
            self._stall_ticks += 1
            self._calm_ticks = 0
            if (self._stall_ticks >= self.degrade.patience
                    and self.tier + 1 < len(self._ladder)):
                return self._transition(self.tier + 1)
            return False
        self._stall_ticks = 0
        if self.tier == 0:
            return False
        pool = self.engine.pool
        if pool.pages_free / pool.num_pages < self.degrade.headroom:
            self._calm_ticks = 0
            return False
        self._calm_ticks += 1
        if self._calm_ticks >= self.degrade.cooldown:
            return self._transition(self.tier - 1)
        return False

    def _transition(self, tier: int) -> bool:
        """Repack the engine's pool at the target tier (False when the
        engine refuses — promotion without room for the live pages)."""
        tr = obs.tracer()
        t0 = tr.now_us() if tr is not None else 0.0
        state = self.engine.apply_kv_plan(self.state, self._ladder[tier])
        if state is None:
            return False
        self.state = state
        if tr is not None:
            tr.complete("engine/apply_kv_plan", t0, self.replica_id,
                        args={"from_tier": self.tier, "to_tier": tier})
        obs.instant("degrade/transition", self.replica_id,
                    args={"from_tier": self.tier, "to_tier": tier,
                          "clock": self.clock})
        self.transitions.append((self.clock, self.tier, tier))
        self.tier = tier
        self._stall_ticks = 0
        self._calm_ticks = 0
        return True

    # -- tick phase 2: the only blocking read ----------------------------------
    def harvest(self) -> None:
        """Read back the chunk ``dispatch`` launched and complete slots."""
        with obs.span("serve/harvest", self.replica_id):
            self._harvest()

    def _harvest(self) -> None:
        if not self._dispatched:
            return
        chaos.fire("replica.harvest", tag=self.replica_id)
        self._dispatched = False
        eng, sched = self.engine, self.sched
        with obs.span("serve/readback", self.replica_id):
            if self._pending_spec is not None:
                delta = {k_: int(v)
                         for k_, v in self._pending_spec._asdict().items()}
                for k_, v in delta.items():
                    self.spec_m[k_] += v
                self._pending_spec = None
                obs.instant("spec/round", self.replica_id, obs.DECODE_TRACK,
                            args=delta)
            done_np, len_np = jax.device_get((self.state.done,
                                              self.state.lengths))
        now = time.perf_counter()
        if self._chunk_t0 is not None:
            gap = now - self._chunk_t0
            self.gaps.append(gap)
            tr = obs.tracer()
            if tr is not None:
                tr.complete("decode/chunk", tr.now_us() - gap * 1e6,
                            self.replica_id, obs.DECODE_TRACK,
                            args={"steps": self.chunk, "tier": self.tier,
                                  "tuned": eng.tuned})
            if self.watchdog_s is not None and gap > self.watchdog_s:
                # dispatch->harvest deadline overrun: an in-process stall
                # cannot be preempted, so it is surfaced (ServeStats
                # watchdog_trips) rather than aborted mid-read
                self.watchdog_trips += 1
        for slot, req in sched.active_slots():
            if len_np[slot] > len(req.prompt):
                sched.mark_first_token(slot, now)
            if not done_np[slot]:
                continue
            self._complete_slot(slot, req, int(len_np[slot]))

    def _complete_slot(self, slot: int, req: Request, n: int,
                       reason: Optional[str] = None) -> None:
        eng, sched = self.engine, self.sched
        with obs.span("serve/complete", self.replica_id):
            row = np.asarray(jax.device_get(self.state.tokens[slot, :n]))
            lps = np.asarray(jax.device_get(
                self.state.logprobs[slot, len(req.prompt):n]))
            if reason is None:
                reason = ("eos" if eng.eos_id is not None and n > 0
                          and row[-1] == eng.eos_id else "length")
            sched.complete(slot, row, lps, reason, self.clock)
            with obs.span("serve/release", self.replica_id):
                self.state = eng.release(self.state, slot)
        self.generated += n - len(req.prompt)

    # -- SLO enforcement -------------------------------------------------------
    def _enforce_running_drops(self) -> None:
        """Cancellation / deadline sweep over reserved and decoding slots:
        the request finalizes (running aborts keep their partial tokens)
        and the slot + pool pages free leak-free."""
        eng, sched = self.engine, self.sched
        for slot, req in sched.reserved_slots():
            reason = sched.drop_reason(req, self.clock)
            if reason is None:
                continue
            task = self.tasks.pop(slot, None)
            if task is not None and task.match is not None \
                    and eng.pool is not None:
                eng.pool.unpin(task.match)
            sched.drop_reserved(slot, reason, self.clock)
        drops = [(slot, req, sched.drop_reason(req, self.clock))
                 for slot, req in sched.active_slots()
                 if sched.drop_reason(req, self.clock) is not None]
        if not drops:
            return
        len_np = jax.device_get(self.state.lengths)
        for slot, req, reason in drops:
            self._complete_slot(slot, req, int(len_np[slot]), reason=reason)

    def _preempt_for_priority(self) -> None:
        """Restart-style preemption: a strictly-higher-priority waiter may
        evict the lowest-priority decoding slot (its pages return through
        ``PoolSession.release``; the victim requeues and prefills again).
        Gated behind ``SLOConfig.preempt``."""
        if self.slo is None or not self.slo.preempt:
            return
        sched = self.sched
        while not sched.free_slots():
            head = sched.peek_ready(self.clock)
            if head is None:
                return
            victim = sched.preempt_victim(head.priority)
            if victim is None:
                return
            self.state = self.engine.release(self.state, victim)
            sched.preempt(victim)

    def _admission_gated(self, req: Request, now: float) -> bool:
        """TPOT-percentile admission gate: defer NEW work while running
        slots' measured per-token latency (rolling mean over the last
        ``admit_window`` chunks) exceeds the target. Priority-0 requests
        and requests already past their TTFT target are never deferred."""
        slo = self.slo
        if slo is None or slo.tpot_target_s is None or req.priority == 0:
            return False
        if self.sched.num_active == 0:
            return False    # never starve an idle engine
        if slo.ttft_target_s is not None:
            rw = self.sched.ready_wall(req.rid)
            if rw is not None and now - rw >= slo.ttft_target_s:
                return False
        window = self.gaps[-slo.admit_window:]
        if not window:
            return False
        return (sum(window) / len(window)) / self.chunk > slo.tpot_target_s

    # -- admissions --------------------------------------------------------------
    def _admit(self, now: float) -> bool:
        """Fill free slots from the ready queue. Returns True when pool
        backpressure stalled an admission (deadlock detection)."""
        eng, sched = self.engine, self.sched
        for slot in sched.free_slots():
            head = sched.peek_ready(self.clock)
            if head is None or self._admission_gated(head, now):
                break
            req = sched.next_ready(self.clock)
            if req is None:
                break
            if eng.pool is not None and (
                    chaos.deny("pool.oom", tag=self.replica_id)
                    or not eng.pool.can_admit(
                        eng.pool.pages_for(eng._slot_seq_budget(
                            len(req.prompt), req.max_new_tokens)))):
                # pool backpressure: not enough free/evictable pages for
                # the worst case — retry after a slot drains
                sched.requeue(req)
                return True
            with obs.span("serve/admit", self.replica_id):
                # the TTFT clock starts at dequeue (reserve) so prefill
                # time (and the prefix cache skipping it) shows up in ttft_s
                sched.reserve(slot, req, self.clock,
                              wall=time.perf_counter())
                with obs.span("serve/prefill", self.replica_id):
                    if self.prefill_chunk is not None:
                        self.tasks[slot] = eng.begin_prefill(
                            req.prompt, frames=req.frames, state=self.state)
                        continue
                    # monolithic: admission is baseline-identical even under
                    # spec (the spec loop recognizes pos == lengths as a
                    # fresh slot and takes the first candidate dist from
                    # these prefill logits)
                    pf = eng.prefill_request(req.prompt, frames=req.frames,
                                             state=self.state)
                self._insert(slot, req, pf)
        return False

    def _insert(self, slot: int, req: Request, pf) -> bool:
        """Insert a finished prefill into its reserved slot; False if the
        pool refused (the request is back in the queue, nothing leaked)."""
        eng, sched = self.engine, self.sched
        temp = (req.temperature if req.temperature is not None
                else self.temperature)
        try:
            with obs.span("serve/insert", self.replica_id):
                state = eng.insert(self.state, slot, pf, req.max_new_tokens,
                                   temperature=temp, top_k=req.top_k,
                                   top_p=req.top_p)
        except OutOfPages:
            # engine.insert unpinned the match and leaked nothing; put the
            # request back (its queue-delay clock resumes) and retry when
            # a slot drains
            sched.unreserve(slot)
            return False
        self.state = state
        # a refill = joining a batch that is already mid-decode
        if self.occupancy and sched.num_active > 0:
            self.admissions += 1
        sched.activate(slot)
        return True

    def _advance_prefills(self) -> None:
        """Advance every in-flight chunked prefill by ONE chunk per tick
        (the Sarathi schedule: a bounded slice of prefill work interleaved
        between decode chunks — in the steady state one long prompt is in
        flight, so a tick adds at most one prefill_chunk-token step);
        insert each task as soon as its prompt is fully in. ``tasks``
        preserves reservation order, so progress is FIFO."""
        for slot in list(self.tasks):
            task = self.tasks[slot]
            with obs.span("serve/prefill", self.replica_id):
                self.engine.advance_prefill(task, self.prefill_chunk)
            self.prefill_chunks += 1
            if not task.done:
                continue
            del self.tasks[slot]
            req = self.sched.reserved_request(slot)
            self._insert(slot, req, task.as_prefill())

    # -- teardown ------------------------------------------------------------
    def abort(self) -> list:
        """Tear down in-flight work leak-free and return the unfinished
        requests (replica failover / exception unwind, DESIGN.md §15):
        chunked-prefill prefix pins drop, every decoding slot's pages
        release, and the scheduler drains — the caller re-drives the
        survivors onto another session, where each re-prefills from its
        original prompt (greedy tokens unchanged). Finished outputs stay
        available through ``finalize``."""
        eng, sched = self.engine, self.sched
        for task in self.tasks.values():
            if task.match is not None and eng.pool is not None:
                eng.pool.unpin(task.match)
        self.tasks.clear()
        for slot, _req in sched.active_slots():
            self.state = eng.release(self.state, slot)
        survivors = sched.drain_unfinished()
        self._dispatched = False
        self._pending_spec = None
        if eng.pool is not None:
            eng.pool.check_invariants()
        return survivors

    # -- wrap-up -------------------------------------------------------------
    def finalize(self):
        """Sorted outputs + ServeStats (call once, after ``done``).

        The run's numbers publish into a fresh per-run registry
        (``obs/serve_metrics.py``) and ``ServeStats`` is reconstructed as
        a snapshot VIEW over it — one source of truth for the CLI report,
        the benchmark rows and the Prometheus/JSON expositions. When a
        process-wide registry is installed (``obs.metrics()``) the run
        merges into it, so sequential/parallel serves accumulate with
        Prometheus counter semantics."""
        from repro.obs.metrics import MetricsRegistry
        from repro.obs.serve_metrics import publish_session
        from repro.quant.compiler import kv_tier_labels
        from repro.serving.engine import ServeStats
        from repro.serving.spec.loop import obs_labels
        eng, sched = self.engine, self.sched
        outputs = sorted(sched.finished, key=lambda o: o.rid)
        pool_kw = None
        if eng.pool is not None:
            pool = eng.pool
            pool.check_invariants()    # engine teardown: zero leaked pages
            if self.tier:
                # sequential serves on this engine restart at tier 0 (the
                # next init_decode_state rebuilds the pool from kv_plan)
                eng.kv_plan = self._ladder[0]
            pool_kw = dict(
                pages_total=pool.num_pages,
                pages_peak=pool.peak_pages,
                page_size=pool.page_size,
                prefix_hits=pool.prefix_hits,
                prefix_hit_tokens=pool.prefix_hit_tokens,
                prompt_tokens=pool.prompt_tokens,
                cow_copies=pool.cow_copies,
                kv_bytes_peak=(pool.peak_pages * eng._page_bytes
                               + self.num_slots
                               * eng._nonpaged_bytes_per_slot()))
        local = MetricsRegistry()
        publish_session(
            local, replica=self.replica_id, outputs=outputs,
            occupancy=(float(np.mean(self.occupancy))
                       if self.occupancy else 0.0),
            num_chunks=len(self.occupancy), chunk=self.chunk,
            admissions=self.admissions, generated=self.generated,
            prefill_chunks=self.prefill_chunks, gaps=self.gaps,
            spec_m=self.spec_m,
            spec_labels=(obs_labels(eng.spec) if self.spec else None),
            watchdog_trips=self.watchdog_trips,
            degraded_steps=self.degraded_steps,
            transitions=len(self.transitions),
            tier_steps=self.tier_steps,
            tier_labels=kv_tier_labels(self._ladder),
            tuned=eng.tuned, pool=pool_kw)
        installed = obs.metrics()
        if installed is not None:
            installed.merge(local)
        pf = obs.profile()
        if pf is not None:
            pf.stop()
        return outputs, ServeStats.from_registry(local)

    def run(self):
        """Drain the stream to completion (single-engine serve loop). Any
        failure first tears the session down leak-free (``abort``), then
        propagates."""
        try:
            while not self.done:
                self.dispatch()
                self.harvest()
        except BaseException:
            self.abort()
            raise
        return self.finalize()
