"""DP x TP replica serving: a router over per-replica ServeEngines
(docs/DESIGN.md §14).

``launch/mesh.py`` has parsed ``data,model`` mesh shapes since the mesh
serving work landed, but every serving path to date was TP-only — the
data axis never carried traffic. ``ReplicaServe`` puts it to work the way
deployments actually use it: the mesh is split into one submesh per data-
axis index (``split_data_replicas``), each submesh gets its OWN engine —
weights device_put per submesh (DP replication), private slotted decode
state, private page pool — and a host-side router partitions the request
stream across replicas with load-aware dispatch (least outstanding
prompt+decode tokens, in arrival order, deterministic).

The serve loop interleaves the replicas' session ticks in two passes —
dispatch every replica's decode chunk, THEN harvest every replica — so
one replica's blocking device read never serializes the others' compute:
JAX dispatch is async, and by the time replica 0's harvest blocks,
replicas 1..R-1 already have their chunks in flight.

Each replica runs its own decode-step clock (it advances only when that
replica decodes), so ``arrival_step`` is interpreted per replica; wall-
clock latency stats remain globally honest. Greedy decoding is
deterministic per request, so a DP x TP serve is token-identical to the
same requests on one TP-only engine — the CI parity anchor.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.serving import chaos
from repro.serving.engine import ServeEngine, ServeStats
from repro.serving.pool import OutOfPages
from repro.serving.scheduler import Request, RequestOutput, SLOConfig
from repro.serving.session import ServeSession


@dataclasses.dataclass(frozen=True)
class FailoverConfig:
    """Replica health + failover policy (docs/DESIGN.md §15).

    A replica tick (dispatch or harvest) that raises a ``TransientFault``
    retries in place up to ``retries`` times with ``backoff_s`` sleep
    between attempts; any other failure quarantines the replica — its
    session tears down leak-free (``ServeSession.abort``) and every
    unfinished request re-drives onto the surviving replicas, where it
    re-prefills from its original prompt (greedy tokens unchanged).
    ``max_restarts`` bounds quarantines (default R - 1: the last replica
    standing must not fail); ``watchdog_s`` arms the per-replica
    dispatch→harvest deadline (overruns surface as ``watchdog_trips``)."""
    retries: int = 2
    backoff_s: float = 0.0
    max_restarts: Optional[int] = None
    watchdog_s: Optional[float] = None


@dataclasses.dataclass
class ReplicaStats:
    """Aggregate + per-replica serve statistics."""
    replicas: int
    aggregate: ServeStats          # merged view (percentiles recomputed
                                   # over ALL requests, counters summed)
    per_replica: list              # list[ServeStats], one per replica
    assignments: list              # requests routed to each replica
    occupancy_per_replica: list    # mean active-slot fraction per replica


class ReplicaServe:
    """Serve one request stream across R replica engines."""

    def __init__(self, engines: Sequence[ServeEngine]):
        if not engines:
            raise ValueError("ReplicaServe needs at least one engine")
        self.engines = list(engines)

    @classmethod
    def build(cls, model, params, *, mesh, max_seq: int,
              **engine_kw) -> "ReplicaServe":
        """One engine per data-axis submesh of ``mesh``. Each engine
        device_puts the (quantized) weights to its own submesh — that IS
        the DP replication; a mesh without a data axis yields a single
        TP-only replica."""
        from repro.launch.mesh import split_data_replicas
        return cls([ServeEngine(model, params, mesh=m, max_seq=max_seq,
                                **engine_kw)
                    for m in split_data_replicas(mesh)])

    @property
    def num_replicas(self) -> int:
        return len(self.engines)

    def route(self, requests: Sequence[Request]) -> list[list[Request]]:
        """Load-aware dispatch: walk the stream in arrival order and send
        each request to the replica with the least outstanding work
        (projected prompt + decode tokens). Deterministic — ties go to the
        lowest replica id."""
        buckets: list[list[Request]] = [[] for _ in self.engines]
        load = [0] * len(self.engines)
        order = sorted(requests, key=lambda r: (r.arrival_step, r.rid))
        for r in order:
            i = min(range(len(load)), key=lambda j: (load[j], j))
            buckets[i].append(r)
            load[i] += len(r.prompt) + r.max_new_tokens
        return buckets

    def serve(self, requests: Sequence[Request], *, num_slots: int = 8,
              chunk: int = 8, temperature: float = 0.0, key=None,
              prefill_chunk: Optional[int] = None,
              slo: Optional[SLOConfig] = None,
              failover: Optional[FailoverConfig] = None,
              degrade=None
              ) -> tuple[list[RequestOutput], ReplicaStats]:
        """Drain the stream across all replicas; ``num_slots`` is PER
        replica (total concurrency = R * num_slots). Outputs merge back
        in request-id order.

        With ``failover`` set, a replica whose tick faults permanently is
        quarantined: its session aborts leak-free and its unfinished
        requests re-drive onto the surviving replicas (DESIGN.md §15).
        Transient faults retry in place. Without ``failover``, failures
        propagate as before. ``degrade`` (a ``session.DegradeConfig``)
        arms per-replica graceful degradation under pool pressure."""
        key = key if key is not None else jax.random.PRNGKey(0)
        buckets = self.route(requests)
        sessions = [
            ServeSession(eng, bucket, num_slots=num_slots, chunk=chunk,
                         temperature=temperature,
                         key=jax.random.fold_in(key, i),
                         prefill_chunk=prefill_chunk, slo=slo,
                         replica_id=i, degrade=degrade,
                         watchdog_s=(failover.watchdog_s
                                     if failover is not None else None))
            for i, (eng, bucket) in enumerate(zip(self.engines, buckets))]
        alive = [True] * len(sessions)
        restarts, redriven = 0, 0
        recovery: list[float] = []
        failovers: list[tuple] = []    # (replica, recovery_s, orphans)

        def tick(i: int, phase: str) -> bool:
            """One session phase under the failover policy; False means
            the replica must be quarantined."""
            s = sessions[i]
            fn = s.dispatch if phase == "dispatch" else s.harvest
            attempts = failover.retries if failover is not None else 0
            while True:
                try:
                    fn()
                    return True
                except chaos.TransientFault:
                    if attempts <= 0:
                        if failover is None:
                            raise
                        return False
                    attempts -= 1   # sites fire before state mutation, so
                    if failover.backoff_s:       # the tick retries in place
                        time.sleep(failover.backoff_s)
                except OutOfPages:
                    raise   # admission deadlock is a sizing error on every
                            # identical replica — re-driving cannot help
                except Exception:
                    if failover is None:
                        raise
                    return False

        def quarantine(i: int) -> None:
            nonlocal restarts, redriven
            tr = obs.tracer()
            span_t0 = tr.now_us() if tr is not None else 0.0
            t0 = time.perf_counter()
            orphans = sessions[i].abort()
            alive[i] = False
            restarts += 1
            targets = [j for j in range(len(sessions)) if alive[j]]
            budget = (failover.max_restarts
                      if failover.max_restarts is not None
                      else len(sessions) - 1)
            if not targets or restarts > budget:
                raise RuntimeError(
                    f"replica failover exhausted: {restarts} replicas "
                    f"failed (budget {budget}), {len(orphans)} requests "
                    f"stranded")
            load = {j: 0 for j in targets}
            for req in orphans:          # load-aware re-drive, like route()
                j = min(targets, key=lambda t: (load[t], t))
                sessions[j].sched.submit(dataclasses.replace(
                    req, arrival_step=sessions[j].clock))
                load[j] += len(req.prompt) + req.max_new_tokens
                redriven += 1
            dt = time.perf_counter() - t0
            recovery.append(dt)
            failovers.append((i, dt, len(orphans)))
            # failover telemetry goes to the INSTALLED sinks directly —
            # it is a router-level event no per-session publish covers
            # (docs/DESIGN.md §16)
            if tr is not None:
                tr.complete("replica/failover", span_t0, i,
                            args={"orphans": len(orphans),
                                  "survivors": len(targets)})
            obs.count("serve_replica_restarts_total", 1, replica=str(i))
            obs.count("serve_redriven_requests_total", len(orphans),
                      replica=str(i))
            obs.observe("serve_recovery_seconds", dt, replica=str(i))

        while any(alive[i] and not s.done
                  for i, s in enumerate(sessions)):
            for i, s in enumerate(sessions):  # launch every live replica...
                if alive[i] and not s.done and not tick(i, "dispatch"):
                    quarantine(i)
            for i, s in enumerate(sessions):  # ...then block on each in turn
                if alive[i] and not tick(i, "harvest"):
                    quarantine(i)             # (no-op unless it dispatched)
        results = [s.finalize() for s in sessions]
        outputs = sorted((o for outs, _ in results for o in outs),
                         key=lambda o: o.rid)
        per_replica = [st for _, st in results]
        aggregate = dataclasses.replace(
            _merge_stats(outputs, per_replica),
            replica_restarts=restarts, redriven_requests=redriven,
            recovery_p95_s=(float(np.percentile(recovery, 95))
                            if recovery else 0.0),
            registry=_merge_registries(per_replica, failovers))
        return outputs, ReplicaStats(
            replicas=len(self.engines),
            aggregate=aggregate,
            per_replica=per_replica,
            assignments=[len(b) for b in buckets],
            occupancy_per_replica=[st.occupancy for st in per_replica])


def _merge_registries(per_replica: list[ServeStats],
                      failovers: list[tuple]):
    """Roll the per-replica run registries into one, then add the
    router-level failover events no per-session publish covers. The
    result rides on the aggregate's ``registry`` field, so the DP
    exposition carries per-replica labels."""
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.serve_metrics import SCHEMA
    merged = MetricsRegistry()
    for st in per_replica:
        if st.registry is not None:
            merged.merge(st.registry)
    for i, dt, orphans in failovers:
        r = str(i)
        merged.counter("serve_replica_restarts_total",
                       SCHEMA["serve_replica_restarts_total"][1]
                       ).inc(1, replica=r)
        merged.counter("serve_redriven_requests_total",
                       SCHEMA["serve_redriven_requests_total"][1]
                       ).inc(orphans, replica=r)
        merged.histogram("serve_recovery_seconds",
                         SCHEMA["serve_recovery_seconds"][1]
                         ).observe(dt, replica=r)
    return merged


def _merge_stats(outputs: list, per_replica: list[ServeStats]) -> ServeStats:
    """Global view: latency percentiles recomputed over the merged request
    outputs (a per-replica percentile of percentiles would be wrong),
    counters and token totals summed, occupancy weighted by chunks."""

    def pct(vals, q):
        return float(np.percentile(vals, q)) if vals else 0.0

    ttfts = [o.ttft_s for o in outputs if o.ttft_s is not None]
    tpots = [o.tpot_s for o in outputs if o.tpot_s is not None]
    qdels = [o.queue_delay_s for o in outputs if o.queue_delay_s is not None]
    chunks = sum(st.num_chunks for st in per_replica)
    proposed = sum(st.draft_proposed for st in per_replica)
    rounds = sum(st.spec_rounds for st in per_replica)
    committed = sum(st.tokens_per_round * st.spec_rounds
                    for st in per_replica)
    return ServeStats(
        decode_steps=sum(st.decode_steps for st in per_replica),
        generated_tokens=sum(st.generated_tokens for st in per_replica),
        occupancy=(sum(st.occupancy * st.num_chunks for st in per_replica)
                   / chunks if chunks else 0.0),
        num_chunks=chunks,
        admissions=sum(st.admissions for st in per_replica),
        ttft_p50_s=pct(ttfts, 50), ttft_p95_s=pct(ttfts, 95),
        tpot_p50_s=pct(tpots, 50), tpot_p95_s=pct(tpots, 95),
        queue_delay_p50_s=pct(qdels, 50), queue_delay_p95_s=pct(qdels, 95),
        preemptions=sum(st.preemptions for st in per_replica),
        timeouts=sum(st.timeouts for st in per_replica),
        cancelled=sum(st.cancelled for st in per_replica),
        prefill_chunks=sum(st.prefill_chunks for st in per_replica),
        decode_gap_p50_s=max((st.decode_gap_p50_s for st in per_replica),
                             default=0.0),
        decode_gap_p95_s=max((st.decode_gap_p95_s for st in per_replica),
                             default=0.0),
        decode_gap_max_s=max((st.decode_gap_max_s for st in per_replica),
                             default=0.0),
        spec_rounds=rounds,
        draft_proposed=proposed,
        draft_accepted=sum(st.draft_accepted for st in per_replica),
        acceptance_rate=(sum(st.draft_accepted for st in per_replica)
                         / proposed if proposed else 0.0),
        tokens_per_round=(committed / rounds if rounds else 0.0),
        pool_pages_total=sum(st.pool_pages_total for st in per_replica),
        pool_pages_peak=sum(st.pool_pages_peak for st in per_replica),
        pool_page_size=max((st.pool_page_size for st in per_replica),
                           default=0),
        prefix_hits=sum(st.prefix_hits for st in per_replica),
        prefix_hit_tokens=sum(st.prefix_hit_tokens for st in per_replica),
        cow_copies=sum(st.cow_copies for st in per_replica),
        kv_bytes_peak=sum(st.kv_bytes_peak for st in per_replica),
        watchdog_trips=sum(st.watchdog_trips for st in per_replica),
        degraded_steps=sum(st.degraded_steps for st in per_replica),
        degrade_transitions=sum(st.degrade_transitions
                                for st in per_replica),
        kv_tier_steps=_sum_tiers([st.kv_tier_steps for st in per_replica]))


def _sum_tiers(tiers: list) -> tuple:
    """Elementwise sum of per-replica tier-step histograms (ragged: a
    replica that never degraded reports fewer tiers)."""
    width = max((len(t) for t in tiers), default=0)
    return tuple(sum(t[i] for t in tiers if i < len(t))
                 for i in range(width))
