"""Serving launcher: train-or-load a model, EWQ/FastEWQ-quantize, serve.

Usage:
  python -m repro.launch.serve --arch yi-9b --smoke --variant 4bit/8bit
  python -m repro.launch.serve --arch llama3.2-3b --smoke --fast \
      --prompt-len 16 --max-new 16

Request-stream simulation (continuous batching — new requests are admitted
into freed slots between decode chunks):
  python -m repro.launch.serve --arch llama3.2-3b --smoke \
      --num-requests 16 --arrival-rate 0.5 --num-slots 4 --chunk 8

Compiled-plan artifacts (compile once, serve many — docs/DESIGN.md §8):
  # first run: train, analyze, compile, persist the quantized checkpoint
  python -m repro.launch.serve --arch zamba2-2.7b --smoke \
      --variant 4bit/8bit --plan-artifact /tmp/zamba_plan
  # later runs boot from the artifact: no weight load, no entropy analysis
  python -m repro.launch.serve --arch zamba2-2.7b --smoke \
      --plan-artifact /tmp/zamba_plan

Paged KV pool with COW prefix sharing (docs/DESIGN.md §13): ``--paged``
serves K/V from a fixed pool of quantized pages instead of contiguous
per-slot reservations; ``--shared-prefix-len N`` gives every simulated
request a common system prefix so the prefix cache gets hits, and
``--check-paged-parity`` asserts token-identical greedy output vs the
dense engine:
  python -m repro.launch.serve --arch llama3.2-3b --smoke \
      --num-requests 8 --paged --page-size 8 --shared-prefix-len 8 \
      --check-paged-parity

Random weights at published width, no training (``--train-steps 0``):
the model is initialized from ``--seed`` under ``jit`` — with ``--mesh``
straight into its serving shardings — and no optimizer state is built.
``--num-layers`` cuts the depth of the published config:
  python -m repro.launch.serve --arch yi-9b --train-steps 0 \
      --num-layers 12 --fast --variant 4bit/8bit --kv-precision int8 \
      --num-requests 16 --num-slots 8 --prompt-len 256 --max-new 48

Self-speculative decoding (docs/DESIGN.md §11): ``--spec-k 4`` serves with
draft-propose/verify rounds — the entropy-ordered all-int4 draft shares
payloads with the target; ``--check-greedy-parity`` additionally runs the
non-spec engine on the same requests and asserts token-identical greedy
output (the CI anchor).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.configs.registry import ARCHS, get_config
from repro.launch.compile_cache import (enable_compile_cache,
                                         keep_submesh_programs_out)
from repro.models.model import build
from repro.serving.engine import ServeEngine
from repro.serving.quantized import plan_for_variant
from repro.serving.scheduler import synthetic_stream
from repro.train.loop import train


def init_params(model, seed: int, mesh=None):
    """Random weights from ``seed``, built on device under ``jit``. With a
    mesh the init writes straight into the TP serving shardings, so no
    device ever holds the whole raw model."""
    key = jax.random.PRNGKey(seed)
    if mesh is None:
        return jax.jit(model.init)(key)
    from repro.sharding.specs import serving_param_shardings
    shardings = serving_param_shardings(jax.eval_shape(model.init, key),
                                        mesh)
    return jax.jit(model.init, out_shardings=shardings)(key)


def print_device_memory(stage: str) -> None:
    """Device 0's bytes in use and its high-water mark so far, where the
    backend reports them (TPU; the CPU backend does not)."""
    stats = jax.devices()[0].memory_stats()
    if stats and "bytes_in_use" in stats:
        print(f"device 0 memory after {stage}: "
              f"{stats['bytes_in_use'] / 2**30:.2f} GiB in use, peak "
              f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")


def main(argv=None) -> dict:
    """Run the launcher on ``argv`` (default: the command line). Returns
    what was served — ``model``, ``engine``, ``plan``, ``requests`` and
    ``outputs``/``stats`` for a stream (``result`` for a single batch) —
    so a caller in the same process can check it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--variant", default="8bit-mixed",
                    choices=["raw", "4bit", "8bit", "8bit-mixed",
                             "4bit/8bit"])
    ap.add_argument("--fast", action="store_true",
                    help="FastEWQ metadata plan (no weight analysis)")
    ap.add_argument("--kv-precision", default=None,
                    choices=["bf16", "int8", "int4", "auto"],
                    help="KV-cache precision: int8/int4 quantize every "
                         "layer's cache; auto derives per-layer precision "
                         "from the plan's entropy decisions "
                         "(docs/DESIGN.md §10). Default: bf16, or the "
                         "policy stamped into --plan-artifact; pass bf16 "
                         "explicitly to override a quantized artifact")
    ap.add_argument("--train-steps", type=int, default=30,
                    help="brief training so weights are non-degenerate "
                         "(0: random weights from --seed, no optimizer)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, training data and requests")
    ap.add_argument("--num-layers", type=int, default=0,
                    help="cut the config's depth to N layers (0: published "
                         "depth); widths are never changed")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--plan-artifact", default=None,
                    help="compiled-plan artifact dir: boot from it when it "
                         "exists, else compile + persist into it")
    # request-stream simulation (continuous batching)
    ap.add_argument("--num-requests", type=int, default=0,
                    help="simulate a stream of N requests (0: single batch)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="requests per decode step (0: all arrive at once)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per jitted chunk")
    ap.add_argument("--num-slots", type=int, default=4,
                    help="concurrent decode slots")
    # self-speculative decoding (docs/DESIGN.md §11)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens per round "
                         "(0 disables; the all-int4 draft is derived from "
                         "the plan and shares payloads with the target)")
    ap.add_argument("--spec-draft", default="model",
                    choices=("model", "ngram"),
                    help="with --spec-k: 'model' drafts with the int4 "
                         "self-draft; 'ngram' proposes by prompt lookup "
                         "(no draft model — a round costs ~one fused "
                         "multi-query verify step)")
    ap.add_argument("--check-greedy-parity", action="store_true",
                    help="with --spec-k: also run the non-spec engine on "
                         "the same requests and assert token-identical "
                         "greedy output")
    # paged KV pool + prefix sharing (docs/DESIGN.md §13)
    ap.add_argument("--paged", action="store_true",
                    help="serve K/V from a paged pool with copy-on-write "
                         "prefix sharing instead of contiguous per-slot "
                         "reservations")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical pages in the pool (0: equal-memory "
                         "default, num_slots * ceil(max_seq/page_size))")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="with --paged: disable the COW prefix cache")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="overwrite the first N prompt tokens of every "
                         "simulated request with a common system prefix "
                         "(exercises prefix sharing)")
    ap.add_argument("--check-paged-parity", action="store_true",
                    help="with --paged: also run the dense (contiguous) "
                         "engine on the same requests and assert "
                         "token-identical greedy output")
    # mesh-parallel serving (docs/DESIGN.md §9)
    ap.add_argument("--mesh", default=None,
                    help="comma-separated mesh axis names (e.g. data,model): "
                         "shard weights/caches and serve mesh-parallel")
    ap.add_argument("--mesh-shape", default=None,
                    help="comma-separated per-axis device counts (e.g. 1,8); "
                         "default puts every device on the last axis")
    # chunked prefill + SLO scheduling + DP replicas (docs/DESIGN.md §14)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="interleave prompt prefill in N-token chunks "
                         "between decode chunks (Sarathi-style) instead of "
                         "one monolithic prefill per admission (0: off)")
    ap.add_argument("--poisson", action="store_true",
                    help="draw seeded exponential inter-arrival gaps with "
                         "mean 1/--arrival-rate (open-loop load) instead "
                         "of fixed spacing")
    ap.add_argument("--priorities", default=None,
                    help="comma-separated priority cycle over the stream "
                         "(0 = most urgent), e.g. 0,1,1,1 for 25%% "
                         "interactive traffic")
    ap.add_argument("--ttft-target-ms", type=float, default=0.0,
                    help="SLO: time-to-first-token target; queued requests "
                         "past it bypass the admission gate (0: unset)")
    ap.add_argument("--tpot-target-ms", type=float, default=0.0,
                    help="SLO: per-output-token target; admissions are "
                         "deferred while the rolling decode-chunk latency "
                         "exceeds it (0: unset)")
    ap.add_argument("--preempt", action="store_true",
                    help="allow a strictly-higher-priority waiter to evict "
                         "the lowest-priority decoding slot (restart-style; "
                         "pages release, the victim requeues)")
    ap.add_argument("--queue-timeout-steps", type=int, default=0,
                    help="drop requests still QUEUED after N decode steps "
                         "(finish_reason='timeout'; 0: never)")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="abort requests (queued or running) N decode steps "
                         "after arrival (finish_reason='deadline'; 0: never)")
    ap.add_argument("--dp", action="store_true",
                    help="serve DP x TP: split the mesh's data axis into "
                         "replicas, one engine each, and route the request "
                         "stream load-aware across them")
    ap.add_argument("--check-dp-parity", action="store_true",
                    help="with --dp: also serve on the single full-mesh "
                         "engine and assert token-identical greedy output")
    # fault tolerance + graceful degradation (docs/DESIGN.md §15)
    ap.add_argument("--chaos", default=None,
                    help="comma-separated fault-injection shorthands "
                         "(serving/chaos.py): replica_fault, "
                         "replica_transient, oom, stall, artifact — "
                         "deterministic under --chaos-seed")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the chaos injector's fault schedule")
    ap.add_argument("--degrade-policy", default="off",
                    choices=["off", "ewq"],
                    help="graceful degradation under pool pressure: 'ewq' "
                         "spills KV precision down the entropy-ordered "
                         "tier ladder (FastEWQ/plan-derived) instead of "
                         "rejecting work, promoting back when headroom "
                         "returns (requires --paged)")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="per-replica dispatch->harvest deadline; overruns "
                         "surface as watchdog_trips (0: off)")
    ap.add_argument("--check-chaos-parity", action="store_true",
                    help="with --chaos: serve fault-free FIRST, then the "
                         "chaos run, and assert token-identical greedy "
                         "output (every request completes despite the "
                         "injected faults)")
    # serving telemetry (docs/DESIGN.md §16)
    ap.add_argument("--trace-out", default=None,
                    help="write per-request/engine span tracing for the "
                         "measured serve as Chrome trace_event JSON "
                         "(load in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the serve metrics registry as Prometheus "
                         "text exposition (plus a stable .json snapshot "
                         "next to it)")
    ap.add_argument("--profile-steps", default=None,
                    help="A:B — arm a jax.profiler capture window over "
                         "decode steps [A, B); the trace carries the serve "
                         "loop's serve/* spans and the model step's named "
                         "scopes on the device clock")
    ap.add_argument("--profile-dir", default="/tmp/repro-profile",
                    help="output dir for --profile-steps traces")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.num_layers:
        if not 0 < args.num_layers <= cfg.num_layers:
            raise SystemExit(f"--num-layers must be in 1..{cfg.num_layers}")
        if args.num_layers < cfg.num_layers:
            print(f"depth cut: {args.num_layers} of {cfg.num_layers} layers "
                  f"of {cfg.name}")
            cfg = dataclasses.replace(cfg, num_layers=args.num_layers)

    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh
        mesh = parse_mesh(args.mesh, args.mesh_shape)
        print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")
    elif args.mesh_shape:
        raise SystemExit("--mesh-shape requires --mesh")

    spec = None
    if args.spec_k > 0:
        from repro.serving.spec import SpecConfig
        spec = SpecConfig(k=args.spec_k, draft_source=args.spec_draft)
    elif args.check_greedy_parity:
        raise SystemExit("--check-greedy-parity requires --spec-k")

    paged = None
    if args.paged:
        from repro.serving.pool import PagedConfig
        paged = PagedConfig(page_size=args.page_size,
                            pool_pages=args.pool_pages or None,
                            prefix_sharing=not args.no_prefix_sharing)
    elif args.check_paged_parity:
        raise SystemExit("--check-paged-parity requires --paged")

    slo = None
    if args.ttft_target_ms or args.tpot_target_ms or args.preempt:
        from repro.serving.scheduler import SLOConfig
        slo = SLOConfig(
            ttft_target_s=(args.ttft_target_ms / 1e3
                           if args.ttft_target_ms else None),
            tpot_target_s=(args.tpot_target_ms / 1e3
                           if args.tpot_target_ms else None),
            preempt=args.preempt)
    if args.poisson and not args.arrival_rate:
        raise SystemExit("--poisson requires --arrival-rate > 0")
    if args.dp and not args.num_requests:
        raise SystemExit("--dp serves a request stream; set --num-requests")
    if args.dp and not args.mesh:
        raise SystemExit("--dp requires --mesh with a data axis >= 2 "
                         "(e.g. --mesh data,model --mesh-shape 2,4)")
    if args.check_dp_parity and not args.dp:
        raise SystemExit("--check-dp-parity requires --dp")
    if args.check_chaos_parity and not args.chaos:
        raise SystemExit("--check-chaos-parity requires --chaos")
    if args.chaos and not args.num_requests:
        raise SystemExit("--chaos injects into the serve loop; set "
                         "--num-requests")
    if ((args.trace_out or args.metrics_out or args.profile_steps)
            and not args.num_requests):
        raise SystemExit("--trace-out/--metrics-out/--profile-steps "
                         "instrument the serve loop; set --num-requests")

    degrade = None
    if args.degrade_policy != "off":
        if paged is None:
            raise SystemExit("--degrade-policy trades KV precision for pool "
                             "pages; it requires --paged")
        from repro.serving.session import DegradeConfig
        degrade = DegradeConfig(policy=args.degrade_policy)
    failover = None
    if args.dp and (args.chaos or args.watchdog_ms):
        from repro.serving.replica import FailoverConfig
        failover = FailoverConfig(watchdog_s=(args.watchdog_ms / 1e3
                                              if args.watchdog_ms else None))

    requests = None
    max_seq = args.prompt_len + args.max_new
    if args.num_requests > 0:
        priorities = (tuple(int(p) for p in args.priorities.split(","))
                      if args.priorities else None)
        requests = synthetic_stream(
            args.num_requests, vocab_size=cfg.vocab_size,
            prompt_len=args.prompt_len, max_new_tokens=args.max_new,
            arrival_rate=args.arrival_rate, poisson=args.poisson,
            priorities=priorities, seed=args.seed)
        for r in requests:
            if args.queue_timeout_steps:
                r.queue_timeout_steps = args.queue_timeout_steps
            if args.deadline_steps:
                r.deadline_steps = args.deadline_steps
        if args.shared_prefix_len > 0:
            if args.shared_prefix_len >= args.prompt_len:
                raise SystemExit("--shared-prefix-len must be shorter than "
                                 "--prompt-len (at least one distinct token "
                                 "must remain per request)")
            shared = requests[0].prompt[:args.shared_prefix_len].copy()
            for r in requests:
                r.prompt[:args.shared_prefix_len] = shared
        max_seq = max(len(r.prompt) + r.max_new_tokens for r in requests)
    elif args.shared_prefix_len > 0:
        raise SystemExit("--shared-prefix-len requires --num-requests")
    if spec is not None:
        max_seq += spec.k   # verify-window headroom (engine asserts)

    def build_engines(make_engine):
        """The serving engine, or with --dp one engine per data-axis
        replica (no full-mesh copy of the weights is built) behind a
        router; returns (engine, replica router or None)."""
        if not args.dp:
            return make_engine(mesh), None
        from repro.launch.mesh import split_data_replicas
        from repro.serving.replica import ReplicaServe
        subs = split_data_replicas(mesh)
        if len(subs) < 2:
            raise SystemExit(f"--dp found {len(subs)} replica(s) in "
                             f"mesh {dict(mesh.shape)}; need a data "
                             "axis of size >= 2")
        if keep_submesh_programs_out():
            print("compile cache off for the rest of the process: cached "
                  "executables of a TPU submesh halt the chip")
        engines = [make_engine(m) for m in subs]
        return engines[0], ReplicaServe(engines)

    from repro.checkpoint import ckpt
    if args.plan_artifact and ckpt.is_artifact(args.plan_artifact):
        # cold boot: quantized weights straight from the compiled artifact —
        # no training/raw-weight load, no entropy analysis, no quantization
        model = build(cfg)
        t0 = time.perf_counter()
        # None = not specified -> the artifact's stamped kv policy governs;
        # an explicit value (including bf16) overrides it
        kv_kw = ({} if args.kv_precision is None
                 else {"kv_precision": args.kv_precision})

        def make_engine(m):
            return ServeEngine.from_artifact(model, args.plan_artifact,
                                             max_seq=max_seq, mesh=m,
                                             spec=spec, paged=paged,
                                             **kv_kw)

        engine, replica = build_engines(make_engine)
        plan = engine.plan
        print(f"booted from artifact {args.plan_artifact} in "
              f"{time.perf_counter() - t0:.2f}s"
              + (" (weights landed sharded)" if mesh is not None else ""))
    else:
        if args.train_steps > 0:
            run = RunConfig(steps=args.train_steps, learning_rate=1e-3,
                            warmup_steps=3, remat=False, seed=args.seed)
            params = train(cfg, run, batch=args.batch,
                           seq=args.prompt_len * 2)["params"]
            model = build(cfg)
        else:
            model = build(cfg)
            init_mesh = mesh
            if args.dp:
                # quantize over every device before the replicas take
                # their copies: a replica-sharded raw model would not fit
                init_mesh = jax.sharding.Mesh(
                    mesh.devices.reshape((1,) * (mesh.devices.ndim - 1)
                                         + (-1,)), mesh.axis_names)
            params = init_params(model, args.seed, init_mesh)
            print(f"initialized {cfg.name} from seed {args.seed} "
                  f"(no training)")
        print_device_memory("init")
        plan = plan_for_variant(model, params, args.variant, fast=args.fast)
        print_device_memory("plan")
        kv_precision = args.kv_precision or "bf16"
        if kv_precision == "auto" and plan is None:
            raise SystemExit("--kv-precision auto derives per-layer cache "
                             "precision from the weight plan; it cannot be "
                             "combined with --variant raw")
        if plan is not None:
            compiled = model.compile_plan(params, plan,
                                          kv_precision=kv_precision)
            params = None    # raw weights no longer needed: free them
            print_device_memory("compile_plan")

            def make_engine(m):
                e = ServeEngine(model, compiled.params, max_seq=max_seq,
                                mesh=m,
                                kv_precision=compiled.kv_plan or "bf16",
                                spec=spec, paged=paged)
                e.plan = plan
                return e

            engine, replica = build_engines(make_engine)
            if args.plan_artifact:
                from repro.quant.compiler import save_artifact
                if spec is not None and spec.draft_source == "model":
                    # stamp the draft derivation into the manifest so cold
                    # boots re-derive the identical draft
                    compiled.draft = engine._ensure_draft().to_manifest()
                path = save_artifact(args.plan_artifact, compiled, mesh=mesh)
                print(f"saved compiled plan artifact to {path}")
        else:
            def make_engine(m):
                return ServeEngine(model, params, max_seq=max_seq, mesh=m,
                                   kv_precision=kv_precision, spec=spec,
                                   paged=paged)

            engine, replica = build_engines(make_engine)

    print_device_memory("engine build")
    raw_bits = 32.0 if cfg.dtype == "float32" else 16.0
    raw_bytes = cfg.param_count() * raw_bits / 8.0
    print(f"weights: {engine.weight_bytes()/2**20:.1f} MiB effective "
          f"(raw {raw_bytes/2**20:.1f} MiB)")
    if mesh is not None:
        print(f"per-device weight bytes: "
              f"{engine.weight_bytes_per_device()/2**20:.1f} MiB "
              f"on {engine.mesh.size} devices")
    if plan:
        print(f"plan: {plan.counts()}")
    if engine.kv_plan is not None:
        kv_counts: dict = {}
        for p in engine.kv_plan.precisions:
            kv_counts[p] = kv_counts.get(p, 0) + 1
        print(f"kv cache: {engine.kv_bytes_per_slot()/2**20:.2f} MiB/slot "
              f"at max_seq={max_seq} ({kv_counts})")

    if spec is not None:
        if spec.draft_source == "ngram":
            print(f"spec decode: k={spec.k}, ngram prompt-lookup draft "
                  f"(no draft model)")
        else:
            print(f"spec decode: k={spec.k}, draft overhead "
                  f"{engine.draft_overhead_bytes()/2**20:.2f} MiB "
                  f"({engine._ensure_draft().shared_blocks} blocks shared, "
                  f"{engine._ensure_draft().requantized_blocks} "
                  f"re-quantized)")

    if requests is not None:
        serve_kw = dict(num_slots=args.num_slots, chunk=args.chunk,
                        prefill_chunk=args.prefill_chunk or None, slo=slo,
                        degrade=degrade)
        rstats = None
        chaos_ref = None
        if args.check_chaos_parity:
            # fault-free baseline FIRST, at nominal precision (no degrade):
            # each serve builds fresh sessions and pool pages, so the chaos
            # run below starts from identical state
            base_kw = dict(serve_kw, degrade=None)
            if replica is not None:
                chaos_ref, _ = replica.serve(requests, **base_kw)
            else:
                chaos_ref, _ = engine.serve(requests, **base_kw)
        injector = None
        if args.chaos:
            from repro.serving import chaos as chaos_mod
            injector = chaos_mod.ChaosInjector(
                chaos_mod.FaultConfig.parse(args.chaos,
                                            seed=args.chaos_seed))
            chaos_mod.install(injector)
            print(f"chaos: injecting {args.chaos} (seed {args.chaos_seed})")
        # serving telemetry (docs/DESIGN.md §16): sinks install AFTER any
        # parity baseline so only the measured serve is traced, and
        # uninstall before the parity re-serves below
        tracer = metrics_reg = prof = None
        obs_on = bool(args.trace_out or args.metrics_out
                      or args.profile_steps)
        if obs_on:
            from repro import obs
            if args.trace_out:
                tracer = obs.Tracer()
            if args.metrics_out:
                metrics_reg = obs.MetricsRegistry()
            if args.profile_steps:
                prof = obs.ProfileHooks.parse(args.profile_steps,
                                              trace_dir=args.profile_dir)
            obs.install(tracer, metrics_reg, prof)
        t0 = time.perf_counter()
        try:
            if replica is not None:
                outputs, rstats = replica.serve(requests,
                                                failover=failover,
                                                **serve_kw)
                stats = rstats.aggregate
            else:
                outputs, stats = engine.serve(requests, **serve_kw)
        finally:
            if injector is not None:
                chaos_mod.install(None)
            if obs_on:
                if prof is not None:
                    prof.stop()
                obs.install(None, None, None)
        dt = time.perf_counter() - t0
        from repro.obs import render as obs_render
        for line in obs_render.serve_report(
                stats, wall_s=dt, num_requests=len(outputs),
                chunk=args.chunk,
                queueing=bool(args.arrival_rate or slo is not None),
                prefill_chunk=args.prefill_chunk,
                replicas=(dict(replicas=rstats.replicas,
                               mesh_shape=dict(
                                   replica.engines[0].mesh.shape),
                               assignments=rstats.assignments,
                               occupancy=rstats.occupancy_per_replica)
                          if rstats is not None else None),
                fault=bool(args.chaos or degrade is not None
                           or args.watchdog_ms),
                chaos_fired=(injector.log if injector is not None
                             else None),
                spec=spec is not None,
                paged=(dict(num_slots=args.num_slots,
                            kv_bytes_per_slot=engine.kv_bytes_per_slot(),
                            max_seq=max_seq)
                       if args.paged else None)):
            print(line)
        if tracer is not None:
            tracer.write(args.trace_out)
            print(f"trace: {len(tracer.events)} events -> {args.trace_out} "
                  f"({len(tracer.open_spans())} open spans)")
        if metrics_reg is not None:
            metrics_reg.write_prometheus(args.metrics_out)
            metrics_reg.write_json(args.metrics_out + ".json")
            print(f"metrics: {len(metrics_reg.names())} families -> "
                  f"{args.metrics_out} (+ .json snapshot)")
        if prof is not None and prof.windows:
            print(f"profiler: {prof.windows} capture window(s) -> "
                  f"{prof.trace_dir}")
        if args.check_chaos_parity:
            import numpy as np
            agree = (len(chaos_ref) == len(outputs)
                     and all(a.rid == b.rid
                             and np.array_equal(a.tokens, b.tokens)
                             for a, b in zip(chaos_ref, outputs)))
            print(f"greedy-agree vs fault-free run: {float(agree):.1f} "
                  f"({len(outputs)}/{len(chaos_ref)} requests completed)")
            if not agree:
                raise SystemExit("chaos-run greedy output DIVERGED from "
                                 "the fault-free run (or requests were "
                                 "lost)")
        if args.check_dp_parity:
            import numpy as np
            ref_out, _ = make_engine(mesh).serve(requests, **serve_kw)
            agree = (len(ref_out) == len(outputs)
                     and all(a.rid == b.rid
                             and np.array_equal(a.tokens, b.tokens)
                             for a, b in zip(ref_out, outputs)))
            print(f"greedy-agree vs single full-mesh engine: "
                  f"{float(agree):.1f}")
            if not agree:
                raise SystemExit("DP x TP greedy output DIVERGED from the "
                                 "single full-mesh engine")
        if args.check_paged_parity:
            import numpy as np
            base = ServeEngine(model, engine.params, max_seq=max_seq,
                               kv_precision=engine.kv_plan or "bf16",
                               spec=spec)
            base.plan = engine.plan
            base_outputs, _ = base.serve(requests,
                                         num_slots=args.num_slots,
                                         chunk=args.chunk)
            agree = all(np.array_equal(a.tokens, b.tokens)
                        for a, b in zip(base_outputs, outputs))
            print(f"greedy-agree vs dense engine: {float(agree):.1f}")
            if not agree:
                raise SystemExit("paged greedy output DIVERGED from the "
                                 "dense (contiguous) engine")
        if args.check_greedy_parity:
            import numpy as np
            base = ServeEngine(model, engine.params, max_seq=max_seq,
                               kv_precision=engine.kv_plan or "bf16")
            base.plan = engine.plan
            base_outputs, _ = base.serve(requests,
                                         num_slots=args.num_slots,
                                         chunk=args.chunk)
            agree = all(np.array_equal(a.tokens, b.tokens)
                        for a, b in zip(base_outputs, outputs))
            print(f"greedy-agree vs non-spec engine: {float(agree):.1f}")
            if not agree:
                raise SystemExit("speculative greedy output DIVERGED from "
                                 "the non-spec engine")
        print("sample:", outputs[0].generated.tolist())
        return dict(model=model, engine=engine, replica=replica, plan=plan,
                    requests=requests, outputs=outputs, stats=stats,
                    max_seq=max_seq)

    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 7),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    out = engine.generate(prompts, args.max_new, chunk=args.chunk)
    print(f"generated {out.tokens.shape[1] - args.prompt_len} tokens/seq; "
          f"mean logprob {float(out.logprobs.mean()):.3f}")
    if args.check_greedy_parity:
        import numpy as np
        base = ServeEngine(model, engine.params, max_seq=max_seq,
                           kv_precision=engine.kv_plan or "bf16")
        base.plan = engine.plan
        ref = base.generate(prompts, args.max_new, chunk=args.chunk)
        agree = bool(np.array_equal(np.asarray(ref.tokens),
                                    np.asarray(out.tokens)))
        print(f"greedy-agree vs non-spec engine: {float(agree):.1f}")
        if not agree:
            raise SystemExit("speculative greedy output DIVERGED from the "
                             "non-spec engine")
    if args.check_paged_parity:
        import numpy as np
        base = ServeEngine(model, engine.params, max_seq=max_seq,
                           kv_precision=engine.kv_plan or "bf16", spec=spec)
        base.plan = engine.plan
        ref = base.generate(prompts, args.max_new, chunk=args.chunk)
        agree = bool(np.array_equal(np.asarray(ref.tokens),
                                    np.asarray(out.tokens)))
        print(f"greedy-agree vs dense engine: {float(agree):.1f}")
        if not agree:
            raise SystemExit("paged greedy output DIVERGED from the dense "
                             "(contiguous) engine")
    print("sample:", out.tokens[0, -args.max_new:].tolist())
    return dict(model=model, engine=engine, plan=plan, result=out,
                max_seq=max_seq)


if __name__ == "__main__":
    main()
