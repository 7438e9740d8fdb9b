"""Continuous-batching serving engine: scheduler/batch/loop mechanics.

Mechanics-only tests on a tiny untrained model (fast): slot lifecycle,
masked sampling, per-slot stop conditions, and quantized-vs-raw parity
through the fused chunked decode loop. Mesh-parallel serving parity
(docs/DESIGN.md §9) runs in a subprocess under 8 virtual CPU devices.
"""

import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models.model import build
from repro.serving import batch as B
from repro.serving.engine import ServeEngine
from repro.serving.quantized import fastewq_metadata_plan
from repro.serving.scheduler import Request, Scheduler, synthetic_stream


@pytest.fixture(scope="module")
def tiny():
    cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                              num_layers=2)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, b, p, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, p), 0,
                              cfg.vocab_size, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_lifecycle():
    s = Scheduler(num_slots=2)
    for i, arrival in enumerate((0, 0, 5)):
        s.submit(Request(rid=i, prompt=np.zeros(4, np.int32),
                         max_new_tokens=4, arrival_step=arrival))
    assert s.free_slots() == [0, 1]
    r0 = s.next_ready(clock=0)
    s.assign(0, r0, clock=0)
    assert s.next_ready(clock=0).rid == 1          # rid 2 not arrived yet
    assert s.next_arrival() == 5
    assert s.num_active == 1 and s.free_slots() == [1]
    out = s.complete(0, np.arange(6, dtype=np.int32), np.zeros(2), "length", 8)
    assert out.rid == 0 and out.admitted_step == 0 and out.finished_step == 8
    assert s.free_slots() == [0, 1] and not s.all_done()


# ---------------------------------------------------------------------------
# fused loop vs per-token loop; slot reuse; masked sampling
# ---------------------------------------------------------------------------

def test_fused_loop_matches_stepwise_greedy(tiny):
    cfg, model, params = tiny
    engine = ServeEngine(model, params, max_seq=24)
    prompts = _prompts(cfg, 2, 8)
    fused = engine.generate(prompts, 8, chunk=3)   # chunk not dividing max_new
    step = engine.generate_stepwise(prompts, 8)
    np.testing.assert_array_equal(np.asarray(fused.tokens),
                                  np.asarray(step.tokens))
    np.testing.assert_allclose(np.asarray(fused.logprobs),
                               np.asarray(step.logprobs), atol=1e-4)


def test_engine_refuses_autotune(tiny):
    """Kernels are chosen from the platform and the shapes alone: the
    engine takes no tuned configuration."""
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="autotuner is gone"):
        ServeEngine(model, params, max_seq=24, autotune=True)


def test_slot_reuse_after_finish(tiny):
    """3 requests through 1 slot: each drains through the same slot and must
    match a dedicated single-request generate (insert fully resets state)."""
    cfg, model, params = tiny
    engine = ServeEngine(model, params, max_seq=24)
    reqs = [Request(rid=i, prompt=np.asarray(_prompts(cfg, 1, 6, seed=i)[0]),
                    max_new_tokens=6) for i in range(3)]
    outs, stats = engine.serve(reqs, num_slots=1, chunk=4)
    assert [o.rid for o in outs] == [0, 1, 2]
    assert stats.occupancy == 1.0
    for r, o in zip(reqs, outs):
        ref = engine.generate(jnp.asarray(r.prompt)[None], r.max_new_tokens)
        np.testing.assert_array_equal(np.asarray(ref.tokens[0]), o.tokens)
        np.testing.assert_allclose(np.asarray(ref.logprobs[0]), o.logprobs,
                                   atol=1e-4)


def test_masked_sampling_never_advances_done_slots(tiny):
    cfg, model, params = tiny
    engine = ServeEngine(model, params, max_seq=24)
    prompts = _prompts(cfg, 2, 8)
    cache, last_logits = engine.prefill(prompts)
    state = B.DecodeState(
        cache=cache._replace(pos=jnp.full((2,), 8, jnp.int32)),
        last_logits=last_logits.astype(jnp.float32),
        tokens=jnp.pad(prompts, ((0, 0), (0, 16))),
        lengths=jnp.full((2,), 8, jnp.int32),
        max_len=jnp.full((2,), 16, jnp.int32),
        done=jnp.array([True, False]),               # slot 0 already done
        active=jnp.array([True, True]),
        logprobs=jnp.zeros((2, 24), jnp.float32),
        key=jax.random.PRNGKey(0),
        temperature=jnp.zeros((2,), jnp.float32),
        top_k=jnp.zeros((2,), jnp.int32),
        top_p=jnp.ones((2,), jnp.float32))
    out = engine._chunk_fn(4)(engine.params, state)
    # done slot: frozen buffers, zero logprobs written
    np.testing.assert_array_equal(np.asarray(out.tokens[0]),
                                  np.asarray(state.tokens[0]))
    assert int(out.lengths[0]) == 8
    assert float(jnp.abs(out.logprobs[0]).sum()) == 0.0
    assert bool(out.done[0])
    # live slot advanced by the full chunk
    assert int(out.lengths[1]) == 12
    assert not bool(out.done[1])


def test_eos_stops_slot_early(tiny):
    cfg, model, params = tiny
    prompts = _prompts(cfg, 1, 8)
    ref = ServeEngine(model, params, max_seq=24).generate(prompts, 6)
    first = int(ref.tokens[0, 8])                    # greedy first new token
    engine = ServeEngine(model, params, max_seq=24, eos_id=first)
    out, stats = engine.serve(
        [Request(rid=0, prompt=np.asarray(prompts[0]), max_new_tokens=6)],
        num_slots=1, chunk=6)
    assert out[0].finish_reason == "eos"
    assert len(out[0].generated) == 1 and out[0].generated[0] == first


def test_degenerate_args_raise(tiny):
    cfg, model, params = tiny
    engine = ServeEngine(model, params, max_seq=24)
    req = [Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=4)]
    with pytest.raises(ValueError):
        engine.serve(req, num_slots=1, chunk=0)
    with pytest.raises(ValueError):
        engine.serve(req, num_slots=0, chunk=4)
    with pytest.raises(ValueError):
        engine.generate(_prompts(cfg, 1, 4), 0)


def test_idle_gap_admission_not_counted_as_refill(tiny):
    """An admission into a fully idle engine (after a clock fast-forward)
    is not a continuous-batching refill."""
    cfg, model, params = tiny
    engine = ServeEngine(model, params, max_seq=24)
    reqs = [Request(rid=0, prompt=np.asarray(_prompts(cfg, 1, 6, seed=0)[0]),
                    max_new_tokens=4, arrival_step=0),
            Request(rid=1, prompt=np.asarray(_prompts(cfg, 1, 6, seed=1)[0]),
                    max_new_tokens=4, arrival_step=100)]
    outs, stats = engine.serve(reqs, num_slots=2, chunk=4)
    assert len(outs) == 2
    assert stats.admissions == 0


def test_continuous_admission_and_occupancy(tiny):
    cfg, model, params = tiny
    engine = ServeEngine(model, params, max_seq=24)
    reqs = synthetic_stream(6, vocab_size=cfg.vocab_size, prompt_len=8,
                            max_new_tokens=8, arrival_rate=0.5, seed=1)
    outs, stats = engine.serve(reqs, num_slots=2, chunk=4)
    assert len(outs) == 6
    assert stats.admissions > 0                      # slots refilled mid-run
    assert 0.0 < stats.occupancy <= 1.0
    for r, o in zip(reqs, outs):
        assert o.rid == r.rid
        assert len(o.tokens) == len(r.prompt) + r.max_new_tokens
        assert o.finish_reason == "length"
        assert np.isfinite(o.logprobs).all()


# ---------------------------------------------------------------------------
# quantized parity through the new engine
# ---------------------------------------------------------------------------

def test_quantized_vs_raw_logprob_parity(tiny):
    cfg, model, params = tiny
    prompts = _prompts(cfg, 2, 8)
    raw = ServeEngine(model, params, max_seq=24)
    plan = fastewq_metadata_plan(cfg, "8bit-mixed")
    q = ServeEngine(model, params, max_seq=24, plan=plan)
    out_raw = raw.generate(prompts, 8)
    out_q = q.generate(prompts, 8)
    assert out_raw.tokens.shape == out_q.tokens.shape == (2, 16)
    agree = float((out_raw.tokens[:, 8:] == out_q.tokens[:, 8:]).mean())
    assert agree >= 0.5
    # where greedy tokens agree, chosen-token logprobs must be close
    same = np.asarray(out_raw.tokens[:, 8:] == out_q.tokens[:, 8:])
    lp_r = np.asarray(out_raw.logprobs)[same]
    lp_q = np.asarray(out_q.logprobs)[same]
    np.testing.assert_allclose(lp_r, lp_q, atol=0.05)
    assert q.weight_bytes() < raw.weight_bytes()


# ---------------------------------------------------------------------------
# mesh-parallel serving (docs/DESIGN.md §9) — 8 virtual devices, subprocess
# ---------------------------------------------------------------------------

def _run_subprocess(code: str):
    """XLA_FLAGS must be set before jax import, hence a subprocess."""
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=560,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_sharded_serve_matches_single_device():
    """serve() on a 1x8 TP mesh returns the same tokens and (atol) logprobs
    as a single-device engine, for transformer AND hybrid under a mixed
    quantized plan; per-device weight bytes genuinely shrink."""
    out = _run_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_config
        from repro.models.model import build
        from repro.launch.mesh import make_mesh
        from repro.serving.engine import ServeEngine
        from repro.serving.quantized import fastewq_metadata_plan
        from repro.serving.scheduler import Request

        mesh = make_mesh((1, 8), ("data", "model"))
        for arch, layers_over in (("llama3.2-3b", {"num_layers": 2}),
                                  ("zamba2-2.7b", {})):
            cfg = dataclasses.replace(get_config(arch, smoke=True),
                                      dtype="float32", **layers_over)
            model = build(cfg)
            params = model.init(jax.random.PRNGKey(0))
            plan = fastewq_metadata_plan(cfg, "4bit/8bit")
            reqs = [Request(rid=i, prompt=np.asarray(jax.random.randint(
                        jax.random.PRNGKey(i), (6,), 0, cfg.vocab_size,
                        dtype=jnp.int32)), max_new_tokens=5)
                    for i in range(3)]
            ref = ServeEngine(model, params, max_seq=24, plan=plan)
            outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=4)
            eng = ServeEngine(model, params, max_seq=24, plan=plan, mesh=mesh)
            outs, _ = eng.serve(reqs, num_slots=2, chunk=4)
            for a, b in zip(outs, outs_ref):
                np.testing.assert_array_equal(a.tokens, b.tokens)
                np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-4)
            per_dev = eng.weight_bytes_per_device()
            single = ref.weight_bytes_per_device()
            assert per_dev < 0.5 * single, (arch, per_dev, single)
            print("OK", arch, per_dev / single)
    """)
    assert out.count("OK") == 2


def test_sharded_artifact_cold_boot_lands_sharded():
    """from_artifact(mesh=...) restores every weight leaf already sharded
    (no replicated materialization) and generates identically to the
    in-memory engine; a pure-DP mesh (no "model" axis) also serves."""
    out = _run_subprocess("""
        import dataclasses, tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_config
        from repro.models.model import build
        from repro.launch.mesh import make_mesh
        from repro.serving.engine import ServeEngine
        from repro.serving.quantized import explicit_plan
        from repro.quant.compiler import compile_plan, save_artifact
        from repro.quant.qtypes import QTensor

        cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                                  dtype="float32", num_layers=2)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        compiled = compile_plan(model, params,
                                explicit_plan(cfg, ["int8", "int4"]))
        d = tempfile.mkdtemp()
        mesh = make_mesh((1, 8), ("data", "model"))
        save_artifact(d, compiled, mesh=mesh)
        art = ServeEngine.from_artifact(model, d, max_seq=24, mesh=mesh)
        # every quantized payload is committed to the 8-device mesh, and at
        # least the stacked attention weights are genuinely TP-split
        qts = [l for l in jax.tree.leaves(
                   art.params["layers"],
                   is_leaf=lambda x: isinstance(x, QTensor))
               if isinstance(l, QTensor)]
        assert qts
        assert all(len(q.data.sharding.device_set) == 8 for q in qts)
        assert any("model" in q.data.sharding.spec for q in qts)
        mem = ServeEngine(model, compiled.params, max_seq=24)
        p = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                               cfg.vocab_size, dtype=jnp.int32)
        o_mem, o_art = mem.generate(p, 6), art.generate(p, 6)
        np.testing.assert_array_equal(np.asarray(o_mem.tokens),
                                      np.asarray(o_art.tokens))
        np.testing.assert_allclose(np.asarray(o_mem.logprobs),
                                   np.asarray(o_art.logprobs), atol=1e-4)
        dp = make_mesh((8,), ("data",))
        o_dp = ServeEngine(model, compiled.params, max_seq=24,
                           mesh=dp).generate(p, 6)
        np.testing.assert_array_equal(np.asarray(o_mem.tokens),
                                      np.asarray(o_dp.tokens))
        print("OK")
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# SLO scheduler mechanics (docs/DESIGN.md §14) — host-side, no model
# ---------------------------------------------------------------------------

def _req(rid, priority=1, arrival=0, **kw):
    return Request(rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=4,
                   arrival_step=arrival, priority=priority, **kw)


def test_scheduler_priority_ordering():
    """Ready queue pops by (priority, arrival, submit order)."""
    s = Scheduler(num_slots=1)
    for r in (_req(0, priority=2), _req(1, priority=0), _req(2, priority=1),
              _req(3, priority=0)):
        s.submit(r)
    order = [s.next_ready(0).rid for _ in range(4)]
    assert order == [1, 3, 2, 0]   # priority-0 pair FIFO, then 1, then 2


def test_scheduler_queue_timeout_and_cancel():
    s = Scheduler(num_slots=1)
    s.submit(_req(0, queue_timeout_steps=3))
    s.submit(_req(1))
    s.cancel(1)
    s.expire(clock=5)                          # both past their drop point
    assert s.next_ready(5) is None and s.all_done()
    reasons = {o.rid: o.finish_reason for o in s.finished}
    assert reasons == {0: "timeout", 1: "cancelled"}
    assert all(o.admitted_step == -1 for o in s.finished)
    assert s.timeouts == 1 and s.cancels == 1


def test_scheduler_deadline_applies_while_running():
    s = Scheduler(num_slots=1)
    s.submit(_req(0, deadline_steps=6))
    s.assign(0, s.next_ready(0), clock=0)
    assert s.drop_reason(s.active_slots()[0][1], clock=3) is None
    assert s.drop_reason(s.active_slots()[0][1], clock=6) == "deadline"


def test_scheduler_preempt_requeues_and_counts():
    s = Scheduler(num_slots=2)
    s.submit(_req(0, priority=2))
    s.submit(_req(1, priority=1))
    s.assign(0, s.next_ready(0), clock=0)      # rid 1 pops first (pri 1)
    s.assign(1, s.next_ready(0), clock=0)      # then rid 0 (pri 2)
    s.submit(_req(2, priority=0, arrival=4))
    # victim for a priority-0 waiter: the lowest-priority slot (rid 0)
    vslot = s.preempt_victim(0)
    assert vslot == 1
    # no victim for a priority-2 waiter (nothing strictly below it)
    assert s.preempt_victim(2) is None
    victim = s.preempt(vslot)
    assert victim.rid == 0 and s.preemptions == 1
    assert s.free_slots() == [1]
    # the victim is back in the ready queue at its own priority
    assert s.next_ready(4).rid == 2            # priority 0 first
    assert s.next_ready(4).rid == 0
    out = s.complete(0, np.arange(8, dtype=np.int32), np.zeros(4),
                     "length", 8)
    assert out.preempted == 0


def test_scheduler_reserve_activate_split():
    """A reserved (prefilling) slot is neither free nor active."""
    s = Scheduler(num_slots=2)
    s.submit(_req(0))
    s.reserve(0, s.next_ready(0), clock=0)
    assert s.free_slots() == [1]
    assert s.num_active == 0 and s.num_reserved == 1
    assert not s.all_done()
    assert s.reserved_request(0).rid == 0
    s.activate(0)
    assert s.num_active == 1 and s.num_reserved == 0


def test_synthetic_stream_poisson_deterministic():
    kw = dict(vocab_size=64, prompt_len=4, max_new_tokens=4,
              arrival_rate=0.5, poisson=True, seed=9)
    a = synthetic_stream(12, **kw)
    b = synthetic_stream(12, **kw)
    arr = [r.arrival_step for r in a]
    assert arr == [r.arrival_step for r in b]      # seeded: reproducible
    assert arr == sorted(arr) and arr[0] == 0
    assert arr != [int(i / 0.5) for i in range(12)]   # not fixed spacing
    fixed = synthetic_stream(12, **{**kw, "poisson": False})
    assert [r.arrival_step for r in fixed] == [int(i / 0.5)
                                               for i in range(12)]
    pri = synthetic_stream(8, **{**kw, "priorities": (0, 1)})
    assert [r.priority for r in pri] == [0, 1] * 4


# ---------------------------------------------------------------------------
# chunked prefill interleaving (docs/DESIGN.md §14)
# ---------------------------------------------------------------------------

def _family_requests(cfg, n=4, prompt_len=12, max_new=6, arrival=0.5):
    rng = np.random.RandomState(17)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       size=(prompt_len,)).astype(np.int32),
                    max_new_tokens=max_new,
                    arrival_step=int(i / arrival) if arrival else 0)
            for i in range(n)]


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid", "encdec"])
def test_chunked_prefill_matches_monolithic(trained, family):
    """Greedy serve() with prefill_chunk (non-dividing) is token-identical
    to monolithic prefill on every family."""
    cfg, model, params = trained[family]
    engine = ServeEngine(model, params, max_seq=24)
    reqs = _family_requests(cfg)
    outs_ref, _ = engine.serve(reqs, num_slots=2, chunk=4)
    outs_c, stats = engine.serve(reqs, num_slots=2, chunk=4,
                                 prefill_chunk=5)
    assert stats.prefill_chunks >= len(reqs) * 2   # 12 tokens / 5 -> 3 each
    for a, b in zip(outs_ref, outs_c):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-4)


@pytest.mark.parametrize("kv_precision", ["int8", "int4"])
def test_chunked_prefill_quantized_kv_parity(trained, kv_precision):
    """Chunked prefill fills a bf16 batch=1 cache; quantization happens at
    insert — so int8/int4 KV engines stay token-identical to monolithic."""
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=24,
                         kv_precision=kv_precision)
    reqs = _family_requests(cfg)
    outs_ref, _ = engine.serve(reqs, num_slots=2, chunk=4)
    outs_c, _ = engine.serve(reqs, num_slots=2, chunk=4, prefill_chunk=5)
    for a, b in zip(outs_ref, outs_c):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_chunked_prefill_spec_decode_parity(trained):
    """Spec engines admit chunked-prefilled slots exactly like monolithic
    ones (pos == lengths marks the fresh slot either way)."""
    from repro.serving.spec import SpecConfig
    cfg, model, params = trained["dense"]
    reqs = _family_requests(cfg)
    ref = ServeEngine(model, params, max_seq=24)
    outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=2)
    spec = ServeEngine(model, params, max_seq=24, spec=SpecConfig(k=2))
    outs_s, _ = spec.serve(reqs, num_slots=2, chunk=2, prefill_chunk=5)
    for a, b in zip(outs_ref, outs_s):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_engine_level_prefill_chunk_default(trained):
    """ServeEngine(prefill_chunk=...) applies when serve() doesn't pass
    one; serve(prefill_chunk=...) still overrides."""
    cfg, model, params = trained["dense"]
    reqs = _family_requests(cfg, n=2)
    ref = ServeEngine(model, params, max_seq=24)
    outs_ref, _ = ref.serve(reqs, num_slots=2, chunk=4)
    eng = ServeEngine(model, params, max_seq=24, prefill_chunk=4)
    outs, stats = eng.serve(reqs, num_slots=2, chunk=4)
    assert stats.prefill_chunks > 0
    for a, b in zip(outs_ref, outs):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    with pytest.raises(ValueError):
        ServeEngine(model, params, max_seq=24, prefill_chunk=0)


# ---------------------------------------------------------------------------
# SLO serving end-to-end: priorities, preemption, timeout, cancellation
# ---------------------------------------------------------------------------

def test_serve_priority_admission_order(trained):
    """With one slot, a later-arriving priority-0 request is admitted ahead
    of earlier priority-1 traffic that is still queued."""
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=24)
    reqs = _family_requests(cfg, n=4, arrival=0)      # rids 0-2 at step 0
    for r in reqs:
        r.priority = 1
    reqs[3].priority = 0
    reqs[3].arrival_step = 2                  # arrives after rid 0 admits
    outs, _ = engine.serve(reqs, num_slots=1, chunk=4)
    admits = {o.rid: o.admitted_step for o in outs}
    assert admits[0] == 0                     # first FIFO pick took the slot
    assert admits[3] < min(admits[1], admits[2])
    assert all(o.priority == r.priority for o, r in zip(outs, reqs))


def test_serve_preemption_roundtrip(trained):
    """A priority-0 arrival evicts the running priority-1 request
    (SLOConfig.preempt); the victim re-prefills from scratch and its final
    tokens are identical to an uncontended run."""
    from repro.serving.scheduler import SLOConfig
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=32)
    rng = np.random.RandomState(23)
    long_req = Request(rid=0, prompt=rng.randint(
        0, cfg.vocab_size, size=(8,)).astype(np.int32),
        max_new_tokens=16, priority=1)
    urgent = Request(rid=1, prompt=rng.randint(
        0, cfg.vocab_size, size=(8,)).astype(np.int32),
        max_new_tokens=4, arrival_step=4, priority=0)
    outs, stats = engine.serve([long_req, urgent], num_slots=1, chunk=4,
                               slo=SLOConfig(preempt=True))
    assert stats.preemptions == 1
    assert outs[0].preempted == 1 and outs[1].preempted == 0
    assert outs[0].finish_reason == "length"
    # the urgent request ran while the victim waited
    assert outs[1].admitted_step <= outs[0].admitted_step
    ref, _ = engine.serve([long_req], num_slots=1, chunk=4)
    np.testing.assert_array_equal(outs[0].tokens, ref[0].tokens)


def test_serve_queue_timeout_drops_without_slot(trained):
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=24)
    reqs = _family_requests(cfg, n=2, arrival=0, max_new=12)
    reqs[1].queue_timeout_steps = 4            # can't outwait rid 0
    outs, stats = engine.serve(reqs, num_slots=1, chunk=4)
    assert outs[0].finish_reason == "length"
    assert outs[1].finish_reason == "timeout"
    assert outs[1].admitted_step == -1 and len(outs[1].generated) == 0
    assert stats.timeouts == 1


def test_serve_cancel_running_keeps_partial_tokens(trained):
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=40)
    reqs = _family_requests(cfg, n=1, arrival=0, max_new=24)
    reqs[0].cancel_at_step = 8                 # mid-decode
    outs, stats = engine.serve(reqs, num_slots=1, chunk=4)
    assert outs[0].finish_reason == "cancelled"
    assert 0 < len(outs[0].generated) < 24     # partial output kept
    assert len(outs[0].logprobs) == len(outs[0].generated)
    assert stats.cancelled == 1
    # the partial tokens are a prefix of the uncontended run
    ref, _ = engine.serve(
        [dataclasses.replace(reqs[0], cancel_at_step=None)],
        num_slots=1, chunk=4)
    n = len(outs[0].tokens)
    np.testing.assert_array_equal(outs[0].tokens, ref[0].tokens[:n])


def test_serve_deadline_applies_while_running(trained):
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=40)
    reqs = _family_requests(cfg, n=1, arrival=0, max_new=24)
    reqs[0].deadline_steps = 8
    outs, _ = engine.serve(reqs, num_slots=1, chunk=4)
    assert outs[0].finish_reason == "deadline"
    assert 0 < len(outs[0].generated) < 24


def test_queue_delay_reported_separately_from_ttft(trained):
    """A request that waits for a slot reports queue_delay; TTFT starts at
    dequeue, so the wait does not inflate it."""
    cfg, model, params = trained["dense"]
    engine = ServeEngine(model, params, max_seq=24)
    reqs = _family_requests(cfg, n=3, arrival=0, max_new=8)
    outs, stats = engine.serve(reqs, num_slots=1, chunk=4)
    assert outs[0].queue_delay_steps == 0
    assert all(o.queue_delay_steps > 0 for o in outs[1:])   # waited
    assert all(o.queue_delay_s is not None and o.ttft_s is not None
               for o in outs)
    assert stats.queue_delay_p95_s >= stats.queue_delay_p50_s >= 0.0


# ---------------------------------------------------------------------------
# DP x TP replica serving (docs/DESIGN.md §14) — 8 virtual devices
# ---------------------------------------------------------------------------

def test_replica_router_is_load_aware():
    from repro.serving.replica import ReplicaServe
    r = ReplicaServe.__new__(ReplicaServe)
    r.engines = [object(), object()]
    reqs = [Request(rid=0, prompt=np.zeros(10, np.int32), max_new_tokens=10),
            Request(rid=1, prompt=np.zeros(2, np.int32), max_new_tokens=2),
            Request(rid=2, prompt=np.zeros(2, np.int32), max_new_tokens=2),
            Request(rid=3, prompt=np.zeros(2, np.int32), max_new_tokens=2)]
    buckets = r.route(reqs)
    # rid 0 weighs 20; rids 1-3 (4 each) all land on the other replica
    assert [q.rid for q in buckets[0]] == [0]
    assert [q.rid for q in buckets[1]] == [1, 2, 3]


def test_dp_replica_serve_matches_tp_only():
    """ReplicaServe on a 2x4 (data, model) mesh is greedy token-identical
    to the same stream on a 1x8 TP-only engine; per-replica occupancy and
    load-aware assignments are reported."""
    out = _run_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import get_config
        from repro.models.model import build
        from repro.launch.mesh import make_mesh, split_data_replicas
        from repro.serving.engine import ServeEngine
        from repro.serving.quantized import fastewq_metadata_plan
        from repro.serving.replica import ReplicaServe
        from repro.serving.scheduler import synthetic_stream

        cfg = dataclasses.replace(get_config("llama3.2-3b", smoke=True),
                                  dtype="float32", num_layers=2)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        plan = fastewq_metadata_plan(cfg, "4bit/8bit")
        reqs = synthetic_stream(6, vocab_size=cfg.vocab_size, prompt_len=8,
                                max_new_tokens=6, arrival_rate=0.5, seed=2)
        tp = ServeEngine(model, params, max_seq=24, plan=plan,
                         mesh=make_mesh((1, 8), ("data", "model")))
        outs_tp, _ = tp.serve(reqs, num_slots=2, chunk=4)

        mesh = make_mesh((2, 4), ("data", "model"))
        subs = split_data_replicas(mesh)
        assert len(subs) == 2
        assert all(dict(m.shape) == {"data": 1, "model": 4} for m in subs)
        rep = ReplicaServe([ServeEngine(model, params, max_seq=24,
                                        plan=plan, mesh=m) for m in subs])
        outs_dp, rstats = rep.serve(reqs, num_slots=2, chunk=4,
                                    prefill_chunk=3)
        assert rstats.replicas == 2
        assert sum(rstats.assignments) == len(reqs)
        assert all(n > 0 for n in rstats.assignments)  # both carried load
        assert len(rstats.occupancy_per_replica) == 2
        assert all(0.0 < o <= 1.0
                   for o in rstats.occupancy_per_replica)
        assert rstats.aggregate.generated_tokens == sum(
            st.generated_tokens for st in rstats.per_replica)
        for a, b in zip(outs_tp, outs_dp):
            assert a.rid == b.rid
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_slotted_decode_matches_lockstep(tiny):
    """Vector-pos decode (slotted cache) equals scalar-pos decode."""
    cfg, model, params = tiny
    b, s = 3, 10
    toks = _prompts(cfg, b, 1)
    ls, cs = model.decode_step(params, model.init_cache(b, s), toks)
    lv, cv = model.decode_step(params, model.slotted_cache(b, s), toks)
    np.testing.assert_allclose(np.asarray(ls, np.float32),
                               np.asarray(lv, np.float32), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(cv.pos), np.ones(b))
