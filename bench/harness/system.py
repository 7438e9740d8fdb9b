"""The system under test: the program's own launch steps, engine and serve
session. Everything the benchmark calls in the program goes through here.

The engine is built as the serving launcher builds it: the program plans
(``plan_for_variant``) and compiles (``model.compile_plan``) the raw
weights the benchmark made, then ``ServeEngine`` takes the quantized
weights with the configuration's KV precision, slots, ``max_seq`` and
paged pool. The serve loop is ``ServeSession.dispatch()`` / ``harvest()``,
the loop ``ServeEngine.serve`` runs.
"""

from __future__ import annotations

import dataclasses
import sys
import time

from bench.harness.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
         "d_ff", "vocab_size", "tie_embeddings", "rope_theta", "norm_eps")


def build_model(conf: dict):
    """The program's model for ``conf``'s architecture."""
    from repro.configs.registry import get_config
    return model_for(get_config(conf["arch"]), conf)


def model_for(cfg, conf: dict):
    """The program's model for its config ``cfg`` cut to ``conf``'s depth;
    refuses a configuration whose sizes differ from the program's."""
    from repro.models.model import build
    cfg = dataclasses.replace(cfg, num_layers=conf["num_layers"])
    have = {k: getattr(cfg, k) for k in SIZES}
    want = {k: conf[k] for k in SIZES}
    if have != want:
        raise SystemExit(f"{conf['name']}: the program's sizes {have} "
                         f"differ from the configuration's {want}")
    return build(cfg)


@dataclasses.dataclass
class Built:
    engine: object
    plan: object
    plan_s: float


def build_engine(model, raw, conf: dict, traffic: dict) -> Built:
    """Plan, quantize and wrap ``raw`` in a serving engine. ``raw`` is
    consumed: the caller must drop its own reference."""
    import jax
    from repro.serving.engine import ServeEngine
    from repro.serving.quantized import plan_for_variant
    t0 = time.perf_counter()
    plan = plan_for_variant(model, raw, conf["variant"], fast=conf["fast"])
    compiled = model.compile_plan(raw, plan,
                                  kv_precision=conf["kv_precision"])
    jax.block_until_ready(compiled.params)
    plan_s = time.perf_counter() - t0
    paged = None
    if conf.get("page_size"):
        from repro.serving.pool import PagedConfig
        paged = PagedConfig(page_size=conf["page_size"])
    engine = ServeEngine(model, compiled.params, max_seq=traffic["max_seq"],
                         kv_precision=compiled.kv_plan or "bf16",
                         paged=paged, autotune=False)
    engine.plan = plan
    if engine.kv_plan is not None and engine.kv_plan.group != conf["kv_group"]:
        raise SystemExit(f"the program's KV group {engine.kv_plan.group} "
                         f"differs from the configuration's "
                         f"{conf['kv_group']}")
    return Built(engine=engine, plan=plan, plan_s=plan_s)


def session(engine, traffic: dict, seed: int):
    import jax
    from repro.serving.session import ServeSession
    return ServeSession(engine, [], num_slots=traffic["slots"],
                        chunk=traffic["chunk"],
                        key=jax.random.PRNGKey(seed % (2 ** 31)))


def request(rid: int, prompt, max_new: int):
    from repro.serving.scheduler import Request
    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new)


def plan_record(plan) -> dict:
    """The program's plan as the check reads it."""
    return {"precisions": plan.precisions(),
            "entropies": [d.entropy for d in plan.decisions],
            "mu": plan.mu, "counts": {k: v for k, v in plan.counts().items()
                                      if v}}


def warm(sess, traffic: dict, rid0: int) -> int:
    """Compile, before the window, every program the window can run: one
    request per prompt length through the session's own loop (prefill,
    insert, decode chunk, release), then the read-backs of a finished
    slot for every (prompt, output) length pair of the mix. A loop that
    makes no progress stops after twice the ticks the warm requests need
    (the window then finds its slots taken). Returns the next free
    request id."""
    import jax
    from bench.harness import traffic as T
    g = min(T.output_levels(traffic["output"]))
    for i, (p, _) in enumerate(traffic["prompt_lengths"]):
        prompt = T.warm_prompt(i, int(p), sess.engine.cfg.vocab_size)
        sess.sched.submit(request(rid0, prompt, g))
        rid0 += 1
    for _ in range(2 * (g // traffic["chunk"] + 2) * len(
            traffic["prompt_lengths"])):
        if sess.done:
            break
        sess.dispatch()
        sess.harvest()
    for p, g in T.shapes(traffic):
        jax.device_get(sess.state.tokens[0, :p + g])
        jax.device_get(sess.state.logprobs[0, p:p + g])
    return rid0


def weight_bytes(engine) -> float:
    return float(engine.weight_bytes())


def kv_bytes(engine, slots: int) -> float:
    return float(engine.kv_bytes_per_slot() * slots)
