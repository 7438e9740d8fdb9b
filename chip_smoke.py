"""Chip smoke: serve EWQ-quantized models end to end on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: yi-9b mesh serving only

Every phase goes through the serving launcher's own ``main``
(``repro.launch.serve``) in this one process, with random weights made
from ``--seed`` at the models' published widths:

* one chip —
  - minicpm-2b, all 40 layers, full EWQ ``8bit-mixed`` plan, int8 KV
    cache: served once with dense slots and once with the paged pool;
  - yi-9b at full width with its depth cut to 12 of 48 layers, FastEWQ
    ``4bit/8bit`` plan, int8 KV cache (its 256-token prefill runs the
    int8 and int4 ``qmatmul`` and ``qkv`` kernels).
* four chips — yi-9b at full width and depth, FastEWQ ``4bit/8bit``,
  int8 KV: served on a 1x4 tensor-parallel mesh, then as two 2-chip
  DP x TP replicas whose logprobs must match the 1x4 run wherever the
  two saw the same context.

Each phase must finish every request, and for the first requests the
engine's logprobs of the tokens it generated must match a teacher-forced
float32 forward (``model.apply`` on prompt + generated tokens with the
same quantized weights, the jnp dequant-then-dot path and "highest"
matmul precision) within the stated tolerances. The compiled prefill and
decode programs are listed with the Pallas kernels they hold; the
expected ones must be there. Any failure exits non-zero. The last line
of output is one JSON object naming the device.

Exits non-zero, with no result, where JAX finds no TPU or the
repository's sources are not next to this file.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import pathlib
import re
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

# Teacher-forced logprob tolerances, in nats, |engine - f32 reference| over
# every generated token of the checked requests. The engine serves in bf16
# activations with an int8 KV cache and f32-accumulating kernels; the
# reference recomputes the same quantized weights in f32 with no KV
# quantization. Both errors are O(1e-2) on logits of unit spread; a wrong
# kernel (e.g. one that drops the KV scales) moves logprobs by whole nats.
TOL_MEAN = 0.05
TOL_MAX = 0.5
CHECK_REQUESTS = 4

STREAM = ["--num-requests", "16", "--num-slots", "8", "--chunk", "8",
          "--max-new", "48"]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _arg(argv: list, flag: str) -> int:
    return int(argv[argv.index(flag) + 1])


def kernels_in(compiled) -> collections.Counter:
    """Pallas kernels (by their jitted wrapper's name) in a compiled
    program's HLO, one count per tpu_custom_call instruction."""
    names = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"jit\((\w+)\)/pallas_call", line)
            names[m.group(1) if m else "unnamed"] += 1
    return names


def teacher_forced_check(out: dict, n_check: int = CHECK_REQUESTS) -> dict:
    """|engine logprob - f32 teacher-forced logprob| over the generated
    tokens of the first ``n_check`` requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import build
    from repro.quant.qtypes import QTensor
    from repro.quant.quantize import dequantize

    model, engine = out["model"], out["engine"]
    cfg = model.cfg
    ref = build(dataclasses.replace(cfg, dtype="float32"))
    sel = out["outputs"][:n_check]
    t = max(len(o.tokens) for o in sel)
    toks = np.zeros((len(sel), t), np.int32)
    for i, o in enumerate(sel):
        toks[i, :len(o.tokens)] = o.tokens

    def chosen_logprobs(params, tokens):
        logits = ref.apply(params, {"tokens": tokens}, remat=False)[0]
        lp = jax.nn.log_softmax(
            logits[:, :-1, :cfg.vocab_size].astype(jnp.float32), -1)
        return jnp.take_along_axis(lp, tokens[:, 1:, None], -1)[..., 0]

    # the served weights dequantized as the jnp path dequantizes them, so
    # that no kernel runs in the reference
    params = jax.tree.map(
        lambda w: dequantize(w, jnp.bfloat16) if isinstance(w, QTensor)
        else w, engine.params, is_leaf=lambda w: isinstance(w, QTensor))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(chosen_logprobs)(params, jnp.asarray(toks)))
    del params
    diffs = np.concatenate([
        np.abs(o.logprobs - got[i, o.prompt_len - 1:len(o.tokens) - 1])
        for i, o in enumerate(sel)])
    return {"tokens": int(diffs.size), "mean": float(diffs.mean()),
            "max": float(diffs.max())}


def compare_runs(outputs: list, reference: list) -> dict:
    """|logprob - reference logprob| per request over the steps both runs
    saw the same context: every step up to and including the first where
    their greedy tokens differ. Meshes of other shapes reduce in another
    order, so on random weights a near tie can go either way; at that
    step each run's logprob is its own maximum over the same context, and
    those must still agree. Later steps continue different texts."""
    import numpy as np

    ref = {o.rid: o for o in reference}
    diffs, diverged = [], 0
    for o in outputs:
        r = ref[o.rid]
        n = min(len(o.generated), len(r.generated))
        other = np.flatnonzero(o.generated[:n] != r.generated[:n])
        k = int(other[0]) + 1 if other.size else n
        diverged += bool(other.size)
        diffs.append(np.abs(o.logprobs[:k] - r.logprobs[:k]))
    diffs = np.concatenate(diffs)
    return {"tokens": int(diffs.size), "mean": float(diffs.mean()),
            "max": float(diffs.max()), "diverged": diverged}


def device_gib(stat: str) -> float:
    """One of device 0's memory_stats() counters, in GiB (0: not reported)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get(stat, 0) / 2**30


def serve_phase(name: str, argv: list, counter, *, decode_kernels=(),
                prefill_kernels=(), compare_to=None) -> dict:
    """Serve through the launcher, then check completion, kernels and
    logprobs. Returns the launcher's result."""
    from repro.kernels.qmatmul.ops import _pallas_aligned
    from repro.launch import serve

    print(f"== {name}: serve {' '.join(argv)}", flush=True)
    in_use = device_gib("bytes_in_use")
    c0, t0 = counter.compile_s, time.perf_counter()
    out = serve.main(argv)
    wall = time.perf_counter() - t0
    cfg, outputs, requests = out["model"].cfg, out["outputs"], out["requests"]
    print(f"{name}: {cfg.name} {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads ({cfg.num_kv_heads} kv) of "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    served = sum(len(o.generated) for o in outputs)
    want = sum(r.max_new_tokens for r in requests)
    done = [o.finish_reason for o in outputs]
    print(f"{name}: {len(outputs)}/{len(requests)} requests finished "
          f"({collections.Counter(done)}), {served}/{want} tokens served")
    if len(outputs) != len(requests) or served != want:
        fail(f"{name}: not every request finished")

    engine = out["engine"]
    prompt_len, slots = _arg(argv, "--prompt-len"), _arg(argv, "--num-slots")
    programs = engine.compile_programs(prompt_len, slots,
                                       chunk=_arg(argv, "--chunk"))
    for prog, expect in (("prefill", prefill_kernels),
                         ("decode", decode_kernels)):
        found = kernels_in(programs[prog])
        print(f"{name}: {prog} program holds {sum(found.values())} "
              f"tpu_custom_call(s): {dict(found) or 'none'}")
        missing = set(expect) - set(found)
        if missing:
            fail(f"{name}: {prog} program lacks {sorted(missing)}")
    d = cfg.d_model
    path = ("Pallas qmatmul" if _pallas_aligned(slots, d, d)
            else "jnp dequant-then-dot (qmatmul needs M % 128 == 0)")
    print(f"{name}: decode weight matmuls at M={slots} slots take {path}")
    if engine.mesh is not None:
        mem = programs["decode"].memory_analysis()
        total = engine.weight_bytes()
        print(f"{name}: decode program arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB per device; "
              f"weights {total / 2**30:.2f} GiB in all, "
              f"{engine.weight_bytes_per_device() / 2**30:.2f} GiB on the "
              f"fullest device of {engine.mesh.size}")
        if engine.weight_bytes_per_device() > 0.75 * total:
            fail(f"{name}: a device holds a full copy of the weights")

    check = teacher_forced_check(out)
    print(f"{name}: logprob vs f32 teacher-forced reference over "
          f"{check['tokens']} tokens: mean |d| {check['mean']:.4f}, max "
          f"|d| {check['max']:.4f} (tolerance {TOL_MEAN} / {TOL_MAX})")
    if check["mean"] > TOL_MEAN or check["max"] > TOL_MAX:
        fail(f"{name}: logprobs outside tolerance")
    if compare_to is not None:
        check = compare_runs(outputs, compare_to)
        print(f"{name}: logprob vs the 1x4 run over {check['tokens']} "
              f"tokens of shared context: mean |d| {check['mean']:.4f}, max "
              f"|d| {check['max']:.4f}; {check['diverged']} of "
              f"{len(outputs)} requests took another greedy token at some "
              f"step (tolerance {TOL_MEAN} / {TOL_MAX})")
        if check["mean"] > TOL_MEAN or check["max"] > TOL_MAX:
            fail(f"{name}: logprobs differ from the 1x4 run")

    print(f"{name}: wall {wall:.1f}s, compile {counter.compile_s - c0:.1f}s, "
          f"device 0 bytes_in_use {in_use:.2f} GiB at the start, "
          f"peak_bytes_in_use {device_gib('peak_bytes_in_use'):.2f} GiB "
          f"(process high-water mark)", flush=True)
    return out


def one_chip(seed: int, counter) -> None:
    base = ["--train-steps", "0", "--seed", str(seed),
            "--kv-precision", "int8"]
    minicpm = ["--arch", "minicpm-2b", "--variant", "8bit-mixed", *base,
               "--prompt-len", "256", *STREAM]
    serve_phase("minicpm-2b dense", minicpm, counter,
                decode_kernels=["decode_attn_pallas"])
    gc.collect()
    serve_phase("minicpm-2b paged", minicpm + ["--paged", "--page-size",
                                               "64"], counter,
                decode_kernels=["decode_attn_pallas"])
    gc.collect()
    serve_phase("yi-9b 12 layers",
                ["--arch", "yi-9b", "--num-layers", "12", "--fast",
                 "--variant", "4bit/8bit", *base, "--prompt-len", "256",
                 *STREAM], counter,
                decode_kernels=["decode_attn_pallas"],
                prefill_kernels=["qmatmul_pallas", "qkv_pallas"])
    gc.collect()


def four_chips(seed: int, counter) -> None:
    yi = ["--arch", "yi-9b", "--train-steps", "0", "--seed", str(seed),
          "--fast", "--variant", "4bit/8bit", "--kv-precision", "int8",
          "--mesh", "data,model", "--prompt-len", "256", *STREAM]
    tp = serve_phase("yi-9b 1x4 TP", yi + ["--mesh-shape", "1,4"], counter)
    outputs = tp["outputs"]
    del tp
    gc.collect()
    serve_phase("yi-9b 2x2 DP x TP", yi + ["--mesh-shape", "2,2", "--dp"],
                counter, compare_to=outputs)
    gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve yi-9b over four chips (1x4 TP and 2x2 "
                         "DP x TP) and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        fail(f"the repository's sources are not at {SRC}")
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform!r} devices only")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        fail(f"need {need} TPU chip(s), JAX found {len(devices)}")

    from repro.launch.compile_cache import CompileCounter, enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print(f"device: {dev.device_kind} x {len(devices)}; compile cache "
          f"{cache_dir}", flush=True)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args.seed, counter)
    print(f"total: {time.perf_counter() - t0:.1f}s, backend compile "
          f"{counter.compile_s:.1f}s; compile cache {cache_dir}: "
          f"{counter.hits} hits, {counter.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
