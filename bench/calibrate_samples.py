"""Program and control readings of a cell's ``logit_gap`` on many seeds in
one session.

    python3 bench/calibrate_samples.py --workload <cell> --seeds a,b,... \
        [--requests 8] [--margins 0,0.002,0.005,0.01] [--out <file>]

The first ``--requests`` requests of each seed's traffic are served
together, on one set-up and in the cell's slots; then each seed's sample
(``check.sample``, drawn as ``bench/run.py`` draws it) is read against the
plain reference twice: for the program (the lower readings) and for the
control, the reference one precision step lower in the program's place
(the upper readings). ``bench/calibrate.py --seeds`` serves a window per
seed after every slot has turned over once, which in a backlog of
1k-token outputs costs minutes a seed; here the seeds share one session.

With a reference that gives routing margins (``Reference.forward``, the
MoE reference) the readings come from one pass per request, and each
seed's row also holds, for every ``--margins`` value, the share of its
served positions whose margin is at least that value and the largest gap
of the program and of the control there; ``buckets`` gives the same over
all seeds by margin range. Not run by ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BUCKETS = (0.0, 0.001, 0.002, 0.005, 0.01, float("inf"))


def serve_together(s, traffic: dict, seeds: list, requests: int) -> dict:
    """Every seed's first ``requests`` requests, served in one session:
    seed -> its requests (``driver.Req``), finished or not."""
    from bench.harness import driver as D, system
    from bench.harness import traffic as T
    sess = system.session(s.built.engine, traffic, seeds[0])
    system.warm(sess, traffic, rid0=10 ** 9)
    drv = D.Driver(sess, traffic)
    rids = {}
    for i, seed in enumerate(seeds):
        gen = T.generate(traffic, seed, s.conf["vocab_size"], 0.0)
        items = [dataclasses.replace(next(gen), rid=i * requests + j)
                 for j in range(requests)]
        rids[seed] = [it.rid for it in items]
        for it in items:
            drv.submit(it, time.perf_counter())
    while not sess.done:
        drv.tick()
    return {seed: [drv.reqs[r] for r in rs] for seed, rs in rids.items()}


def positions(checker, other, samples: list):
    """Per served position of ``samples``: the reference's routing margin
    and the gaps of the program's served token and of ``other``'s first
    token, from one pass of each."""
    import jax.numpy as jnp
    import numpy as np
    from bench.harness import check
    out = []
    for r in samples:
        toks = np.asarray(r.tokens, np.int32)
        t, p = len(toks), r.prompt_len
        padded = check._padded(toks)
        lg, margin = checker.ref.forward(padded)
        lg, margin = lg[p - 1:t - 1], margin[p - 1:t - 1]
        pick = jnp.argmax(other.logits(padded)[p - 1:t - 1], axis=-1)
        best = jnp.max(lg, axis=-1)
        at = lambda ids: jnp.take_along_axis(lg, ids[:, None], -1)[:, 0]
        out.append(np.stack([np.asarray(margin),
                             np.asarray(best - at(jnp.asarray(toks[p:]))),
                             np.asarray(best - at(pick))], 1))
    return np.concatenate(out)


def _worst(gaps) -> float:
    return float(gaps.max()) if len(gaps) else 0.0


def calibrate(cell, seeds: list, requests: int, margins: list) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from bench.harness import check, runner
    s = runner.setup(cell.config, cell.traffic)
    served = serve_together(s, cell.traffic, seeds, requests)
    runner.free_engine(s)
    checker = check.Checker(cell.config, s.make_raw(), cell.limits)
    mod, raw, conf = checker.mod, checker.raw, checker.conf
    low = mod.plan(raw, conf, dtype=jnp.bfloat16)
    other = mod.Reference(raw, conf, [mod.LOWER[p] for p in
                                      low["precisions"]])
    witness = hasattr(checker.ref, "forward")
    out = {"cell": cell.name, "requests": requests, "seeds": []}
    every = []
    for seed in seeds:
        reqs = served[seed]
        missing = sum(1 for r in reqs if r.tokens is None
                      or len(r.tokens) - r.prompt_len != r.max_new)
        samples = check.sample(reqs, seed, runner.SAMPLE_TOKENS,
                               runner.SAMPLE_REQUESTS)
        row = {"seed": seed, "missing": missing,
               "served": sum(len(r.tokens) - r.prompt_len
                             for r in samples)}
        if not witness:
            row["program"] = checker.program(s.plan, samples, missing)
            row["control"] = checker.control(samples)
        else:
            pos = positions(checker, other, samples)
            every.append(pos)
            keep = pos[:, 0] >= mod.ROUTE_MARGIN
            row["program"] = {"logit_gap": _worst(pos[keep, 1])}
            row["control"] = {"logit_gap": _worst(pos[keep, 2])}
            row["margins"] = [
                {"margin": m, "keep": float(np.mean(pos[:, 0] >= m)),
                 "program": _worst(pos[pos[:, 0] >= m, 1]),
                 "control": _worst(pos[pos[:, 0] >= m, 2])}
                for m in margins]
            if seed == seeds[0]:
                # the one-pass readings are the check's own
                row["check"] = {
                    "program": checker.program(s.plan, samples, missing),
                    "control": checker.control(samples)}
        out["seeds"].append(row)
        print("readings", json.dumps(row), flush=True)
    if every:
        pos = np.concatenate(every)
        out["buckets"] = []
        for lo, hi in zip(BUCKETS, BUCKETS[1:]):
            sel = pos[(pos[:, 0] >= lo) & (pos[:, 0] < hi)]
            out["buckets"].append({
                "margin": [lo, hi], "positions": int(len(sel)),
                "program": _worst(sel[:, 1]),
                "program_over_0.05": int(np.sum(sel[:, 1] > 0.05)),
                "control": _worst(sel[:, 2]),
                "control_over_0.05": int(np.sum(sel[:, 2] > 0.05))})
        print("buckets", json.dumps(out["buckets"]), flush=True)
    out["reference_plan"] = checker.plan["precisions"]
    out["program_plan"] = s.plan["precisions"]
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--margins", default="0,0.002,0.005,0.01")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench.harness import spec
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate_samples: no TPU", file=sys.stderr)
        sys.exit(2)
    out = calibrate(spec.load_cell(args.workload),
                    [int(x) for x in args.seeds.split(",") if x],
                    args.requests,
                    [float(x) for x in args.margins.split(",") if x])
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
