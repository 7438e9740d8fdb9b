"""Readings that set a cell's limits and rate, in one process on one set-up.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        [--seeds a,b,c ...] [--rates r1,r2 ...] [--out <file>]

``--rates`` serves one window of the cell's Poisson mix at each rate
(seed: the first of ``--seeds``) and prints its latency tails, how many
requests due in the window finished inside it, and the generator's lag:
the sweep that finds the knee. ``--seeds`` serves one window per seed,
then frees the program's state and reads, for each seed, the numbers
compared against the plain reference twice: for the program (the lower
readings) and for the control, the reference one precision step lower
in the program's place (the upper readings). Not run by ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from bench.harness import check, runner, spec, stats
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        sys.exit(2)
    cell = spec.load_cell(args.workload)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    rates = [float(x) for x in args.rates.split(",") if x]
    s = runner.setup(cell.config, cell.traffic)
    out = {"cell": cell.name, "sweep": [], "seeds": []}
    for rate in rates:
        s.traffic = dict(cell.traffic, rate=rate)
        w = runner.serve(s, seeds[0] if seeds else 1, args.seconds)
        inside = sum(1 for r in w.finished if r.finished <= w.t_close)
        row = {"rate": rate, "requests": len(w.reqs), "inside": inside,
               "ttft_p50_ms": stats.median(w.ttft_ms),
               "ttft_p95_ms": stats.percentile(w.ttft_ms, 95),
               "tpot_p50_ms": stats.median(w.tpot_ms),
               "tpot_p95_ms": stats.percentile(w.tpot_ms, 95),
               "lag_max_ms": max(w.lag_ms), "missing": w.missing,
               "compiles": w.n_compiles,
               "occupancy": sum(w.occupancy) / max(1, len(w.occupancy))}
        out["sweep"].append(row)
        print("sweep", json.dumps(row), flush=True)
    s.traffic = cell.traffic
    windows = []
    for seed in seeds:
        w = runner.serve(s, seed, args.seconds)
        windows.append((seed, w, runner.sample(w, seed)))
        print(f"seed {seed}: {len(w.finished)} finished, missing "
              f"{w.missing}, compiles {w.n_compiles}, tokens/s "
              f"{w.tokens_per_s}", flush=True)
    runner.free_engine(s)
    if windows:
        checker = check.Checker(cell.config, s.make_raw(), cell.limits)
        for seed, w, samples in windows:
            row = {"seed": seed,
                   "served": sum(len(r.tokens) - r.prompt_len
                                 for r in samples),
                   "program": checker.program(s.plan, samples, w.missing),
                   "control": checker.control(samples)}
            out["seeds"].append(row)
            print("readings", json.dumps(row), flush=True)
        out["reference_plan"] = {k: checker.plan[k]
                                 for k in ("precisions", "mu")}
        out["program_plan"] = s.plan
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
