"""Run one benchmark cell once, on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's model from its configuration file: raw weights
made on the device from the configuration's weight seed, then the
program's own EWQ plan, quantization and serving engine. It warms every
program the cell's traffic can run, and for a backlog mix serves until
every slot has turned over once. The window then offers the traffic
generated from ``--seed``, open loop on the wall clock, for ``--seconds``.
A Poisson mix is served on after the window until every request due in
it has finished. With ``--trace 1`` a few seconds in the middle of the
window are traced and the per-layer metrics are reported instead of the
end-to-end ones.

Once the window has closed and the peak memory is read, the program's
state is freed and the plain reference checks the plan and a seeded
sample of the served tokens. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and ``checks``, each number compared
beside its limit; the same numbers end standard error.

Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell needs, or no program beside the benchmark.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
OUT = ROOT / ".bench_out"


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program beside the benchmark ({ROOT / 'src' / 'repro'})")
    from bench.harness import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}")
    result = run(cell, args, devices, spec.peaks(devices[0].device_kind))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))


def run(cell, args, devices, peaks: dict) -> dict:
    """One run of ``cell`` on ``devices``."""
    from bench.harness import check, runner
    dev = devices[0]
    s = runner.setup(cell.config, cell.traffic)
    print(f"{cell.name}: {runner.sizes(s)}", flush=True)
    w = runner.serve(s, args.seed, args.seconds,
                     trace_dir=(OUT / cell.name / "trace") if args.trace
                     else None)
    setup_s = w.t0 - T_PROCESS
    mem = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    for line in runner.describe(w, s, mem, setup_s):
        print(line, flush=True)

    # the program's state is freed before the reference runs
    runner.free_engine(s)
    samples = runner.sample(w, args.seed)
    numbers = check.Checker(cell.config, s.make_raw(), cell.limits).program(
        s.plan, samples, w.missing)
    print(f"reference: {len(samples)} requests, "
          f"{sum(len(r.tokens) - r.prompt_len for r in samples)} served "
          f"tokens compared")
    # nothing compiles inside the window, and some tokens were compared
    limits = dict(cell.limits, compiles=0, unchecked=0)
    numbers.update(compiles=w.n_compiles, unchecked=int(not samples))
    correct = check.decide(numbers, limits)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": len(w.reqs),
              "failed": int(w.missing)}
    if not args.trace:
        from bench.harness import stats
        values = {"setup_s": setup_s, "hbm_peak_gib": mem / 2**30,
                  "tokens_per_s": w.tokens_per_s}
        if w.poisson:
            values["ttft_p95_ms"] = stats.percentile(w.ttft_ms, 95)
            values["tpot_p50_ms"] = (stats.median(w.tpot_ms)
                                     if w.tpot_ms else None)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    else:
        rec = {"conf": cell.config, "traffic": cell.traffic, "peaks": peaks,
               "plan_s": s.plan_s, "occupancy": w.occupancy,
               "window_reqs": w.reqs, "traced_ticks": w.traced_ticks,
               "trace": w.trace, "kernel_calls": w.kernel_calls}
        metrics = {}
        for m in cell.per_layer:
            v = m.read(rec)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        if w.trace is not None:
            device["busy_s"] = w.trace["busy_s"]
            device["window_s"] = w.trace["window_s"]
            result["breakdown"] = {"device_ops": w.trace["device_ops"],
                                   "idle_gaps": w.trace["idle_gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": limits[k]}
                        for k, v in numbers.items()}
    return result


if __name__ == "__main__":
    main()
