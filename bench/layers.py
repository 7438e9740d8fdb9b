"""One traced run of a cell with the program's layer split.

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> \
        [--dump <dir>]

Runs ``bench/run.py``'s own ``run`` with ``--trace 1`` and two hooks: the
trace loader also keeps the program's ``serve/`` host spans
(``layers.load``), and before the engine is freed its decode-chunk and
prefill programs are compiled once more (``ServeEngine.compile_programs``,
one prefill per prompt length of the mix) for the scope map. It prints
``run``'s result line with a ``layers`` object added: ``spans``,
``idle_by_span``, ``scopes`` (``bench/harness/layers.py``) and the
readings of the layer metrics that read this cell. ``--dump`` writes,
to redo the split offline: the compiled texts (``texts/``), the window's
leaf seconds by op (``leaf_seconds.json.gz``), and ``slice.json.gz``, the
trace trimmed to two short windows of its second half with the scope map
of the instructions they hold (the reducer's tests read such slices):
``decode``, from the tail of a prefill into the decode chunk launched after
it, and ``completions``, from the first finished slot. Not run by the
benchmark's driver.
"""

from __future__ import annotations

import argparse
import gzip
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as R  # noqa: E402

# the dumped windows: before and after a decode chunk's start, and after
# a slot's completion
DECODE_NS = (20e6, 55e6)
COMPLETIONS_NS = (5e6, 30e6)


def compile_texts(s) -> dict:
    """Module name -> compiled texts of the cell's decode chunk and of its
    prefill for each prompt length."""
    engine, traffic = s.built.engine, s.traffic
    out: dict = {"jit_run": [], "jit__prefill_impl": []}
    for i, (p, _) in enumerate(traffic["prompt_lengths"]):
        progs = engine.compile_programs(int(p), traffic["slots"],
                                        chunk=traffic["chunk"])
        out["jit__prefill_impl"].append(progs["prefill"].as_text())
        if i == 0:
            out["jit_run"].append(progs["decode"].as_text())
    return out


def trim(events: dict, programs: dict, windows: dict, about: str) -> dict:
    """The events of ``windows`` (name -> [lo, hi)) and the scope-map
    entries they reach."""
    from bench.harness import layers
    spans = list(windows.values())

    def inside(evs):
        return [e for e in evs
                if any(e[1] < b and e[1] + e[2] > a for a, b in spans)]

    ops = [e for e in events["ops"] if any(a <= e[1] < b for a, b in spans)]
    names = sorted({n for n, _, _ in ops})
    index = {n: i for i, n in enumerate(names)}
    wanted = {layers.event_instr(n)[0] for n in names}
    maps: dict = {}
    for mod, tables in programs.items():
        maps[mod] = []
        for table in tables:
            keep, todo = {}, [i for i in wanted if i in table]
            while todo:          # the instructions and both their chains
                i = todo.pop()
                if i in keep or i not in table:
                    continue
                keep[i] = list(table[i])
                todo += [x for x in table[i][2:] if x is not None]
            maps[mod].append(keep)
    return {"about": about, "windows_ns": windows, "names": names,
            "modules": inside(events["modules"]),
            "ops": [[index[n], s, d] for n, s, d in ops],
            "host": inside(events["host"]),
            "program": inside(events["program"]), "programs": maps}


def windows(events: dict, lo: float, hi: float) -> dict:
    """The dumped windows of the traced window [lo, hi), those it has."""
    from bench.harness import trace as TR
    mid = (lo + hi) / 2
    mods = sorted((s, TR.module_name(n)) for n, s, _ in events["modules"]
                  if s >= mid)
    out = {}
    pre = next((s for s, n in mods if n == "jit__prefill_impl"), None)
    run = next((s for s, n in mods if pre is not None and s > pre
                and n == "jit_run"), None)
    if run is not None:
        out["decode"] = [max(lo, run - DECODE_NS[0]),
                         min(hi, run + DECODE_NS[1])]
    done = min((s for n, s, _ in events["program"]
                if n == "serve/complete" and s >= mid), default=None)
    if done is not None:
        out["completions"] = [max(lo, done - COMPLETIONS_NS[0]),
                              min(hi, done + COMPLETIONS_NS[1])]
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()
    from bench.harness import layers, spec
    from bench.harness import trace as TR
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        R.fail(f"no TPU: JAX found {devices[0].platform!r} devices")
    cell = spec.load_cell(args.workload)
    result, kept = measure(cell, args, devices,
                           spec.peaks(devices[0].device_kind))
    events, texts = kept["events"], kept["texts"]
    programs = {mod: [layers.parse_program(t) for t in ts]
                for mod, ts in texts.items()}
    lo, hi = TR.window(events)
    split = layers.reduce(events, lo, hi, programs)
    split["readings"] = layers.readings(split, cell.name)
    result["layers"] = split
    if args.dump:
        out = pathlib.Path(args.dump)
        (out / "texts").mkdir(parents=True, exist_ok=True)
        for mod, ts in texts.items():
            for i, t in enumerate(ts):
                path = out / "texts" / f"{mod}.{i}.txt.gz"
                with gzip.open(path, "wt") as f:
                    f.write(t)
        with gzip.open(out / "leaf_seconds.json.gz", "wt") as f:
            json.dump(layers.leaf_seconds(events, lo, hi), f)
        about = (f"{cell.name} seed {args.seed}: windows of a traced run "
                 f"on {devices[0].device_kind}")
        with gzip.open(out / "slice.json.gz", "wt") as f:
            json.dump(trim(events, programs, windows(events, lo, hi),
                           about), f)
    print(json.dumps(result))


def measure(cell, args, devices, peaks) -> tuple:
    """``run``'s traced result, and the events it read and the compiled
    texts of its programs."""
    from bench.harness import layers, runner
    from bench.harness import trace as TR
    kept: dict = {}
    load, free = TR.load, runner.free_engine

    def keep_load(trace_dir):
        kept["events"] = layers.load(trace_dir)
        return kept["events"]

    def keep_free(s):
        kept["texts"] = compile_texts(s)
        free(s)

    TR.load, runner.free_engine = keep_load, keep_free
    try:
        result = R.run(cell, types.SimpleNamespace(
            seed=args.seed, seconds=args.seconds, trace=1), devices, peaks)
    finally:
        TR.load, runner.free_engine = load, free
    return result, kept


if __name__ == "__main__":
    main()
