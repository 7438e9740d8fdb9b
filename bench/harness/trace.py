"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
event dict (kept as JSON for the reducer's test); ``reduce`` computes, for
the traced window:

* ``busy_s``: the union of the device's program executions (the "XLA
  Modules" line of the device plane) inside the window;
* per-program device time (``programs``: module name without its hash ->
  [calls, seconds]);
* per-kernel device time (``kernels``): Pallas kernels are HLO custom
  calls whose instruction is named after the kernel (``%qmatmul_pallas.27
  = ...``), so events on the "XLA Ops" line are summed by that name, and
  by instruction and output shape (``calls``) for the kernels whose
  operations the compiled program's text gives;
* ``device_ops``: the ten leaf operations (events that contain no other
  event) with the most device time, by instruction name without its
  numeric suffix;
* ``idle_gaps``: device idle time inside the window, attributed to the
  benchmark's host span (``bench/...``) that overlaps each gap most.

All times on the trace's own clock, in nanoseconds, converted to seconds
on output.
"""

from __future__ import annotations

import collections
import glob
import re

from bench.harness.counts import call_key

DEVICE_PLANE = "/device:TPU:0"
HOST_PREFIX = "bench/"
KERNEL_SUFFIX = "_pallas"
_SUFFIX = re.compile(r"\.\d+")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def load(trace_dir: str) -> dict:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                out[key] += [[e.name, e.start_ns, e.duration_ns]
                             for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX)]
    return out


def module_name(name: str) -> str:
    return _MODULE.match(name).group(1)


def instr_name(name: str) -> str:
    """``%fusion.1252.remat_compressed = s8[...] copy(...)`` ->
    ``fusion.remat_compressed``: the instruction without its numbers."""
    head = name.split(" ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def _clip(start: float, dur: float, lo: float, hi: float) -> tuple:
    return max(start, lo), min(start + dur, hi)


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _leaves(ops: list) -> list:
    """Events that contain no other event (the trace nests a loop's body
    events inside the loop's own event)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    leaf = [True] * len(evs)
    stack: list = []
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
        stack.append(i)
    return [e for e, keep in zip(evs, leaf) if keep]


def reduce(events: dict, lo_ns: float, hi_ns: float) -> dict:
    """Reduce ``events`` over the window [lo_ns, hi_ns)."""
    win = (hi_ns - lo_ns) / 1e9
    mods = [(n, *_clip(s, d, lo_ns, hi_ns)) for n, s, d in events["modules"]]
    mods = [m for m in mods if m[2] > m[1]]
    busy = _union([[a, b] for _, a, b in mods])
    busy_s = sum(b - a for a, b in busy) / 1e9
    programs: dict = collections.defaultdict(lambda: [0, 0.0])
    for n, a, b in mods:
        p = programs[module_name(n)]
        p[0] += 1
        p[1] += (b - a) / 1e9
    ops = [e for e in events["ops"] if lo_ns <= e[1] < hi_ns]
    kernels: dict = collections.defaultdict(lambda: [0, 0.0])
    calls: dict = collections.defaultdict(lambda: [0, 0.0])
    for n, s, d in ops:
        name = instr_name(n)
        if name.endswith(KERNEL_SUFFIX):
            for table, key in ((kernels, name), (calls, call_key(n))):
                table[key][0] += 1
                table[key][1] += d / 1e9
    self_time: dict = collections.Counter()
    for n, s, d in _leaves(ops):
        self_time[instr_name(n)] += d / 1e9
    device_ops = [[n, t] for n, t in self_time.most_common(10)]
    # idle gaps inside the window, by the host span that overlaps most
    edges = [lo_ns] + [x for ab in busy for x in ab] + [hi_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    host = [(n, s, s + d) for n, s, d in events["host"]
            if s < hi_ns and s + d > lo_ns and n != HOST_PREFIX + "window"]
    by_span: dict = collections.Counter()
    for a, b in gaps:
        best, cover = "unattributed", 0.0
        for n, s, e in host:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = n, c
        by_span[best] += (b - a) / 1e9
    return {"window_s": win, "busy_s": busy_s,
            "programs": {k: list(v) for k, v in programs.items()},
            "kernels": {k: list(v) for k, v in kernels.items()},
            "calls": {k: list(v) for k, v in calls.items()},
            "device_ops": device_ops,
            "idle_gaps": [[n, t] for n, t in by_span.most_common(10)]}


def window(events: dict) -> tuple:
    """The benchmark's ``bench/window`` span: [start, end) in ns."""
    spans = [(s, s + d) for n, s, d in events["host"]
             if n == HOST_PREFIX + "window"]
    if not spans:
        raise ValueError("the trace holds no bench/window span")
    return spans[0]
