"""The program's own instrumentation in a profiler trace, reduced to the
layer split: the serve loop's ``serve/*`` host spans (``obs.span``) and
the model step's named scopes (``embed``, ``attn``, ``mlp``, ``kv``,
``head``, ``sample``, and ``ewq/dequant`` around the jnp dequantize-and-
dot path of a quantized weight).

``load`` reads the ``.xplane.pb`` once and returns what
``bench.harness.trace.load`` returns (so ``trace.reduce`` reads it
unchanged) plus ``program``: the host events whose names start with
``serve/``. ``reduce`` adds, over the window:

* ``spans``: span name -> [spans that start in the window, seconds in it];
* ``idle_by_span``: each device-idle gap cut at the host spans' edges,
  each piece to the innermost span open over it (a ``serve/`` span nests
  inside the benchmark's ``bench/dispatch``/``bench/harvest``), else
  ``outside``;
* ``scopes``: for each program in ``PROGRAMS``, its leaf-op device
  seconds by top scope, the ``ewq/dequant`` seconds among them,
  ``unscoped`` (an instruction whose path holds no top scope) and
  ``unmatched`` (no instruction of the compiled text), and the ops that
  take the most time, by instruction and scope.

A TPU trace names each op by its instruction text (``%copy.108 = f32[..]
copy(f32[..] %add_convert_fusion)``) and carries no ``op_name``, so the
scope comes from the ``metadata={op_name=...}`` of the compiled program
that ran (``parse_program`` of ``ServeEngine.compile_programs``' text):
instruction names are unique in a module and a compile is deterministic.
A module name can stand for several programs (one prefill per prompt
length); the instruction's output type picks among them. The innermost
top scope of the ``op_name`` path wins (``kv`` holds the decode layer
scan, whose body's ``attn`` and ``mlp`` nest inside it). An instruction
with no top scope, such as a layout ``copy``, takes its first operand's;
where that chain ends in a parameter or the loop state with no scope
(XLA's clones, async copies and hoisted converts carry no metadata), its
first user's; and where that ends too, the scope of the instruction that
calls its computation (a loop XLA expands from a scatter keeps the
scatter's metadata on the ``while`` alone).
"""

from __future__ import annotations

import bisect
import collections
import glob
import re

from bench.harness import trace as TR

SPAN_PREFIX = "serve/"
TOP_SCOPES = ("embed", "attn", "mlp", "kv", "head", "sample")
DEQUANT = "ewq/dequant"
PROGRAMS = ("jit_run", "jit__prefill_impl")
OUTSIDE = "outside"
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) [a-z][\w-]*\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_DEPTH = 16          # hops along a chain before an instruction is unscoped


def load(trace_dir: str) -> dict:
    """``trace.load``'s events of the newest ``.xplane.pb`` under
    ``trace_dir``, plus ``program``: the ``serve/`` host spans."""
    import jax
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"modules": [], "ops": [], "host": [], "program": []}
    for plane in pd.planes:
        if plane.name == TR.DEVICE_PLANE:
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is not None:
                    out[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    for prefix, key in ((TR.HOST_PREFIX, "host"),
                                        (SPAN_PREFIX, "program")):
                        if e.name.startswith(prefix):
                            out[key].append([e.name, e.start_ns,
                                             e.duration_ns])
    return out


# ---------------------------------------------------------------------------
# the compiled program's scope map

def parse_program(text: str) -> dict:
    """Instruction name -> (output type, op_name or None, first operand,
    first user, caller: the instruction that calls its computation; each
    None where there is none), for every instruction of a compiled
    program's text (names are unique in a module)."""
    rows, users, callers, computation = [], {}, {}, None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name, typ, rest = m.groups()
        meta = _OP_NAME.search(rest)
        operands = _OPERAND.findall(rest[:rest.find(")")])
        for o in operands:
            users.setdefault(o, name)
        for one, many in _CALLED.findall(rest):
            for called in [one] if one else _OPERAND.findall(many):
                callers.setdefault(called, name)
        rows.append((name, typ, meta.group(1) if meta else None,
                     operands[0] if operands else None, computation))
    return {name: (typ, path, operand, users.get(name), callers.get(comp))
            for name, typ, path, operand, comp in rows}


def top_scope(path: str):
    """The innermost top scope named in an ``op_name`` path, else None."""
    for part in reversed(path.split("/")):
        if part in TOP_SCOPES:
            return part
    return None


def event_instr(name: str):
    """``%copy.108 = f32[8] copy(...)`` -> ("copy.108", "f32[8]")."""
    m = _INSTR.match(name)
    return (None, None) if m is None else m.group(1, 2)


def _scope(instr: str, table: dict, calls: int = _DEPTH) -> tuple:
    """(scope or None, dequant) of ``instr``: its own path's, else the
    first along its first-operand chain, then its first-user chain, then
    its computation's caller's."""
    for link in (2, 3):          # the first operand's chain, then the user's
        at = instr
        for _ in range(_DEPTH):
            entry = table.get(at)
            if entry is None:
                break
            path = entry[1]
            if path is not None and top_scope(path) is not None:
                return top_scope(path), DEQUANT in path
            at = entry[link]
    caller = table[instr][4]
    if caller is not None and caller in table and calls:
        return _scope(caller, table, calls - 1)
    return None, False


def resolve(name: str, maps: list):
    """(scope or None, dequant, matched) of the op event ``name`` against
    the parsed texts ``maps`` of the program it ran in."""
    instr, typ = event_instr(name)
    table = next((m for m in maps if m.get(instr, (None,))[0] == typ),
                 next((m for m in maps if instr in m), None))
    if table is None:
        return None, False, False
    return (*_scope(instr, table), True)


# ---------------------------------------------------------------------------
# the reduction

def _innermost_timeline(spans: list, lo: float, hi: float) -> list:
    """[start, end, name] segments covering [lo, hi): in each, the
    innermost of the (nested) spans open over it, else ``OUTSIDE``."""
    evs = sorted(((s, s + d, n) for n, s, d in spans if s < hi and s + d > lo),
                 key=lambda e: (e[0], -e[1]))
    out, stack, cursor = [], [], lo

    def emit(until):
        nonlocal cursor
        until = min(until, hi)
        if until > cursor:
            out.append([cursor, until, stack[-1][2] if stack else OUTSIDE])
            cursor = until

    for s, e, n in evs:
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(max(s, lo))
        stack.append((s, e, n))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return out


def _idle_by_span(events: dict, lo: float, hi: float) -> dict:
    mods = [TR._clip(s, d, lo, hi) for _, s, d in events["modules"]]
    busy = TR._union([[a, b] for a, b in mods])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in events["host"] if e[0] != TR.HOST_PREFIX + "window"]
    timeline = _innermost_timeline(spans + events["program"], lo, hi)
    starts = [seg[0] for seg in timeline]
    out: dict = collections.Counter()
    for a, b in gaps:
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(timeline) and timeline[i][0] < b:
            s, e, n = timeline[i]
            cover = min(b, e) - max(a, s)
            if cover > 0:
                out[n] += cover / 1e9
            i += 1
    return dict(out.most_common())


def _spans(events: dict, lo: float, hi: float) -> dict:
    out: dict = collections.defaultdict(lambda: [0, 0.0])
    for n, s, d in events["program"]:
        a, b = TR._clip(s, d, lo, hi)
        if b <= a:
            continue
        out[n][0] += int(lo <= s < hi)
        out[n][1] += (b - a) / 1e9
    return {k: v for k, v in sorted(out.items())}


def leaf_seconds(events: dict, lo: float, hi: float) -> dict:
    """Program in ``PROGRAMS`` -> {op event name: device seconds of its
    leaf events that start in the window}."""
    mods = sorted((s, s + d, TR.module_name(n))
                  for n, s, d in events["modules"])
    starts = [m[0] for m in mods]
    out: dict = {}
    for n, s, d in TR._leaves([e for e in events["ops"] if lo <= e[1] < hi]):
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1] or mods[i][2] not in PROGRAMS:
            continue
        by_name = out.setdefault(mods[i][2], collections.Counter())
        by_name[n] += d / 1e9
    return out


def split(leaves: dict, programs: dict) -> dict:
    """``leaf_seconds`` by top scope, per program."""
    out: dict = {}
    for prog, by_name in leaves.items():
        acc = {"total": 0.0, **{k: 0.0 for k in TOP_SCOPES}, DEQUANT: 0.0,
               "unscoped": 0.0, "unmatched": 0.0}
        ops: dict = collections.Counter()
        for n, t in by_name.items():
            scope, dq, matched = resolve(n, programs.get(prog, []))
            label = scope or ("unscoped" if matched else "unmatched")
            acc["total"] += t
            acc[label] += t
            if dq:
                acc[DEQUANT] += t
            ops[(TR.instr_name(n), label)] += t
        acc["ops"] = [[op, label, t]
                      for (op, label), t in ops.most_common(10)]
        out[prog] = acc
    return out


def reduce(events: dict, lo_ns: float, hi_ns: float, programs: dict) -> dict:
    """The layer split of ``events`` over the window [lo_ns, hi_ns);
    ``programs``: module name -> [``parse_program`` of each compiled text
    the module name stands for]."""
    return {"spans": _spans(events, lo_ns, hi_ns),
            "idle_by_span": _idle_by_span(events, lo_ns, hi_ns),
            "scopes": split(leaf_seconds(events, lo_ns, hi_ns), programs)}


# ---------------------------------------------------------------------------
# the readings of the layer metrics

def dequant_pct(layers: dict, program: str):
    """Share of ``program``'s leaf device time under ``ewq/dequant``."""
    sc = layers["scopes"].get(program)
    if not sc or sc["total"] <= 0:
        return None
    return 100.0 * sc[DEQUANT] / sc["total"]


def complete_idle_ms(layers: dict):
    """Device-idle ms under ``serve/complete`` (its ``serve/release``
    included) per request completed in the window."""
    done = layers["spans"].get(SPAN_PREFIX + "complete", [0, 0.0])[0]
    if not done:
        return None
    idle = sum(layers["idle_by_span"].get(SPAN_PREFIX + n, 0.0)
               for n in ("complete", "release"))
    return 1e3 * idle / done


# metric -> (the cell it reads, reader)
READINGS = {
    "dequant_pct.batch": ("yi-9b-12L.decode-batch",
                          lambda L: dequant_pct(L, "jit_run")),
    "dequant_pct.online": ("yi-9b-12L.docqa",
                           lambda L: dequant_pct(L, "jit_run")),
    "dequant_pct.prefill": ("yi-9b-12L.docqa",
                            lambda L: dequant_pct(L, "jit__prefill_impl")),
    "complete_idle_ms.batch": ("yi-9b-12L.decode-batch", complete_idle_ms),
}


def readings(layers: dict, cell: str) -> dict:
    out = {}
    for name, (c, read) in READINGS.items():
        v = read(layers) if c == cell else None
        if v is not None:
            out[name] = v
    return out
