"""The layer split of a trace: the program's ``serve/`` spans and the
model step's named scopes (``bench/harness/layers.py``), on synthetic
events, on trimmed traces recorded on a TPU v5 lite, and in a CPU
rehearsal of ``bench/layers.py``."""

import gzip
import json
import pathlib
import types

import jax
import pytest

from bench import layers as tool
from bench.harness import layers as L
from bench.harness import runner, system
from bench.harness import trace as TR
from bench.tests import smoke

DATA = pathlib.Path(__file__).with_name("data")
RECORDED = ("trace-yi-docqa-layers.json.gz",
            "trace-yi-decode-batch-layers.json.gz")


def _recorded(name: str) -> dict:
    return _recorded_from(DATA / name)


def _recorded_from(path: pathlib.Path) -> dict:
    d = json.loads(gzip.decompress(path.read_bytes()))
    names = d.pop("names")
    d["ops"] = [[names[i], s, dur] for i, s, dur in d["ops"]]
    d["programs"] = {mod: [{k: tuple(v) for k, v in t.items()}
                           for t in tables]
                     for mod, tables in d["programs"].items()}
    return d


# ---------------------------------------------------------------------------
# synthetic events

def test_idle_goes_to_the_innermost_span():
    """Device busy [0, 10) and [40, 100). The idle [10, 40) meets
    bench/harvest [5, 37) holding serve/harvest [6, 36), in which
    serve/readback covers [12, 20) and serve/complete [20, 35) holds
    serve/release [30, 34)."""
    ev = {"modules": [["jit_run(1)", 0, 10], ["jit_run(1)", 40, 60]],
          "ops": [],
          "host": [["bench/window", 0, 100], ["bench/harvest", 5, 32]],
          "program": [["serve/harvest", 6, 30], ["serve/readback", 12, 8],
                      ["serve/complete", 20, 15], ["serve/release", 30, 4]]}
    r = L.reduce(ev, 0, 100, {})
    got = {k: round(v * 1e9, 6) for k, v in r["idle_by_span"].items()}
    assert got == {"serve/readback": 8, "serve/complete": 11,
                   "serve/release": 4, "serve/harvest": 3,
                   "bench/harvest": 1, "outside": 3}
    assert sum(r["idle_by_span"].values()) == pytest.approx(30e-9)
    assert r["spans"]["serve/complete"] == [1, pytest.approx(15e-9)]
    assert L.complete_idle_ms(r) == pytest.approx(1e3 * 15e-9)


def test_idle_outside_every_span_and_window_clipping():
    ev = {"modules": [["jit_run(1)", 50, 10]], "ops": [],
          "host": [["bench/idle_wait", -20, 40]],
          "program": [["serve/dispatch", 90, 30]]}
    r = L.reduce(ev, 0, 100, {})
    got = {k: round(v * 1e9, 6) for k, v in r["idle_by_span"].items()}
    assert got == {"bench/idle_wait": 20, "outside": 60, "serve/dispatch": 10}
    # the span that starts in the window counts; its seconds are clipped
    assert r["spans"]["serve/dispatch"] == [1, pytest.approx(10e-9)]


TEXT = """\
%body.1 (p: (s32[], s8[4,16])) -> (s32[], s8[4,16]) {
  %param.9 = (s32[], s8[4,16]) parameter(0)
  %gte.1 = s8[4,16]{1,0} get-tuple-element(%param.9), index=1
  %dynamic-update-slice.3 = s8[4,16]{1,0} dynamic-update-slice(%gte.1, %c.2)
  ROOT %tuple.1 = (s32[], s8[4,16]) tuple(%i.2, %dynamic-update-slice.3)
}

ENTRY %main {
  %p.1 = s8[8,4]{1,0} parameter(0), metadata={op_name="w"}
  %convert_fusion.3 = bf16[8,4]{1,0} fusion(%p.1), kind=kLoop, metadata={op_name="jit(run)/while/body/closed_call/kv/while/body/closed_call/mlp/ewq/dequant/convert"}
  %copy.7 = bf16[8,4]{0,1} copy(%convert_fusion.3)
  %copy.8 = bf16[8,4]{0,1} copy(%copy.7)
  %fusion.2 = f32[2,8]{1,0} fusion(%x.1, %copy.8), kind=kOutput, metadata={op_name="jit(run)/while/body/closed_call/kv/while/body/closed_call/attn/dot_general"}
  %p.2 = s8[8,4]{1,0} parameter(1)
  %convert.4 = f32[8,4]{1,0} convert(%p.2)
  %fusion.5 = f32[2,8]{1,0} fusion(%x.1, %convert.4), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(run)/while/body/closed_call/kv/while/body/closed_call/attn/ewq/dequant/dot_general"}
  %dus.1 = s8[4,16]{1,0} dynamic-update-slice(%c.1, %q.1), metadata={op_name="jit(run)/while/body/closed_call/kv/while/body/dynamic_update_slice"}
  %add.9 = s32[] add(%i.1, %one.1), metadata={op_name="jit(run)/while/body/add"}
  %while.2 = (s32[], s8[4,16]) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(run)/while/body/closed_call/kv/while/body/closed_call/attn/kv/vmap(vmap())/scatter"}
}
"""


def test_scope_map_first_operand_and_innermost_scope():
    table = L.parse_program(TEXT)
    assert table["copy.7"] == ("bf16[8,4]{0,1}", None, "convert_fusion.3",
                               "copy.8", None)
    assert table["gte.1"][4] == "while.2"
    maps = [table]
    # innermost top scope; the dequant flag rides along
    assert L.resolve("%convert_fusion.3 = bf16[8,4]{1,0} fusion(s8[8,4]{1,0}"
                     " %p.1), kind=kLoop", maps) == ("mlp", True, True)
    assert L.resolve("%fusion.2 = f32[2,8]{1,0} fusion(f32[2,4] %x.1)",
                     maps) == ("attn", False, True)
    # a layout copy with no scope takes its first operand's, through a
    # chain of copies
    assert L.resolve("%copy.8 = bf16[8,4]{0,1} copy(bf16[8,4]{0,1} "
                     "%copy.7)", maps) == ("mlp", True, True)
    # the scan's stacking of a layer's output is kv's
    assert L.resolve("%dus.1 = s8[4,16]{1,0} dynamic-update-slice(...)",
                     maps) == ("kv", False, True)
    # where the operand chain ends unscoped (a parameter, the loop
    # state), the first user's scope: the dequantized weight's convert
    # feeds the dot
    assert L.resolve("%convert.4 = f32[8,4]{1,0} convert(s8[8,4]{1,0} "
                     "%p.2)", maps) == ("attn", True, True)
    # where both chains end unscoped, the instruction that calls its
    # computation: the loop XLA expands from a scatter keeps the scatter's
    # metadata on the while alone
    assert L.resolve("%dynamic-update-slice.3 = s8[4,16]{1,0} "
                     "dynamic-update-slice(s8[4,16]{1,0} %gte.1)",
                     maps) == ("kv", False, True)
    # no scope any way is unscoped; an instruction the text lacks is
    # unmatched
    assert L.resolve("%add.9 = s32[] add(s32[] %i.1)", maps) == (
        None, False, True)
    assert L.resolve("%nowhere.1 = f32[] add()", maps) == (None, False,
                                                            False)


def test_output_type_picks_the_program():
    """One module name (a prefill per prompt length) stands for several
    compiled texts; the instruction's output type picks among them."""
    a = {"fusion.1": ("f32[1,1024]{1,0}", "jit(p)/attn/dot", None)}
    b = {"fusion.1": ("f32[1,2048]{1,0}", "jit(p)/mlp/dot", None)}
    assert L.resolve("%fusion.1 = f32[1,2048]{1,0} fusion()",
                     [a, b])[0] == "mlp"
    assert L.resolve("%fusion.1 = f32[1,1024]{1,0} fusion()",
                     [a, b])[0] == "attn"


def test_scopes_by_program_and_leaf():
    ev = {"modules": [["jit_run(7)", 0, 100], ["jit__insert_impl(3)", 100,
                                               10]],
          "ops": [["%while.1 = () while()", 0, 90],
                  ["%convert_fusion.3 = bf16[8,4]{1,0} fusion()", 5, 30],
                  ["%copy.8 = bf16[8,4]{0,1} copy(bf16[8,4] %copy.7)", 40,
                   20],
                  ["%dus.1 = s8[4,16]{1,0} dynamic-update-slice()", 60, 10],
                  ["%gone.1 = f32[] add()", 70, 5],
                  ["%fusion.9 = f32[] fusion()", 101, 5]],
          "host": [], "program": []}
    sc = L.reduce(ev, 0, 200, {"jit_run": [L.parse_program(TEXT)]}
                  )["scopes"]
    assert set(sc) == {"jit_run"}         # the insert program is not split
    r = sc["jit_run"]
    assert r["total"] == pytest.approx(65e-9)       # leaves only
    assert r["mlp"] == pytest.approx(50e-9)
    assert r["ewq/dequant"] == pytest.approx(50e-9)
    assert r["kv"] == pytest.approx(10e-9)
    assert r["unmatched"] == pytest.approx(5e-9)
    assert r["ops"][0] == ["convert_fusion", "mlp", pytest.approx(30e-9)]
    assert L.dequant_pct({"scopes": sc}, "jit_run") == pytest.approx(
        100 * 50 / 65)
    assert L.dequant_pct({"scopes": sc}, "jit__prefill_impl") is None


# ---------------------------------------------------------------------------
# recorded traces

@pytest.mark.parametrize("name", RECORDED)
def test_recorded_trace_keeps_the_reducer_unchanged(name):
    """The loader's added ``program`` key leaves ``trace.reduce`` as it
    was: every pre-existing key reads the same."""
    ev = _recorded(name)
    base = {k: ev[k] for k in ("modules", "ops", "host")}
    for lo, hi in ev["windows_ns"].values():
        assert TR.reduce(ev, lo, hi) == TR.reduce(base, lo, hi)
    old = json.loads(gzip.decompress(
        (DATA / "trace-yi-prefill.json.gz").read_bytes()))
    lo, hi = old["window_ns"]
    assert TR.reduce(dict(old, program=[]), lo, hi) == TR.reduce(old, lo, hi)


@pytest.mark.parametrize("name, cell, program_pct", [
    (RECORDED[0], "yi-9b-12L.docqa", {"dequant_pct.online": 41.4764,
                                      "dequant_pct.prefill": 25.8743}),
    (RECORDED[1], "yi-9b-12L.decode-batch", {"dequant_pct.batch": 40.4282}),
])
def test_recorded_decode_window(name, cell, program_pct):
    """From the tail of a prefill into a decode chunk: the device is busy
    while the host waits in ``serve/readback``; the scope map covers the
    chunk."""
    ev = _recorded(name)
    lo, hi = ev["windows_ns"]["decode"]
    r = L.reduce(ev, lo, hi, ev["programs"])
    base = TR.reduce(ev, lo, hi)
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(base["window_s"] - base["busy_s"], abs=1e-9)
    assert set(r["idle_by_span"]) == {"serve/readback"}
    assert r["spans"]["serve/readback"][1] == pytest.approx(base["window_s"])
    run = r["scopes"]["jit_run"]
    assert run["unmatched"] == 0.0
    assert run["unscoped"] <= 0.1 * run["total"]
    for scope in ("attn", "mlp", "kv", "head", "sample", "ewq/dequant"):
        assert run[scope] > 0, scope
    assert sum(run[k] for k in L.TOP_SCOPES) + run["unscoped"] == \
        pytest.approx(run["total"])
    assert r["scopes"]["jit__prefill_impl"]["ewq/dequant"] > 0
    got = L.readings(r, cell)
    assert set(got) == set(program_pct)
    for k, v in program_pct.items():
        assert got[k] == pytest.approx(v, abs=1e-4), k


def test_recorded_completion_burst():
    """Finished slots one after another: each ``serve/complete`` holds its
    read-backs and a ``serve/release``, and the chip idles between the
    release programs."""
    ev = _recorded(RECORDED[1])
    lo, hi = ev["windows_ns"]["completions"]
    r = L.reduce(ev, lo, hi, ev["programs"])
    assert r["spans"]["serve/complete"][0] == 4
    assert r["spans"]["serve/release"][0] == 4
    assert set(r["idle_by_span"]) == {"serve/complete", "serve/release"}
    assert r["scopes"] == {}                  # only release programs ran
    assert L.complete_idle_ms(r) == pytest.approx(4.8767, abs=1e-4)
    assert L.readings(r, "yi-9b-12L.decode-batch") == {
        "complete_idle_ms.batch": L.complete_idle_ms(r)}


# ---------------------------------------------------------------------------
# the tool, rehearsed on the CPU

def test_tool_keeps_spans_and_scope_map(monkeypatch, tmp_path):
    monkeypatch.setattr(system, "build_model", smoke.build_model)
    monkeypatch.setattr(runner, "DRAIN_S", 1.0)
    monkeypatch.setattr(runner, "TRACE_S", 1.0)
    c = smoke.cell("smoke-yi", "smoke-poisson", "yi-9b-12L.docqa")
    result, kept = tool.measure(
        c, types.SimpleNamespace(seed=2**31 + 5, seconds=2.0),
        jax.devices(), smoke.PEAKS)
    assert result["correct"] is True
    # the hooks are undone
    assert TR.load is not L.load and runner.free_engine.__name__ == \
        "free_engine"
    names = {n for n, _, _ in kept["events"]["program"]}
    assert {"serve/dispatch", "serve/harvest", "serve/launch",
            "serve/readback"} <= names
    progs = {mod: [L.parse_program(t) for t in ts]
             for mod, ts in kept["texts"].items()}
    assert len(progs["jit_run"]) == 1
    assert len(progs["jit__prefill_impl"]) == len(
        c.traffic["prompt_lengths"])
    paths = [e[1] for e in progs["jit_run"][0].values() if e[1]]
    assert {L.top_scope(p) for p in paths} >= set(L.TOP_SCOPES)
    lo, hi = TR.window(kept["events"])
    r = L.reduce(kept["events"], lo, hi, progs)
    assert r["spans"]["serve/dispatch"][0] >= 1
    # --dump's slice round-trips through the recorded-trace reader
    mid = (lo + hi) / 2
    path = tmp_path / "slice.json.gz"
    path.write_bytes(gzip.compress(json.dumps(tool.trim(
        kept["events"], progs, {"late": [mid, hi]}, "cpu")).encode()))
    ev = _recorded_from(path)
    assert ev["windows_ns"] == {"late": [mid, hi]}
    assert L.reduce(ev, mid, hi, ev["programs"]) == L.reduce(
        kept["events"], mid, hi, progs)
