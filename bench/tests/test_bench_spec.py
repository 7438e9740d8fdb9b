"""``BENCHMARK.json`` and the files it names agree."""

import importlib.util
import json
import pathlib
import re

import pytest

from bench.harness import spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(cell):
    c = spec.load_cell(cell)
    assert c.chips == 1
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "hbm_peak_gib"}
    assert len(c.end_to_end) >= 3 and c.per_layer
    # every per-layer metric moves an end-to-end metric this cell reports
    e2e = {m["name"] for m in c.end_to_end}
    for m in BENCH["per_layer"]:
        if cell in m.get("workloads", CELLS):
            assert m["moves"] in e2e


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_declares_what_the_benchmark_says(m):
    path = ROOT / "bench" / "metrics" / f"{m['name']}.py"
    s = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.MOVES) == \
        (m["layer"], m["unit"], m["better"], m["moves"])


def test_names_and_files():
    names = CELLS + [c["name"] for c in BENCH["configs"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
