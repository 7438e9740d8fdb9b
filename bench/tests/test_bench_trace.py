"""The trace reducer, on a trimmed trace recorded on a TPU v5 lite."""

import gzip
import json
import pathlib

import numpy as np
import pytest

from bench.harness import trace as TR

DATA = pathlib.Path(__file__).with_name("data") / "trace-yi-prefill.json.gz"


@pytest.fixture(scope="module")
def events():
    return json.loads(gzip.decompress(DATA.read_bytes()))


def _busy_by_grid(events, lo, hi, step=1000.0):
    """Busy seconds counted on a 1 us grid: an independent union."""
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for _, s, d in events["modules"]:
        a = max(0, int((s - lo) // step))
        b = min(len(grid), int(np.ceil((s + d - lo) / step)))
        grid[a:b] = True
    return grid.sum() * step / 1e9


def test_busy_and_idle(events):
    lo, hi = events["window_ns"]
    r = TR.reduce(events, lo, hi)
    assert r["window_s"] == pytest.approx(0.110)
    assert r["busy_s"] == pytest.approx(0.109962436, abs=1e-9)
    assert r["busy_s"] == pytest.approx(_busy_by_grid(events, lo, hi),
                                        abs=2e-5)
    # a window that starts inside the gap after the 1024-token prefill
    gap = TR.reduce(events, 260.83e6, 262.2e6)
    assert gap["busy_s"] < gap["window_s"]


def test_programs_and_kernels(events):
    lo, hi = events["window_ns"]
    r = TR.reduce(events, lo, hi)
    calls, secs = r["programs"]["jit__prefill_impl"]
    assert calls == 2 and secs == pytest.approx(0.104002355, abs=1e-9)
    assert r["kernels"]["qmatmul_pallas"][0] == 15
    assert r["kernels"]["qkv_pallas"][0] == 5
    want = sum(d for n, s, d in events["ops"]
               if n.startswith("%qmatmul_pallas") and lo <= s < hi) / 1e9
    assert r["kernels"]["qmatmul_pallas"][1] == pytest.approx(want)
    assert sum(n for n, _ in r["calls"].values()) == sum(
        n for n, _ in r["kernels"].values())


def test_breakdown(events):
    lo, hi = events["window_ns"]
    r = TR.reduce(events, lo, hi)
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "fusion" and "qmatmul_pallas" in names
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    # every idle nanosecond lies under the one host span of this trace
    idle = sum(t for _, t in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    assert r["idle_gaps"][0][0] == "bench/probe"


def test_names():
    assert TR.instr_name("%fusion.1252.remat_compressed = s8[1] copy()") \
        == "fusion.remat_compressed"
    assert TR.instr_name("%qmatmul_pallas.27 = f32[8]") == "qmatmul_pallas"
    assert TR.module_name("jit_run(14285357246026604812)") == "jit_run"


def test_window_span():
    ev = {"host": [["bench/dispatch", 5, 1], ["bench/window", 10, 90]]}
    assert TR.window(ev) == (10, 100)
    with pytest.raises(ValueError):
        TR.window({"host": []})
