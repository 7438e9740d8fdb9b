"""The MLA/MoE cell's counters against sums made by hand, its per-layer
readers on a made-up traced window, and a CPU run of the whole cell at
the program's tiny preset."""

import json
import math
import pathlib
import types

import jax
import pytest

from bench import run as R
from bench.harness import counts, counts_mla, runner, spec, system
from bench.tests import smoke

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "moonlight-16b-5L.longdoc-batch"
CONF = json.loads((ROOT / "bench" / "configs" / "moonlight-16b-5L.json")
                  .read_text())


def test_latent_row_bytes_by_hand():
    # 512 + 64 int8 values, 576 / 64 = 9 bf16 scales
    assert counts_mla.latent_row_bytes(CONF) == 576 + 9 * 2
    flops, nbytes = counts_mla.mla_decode_call(CONF, [300, 1025, 7])
    assert nbytes == 5 * 594 * 1332
    # QK over 576 and PV over 512 columns, 16 heads, 5 layers
    assert flops == 2 * 5 * 16 * (576 + 512) * 1332


def test_decode_token_flops_by_hand():
    d, h = 2048, 16
    proj = (d * h * 192 + d * 576 + h * 128 * 512 + h * 512 * 128
            + h * 128 * d)
    dense = 3 * d * 11264
    moe = d * 64 + 3 * d * 1408 * (6 + 2)
    head = 163840 * d
    want = 2 * (5 * proj + dense + 4 * moe + head) \
        + 2 * 5 * h * 1088 * 100
    assert counts_mla.decode_token_flops(CONF, 100) == want


def test_prefill_flops_by_hand():
    d, h, p = 2048, 16, 3
    proj = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    ffn = 3 * d * 11264 + 4 * (d * 64 + 3 * d * 1408 * 8)
    attn = 2 * 5 * h * (192 + 128) * (1 + 2 + 3)
    assert counts_mla.prefill_flops(CONF, p) == \
        2 * p * (5 * proj + ffn) + 2 * 163840 * d + attn


def _rec(trace):
    tick = types.SimpleNamespace(slots=[(1024, 10, 8), (4096, 0, 3)],
                                 admitted=[2048])
    return {"conf": CONF, "traffic": {"chunk": 8}, "trace": trace,
            "traced_ticks": [tick], "peaks": spec.peaks("TPU v5 lite"),
            "plan_s": 1.0, "occupancy": [1.0]}


def test_readers_on_a_made_up_window():
    cell = spec.load_cell(CELL)
    names = {m.name for m in cell.per_layer}
    assert names >= {"decode_step_ms.batch", "prefill_ms.longdoc",
                     "mfu.decode.longdoc", "mla_decode_roofline",
                     "occupancy_pct.batch", "device_idle_pct.batch"}
    trace = {"window_s": 2.0, "busy_s": 1.9,
             "programs": {"jit_run": [2, 0.4],
                          "jit__prefill_impl": [1, 0.25]},
             "kernels": {"decode_attn_pallas": [88, 0.05]}}
    read = {m.name: m.read(_rec(trace)) for m in cell.per_layer}
    assert read["decode_step_ms.batch"] == pytest.approx(25.0)
    assert read["prefill_ms.longdoc"] == pytest.approx(250.0)
    assert read["occupancy_pct.batch"] == pytest.approx(100.0)
    assert read["device_idle_pct.batch"] == pytest.approx(5.0)
    pk = spec.peaks("TPU v5 lite")
    rows = [[1024 + 10 + j + 1, 4096 + j + 1] if j < 3 else
            [1024 + 10 + j + 1] for j in range(8)]
    least = sum(counts.roofline_s(*counts_mla.mla_decode_call(CONF, r),
                                  pk["bf16_flops"], pk["hbm_bytes_per_s"])
                for r in rows)
    assert read["mla_decode_roofline"] == pytest.approx(100 * least / 0.05)
    flops = sum(counts_mla.decode_token_flops(CONF, p + g + j + 1)
                for p, g, n in [(1024, 10, 8), (4096, 0, 3)]
                for j in range(n))
    assert read["mfu.decode.longdoc"] == pytest.approx(
        100 * flops / (2.0 * pk["bf16_flops"]))
    untraced = {m.name: m.read(_rec(None)) for m in cell.per_layer}
    assert all(untraced[n] is None for n in
               ("decode_step_ms.batch", "prefill_ms.longdoc",
                "mfu.decode.longdoc", "mla_decode_roofline",
                "device_idle_pct.batch"))


def test_cell_runs_on_the_cpu_preset(monkeypatch):
    """The whole cell at the program's tiny preset: served, checked by the
    plain reference, every request finished with its length."""
    monkeypatch.setattr(system, "build_model", smoke.build_model)
    c = smoke.cell("smoke-moonlight", "smoke-backlog", CELL)
    res = R.run(c, smoke.args(2**31 + 23, 1.5), jax.devices(), smoke.PEAKS)
    checks = res["checks"]
    assert list(checks) == ["missing", "plan_mismatch", "logit_gap",
                            "compiles", "unchecked"]
    for name in ("missing", "plan_mismatch", "compiles", "unchecked"):
        assert checks[name]["value"] == 0, name
    assert math.isfinite(checks["logit_gap"]["value"])
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def _smoke_reference():
    from bench.harness import weights
    from bench.reference import mla_moe
    conf = smoke.load("smoke-moonlight")
    model = smoke.build_model(conf)
    raw = weights.make(model.abstract_params(), conf["num_layers"],
                       conf["weight_seed"])
    return mla_moe, conf, raw


def test_reference_screens_near_tied_routing(monkeypatch):
    """The reference at its own plan returns flat rows where some MoE
    layer's k-th and (k+1)-th choice values lie within ROUTE_MARGIN, so
    the check compares no token there; the control's stand-in (one
    precision step lower) screens nothing."""
    import jax.numpy as jnp
    import numpy as np
    from bench.harness import check
    mod, conf, raw = _smoke_reference()
    plan = mod.plan(raw, conf)["precisions"]
    ref = mod.Reference(raw, conf, plan)
    low = mod.Reference(raw, conf, [mod.LOWER[p] for p in plan])
    assert ref.screen and not low.screen
    toks = np.random.default_rng(3).integers(16, 512, 96).astype(np.int32)
    lg, margin = ref.forward(toks)
    assert bool(jnp.all(margin >= 0)) and bool(jnp.all(jnp.isfinite(margin)))
    # a threshold at the median screens about half the rows
    monkeypatch.setattr(mod, "ROUTE_MARGIN", float(jnp.median(margin)))
    tied = np.asarray(margin < mod.ROUTE_MARGIN)
    assert 0 < tied.sum() < len(toks)
    got = np.asarray(ref.logits(toks))
    assert not got[tied].any()
    np.testing.assert_array_equal(got[~tied], np.asarray(lg)[~tied])
    np.testing.assert_array_equal(np.asarray(low.logits(toks)),
                                  np.asarray(low.forward(toks)[0]))
    # served tokens the reference ranks low read a gap only where the
    # position is compared
    worst = np.asarray(jnp.argmin(lg, axis=-1)).astype(np.int32)
    p = 32
    served = np.concatenate([toks[:p], worst[p - 1:-1]])
    req = types.SimpleNamespace(tokens=served, prompt_len=p)
    gap = check.logit_gaps(ref, [req])
    lgp, mp = ref.forward(check._padded(served))
    lgp = np.asarray(lgp)[p - 1:95]
    keep = np.asarray(mp)[p - 1:95] >= mod.ROUTE_MARGIN
    assert 0 < keep.sum() < len(keep)
    got_tok = lgp[np.arange(len(lgp)), served[p:96]]
    want = (lgp.max(-1) - got_tok)[keep].max()
    assert gap == pytest.approx(float(want), rel=1e-5) and gap > 1.0


def test_calibrate_samples_on_the_cpu_preset(monkeypatch):
    """The one-session calibration at the tiny preset: its one-pass
    readings are the check's own, and its margin buckets cover every
    served position."""
    from bench import calibrate_samples as CS
    monkeypatch.setattr(system, "build_model", smoke.build_model)
    c = smoke.cell("smoke-moonlight", "smoke-backlog", CELL)
    out = CS.calibrate(c, [2**31 + 5, 7], 3, [0.0, 0.005])
    first = out["seeds"][0]
    for side in ("program", "control"):
        assert first["check"][side]["logit_gap"] == pytest.approx(
            first[side]["logit_gap"], abs=1e-6)
    assert all(r["missing"] == 0 and r["served"] > 0 for r in out["seeds"])
    assert sum(b["positions"] for b in out["buckets"]) == sum(
        r["served"] for r in out["seeds"])
    assert out["reference_plan"] == out["program_plan"]
