"""The comparison that decides ``correct``: the program as it is passes,
and a run with the timed path broken underneath does not. A single-chip
cell has no exchange between chips to leave out."""

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.harness import runner, system
from bench.tests import smoke
from repro.serving.engine import ServeEngine

SEED = 2**31 + 11


@pytest.fixture(autouse=True)
def _presets(monkeypatch):
    monkeypatch.setattr(system, "build_model", smoke.build_model)


def _run(monkeypatch, wrap=None):
    monkeypatch.setattr(runner, "DRAIN_S", 1.0)
    if wrap is not None:
        make = ServeEngine._make_chunk_fn
        monkeypatch.setattr(ServeEngine, "_make_chunk_fn",
                            lambda self, steps: wrap(make(self, steps)))
    c = smoke.cell("smoke-yi", "smoke-poisson", "yi-9b-12L.docqa")
    return R.run(c, smoke.args(SEED, 1.5), jax.devices(), smoke.PEAKS)


def test_sound_program_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"] is True
    assert list(res["checks"]) == ["missing", "plan_mismatch", "logit_gap",
                                   "compiles", "unchecked"]
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_state_returned_unchanged(monkeypatch):
    res = _run(monkeypatch, lambda fn: lambda params, state: state)
    assert res["correct"] is False
    assert res["checks"]["missing"]["value"] == res["attempted"]


def test_half_the_batch_left_out(monkeypatch):
    calls = [0]

    def wrap(fn):
        def run(params, state):
            new = fn(params, state)
            calls[0] += 1
            if calls[0] <= 2:          # the warm-up's two chunks run whole
                return new
            # every other slot, slot 0 (the first one filled) among them
            left = jnp.arange(state.lengths.shape[0]) % 2 == 0
            keep = lambda a, b: jnp.where(
                left.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
            return new._replace(tokens=keep(state.tokens, new.tokens),
                                lengths=keep(state.lengths, new.lengths),
                                done=keep(state.done, new.done))
        return run
    res = _run(monkeypatch, wrap)
    assert res["correct"] is False
    assert res["checks"]["missing"]["value"] > 0


def test_token_altered_where_produced(monkeypatch):
    def wrap(fn):
        def run(params, state):
            new = fn(params, state)
            at = jnp.arange(new.tokens.shape[1])[None, :]
            fresh = (at >= state.lengths[:, None]) \
                & (at < new.lengths[:, None])
            return new._replace(tokens=jnp.where(
                fresh, (new.tokens + 1) % 512, new.tokens))
        return run
    res = _run(monkeypatch, wrap)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_plan_altered_where_produced(monkeypatch):
    from repro.serving import quantized

    plan_for_variant = quantized.plan_for_variant

    def flipped(*a, **kw):
        plan = plan_for_variant(*a, **kw)
        return plan.with_precisions(["raw" if p == "int8" else "int8"
                                     for p in plan.precisions()])

    monkeypatch.setattr(quantized, "plan_for_variant", flipped)
    c = smoke.cell("smoke-minicpm", "smoke-backlog",
                   "yi-9b-12L.decode-batch")
    res = R.run(c, smoke.args(SEED, 1.0), jax.devices(), smoke.PEAKS)
    assert res["correct"] is False
    assert res["checks"]["plan_mismatch"]["value"] > 0
