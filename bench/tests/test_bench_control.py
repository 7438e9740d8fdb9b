"""The control: the plain reference one precision step lower, in the
program's place, comes out as not correct where the program does not."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import check, runner
from bench.tests import smoke


@pytest.fixture(scope="module")
def readings():
    c = smoke.cell("smoke-minicpm", "smoke-backlog",
                   "yi-9b-12L.decode-batch")
    with smoke.presets():
        s = runner.setup(c.config, c.traffic)
    windows = [(seed, runner.serve(s, seed, 1.0)) for seed in (1, 2, 3)]
    runner.free_engine(s)
    ck = check.Checker(c.config, s.make_raw(), c.limits)
    out = []
    for seed, w in windows:
        samples = runner.sample(w, seed)
        out.append((ck.program(s.plan, samples, w.missing),
                    ck.control(samples)))
    return c.limits, out


def test_program_passes_and_control_fails(readings):
    limits, rows = readings
    for prog, ctrl in rows:
        assert check.decide(prog, limits)
        assert not check.decide(ctrl, limits)
        assert ctrl["logit_gap"] > limits["logit_gap"]
        assert ctrl["entropy_gap"] > limits["entropy_gap"]


def test_limits_sit_between_the_readings(readings):
    limits, rows = readings
    lower = max(p["logit_gap"] for p, _ in rows)
    upper = min(c["logit_gap"] for _, c in rows)
    assert upper >= 3 * lower
    assert lower < limits["logit_gap"] < upper


class _Table:
    """A model whose logits are a fixed table, one row per position."""

    def __init__(self, rows):
        self.rows = jnp.asarray(rows, jnp.float32)

    def logits(self, tokens):
        return self.rows[:len(tokens)]


def test_control_is_read_where_the_program_is():
    # 4 prompt tokens, 2 served: rows 3 and 4 predict the served tokens
    ref = np.zeros((check.BUCKET, 3))
    ref[:, 0] = 1.0
    ref[4, 1] = 0.25
    other = ref.copy()
    other[1, 2] = 5.0      # disagrees inside the prompt: not read
    other[4, 1] = 5.0      # and at the last served position: read
    r = types.SimpleNamespace(tokens=[1, 2, 1, 2, 0, 0], prompt_len=4)
    assert check.logit_gaps(_Table(ref), [r]) == 0.0
    assert check.logit_gaps(_Table(ref), [r], other=_Table(other)) == \
        pytest.approx(0.75)
