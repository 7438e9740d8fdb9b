"""A CPU rehearsal of the driver loop on the program's tiny presets."""

import pytest

from bench.harness import runner
from bench.tests import smoke


@pytest.fixture(scope="module")
def burst():
    # one slot and a rate far above what it serves: requests queue
    c = smoke.cell("smoke-yi", "smoke-poisson", "yi-9b-12L.docqa")
    c.traffic.update(slots=1, rate=80.0)
    with smoke.presets():
        s = runner.setup(c.config, c.traffic)
    w = runner.serve(s, 2**31 + 3, 0.5)
    runner.free_engine(s)
    return w


def test_ttft_runs_from_the_due_time(burst):
    w = burst
    assert w.missing == 0 and len(w.finished) == len(w.reqs) == 40
    for r, ttft in zip(w.reqs, w.ttft_ms):
        assert r.due <= r.submitted <= r.admitted <= r.first <= r.finished
        assert ttft == pytest.approx((r.first - r.due) * 1e3)
    # with one slot, later requests wait for earlier ones: queueing is in
    # the latency, not only the request's own service
    service = sorted((r.finished - r.admitted) * 1e3 for r in w.reqs)
    assert max(w.ttft_ms) > 3 * service[len(service) // 2]
    assert max(r.queue_delay_s for r in w.reqs) > 0


def test_tokens_per_s_counts_running_requests():
    c = smoke.cell("smoke-minicpm", "smoke-backlog",
                   "yi-9b-12L.decode-batch")
    with smoke.presets():
        s = runner.setup(c.config, c.traffic)
    w = runner.serve(s, 7, 1.5)
    runner.free_engine(s)
    span = w.t_close - w.t0
    counted = w.tokens_per_s * span
    # requests finished in the window, each counted whole
    whole = sum(r.max_new for r in w.finished
                if w.t0 <= r.finished <= w.t_close)
    assert w.n_compiles == 0
    assert counted > 0
    # tokens of the requests still running at the close are counted, and
    # tokens made before the window are not
    running = [r for r in w.reqs if r.finished is None
               or r.finished > w.t_close]
    assert running
    assert counted != pytest.approx(whole)
