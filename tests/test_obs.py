"""Observability layer: tracer, metrics registry, serve-metric schema,
renderer (docs/DESIGN.md §16).

Five layers:

* the metrics registry (obs/metrics.py): counter/gauge/histogram
  semantics, label handling, exact quantiles, merge roll-up, and golden
  Prometheus / JSON expositions;
* the span tracer (obs/trace.py): B/E balance bookkeeping, the
  per-request phase state machine, abandon, and the Chrome trace_event
  JSON schema Perfetto loads;
* the facade (obs/__init__.py): off-by-default no-ops, install/restore,
  scoped capture;
* the serve-metric schema (obs/serve_metrics.py): two-way coverage
  between ``SCHEMA``/``STATS_FIELD_METRICS`` and the ``ServeStats``
  fields, and the publish -> stats_fields round trip;
* end-to-end leak freedom: ``open_spans() == []`` after plain streams,
  cancellation/preemption, OutOfPages backpressure and chaos-driven
  failover re-drive — and ``ServeStats`` back-compat across all four
  model families (traced or not, the snapshot is identical).
"""

import dataclasses
import json

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.serve_metrics import SCHEMA, STATS_FIELD_METRICS
from repro.obs.trace import DECODE_TRACK, ENGINE_TRACK, REQ_TRACK_BASE, Tracer


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_accumulates_per_label_set():
    reg = MetricsRegistry()
    c = reg.counter("serve_x_total", "help")
    c.inc(2, replica="0")
    c.inc(3, replica="0")
    c.inc(1, replica="1")
    assert c.value(replica="0") == 5
    assert c.value(replica="1") == 1
    assert c.total() == 6
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_gauge_set_is_level_not_flow():
    reg = MetricsRegistry()
    g = reg.gauge("serve_level")
    g.set(4.0, kind="peak")
    g.set(2.0, kind="peak")
    assert g.value(kind="peak") == 2.0
    g.inc(1.5, kind="peak")
    assert g.value(kind="peak") == 3.5


def test_registry_rejects_kind_conflicts_and_backfills_help():
    reg = MetricsRegistry()
    reg.counter("serve_x_total")            # created help-less (live emitter)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("serve_x_total")
    m = reg.counter("serve_x_total", "later help")
    assert m.help == "later help"           # schema-carrying call backfills


def test_histogram_quantiles_are_exact():
    reg = MetricsRegistry()
    h = reg.histogram("serve_lat_seconds")
    vals = [0.001 * i for i in range(1, 101)]
    for v in vals:
        h.observe(v, replica="0")
    assert h.count() == 100
    assert h.sum() == pytest.approx(sum(vals))
    assert h.quantile(50) == pytest.approx(np.percentile(vals, 50))
    assert h.quantile(95) == pytest.approx(np.percentile(vals, 95))
    assert h.max() == pytest.approx(max(vals))
    assert reg.quantile("serve_lat_seconds", 50) == h.quantile(50)
    assert reg.quantile("serve_missing", 50) == 0.0


def test_histogram_label_superset_matching():
    h = Histogram("serve_lat_seconds")
    h.observe(0.1, replica="0", priority="0")
    h.observe(0.3, replica="0", priority="1")
    h.observe(0.5, replica="1", priority="1")
    assert sorted(h.samples()) == [0.1, 0.3, 0.5]          # aggregate
    assert h.samples(priority="1") == [0.3, 0.5]           # narrow one key
    assert h.samples(replica="0", priority="0") == [0.1]
    assert h.label_values("priority") == ["0", "1"]


def test_merge_counters_add_gauges_take_level_histograms_add():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("serve_x_total").inc(1, replica="0")
    b.counter("serve_x_total").inc(2, replica="0")
    a.gauge("serve_g").set(1.0)
    b.gauge("serve_g").set(9.0)
    a.histogram("serve_h_seconds").observe(0.1)
    b.histogram("serve_h_seconds").observe(0.2)
    a.merge(b)
    assert a.get("serve_x_total").value(replica="0") == 3
    assert a.get("serve_g").value() == 9.0
    assert sorted(a.get("serve_h_seconds").samples()) == [0.1, 0.2]
    assert a.get("serve_h_seconds").count() == 2
    bad = MetricsRegistry()
    bad.histogram("serve_h_seconds", buckets=(1.0, 2.0)).observe(0.5)
    with pytest.raises(ValueError, match="bucket mismatch"):
        a.merge(bad)


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", "finished requests").inc(
        3, replica="0", reason="eos")
    reg.gauge("serve_occupancy_ratio", "mean active fraction").set(0.5)
    h = reg.histogram("serve_ttft_seconds", "time to first token",
                      buckets=(0.1, 1.0))
    h.observe(0.05, replica="0")
    h.observe(0.5, replica="0")
    assert reg.to_prometheus() == (
        "# HELP serve_occupancy_ratio mean active fraction\n"
        "# TYPE serve_occupancy_ratio gauge\n"
        "serve_occupancy_ratio 0.5\n"
        "# HELP serve_requests_total finished requests\n"
        "# TYPE serve_requests_total counter\n"
        'serve_requests_total{reason="eos",replica="0"} 3\n'
        "# HELP serve_ttft_seconds time to first token\n"
        "# TYPE serve_ttft_seconds histogram\n"
        'serve_ttft_seconds_bucket{replica="0",le="0.1"} 1\n'
        'serve_ttft_seconds_bucket{replica="0",le="1"} 2\n'
        'serve_ttft_seconds_bucket{replica="0",le="+Inf"} 2\n'
        'serve_ttft_seconds_sum{replica="0"} 0.55\n'
        'serve_ttft_seconds_count{replica="0"} 2\n')


def test_json_snapshot_is_stable_and_round_trips(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve_b_total", "b").inc(1, replica="1")
    reg.counter("serve_a_total", "a").inc(2)
    reg.histogram("serve_h_seconds", "h").observe(0.01)
    snap = json.loads(reg.to_json())
    assert list(snap) == sorted(snap)               # sorted family names
    assert snap["serve_a_total"]["type"] == "counter"
    assert snap["serve_a_total"]["samples"][0] == {"labels": {}, "value": 2}
    assert snap["serve_h_seconds"]["buckets"] == list(DEFAULT_BUCKETS)
    assert snap["serve_h_seconds"]["samples"][0]["count"] == 1
    reg.write_prometheus(str(tmp_path / "m.prom"))
    reg.write_json(str(tmp_path / "m.json"))
    assert json.loads((tmp_path / "m.json").read_text()) == snap
    assert "# TYPE serve_a_total counter" in (
        tmp_path / "m.prom").read_text()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_spans_balance_and_misnesting_asserts():
    tr = Tracer()
    tr.begin("serve/dispatch", 0)
    tr.begin("inner", 0)
    assert tr.open_spans() == [(0, ENGINE_TRACK, "serve/dispatch"),
                               (0, ENGINE_TRACK, "inner")]
    tr.end("inner", 0)
    tr.end("serve/dispatch", 0)
    assert tr.open_spans() == []
    tr.begin("a", 0)
    with pytest.raises(AssertionError, match="misnesting"):
        tr.end("b", 0)


def test_request_phase_state_machine_closes_previous():
    tr = Tracer()
    tr.request_phase(0, 3, "queued")
    tr.request_phase(0, 3, "prefill")
    tr.request_phase(0, 3, "decode")
    tr.request_done(0, 3, "finish", args={"reason": "eos"})
    assert tr.open_spans() == []
    counts = tr.counts()
    for phase in ("queued", "prefill", "decode"):
        assert counts[(f"request/{phase}", "B")] == 1
        assert counts[(f"request/{phase}", "E")] == 1
    assert counts[("request/finish", "i")] == 1
    # all on the request's own track
    assert all(ev["tid"] == REQ_TRACK_BASE + 3
               for ev in tr.events if ev["name"].startswith("request/"))


def test_abandon_closes_one_track():
    tr = Tracer()
    tr.begin("a", 1, DECODE_TRACK)
    tr.begin("b", 1, DECODE_TRACK)
    tr.begin("c", 0)
    tr.abandon(1, DECODE_TRACK, reason="quarantine")
    assert tr.open_spans() == [(0, ENGINE_TRACK, "c")]
    ends = [ev for ev in tr.events if ev["ph"] == "E"]
    assert [e["name"] for e in ends] == ["b", "a"]       # LIFO unwind
    assert all(e["args"]["reason"] == "quarantine" for e in ends)


def test_trace_json_schema_golden():
    tr = Tracer()
    tr.set_process_name(0, "replica0")
    tr.set_process_name(0, "replica0")                   # idempotent
    tr.begin("serve/dispatch", 0)
    tr.end("serve/dispatch", 0)
    t0 = tr.now_us()
    tr.complete("decode/chunk", t0, 0, DECODE_TRACK, args={"steps": 4})
    tr.instant("chaos/fire", 0, args={"site": "pool.oom"})
    doc = tr.to_json()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert json.loads(json.dumps(doc)) == doc            # serializable
    # one process_name + two thread_name M records, emitted once
    assert sum(e["ph"] == "M" for e in evs) == 3
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    for e in by_ph.get("B", []) + by_ph.get("E", []) + by_ph.get("i", []):
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e)
    (x,) = by_ph["X"]
    assert x["dur"] >= 0 and x["ts"] == pytest.approx(t0)
    assert x["args"] == {"steps": 4}
    (i,) = by_ph["i"]
    assert i["s"] == "t"
    assert len(by_ph["B"]) == len(by_ph["E"]) == 1


# ---------------------------------------------------------------------------
# facade
# ---------------------------------------------------------------------------

def test_off_by_default_and_install_restores():
    assert obs.tracer() is None and obs.metrics() is None
    assert not obs.enabled()
    # every free helper is a no-op with nothing installed
    obs.request_phase(0, 0, "queued")
    obs.request_done(0, 0, "finish")
    obs.instant("x", 0)
    obs.count("serve_x_total", 1)
    obs.observe("serve_x_seconds", 0.1)
    tr, mx = Tracer(), MetricsRegistry()
    prev = obs.install(tr, mx)
    try:
        assert obs.enabled()
        obs.instant("x", 0)
        obs.count("serve_x_total", 2, "help text", replica="0")
        obs.observe("serve_x_seconds", 0.5)
    finally:
        obs.install(*prev)
    assert obs.tracer() is None and obs.metrics() is None
    assert tr.counts()[("x", "i")] == 1
    assert mx.get("serve_x_total").value(replica="0") == 2
    assert mx.get("serve_x_total").help == "help text"
    assert mx.get("serve_x_seconds").count() == 1


def test_capture_is_scoped():
    with obs.capture() as (tr, mx):
        assert obs.tracer() is tr and obs.metrics() is mx
        obs.instant("y", 0)
    assert obs.tracer() is None
    assert tr.counts()[("y", "i")] == 1


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records enters and
    exits."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
        return Ann()


def test_span_enters_a_profiler_annotation_with_nothing_installed(
        monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(obs, "TraceAnnotation", ann)
    assert obs.tracer() is None
    with obs.span("serve/dispatch"):
        with obs.span("serve/launch"):
            pass
    assert ann.log == [("enter", "serve/dispatch"), ("enter", "serve/launch"),
                       ("exit", "serve/launch"), ("exit", "serve/dispatch")]


def test_span_writes_balanced_pairs_with_a_tracer(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(obs, "TraceAnnotation", ann)
    with obs.capture() as (tr, _):
        with obs.span("serve/harvest", 3):
            with obs.span("serve/complete", 3):
                pass
        with pytest.raises(RuntimeError):
            with obs.span("serve/dispatch", 3):
                raise RuntimeError("unwinds")
    assert tr.open_spans() == []
    names = [(e["name"], e["ph"]) for e in tr.events]
    assert names == [("serve/harvest", "B"), ("serve/complete", "B"),
                     ("serve/complete", "E"), ("serve/harvest", "E"),
                     ("serve/dispatch", "B"), ("serve/dispatch", "E")]
    assert all(e["pid"] == 3 and e["tid"] == ENGINE_TRACK
               for e in tr.events)
    assert [x for x, _ in ann.log].count("enter") == 3
    assert ann.log[-1] == ("exit", "serve/dispatch")


def test_span_annotation_is_real_outside_a_profiler_session():
    """With no profiler session recording, the real annotation is a no-op
    that still enters and exits cleanly."""
    with obs.span("serve/policy"):
        pass


# ---------------------------------------------------------------------------
# profiler capture window
# ---------------------------------------------------------------------------

def _fake_profiler(prof, calls):
    """Replace the jax.profiler start/stop with recorders."""
    def fake_start():
        calls.append("start")
        prof._capturing = True
    def fake_stop():
        if not prof._capturing:
            return
        prof._capturing = False
        prof.steps = None
        prof.windows += 1
        calls.append("stop")
    prof._start = fake_start
    prof.stop = fake_stop


def test_profile_window_triggers_on_crossing():
    """The decode clock advances by ``chunk`` per tick, so a window
    narrower than one stride must trigger on *crossing* A, not on a
    tick landing inside [A, B) — `1:3` with chunk=4 sees clocks
    0, 4, 8 and still records exactly one window."""
    prof = obs.ProfileHooks.parse("1:3")
    calls = []
    _fake_profiler(prof, calls)
    for clock in (0, 4, 8):
        prof.tick(clock)
    assert calls == ["start", "stop"]
    assert prof.windows == 1 and not prof._capturing
    # disarmed after one window: later ticks past A never re-open it
    prof.tick(12)
    assert calls == ["start", "stop"]


def test_profile_window_aligned_and_teardown_flush():
    prof = obs.ProfileHooks.parse("2:6")
    calls = []
    _fake_profiler(prof, calls)
    for clock in (0, 2, 4):
        prof.tick(clock)
    assert calls == ["start"] and prof._capturing
    prof.stop()              # session teardown flushes an open window
    assert calls == ["start", "stop"] and prof.windows == 1
    prof.stop()              # idempotent
    assert prof.windows == 1


@pytest.mark.parametrize("fails", ["start_trace", "stop_trace"])
def test_profile_failure_raises(monkeypatch, fails):
    """A capture that was asked for and could not start or stop fails the
    run instead of passing for one that recorded a trace."""
    import jax

    def broken(*a, **k):
        raise RuntimeError(f"{fails} unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, fails, broken)
    prof = obs.ProfileHooks.parse("0:4")
    with pytest.raises(RuntimeError, match=fails):
        prof.tick(0)
        prof.tick(4)
    assert prof.steps is None and prof.windows == 0   # never re-armed


def test_profile_parse_rejects_bad_specs():
    with pytest.raises(ValueError):
        obs.ProfileHooks.parse("3:1")
    with pytest.raises(ValueError):
        obs.ProfileHooks.parse("nope")


# ---------------------------------------------------------------------------
# serve-metric schema coverage
# ---------------------------------------------------------------------------

def test_schema_naming_conventions():
    for name, (kind, help_) in SCHEMA.items():
        assert name.startswith("serve_"), name
        assert kind in ("counter", "gauge", "histogram"), name
        assert help_, f"{name} has no help text"
        if kind == "counter":
            assert name.endswith("_total"), name
        if kind == "histogram":
            assert name.endswith("_seconds"), name


def test_every_stats_field_has_a_metric_and_vice_versa():
    from repro.serving.engine import ServeStats
    fields = {f.name for f in dataclasses.fields(ServeStats)} - {"registry"}
    assert fields == set(STATS_FIELD_METRICS), (
        fields ^ set(STATS_FIELD_METRICS))
    for field, metric in STATS_FIELD_METRICS.items():
        assert metric in SCHEMA, (field, metric)


def test_publish_stats_fields_round_trip():
    """publish -> stats_fields reconstructs exactly what went in, with
    quantiles matching np.percentile over the original lists."""
    from repro.obs.serve_metrics import publish_session, stats_fields

    @dataclasses.dataclass
    class Out:
        priority: int = 1
        finish_reason: str = "eos"
        preempted: int = 0
        ttft_s: float = 0.1
        tpot_s: float = 0.01
        queue_delay_s: float = 0.05

    outs = [Out(), Out(priority=0, finish_reason="timeout", ttft_s=0.3),
            Out(finish_reason="cancelled", preempted=2)]
    reg = MetricsRegistry()
    publish_session(
        reg, replica=1, outputs=outs, occupancy=0.75, num_chunks=5,
        chunk=4, admissions=2, generated=40, prefill_chunks=3,
        gaps=[0.02, 0.04], spec_m=dict(rounds=10, proposed=20, accepted=15,
                                       committed=25),
        spec_labels={"k": "2", "source": "self"}, watchdog_trips=1,
        degraded_steps=8, transitions=2, tier_steps=(12, 8),
        tier_labels=["bf16", "int8"], tuned="dense/int8",
        pool=dict(pages_total=6, pages_peak=5, page_size=8, prefix_hits=2,
                  prefix_hit_tokens=12, prompt_tokens=24, cow_copies=1,
                  kv_bytes_peak=4096.0),
        recovery=[0.2], restarts=1, redriven=4)
    f = stats_fields(reg)
    assert f["decode_steps"] == 20 and f["num_chunks"] == 5
    assert f["generated_tokens"] == 40 and f["admissions"] == 2
    assert f["occupancy"] == 0.75 and f["prefill_chunks"] == 3
    assert f["ttft_p95_s"] == pytest.approx(
        np.percentile([0.1, 0.3, 0.1], 95))
    assert f["preemptions"] == 2 and f["timeouts"] == 1
    assert f["cancelled"] == 1
    assert f["decode_gap_max_s"] == 0.04
    assert f["spec_rounds"] == 10
    assert f["acceptance_rate"] == pytest.approx(15 / 20)
    assert f["tokens_per_round"] == pytest.approx(25 / 10)
    assert f["pool_pages_total"] == 6 and f["pool_pages_peak"] == 5
    assert f["pool_page_size"] == 8 and f["cow_copies"] == 1
    assert f["prefix_hit_rate"] == pytest.approx(12 / 24)
    assert f["kv_bytes_peak"] == 4096.0
    assert f["tuned"] == "dense/int8"
    assert f["kv_tier_steps"] == (12, 8)
    assert f["degraded_steps"] == 8 and f["degrade_transitions"] == 2
    assert f["replica_restarts"] == 1 and f["redriven_requests"] == 4
    assert f["recovery_p95_s"] == pytest.approx(0.2)
    # the per-priority breakdown the flat fields aggregate away
    m = reg.get("serve_ttft_seconds")
    assert m.samples(priority="0") == [0.3]
    # every published family carries its schema help line
    prom = reg.to_prometheus()
    for name in reg.names():
        assert f"# HELP {name} {SCHEMA[name][1]}" in prom


def test_priority_report_needs_two_classes():
    from repro.obs.render import priority_report
    assert priority_report(None) == []
    reg = MetricsRegistry()
    assert priority_report(reg) == []
    reg.counter("serve_requests_total").inc(3, priority="1", reason="eos")
    assert priority_report(reg) == []                   # one class: silent
    reg.counter("serve_requests_total").inc(1, priority="0", reason="eos")
    reg.histogram("serve_ttft_seconds").observe(0.2, priority="0")
    reg.histogram("serve_ttft_seconds").observe(0.4, priority="1")
    lines = priority_report(reg)
    assert len(lines) == 2
    assert lines[0].lstrip().startswith("priority 0: 1 reqs")
    assert "ttft p50 200ms" in lines[0]


# ---------------------------------------------------------------------------
# end-to-end: span balance / leak freedom on the serving stack
# ---------------------------------------------------------------------------

def _requests(cfg, n=6, prompt_len=8, max_new=8, arrival_every=2, **kw):
    import jax
    import jax.numpy as jnp

    from repro.serving.scheduler import Request
    out = []
    for i in range(n):
        pr = np.array(jax.random.randint(jax.random.PRNGKey(10 + i),
                                         (prompt_len,), 0, cfg.vocab_size,
                                         dtype=jnp.int32))
        out.append(Request(rid=i, prompt=pr, max_new_tokens=max_new,
                           arrival_step=i * arrival_every, **kw))
    return out


def _balanced(tr):
    assert tr.open_spans() == []
    counts = tr.counts()
    b = sum(n for (_, ph), n in counts.items() if ph == "B")
    e = sum(n for (_, ph), n in counts.items() if ph == "E")
    assert b == e and b > 0
    return counts


def test_traced_stream_is_leak_free_and_stats_match(trained):
    from repro.serving.engine import ServeEngine
    cfg, model, params = trained["dense"]
    eng = ServeEngine(model, params, max_seq=18)
    reqs = _requests(cfg)
    ref_out, ref_stats = eng.serve(reqs, num_slots=2, chunk=4)
    with obs.capture() as (tr, mx):
        out, stats = eng.serve(reqs, num_slots=2, chunk=4)
    counts = _balanced(tr)
    # every request walked queued -> prefill -> decode -> finish
    assert counts[("request/prefill", "B")] == len(reqs)
    assert counts[("request/decode", "B")] == len(reqs)
    assert counts[("request/finish", "i")] == len(reqs)
    assert counts[("decode/chunk", "X")] == stats.num_chunks
    assert counts[("serve/dispatch", "B")] == counts[("serve/harvest", "B")]
    # the nested spans: one per admission, launch, read-back, completion
    for name, n in (("serve/admit", len(reqs)), ("serve/prefill", len(reqs)),
                    ("serve/insert", len(reqs)),
                    ("serve/launch", stats.num_chunks),
                    ("serve/readback", stats.num_chunks),
                    ("serve/complete", len(reqs)),
                    ("serve/release", len(reqs))):
        assert counts[(name, "B")] == counts[(name, "E")] == n, name
    # tracing changes no tokens and no counted stats
    for a, b in zip(ref_out, out):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    for f in ("decode_steps", "generated_tokens", "num_chunks",
              "admissions", "preemptions", "timeouts", "cancelled"):
        assert getattr(stats, f) == getattr(ref_stats, f)
    # the run merged into the installed registry
    assert mx.total("serve_generated_tokens_total") == stats.generated_tokens
    assert mx.get("serve_requests_total").value(
        replica="0", reason="length", priority="1") == len(reqs)


def test_traced_cancellation_preemption_leak_free(trained):
    from repro.serving.engine import ServeEngine
    from repro.serving.pool import PagedConfig
    from repro.serving.scheduler import SLOConfig
    cfg, model, params = trained["dense"]
    eng = ServeEngine(model, params, max_seq=34,
                      paged=PagedConfig(page_size=8))
    reqs = _requests(cfg, n=8, max_new=24, arrival_every=1,
                     priority=1)
    for r in reqs[::3]:
        r.cancel_at_step = r.arrival_step + 4
    for r in reqs[1::3]:
        r.queue_timeout_steps = 2
    reqs[-1].priority = 0          # late high-priority arrival -> preempt
    with obs.capture() as (tr, mx):
        out, stats = eng.serve(reqs, num_slots=2, chunk=4,
                               slo=SLOConfig(preempt=True))
    counts = _balanced(tr)
    assert stats.cancelled + stats.timeouts > 0
    assert counts.get(("request/finish", "i"), 0) == len(out)
    if stats.preemptions:
        assert counts[("request/preempt", "i")] == stats.preemptions
    eng.pool.check_invariants()
    # per-priority histograms recorded both classes
    m = mx.get("serve_requests_total")
    assert set(m.labeled("priority")) >= {"0", "1"}


def test_traced_out_of_pages_unwinds_leak_free(trained):
    from repro.serving.engine import ServeEngine
    from repro.serving.pool import OutOfPages, PagedConfig
    from repro.serving.scheduler import Request
    from repro.serving.session import DegradeConfig
    cfg, model, params = trained["dense"]
    eng = ServeEngine(model, params, max_seq=64,
                      paged=PagedConfig(page_size=8, pool_pages=1))
    req = Request(rid=0, prompt=np.zeros(32, np.int32), max_new_tokens=32)
    with obs.capture() as (tr, _):
        with pytest.raises(OutOfPages):
            eng.serve([req], num_slots=1, chunk=4, degrade=DegradeConfig())
    _balanced(tr)
    assert tr.counts().get(("request/redrive", "i"), 0) == 1


def test_traced_chaos_failover_redrive_leak_free(trained):
    from repro.serving import chaos
    from repro.serving.chaos import FaultConfig
    from repro.serving.engine import ServeEngine
    from repro.serving.pool import PagedConfig
    from repro.serving.replica import FailoverConfig, ReplicaServe
    cfg, model, params = trained["dense"]
    pc = PagedConfig(page_size=8, pool_pages=6)

    def two():
        return ReplicaServe([
            ServeEngine(model, params, max_seq=18, paged=pc),
            ServeEngine(model, params, max_seq=18, paged=pc)])

    reqs = _requests(cfg)
    ref_out, _ = two().serve(reqs, num_slots=2, chunk=4)
    with obs.capture() as (tr, mx):
        with chaos.chaos(FaultConfig.parse("replica_fault")):
            out, stats = two().serve(reqs, num_slots=2, chunk=4,
                                     failover=FailoverConfig())
    counts = _balanced(tr)
    agg = stats.aggregate
    assert counts[("replica/failover", "X")] == agg.replica_restarts == 1
    assert counts[("request/redrive", "i")] == agg.redriven_requests > 0
    assert counts[("chaos/fire", "i")] >= 1
    for a, b in zip(ref_out, out):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # router-level counters landed in the installed registry AND in the
    # aggregate's merged view
    assert mx.total("serve_replica_restarts_total") == 1
    assert mx.total("serve_chaos_faults_total") >= 1
    assert agg.registry.total("serve_replica_restarts_total") == 1
    assert agg.registry.quantile("serve_recovery_seconds", 95) > 0


# ---------------------------------------------------------------------------
# ServeStats back-compat across families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid", "encdec"])
def test_stats_view_round_trips_per_family(trained, family):
    """Every family's serve stats are a registry view: rebuilding the
    snapshot from the attached registry reproduces the dataclass
    field-for-field (registry is excluded from ==)."""
    from repro.serving.engine import ServeEngine, ServeStats
    cfg, model, params = trained[family]
    eng = ServeEngine(model, params, max_seq=18)
    out, stats = eng.serve(_requests(cfg, n=3), num_slots=2, chunk=4)
    assert len(out) == 3
    assert stats.generated_tokens > 0 and stats.num_chunks > 0
    assert 0.0 < stats.occupancy <= 1.0
    assert stats.ttft_p50_s >= 0.0
    assert stats.registry is not None
    assert ServeStats.from_registry(stats.registry) == stats
