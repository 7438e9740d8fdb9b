"""One cell's set-up and measured windows, shared by ``bench/run.py`` (one
window) and ``bench/calibrate.py`` (several windows on one set-up)."""

from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import shutil
import time
from typing import Optional

from bench.harness import check, counts, driver as D, stats, system
from bench.harness import trace as TR
from bench.harness import traffic as T
from bench.harness import weights

TRACE_S = 8.0          # traced seconds, the last of the window
DRAIN_S = 60.0         # how long past the window a due request may take
TURNOVER_S = 300.0     # longest a backlog's set-up traffic may run
SAMPLE_TOKENS = 2048   # served tokens the reference checks, at least
SAMPLE_REQUESTS = 8    # and requests, at most


class Compiles:
    """Programs compiled, or read from the persistent cache, as JAX's
    monitoring events report them."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


@dataclasses.dataclass
class Setup:
    conf: dict
    traffic: dict
    model: object
    abstract: object
    built: Optional[system.Built]
    plan: dict
    plan_s: float
    compiles: Compiles

    def make_raw(self):
        return weights.make(self.abstract, self.conf["num_layers"],
                            self.conf["weight_seed"])


def setup(conf: dict, traffic: dict) -> Setup:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    compiles = Compiles()
    model = system.build_model(conf)
    abstract = model.abstract_params()
    s = Setup(conf=conf, traffic=traffic, model=model, abstract=abstract,
              built=None, plan={}, plan_s=0.0, compiles=compiles)
    s.built = system.build_engine(model, s.make_raw(), conf, traffic)
    s.plan, s.plan_s = system.plan_record(s.built.plan), s.built.plan_s
    return s


def _start_trace(path: pathlib.Path):
    import jax
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(path), profiler_options=opts)
    ann = jax.profiler.TraceAnnotation("bench/window")
    ann.__enter__()
    return ann


@dataclasses.dataclass
class Window:
    poisson: bool
    t0: float                 # the window opens (perf_counter)
    t_close: float
    reqs: list                # requests of the window (D.Req)
    finished: list
    missing: int
    n_compiles: int
    occupancy: list
    admissions: int
    tokens_per_s: Optional[float]
    ttft_ms: list             # one per request; a missing one reads late
    tpot_ms: list
    lag_ms: list
    turnover_s: float
    traced_ticks: list
    trace: Optional[dict]
    kernel_calls: dict


def serve(s: Setup, seed: int, seconds: float, trace_dir=None) -> Window:
    """One window of the cell's traffic from ``seed`` on a fresh session.
    ``trace_dir`` traces the window's last ``TRACE_S`` seconds."""
    import jax
    traffic, conf = s.traffic, s.conf
    engine = s.built.engine
    sess = system.session(engine, traffic, seed)
    system.warm(sess, traffic, rid0=10 ** 9)
    gen = T.generate(traffic, seed, conf["vocab_size"], seconds)
    drv = D.Driver(sess, traffic, spans=trace_dir is not None)
    tr = {"ann": None, "t0": None, "t1": None, "k0": 0, "k1": 0}
    opened: dict = {}

    def stop_trace():
        tr["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        tr["t1"], tr["k1"] = time.perf_counter(), len(drv.ticks)

    def on_tick():
        if trace_dir is None or tr["t1"] is not None or "t0" not in opened:
            return
        now = time.perf_counter()
        # the trace closes with the window, so the stall of writing it
        # falls after the window
        begin = opened["t0"] + max(0.0, seconds - TRACE_S)
        if tr["ann"] is None and now >= begin:
            tr["ann"] = _start_trace(pathlib.Path(trace_dir))
            tr["t0"], tr["k0"] = time.perf_counter(), len(drv.ticks)
        elif tr["ann"] is not None and now >= tr["t0"] + TRACE_S:
            stop_trace()

    drv.on_tick = on_tick
    poisson = traffic["arrival"] == "poisson"
    turnover_s = 0.0
    tokens_per_s = None
    if poisson:
        items = list(gen)
        c0 = s.compiles.n
        opened["t0"] = t0 = time.perf_counter()
        occ0, k0 = len(sess.occupancy), len(drv.ticks)
        drv.run_poisson(items, t0, seconds, DRAIN_S)
        t_close = t0 + seconds
        in_win = [t for t in drv.ticks[k0:] if t.harvested <= t_close]
        occ = sess.occupancy[occ0:occ0 + sum(1 for t in in_win if t.slots)]
        reqs = [drv.reqs[it.rid] for it in items]
    else:
        slots = traffic["slots"]
        t_turn = time.perf_counter()
        drv.run_backlog(gen, slots, lambda: len(
            {r.slot for r in drv.reqs.values() if r.finished is not None})
            >= slots or time.perf_counter() > t_turn + TURNOVER_S)
        turnover_s = time.perf_counter() - t_turn
        opened["t0"] = t0 = drv.ticks[-1].harvested
        g0 = drv.generated()
        c0 = s.compiles.n
        occ0, k0 = len(sess.occupancy), len(drv.ticks)
        drv.run_backlog(gen, slots,
                        lambda: time.perf_counter() >= t0 + seconds)
        t_close = drv.ticks[-1].harvested
        tokens_per_s = (drv.generated() - g0) / (t_close - t0)
        occ = sess.occupancy[occ0:]
        reqs = [r for r in drv.reqs.values() if r.admitted is not None
                and t0 <= r.admitted <= t_close]
    n_compiles = s.compiles.n - c0
    if tr["ann"] is not None and tr["t1"] is None:
        stop_trace()
    finished = [r for r in reqs if r.finished is not None]
    wrong = [r for r in finished if len(r.tokens) - r.prompt_len != r.max_new]
    missing = (len(reqs) - len(finished) if poisson else 0) + len(wrong)
    late = (seconds + DRAIN_S) * 1e3
    ttft = [(r.first - r.due) * 1e3 if r.first is not None else late
            for r in reqs]
    tpot = [(r.finished - r.first) * 1e3 / (r.max_new - 1) for r in finished
            if r.max_new > 1]
    summary, kernel_calls = None, {}
    if trace_dir is not None and tr["t1"] is not None:
        events = TR.load(str(trace_dir))
        summary = TR.reduce(events, *TR.window(events))
        if any(k in counts.KERNELS for k in summary["kernels"]):
            import jax.numpy as jnp
            for p, _ in traffic["prompt_lengths"]:
                text = engine._prefill.lower(
                    engine.params, jnp.zeros((1, int(p)), jnp.int32)
                ).compile().as_text()
                kernel_calls.update(counts.kernel_calls(text))
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = Window(poisson=poisson, t0=t0, t_close=t_close, reqs=reqs,
               finished=finished, missing=missing, n_compiles=n_compiles,
               occupancy=list(occ),
               admissions=sum(len(t.admitted) for t in drv.ticks[k0:]),
               tokens_per_s=tokens_per_s, ttft_ms=ttft, tpot_ms=tpot,
               lag_ms=[(r.submitted - r.due) * 1e3 for r in reqs],
               turnover_s=turnover_s,
               traced_ticks=drv.ticks[tr["k0"]:tr["k1"]], trace=summary,
               kernel_calls=kernel_calls)
    del sess, drv
    gc.collect()
    return w


def describe(w: Window, s: Setup, mem: int, setup_s: float) -> list:
    """The window's numbers for earlier lines of the output."""
    lines = []
    if w.poisson:
        done_in = [r for r in w.finished if r.finished <= w.t_close]
        rate = sum(r.max_new for r in done_in) / (w.t_close - w.t0)
        lines.append(f"served {len(done_in)} of {len(w.reqs)} requests due "
                     f"in the window within it ({rate:.1f} tokens/s); "
                     f"generator lag median {stats.median(w.lag_ms):.3f} "
                     f"ms, max {max(w.lag_ms):.3f} ms")
    else:
        lines.append(f"turnover: every slot finished a request after "
                     f"{w.turnover_s:.2f} s of traffic (set-up); window "
                     f"{w.tokens_per_s:.1f} tokens/s over "
                     f"{w.t_close - w.t0:.3f} s")
    if w.ttft_ms:
        lines.append(
            f"ttft p50 {stats.median(w.ttft_ms):.1f} ms, p95 "
            f"{stats.percentile(w.ttft_ms, 95):.1f} ms; tpot p50 "
            f"{stats.median(w.tpot_ms) if w.tpot_ms else 0:.2f} ms, p95 "
            f"{stats.percentile(w.tpot_ms, 95) if w.tpot_ms else 0:.2f} ms "
            f"over {len(w.reqs)} requests")
    lines.append(f"compiles inside the window: {w.n_compiles}")
    lines.append(f"admissions {w.admissions}, completions "
                 f"{len(w.finished)}, missing {w.missing}, occupancy "
                 f"{100 * sum(w.occupancy) / max(1, len(w.occupancy)):.1f}% "
                 f"over {len(w.occupancy)} chunks")
    lines.append(f"plan: {s.plan['counts']}, plan_s {s.plan_s:.2f} s; "
                 f"peak_bytes_in_use {mem / 2**30:.3f} GiB; setup_s "
                 f"{setup_s:.2f}")
    if w.trace is not None:
        lines.append(f"traced {w.trace['window_s']:.3f} s: device busy "
                     f"{w.trace['busy_s']:.3f} s; programs "
                     f"{json.dumps(w.trace['programs'])}; kernels "
                     f"{json.dumps(w.trace['kernels'])}")
    return lines


def sizes(s: Setup) -> str:
    e = s.built.engine
    return (f"weights {system.weight_bytes(e) / 2**30:.3f} GiB, KV "
            f"{system.kv_bytes(e, s.traffic['slots']) / 2**30:.3f} GiB")


def free_engine(s: Setup) -> None:
    s.built = None
    gc.collect()


def sample(w: Window, seed: int) -> list:
    return check.sample(w.finished, seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
