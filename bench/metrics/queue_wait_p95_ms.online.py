"""Scheduler: 95th percentile of due time -> admission over the window's
requests admitted before the trace closed (writing the trace stalls the
loop): the generator's lag (due -> handed over) plus the session's own
queue delay (handed over -> dequeued for prefill)."""

from bench.harness.stats import percentile

LAYER = "scheduler"
UNIT, BETTER, MOVES = "ms", "lower", "ttft_p95_ms"


def read(rec):
    ticks = rec["traced_ticks"]
    end = ticks[-1].harvested if ticks else float("inf")
    waits = [(r.submitted - r.due + r.queue_delay_s) * 1e3
             for r in rec["window_reqs"]
             if r.queue_delay_s is not None and r.admitted is not None
             and r.admitted <= end]
    return percentile(waits, 95) if waits else None
