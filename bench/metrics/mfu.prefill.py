"""Model step, prefill: model operations of the prompts prefilled in the
traced window (every token through every layer, the head once, causal
attention) over the prefill program's device time there, as a share of
the chip's bf16 peak."""

from bench.harness import counts

LAYER = "model step"
UNIT, BETTER, MOVES = "%", "higher", "ttft_p95_ms"
PROGRAM = "jit__prefill_impl"


def read(rec):
    tr = rec["trace"]
    if tr is None or PROGRAM not in tr["programs"]:
        return None
    flops = sum(counts.prefill_flops(rec["conf"], p)
                for t in rec["traced_ticks"] for p in t.admitted)
    secs = tr["programs"][PROGRAM][1]
    return 100.0 * flops / (secs * rec["peaks"]["bf16_flops"]) if flops \
        else None
