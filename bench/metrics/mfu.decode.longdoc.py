"""Model step, decode (DeepSeek-V3 block): active model operations of the
tokens decoded in the traced window (six routed experts, the shared
experts, the dense layer, absorbed latent attention over the live context
and the head per token) over the window's wall time, as a share of the
chip's bf16 peak. Host gaps count against it."""

from bench.harness import counts_mla

LAYER = "model step"
UNIT, BETTER, MOVES = "%", "higher", "tokens_per_s"


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    flops = 0.0
    for t in rec["traced_ticks"]:
        for p, g, steps in t.slots:
            flops += sum(counts_mla.decode_token_flops(rec["conf"],
                                                       p + g + j + 1)
                         for j in range(steps))
    return 100.0 * flops / (tr["window_s"] * rec["peaks"]["bf16_flops"]) \
        if flops else None
