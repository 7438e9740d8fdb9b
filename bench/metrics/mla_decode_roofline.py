"""Kernels: ``decode_attn`` over the latent cache, least time over its
device time in the traced window. The least time of each step is the
larger of its operations (QK over the r + rope latent row, PV over its r
columns, per head) over the bf16 peak and its bytes over HBM bandwidth:
each live int8 latent row and its scales read once, up to each advancing
slot's live length, in every layer."""

from bench.harness import counts, counts_mla

LAYER = "kernels"
UNIT, BETTER, MOVES = "%", "higher", "tokens_per_s"
KERNEL = "decode_attn_pallas"


def read(rec):
    tr = rec["trace"]
    if tr is None or KERNEL not in tr["kernels"]:
        return None
    conf, pk = rec["conf"], rec["peaks"]
    least = 0.0
    for t in rec["traced_ticks"]:
        for j in range(rec["traffic"]["chunk"]):
            rows = [p + g + j + 1 for p, g, steps in t.slots if j < steps]
            if rows:
                f, b = counts_mla.mla_decode_call(conf, rows)
                least += counts.roofline_s(f, b, pk["bf16_flops"],
                                           pk["hbm_bytes_per_s"])
    return 100.0 * least / tr["kernels"][KERNEL][1]
