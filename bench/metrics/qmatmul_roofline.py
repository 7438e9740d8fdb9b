"""Kernels: the ``qmatmul`` family (``qmatmul_pallas``, ``qmlp_pallas``,
``qkv_pallas``): least time over device time, summed over the calls in
the traced window. Each call's operations (2 M K N) and bytes (operands
read once, output written once) come from its operand shapes in the
compiled program; the peak from its operand types (int8 activations and
weights: the int8 peak; otherwise bf16)."""

from bench.harness import counts

LAYER = "kernels"
UNIT, BETTER, MOVES = "%", "higher", "ttft_p95_ms"


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    pk, table = rec["peaks"], rec["kernel_calls"]
    least = spent = 0.0
    for key, (n, secs) in tr["calls"].items():
        c = table.get(key)
        if c is None:
            continue
        peak = pk["int8_ops"] if c["int8"] else pk["bf16_flops"]
        least += n * counts.roofline_s(c["flops"], c["bytes"], peak,
                                       pk["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent else None
