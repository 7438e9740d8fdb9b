"""Device: share of the traced window in which no program ran on the chip
(1 - union of the "XLA Modules" intervals / window)."""

LAYER = "device"
UNIT, BETTER, MOVES = "%", "lower", "tpot_p50_ms"


def read(rec):
    tr = rec["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
