"""Engine: mean device milliseconds per call of the prefill program
(``jit__prefill_impl``) in the traced window."""

LAYER = "engine"
UNIT, BETTER, MOVES = "ms", "lower", "ttft_p95_ms"
PROGRAM = "jit__prefill_impl"


def read(rec):
    tr = rec["trace"]
    if tr is None or PROGRAM not in tr["programs"]:
        return None
    calls, secs = tr["programs"][PROGRAM]
    return 1e3 * secs / calls
