"""Engine: device milliseconds per decode step: the decode-chunk program's
(``jit_run``) device time in the traced window over the steps it ran."""

LAYER = "engine"
UNIT, BETTER, MOVES = "ms", "lower", "tokens_per_s"
PROGRAM = "jit_run"


def read(rec):
    tr = rec["trace"]
    if tr is None or PROGRAM not in tr["programs"]:
        return None
    calls, secs = tr["programs"][PROGRAM]
    return 1e3 * secs / (calls * rec["traffic"]["chunk"])
