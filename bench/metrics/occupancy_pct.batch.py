"""Scheduler: mean share of decode slots holding a request, over the
window's decode chunks (the session's own ``occupancy`` counter)."""

LAYER = "scheduler"
UNIT, BETTER, MOVES = "%", "higher", "tokens_per_s"


def read(rec):
    occ = rec["occupancy"]
    return 100.0 * sum(occ) / len(occ) if occ else None
