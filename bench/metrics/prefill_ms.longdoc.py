"""Engine: ``prefill_ms.online``'s reading (mean device milliseconds per
call of ``jit__prefill_impl`` in the traced window) in a backlog cell. It
moves ``tokens_per_s`` there, not ``ttft_p95_ms``: a backlog reports no
TTFT, and each admission's prefill holds up the decode of every slot."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_metric_prefill_ms_online",
    pathlib.Path(__file__).with_name("prefill_ms.online.py"))
_online = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_online)

LAYER = _online.LAYER
UNIT, BETTER, MOVES = "ms", "lower", "tokens_per_s"
read = _online.read
