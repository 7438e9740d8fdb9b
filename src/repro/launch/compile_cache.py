"""Persistent XLA compilation cache for the entry points.

``launch/serve.py``, ``chip_smoke.py`` and ``benchmarks/run.py`` call
``enable_compile_cache()`` before their first compile, so a second run of
the same programs on the same machine reads its executables back instead
of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code.
* unset, on a TPU: the cache lives at ``<checkout>/.jax_cache``
  (gitignored); elsewhere it stays off. The path is fixed: a temp, pid
  or time-based directory would never be found again by the next run.

DP x TP replicas (``launch/serve.py --dp``) turn the cache off for the
rest of the process on a TPU: see ``keep_submesh_programs_out``.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory in use (None:
    off — without the env var only a TPU run caches, since the cache is
    there to save chip time and XLA:CPU entries are host-specific)."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def keep_submesh_programs_out() -> bool:
    """Turn the persistent cache off for the rest of the process, on a TPU,
    before programs for a submesh of the chips compile; returns whether it
    did. On TPU v5e (jax/jaxlib 0.9.0, libtpu 0.0.34) an executable read
    back from the cache for a 2-chip submesh whose chips are not the
    first two halts the chip at its first cross-chip barrier ("Invalid
    logical z: enhanced-barrier-parent-phase-1 no HLO mapping"), while the
    same program compiled in the process runs. Elsewhere nothing changes."""
    if jax.default_backend() != "tpu":
        return False
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return True


class CompileCounter:
    """From construction on: persistent-cache hits and misses, and the
    seconds spent in backend compiles (cache reads included), from JAX's
    monitoring events. A miss is written back only when its compile took
    at least ``jax_persistent_cache_min_compile_time_secs``."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            setattr(self, name, getattr(self, name) + 1)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self._COMPILE:
            self.compile_s += duration
