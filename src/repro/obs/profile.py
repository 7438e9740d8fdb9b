"""Profiler capture windows (docs/DESIGN.md §16).

Armed by installing a ``ProfileHooks`` via ``obs.install(profile=...)``
(CLI ``--profile-steps A:B``): ``jax.profiler.start_trace`` fires when
the decode-step clock reaches A and stops at B (or at session teardown),
writing an XPlane/Perfetto trace under ``trace_dir``. The trace carries
the serve loop's ``serve/*`` host spans (``obs.span``) and the model
step's named scopes on the device's clock, so the device/host split of
each tick is read from the trace, with no fence in the serve loop. A
start/stop failure raises: profiling was asked for, so a run that
silently recorded nothing must not pass for one that did.

Disabled cost: the serve loop consults one module-level ``None`` check
per site (``obs.profile()``), the same discipline as ``serving/chaos``.
"""

from __future__ import annotations

from typing import Optional


class ProfileHooks:
    def __init__(self, steps: Optional[tuple] = None,
                 trace_dir: str = "/tmp/repro-profile"):
        if steps is not None:
            a, b = steps
            if not (0 <= a < b):
                raise ValueError(f"profile window must be 0 <= A < B, "
                                 f"got {a}:{b}")
        self.steps = steps
        self.trace_dir = trace_dir
        self._capturing = False
        self.windows = 0              # capture windows actually recorded

    @classmethod
    def parse(cls, spec: str, trace_dir: str = "/tmp/repro-profile"
              ) -> "ProfileHooks":
        """``"A:B"`` -> a capture window over decode steps [A, B)."""
        try:
            a, b = (int(x) for x in spec.split(":"))
        except ValueError:
            raise ValueError(f"--profile-steps wants A:B, got {spec!r}")
        return cls(steps=(a, b), trace_dir=trace_dir)

    # -- capture window -------------------------------------------------------
    def tick(self, clock: int) -> None:
        """Advance the capture window against the decode-step clock.
        Called once per dispatch; idempotent outside the window.

        The clock advances by ``chunk`` per tick, so the window triggers
        on *crossing*: capture starts at the first tick with
        ``clock >= A`` and stops at the first subsequent tick with
        ``clock >= B``. A window narrower than one chunk stride still
        records at least one tick instead of silently missing."""
        if self.steps is None:
            return
        a, b = self.steps
        if not self._capturing:
            if clock >= a:
                self._start()
        elif clock >= b:
            self.stop()

    def _start(self) -> None:
        import jax
        try:
            jax.profiler.start_trace(self.trace_dir)
        except Exception:
            self.steps = None    # one attempt per arm
            raise
        self._capturing = True

    def stop(self) -> None:
        """Close an open capture window (also called at session teardown
        so a window that spans the end of the stream still flushes)."""
        if not self._capturing:
            return
        import jax
        self._capturing = False
        self.steps = None        # one window per arm; never re-open
        jax.profiler.stop_trace()
        self.windows += 1
