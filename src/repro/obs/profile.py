"""Profiler hooks: device-time fences and ``jax.profiler`` capture
windows (docs/DESIGN.md §16).

Two opt-in mechanisms, both armed by installing a ``ProfileHooks`` via
``obs.install(profile=...)``:

* **Device fences** (``device_fences=True``): the serve loop adds a
  ``jax.block_until_ready`` fence right after launching each decode
  chunk, splitting PR 8's dispatch→harvest ``decode-gap`` wall into
  *device compute* (launch → arrays ready) and *host scheduling gap*
  (ready → harvest read). The split lands in the ``decode/chunk`` trace
  span args and in the ``serve_device_time_seconds`` /
  ``serve_host_gap_seconds`` histograms. The fence serializes the host
  against the device — it is a measurement mode, not a serving mode, so
  it is never on by default.

* **Capture windows** (``steps=(A, B)``, CLI ``--profile-steps A:B``):
  ``jax.profiler.start_trace`` fires when the decode-step clock reaches
  A and stops at B (or at session teardown), writing an XPlane/Perfetto
  trace under ``trace_dir``. A start/stop failure raises: profiling
  was asked for, so a run that silently recorded nothing must not pass
  for one that did.

Disabled cost: the serve loop consults one module-level ``None`` check
per site (``obs.profile()``), the same discipline as ``serving/chaos``.
"""

from __future__ import annotations

from typing import Optional


class ProfileHooks:
    def __init__(self, steps: Optional[tuple] = None,
                 trace_dir: str = "/tmp/repro-profile",
                 device_fences: bool = True):
        if steps is not None:
            a, b = steps
            if not (0 <= a < b):
                raise ValueError(f"profile window must be 0 <= A < B, "
                                 f"got {a}:{b}")
        self.steps = steps
        self.trace_dir = trace_dir
        self.device_fences = device_fences
        self._capturing = False
        self.windows = 0              # capture windows actually recorded

    @classmethod
    def parse(cls, spec: str, trace_dir: str = "/tmp/repro-profile",
              device_fences: bool = True) -> "ProfileHooks":
        """``"A:B"`` -> a capture window over decode steps [A, B)."""
        try:
            a, b = (int(x) for x in spec.split(":"))
        except ValueError:
            raise ValueError(f"--profile-steps wants A:B, got {spec!r}")
        return cls(steps=(a, b), trace_dir=trace_dir,
                   device_fences=device_fences)

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
