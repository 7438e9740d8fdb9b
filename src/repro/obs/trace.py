"""Span tracer exporting Chrome ``trace_event`` JSON (docs/DESIGN.md §16).

Records the serving stack's per-request lifecycle and engine-level timing
as explicit begin/end (``B``/``E``) spans, complete (``X``) spans and
instant (``i``) events, written as a ``{"traceEvents": [...]}`` object
that Perfetto / chrome://tracing load directly.

Track mapping:

* ``pid`` = replica id. Process metadata names each ``replica<r>``.
* ``tid 0`` = the replica's ENGINE track: the serve loop's ``serve/*``
  spans (``obs.span``: ``serve/dispatch`` / ``serve/harvest`` per
  ``ServeSession`` tick, nested policy, admission, launch, read-back and
  completion spans), ``engine/apply_kv_plan`` repack spans,
  ``replica/failover`` spans and ``degrade/transition`` / chaos
  instants.
* ``tid 1`` = the DECODE track: one ``decode/chunk`` X-span per launched
  chunk (dispatch -> harvest wall; args carry the steps and the tier).
* ``tid REQ_TRACK_BASE + rid`` = one track per REQUEST: its
  ``request/queued`` → ``request/prefill`` → ``request/decode`` phases
  are strictly sequential, so they form balanced B/E pairs; phase
  boundaries (finish/cancel/preempt/re-drive) land as instants on the
  same track.

Request phases are driven through ``request_phase``/``request_done``, a
tiny per-(pid, rid) state machine that closes the previous phase before
opening the next — span balance holds by construction, and
``open_spans()`` returning empty is the leak-freedom assertion the obs
tests pin under cancellation, preemption, OutOfPages backpressure and
chaos-driven failover.

Timestamps are microseconds since the tracer was constructed (Chrome's
``ts`` unit), from ``time.perf_counter``. Import-light (stdlib only) so
any serving layer can emit without cycles.
"""

from __future__ import annotations

import json
import time
from typing import Optional

ENGINE_TRACK = 0
DECODE_TRACK = 1
REQ_TRACK_BASE = 1000


class Tracer:
    def __init__(self):
        self._t0 = time.perf_counter()
        self.events: list[dict] = []
        # (pid, tid) -> stack of open span names (B/E balance bookkeeping)
        self._open: dict[tuple, list[str]] = {}
        # (pid, rid) -> current request phase
        self._req: dict[tuple, str] = {}
        self._named_pids: set = set()

    # -- clock ---------------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # -- metadata ------------------------------------------------------------
    def set_process_name(self, pid: int, name: str) -> None:
        if pid in self._named_pids:
            return
        self._named_pids.add(pid)
        self.events.append({"name": "process_name", "ph": "M", "pid": pid,
                            "tid": 0, "args": {"name": name}})
        for tid, tname in ((ENGINE_TRACK, "engine"),
                           (DECODE_TRACK, "decode")):
            self.events.append({"name": "thread_name", "ph": "M",
                                "pid": pid, "tid": tid,
                                "args": {"name": tname}})

    # -- spans ---------------------------------------------------------------
    def begin(self, name: str, pid: int = 0, tid: int = ENGINE_TRACK,
              cat: str = "serve", args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": "B", "pid": pid, "tid": tid,
              "ts": self.now_us(), "cat": cat}
        if args:
            ev["args"] = args
        self.events.append(ev)
        self._open.setdefault((pid, tid), []).append(name)

    def end(self, name: str, pid: int = 0, tid: int = ENGINE_TRACK,
            args: Optional[dict] = None) -> None:
        stack = self._open.get((pid, tid), [])
        assert stack and stack[-1] == name, \
            (f"span misnesting on pid={pid} tid={tid}: ending {name!r}, "
             f"open stack {stack}")
        stack.pop()
        ev = {"name": name, "ph": "E", "pid": pid, "tid": tid,
              "ts": self.now_us()}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def complete(self, name: str, t0_us: float, pid: int = 0,
                 tid: int = ENGINE_TRACK, cat: str = "serve",
                 args: Optional[dict] = None) -> None:
        """A finished span in one event (``ph: "X"``): start at ``t0_us``
        (from ``now_us``), duration measured to now."""
        ev = {"name": name, "ph": "X", "pid": pid, "tid": tid,
              "ts": t0_us, "dur": max(0.0, self.now_us() - t0_us),
              "cat": cat}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, pid: int = 0, tid: int = ENGINE_TRACK,
                args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": "i", "pid": pid, "tid": tid,
              "ts": self.now_us(), "s": "t", "cat": "serve"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    # -- per-request lifecycle state machine ----------------------------------
    def request_phase(self, pid: int, rid: int, phase: str,
                      args: Optional[dict] = None) -> None:
        """Move request ``rid`` into ``phase`` (queued/prefill/decode):
        the previous phase span (if any) ends first, so the request track
        is always a flat sequence of balanced spans."""
        tid = REQ_TRACK_BASE + rid
        prev = self._req.pop((pid, rid), None)
        if prev is not None:
            self.end(f"request/{prev}", pid, tid)
        self.begin(f"request/{phase}", pid, tid, cat="request", args=args)
        self._req[(pid, rid)] = phase

    def request_done(self, pid: int, rid: int, event: str,
                     args: Optional[dict] = None) -> None:
        """Terminal (or migrating) lifecycle event: close the open phase
        and mark the boundary — ``finish``, ``preempt``, ``redrive``."""
        tid = REQ_TRACK_BASE + rid
        prev = self._req.pop((pid, rid), None)
        if prev is not None:
            self.end(f"request/{prev}", pid, tid)
        self.instant(f"request/{event}", pid, tid, args=args)

    # -- inspection / export ---------------------------------------------------
    def open_spans(self) -> list[tuple]:
        """Every still-open (pid, tid, name) — empty iff leak-free."""
        return [(pid, tid, name)
                for (pid, tid), stack in sorted(self._open.items())
                for name in stack]

    def abandon(self, pid: int, tid: int,
                reason: str = "abandoned") -> None:
        """Force-close every open span on one track (exception unwind /
        replica quarantine keeps the trace loadable)."""
        for name in reversed(self._open.get((pid, tid), []).copy()):
            self.end(name, pid, tid, args={"reason": reason})

    def to_json(self) -> dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)

    def counts(self) -> dict:
        """Event counts by (name, ph) — the trace-schema tests and the CI
        validator read these instead of re-deriving them."""
        out: dict[tuple, int] = {}
        for ev in self.events:
            k = (ev["name"], ev["ph"])
            out[k] = out.get(k, 0) + 1
        return out
