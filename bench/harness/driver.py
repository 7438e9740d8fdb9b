"""The open-loop driver: hands requests to the serve session when they fall
due on the wall clock and runs the session's loop (``dispatch`` then
``harvest``), never waiting for completions to send more.

It times each request from its due time. A request's first token is
shown by the harvest of the tick that admitted it (the decode chunk
launched in that tick samples it), and its last by the harvest that
finishes it. While nothing is queued or running it sleeps until the next
request is due.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Iterator, Optional

from bench.harness import system


@dataclasses.dataclass
class Req:
    rid: int
    prompt_len: int
    max_new: int
    due: float
    submitted: float
    tokens: object = None          # prompt + generated, once finished
    admitted: Optional[float] = None
    first: Optional[float] = None
    finished: Optional[float] = None
    queue_delay_s: Optional[float] = None
    slot: Optional[int] = None


@dataclasses.dataclass
class Tick:
    dispatched: float
    harvested: float
    # (prompt length, tokens generated before the chunk, steps advanced)
    # of every slot the chunk advanced; empty when no chunk ran
    slots: list
    admitted: list          # prompt lengths prefilled in this tick


class Driver:
    def __init__(self, sess, traffic: dict, spans: bool = False):
        self.sess = sess
        self.chunk = int(traffic["chunk"])
        self.reqs: dict = {}
        self.ticks: list = []
        self.gen: dict = {}         # rid -> tokens generated so far
        self._nfin = len(sess.sched.finished)
        self._spans = spans
        self.on_tick: Optional[Callable[[], None]] = None

    def span(self, name: str):
        if not self._spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, item, due: float) -> None:
        now = time.perf_counter()
        with self.span("bench/submit"):
            self.sess.sched.submit(system.request(item.rid, item.prompt,
                                                  item.max_new))
        self.reqs[item.rid] = Req(rid=item.rid, prompt_len=len(item.prompt),
                                  max_new=item.max_new, due=due,
                                  submitted=now)

    def active(self) -> dict:
        return {req.rid: slot for slot, req in self.sess.sched.active_slots()}

    def tick(self) -> None:
        sched = self.sess.sched
        before = self.active()
        t_d = time.perf_counter()
        with self.span("bench/dispatch"):
            self.sess.dispatch()
        after = self.active()
        slots = []
        for rid in after:
            g0 = self.gen.get(rid, 0)
            steps = min(self.chunk, self.reqs[rid].max_new - g0) \
                if rid in self.reqs else 0
            slots.append((self.reqs[rid].prompt_len if rid in self.reqs
                          else 0, g0, steps))
            self.gen[rid] = g0 + steps
        with self.span("bench/harvest"):
            self.sess.harvest()
        t_h = time.perf_counter()
        new = [self.reqs[rid] for rid in after.keys() - before.keys()
               if rid in self.reqs]
        self.ticks.append(Tick(dispatched=t_d, harvested=t_h, slots=slots,
                               admitted=[r.prompt_len for r in new]))
        for r in new:
            r.admitted, r.first, r.slot = t_d, t_h, after[r.rid]
        for out in sched.finished[self._nfin:]:
            r = self.reqs.get(out.rid)
            if r is not None:
                r.finished, r.tokens = t_h, out.tokens
                r.queue_delay_s = out.queue_delay_s
            self.gen.pop(out.rid, None)
        self._nfin = len(sched.finished)
        if self.on_tick is not None:
            self.on_tick()

    def idle_until(self, t: float) -> None:
        with self.span("bench/idle_wait"):
            while True:
                left = t - time.perf_counter()
                if left <= 0:
                    return
                time.sleep(min(left, 0.01))

    def generated(self) -> int:
        """Tokens generated so far, read from the session's state: the
        finished requests' and the running slots' (``state.lengths``)."""
        import jax
        done = sum(len(o.tokens) - o.prompt_len
                   for o in self.sess.sched.finished
                   if o.rid in self.reqs)
        lengths = jax.device_get(self.sess.state.lengths)
        running = sum(int(lengths[slot]) - self.reqs[rid].prompt_len
                      for rid, slot in self.active().items()
                      if rid in self.reqs)
        return done + running

    # -- the two arrival processes ------------------------------------------
    def run_poisson(self, items: list, t0: float, seconds: float,
                    drain_s: float) -> float:
        """Serve ``items`` (offsets from ``t0``) until each has finished or
        ``drain_s`` past the window's close. Returns when it stopped."""
        i = 0
        stop = t0 + seconds + drain_s
        while True:
            now = time.perf_counter()
            while i < len(items) and t0 + items[i].offset_s <= now:
                self.submit(items[i], t0 + items[i].offset_s)
                i += 1
            if now >= stop:
                return now
            if not self.sess.done:
                self.tick()
            elif i < len(items):
                self.idle_until(min(t0 + items[i].offset_s, stop))
                if self.on_tick is not None:
                    self.on_tick()
            else:
                return now

    def run_backlog(self, items: Iterator, slots: int,
                    until: Callable[[], bool]) -> None:
        """Keep at least ``slots`` requests queued and serve until
        ``until()`` holds after a harvest."""
        sched = self.sess.sched
        while True:
            while sched.num_pending < slots:
                self.submit(next(items), time.perf_counter())
            self.tick()
            if until():
                return
