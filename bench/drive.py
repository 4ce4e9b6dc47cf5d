"""The step hook that feeds a schedule to the engine on the host wall clock.

``ServeEngine.run_until_drained()`` calls the hook once per scheduling
iteration, right after the previous step's token ids came back to the
host.  At each call the feeder

- stamps every new token of every live request with the call's time;
- submits each open-loop request once its due time has passed, and each
  closed-loop request as soon as its client's previous one finished (due
  at the call that saw it finish);
- when no slot is live and nothing is queued, sleeps until the next due
  time or mark, so that an idle engine spins once per gap;
- opens the measured window ``warm_s`` after traffic started (the window
  is that nominal interval; the engine's counters are read at the first
  call inside it), fires the marks (trace start and stop) as their times
  pass, and after the window keeps the traffic going until every request
  due in the window has its first token or ``tail_s`` has passed, not
  counting the time the marks took (writing the trace), then raises
  :class:`Stop`, which ends the drain.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from repro.serve.engine import Request


class Stop(Exception):
    """Raised from the hook to end the drain once the run has its data."""


@dataclasses.dataclass
class Rec:
    """One submitted request and what the feeder saw of it."""

    uid: int
    due: float
    submitted: float
    req: Request
    stamps: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None


@dataclasses.dataclass
class Counters:
    t: float  # the window's nominal edge
    steps: int
    busy_slot_steps: int


class Feeder:
    def __init__(self, specs, *, loop: str, clients: int, warm_s: float,
                 seconds: float, tail_s: float,
                 marks: Tuple[Tuple[float, Callable[[float], None]], ...] = (),
                 clock: Callable[[], float] = time.time,
                 sleep: Callable[[float], None] = time.sleep):
        self.specs = deque(specs)
        self.loop, self.clients = loop, clients
        self.warm_s, self.seconds, self.tail_s = warm_s, seconds, tail_s
        self.clock, self.sleep = clock, sleep
        self.recs: List[Rec] = []
        self.live: Dict[int, Rec] = {}
        self.t0: Optional[float] = None
        self.next_due: Optional[float] = None  # open loop
        self.idle_clients = 0  # closed loop: clients waiting to send
        self.w0: Optional[Counters] = None
        self.w1: Optional[Counters] = None
        # (offset from the window's start, callback), in time order
        self.marks = sorted(marks, key=lambda m: m[0])
        self._fired = 0
        self._marks_s = 0.0  # host time spent inside the marks' callbacks

    # -- phases --------------------------------------------------------------

    @property
    def window(self) -> Tuple[float, float]:
        return self.w0.t, self.w0.t + self.seconds

    def _mark_times(self):
        if self.w0 is None:
            return [self.t0 + self.warm_s]
        return [self.w0.t + off for off, _ in self.marks[self._fired:]] + [
            self.w0.t + self.seconds]

    def _phase(self, engine, now: float) -> None:
        if self.w0 is None and now >= self.t0 + self.warm_s:
            self.w0 = Counters(self.t0 + self.warm_s, engine.steps,
                               engine.busy_slot_steps)
        if self.w0 is None:
            return
        while (self._fired < len(self.marks)
               and now >= self.w0.t + self.marks[self._fired][0]):
            t = self.clock()
            self.marks[self._fired][1](now)
            self._marks_s += self.clock() - t
            self._fired += 1
        if self.w1 is None and now >= self.w0.t + self.seconds:
            self.w1 = Counters(self.w0.t + self.seconds, engine.steps,
                               engine.busy_slot_steps)
        if self.w1 is not None:
            lo, hi = self.window
            waiting = any(lo <= r.due < hi and not r.stamps for r in self.recs)
            if not waiting or now >= hi + self.tail_s + self._marks_s:
                raise Stop

    # -- traffic -------------------------------------------------------------

    def _submit(self, engine, due: float, now: float) -> None:
        s = self.specs.popleft()
        req = Request(uid=s.index, prompt=s.prompt, max_new_tokens=s.max_new)
        engine.submit(req)
        rec = Rec(s.index, due, now, req)
        self.recs.append(rec)
        self.live[rec.uid] = rec
        if self.loop == "open" and self.specs:
            self.next_due = self.t0 + self.specs[0].at_s

    def _stamp(self, now: float) -> None:
        for uid in list(self.live):
            rec = self.live[uid]
            n = len(rec.req.generated)
            if n > len(rec.stamps):
                rec.stamps.extend([now] * (n - len(rec.stamps)))
            if rec.req.done:
                rec.finished = now
                del self.live[uid]
                self.idle_clients += 1

    def _send_due(self, engine, now: float) -> None:
        if self.loop == "open":
            while self.specs and self.next_due <= now:
                self._submit(engine, self.next_due, now)
        else:
            while self.idle_clients and self.specs:
                self.idle_clients -= 1
                self._submit(engine, now, now)

    def __call__(self, engine, busy: bool) -> bool:
        now = self.clock()
        if self.t0 is None:
            self.t0 = now
            if self.loop == "open":
                self.next_due = now + self.specs[0].at_s
            else:
                self.idle_clients = self.clients
        self._stamp(now)
        if not busy and not engine.queue and not self.live:
            wake = min(self._mark_times()
                       + ([self.next_due] if self.loop == "open"
                          and self.specs else []))
            if wake > now:
                self.sleep(wake - now)
                now = self.clock()
        self._phase(engine, now)
        self._send_due(engine, now)
        if not self.specs and not self.live:
            raise RuntimeError("the schedule ran out before the run ended")
        return True
