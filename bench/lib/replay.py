"""The replay loop: requests go to ``ServeEngine.submit`` when they are
due, ``ServeEngine.step`` runs while any is outstanding, and every
latency is timed from the request's due time on the host clock.

Times in a finished run are seconds after the window opened (set-up
requests' tokens read negative).  The loop
never reads the engine's own latency figures: a request's first token
and every later one are stamped by the streaming callback the moment
the engine emits them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

from bench.lib.traffic import Request


@dataclasses.dataclass
class Record:
    req: Request
    rid: int = -1
    submit_t: float = math.nan
    admit_t: float = math.nan
    admit_step: int = -1
    first_step: int = -1
    failed: str = ""
    done: bool = False
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Step:
    index: int
    t0: float
    t1: float
    decoded: List[int]        # request indices that decoded a token


@dataclasses.dataclass
class Run:
    seconds: float
    records: List[Record]
    steps: List[Step]
    drain_end: float = 0.0
    opened: float = 0.0       # the clock's reading as the window opened


def no_annotation(name: str):
    return contextlib.nullcontext()


class Replay:
    """Drives one engine through set-up requests and one window."""

    def __init__(self, engine, annotate: Callable = no_annotation,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.annotate = annotate
        self.clock = clock
        self.by_rid: Dict[int, Record] = {}
        self.outstanding = 0
        self.step_index = 0
        self.steps: List[Step] = []

    def _on_token(self, rid: int, tok: int, done: bool) -> None:
        rec = self.by_rid[rid]
        if not rec.times:
            rec.first_step = self.step_index
        rec.tokens.append(int(tok))
        rec.times.append(self.clock())
        if done:
            rec.done = True
            self.outstanding -= 1

    def submit(self, rec: Record) -> None:
        with self.annotate("bench.submit"):
            try:
                rid = self.engine.submit(rec.req.prompt, rec.req.max_new,
                                         on_token=self._on_token)
            except ValueError as e:       # refused: counts as failed
                rec.failed = f"refused: {e}"
                return
        rec.rid = rid
        rec.submit_t = self.clock()
        self.by_rid[rid] = rec
        self.outstanding += 1

    def step(self) -> None:
        t0 = self.clock()
        with self.annotate("bench.step"):
            ev = self.engine.step()
        t1 = self.clock()
        for rid in ev["admitted"]:
            self.by_rid[rid].admit_t = t0
            self.by_rid[rid].admit_step = self.step_index
        self.steps.append(Step(
            self.step_index, t0, t1,
            [self.by_rid[r].req.index for r in ev["decoded"]]))
        self.step_index += 1

    def warm(self, records: List[Record]) -> None:
        """Set-up: serve ``records`` to their end, untimed."""
        for rec in records:
            self.submit(rec)
        while self.outstanding:
            self.step()

    def fill(self, records: List[Record]) -> None:
        """Set-up: submit ``records`` and step until each has its first
        token (every slot decoding)."""
        for rec in records:
            self.submit(rec)
        while any(not r.times and not r.failed for r in records):
            self.step()

    def _serve(self, pending: List[Record], i: int,
               now: Callable[[], float], until: float) -> int:
        """Submit ``pending[i:]`` as they fall due and step while any is
        outstanding, until ``now()`` reaches ``until``; returns the index
        of the first request not yet submitted."""
        while True:
            t = now()
            if t >= until:
                return i
            while i < len(pending) and pending[i].req.due_s <= t:
                self.submit(pending[i])
                i += 1
            if self.outstanding:
                self.step()
                continue
            nxt = pending[i].req.due_s if i < len(pending) else until
            with self.annotate("bench.idle_wait"):
                time.sleep(max(0.0, min(nxt, until) - now()))

    def window(self, records: List[Record], seconds: float,
               drain_s: float = 0.0,
               on_open: Optional[Callable[[], None]] = None) -> Run:
        """Replay ``records`` (due times relative to the window's
        opening) and keep the window open for ``seconds``.  Requests due
        before 0 are the lead: they are replayed first, untimed, as
        set-up, and ``on_open()`` runs as the window opens.  With
        ``drain_s``, stepping goes on past the close, with no new
        arrivals, until every request due in the window has its first
        token or ``drain_s`` has passed."""
        pending = sorted(records, key=lambda r: r.req.due_s)
        lead = max([0.0] + [-r.req.due_s for r in pending])
        start = self.clock() + lead
        i = self._serve(pending, 0, lambda: self.clock() - start, 0.0)
        if on_open is not None:
            on_open()
        self.steps = []
        t_open = self.clock()

        def now():
            return self.clock() - t_open

        with self.annotate("bench.window"):
            i = self._serve(pending, i, now, seconds)
        due = [r for r in pending[i:] if r.req.due_s <= seconds]
        for rec in due:
            self.submit(rec)
        waiting = [r for r in records if r.req.due_s <= seconds
                   and not r.times and not r.failed]
        if drain_s:
            with self.annotate("bench.drain"):
                while self.outstanding and now() < seconds + drain_s \
                        and any(not r.times and not r.failed
                                for r in waiting):
                    self.step()
        end = now()
        for r in waiting:
            if not r.times and not r.failed and drain_s:
                r.failed = f"no first token {end - r.req.due_s:.1f} s " \
                           f"after it was due"
        for r in self.by_rid.values():
            r.times = [t - t_open for t in r.times]
            r.submit_t -= t_open
            r.admit_t -= t_open
        for st in self.steps:
            st.t0 -= t_open
            st.t1 -= t_open
        return Run(seconds, list(records), self.steps, end, t_open)


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, as numpy's default; None when
    there is nothing to take it over."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
