"""The open-loop client: one process drives ``ServeEngine`` on the real
clock.

It submits every arrival that is due, then steps the engine; with no work
it sleeps until the next arrival.  Each request is timed from its
*scheduled* arrival, so a stall also delays the requests that queue
behind it, and the client records how late it submitted each one.  Tokens
are seen when ``step()`` returns.

The benchmark's spans and counters are kept here, from outside the
program: ``_admit`` and ``_decode_once`` of the engine are wrapped (as
``chip_smoke._LaneLog`` wraps the scheduler) to time each request's
admission and to log the work of every dispatch; when tracing, each
phase of the loop is a ``jax.profiler.TraceAnnotation`` named
``bench.<phase>`` so that device idle time can be laid against it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax

from chipbench.traffic import Arrival, Traffic


@dataclasses.dataclass
class Track:
    """One request as the client sees it (times in seconds from the
    window's start)."""

    arrival: Arrival
    req: Any
    t: float                      # scheduled arrival (or submission)
    t_submit: float
    t_admit: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)
    t_done: Optional[float] = None


class Client:
    def __init__(self, eng, traffic: Traffic, *, annotate: bool = False):
        self.eng = eng
        self.traffic = traffic
        self.arrivals = traffic.arrivals
        self.backlog = (traffic.mix["arrivals"]["depth"]
                        if traffic.mix["arrivals"]["kind"] == "backlog"
                        else None)
        self.tracks: List[Track] = []
        self._open: Dict[int, Track] = {}
        self._by_rid: Dict[int, Track] = {}
        self._next = 0
        self.origin = 0.0
        #: (t, kind, tokens, attention context summed over tokens, pieces)
        #: per dispatch: "prefill" per admission, "decode" per step
        self.work: List[tuple] = []
        self._span = (jax.profiler.TraceAnnotation if annotate
                      else lambda name: contextlib.nullcontext())
        self._wrap()

    # ---------------------------------------------------------- plumbing
    def now(self) -> float:
        return time.perf_counter() - self.origin

    def _wrap(self):
        eng = self.eng
        admit, decode = eng._admit, eng._decode_once

        def wrapped_admit(slot, req):
            t = self.now()
            tr = self._by_rid.get(req.rid)
            if tr is not None and tr.t_admit is None:
                tr.t_admit = t
            ctx = len(req.context)
            before = (eng.stats["prefill_tokens"],
                      eng.stats["prefill_dispatches"])
            with self._span("bench.admit"):
                out = admit(slot, req)
            n = eng.stats["prefill_tokens"] - before[0]
            if n:
                start = ctx - n
                ctx_sum = (start + 1 + ctx) * n // 2   # keys seen per row
                self.work.append((t, "prefill", n, ctx_sum,
                                  eng.stats["prefill_dispatches"]
                                  - before[1]))
            return out

        def wrapped_decode():
            t = self.now()
            lanes = [(r, r.pos, len(r.generated))
                     for r in eng.scheduler.active.values()]
            with self._span("bench.decode"):
                out = decode()
            ran = [(pos + 1) for r, pos, g in lanes if len(r.generated) > g]
            if ran:
                self.work.append((t, "decode", len(ran), sum(ran), 1))
            return out

        eng._admit, eng._decode_once = wrapped_admit, wrapped_decode
        self._unwrap = (admit, decode)

    def detach(self):
        """Give the engine its own methods back and drop every reference
        to it (the requests' tokens stay in the tracks)."""
        self.eng._admit, self.eng._decode_once = self._unwrap
        self.eng = None
        self._unwrap = None

    # ----------------------------------------------------------- loop
    def _submit(self, a: Arrival, t_sched: float, now: float) -> None:
        with self._span("bench.submit"):
            req = self.eng.submit(self.traffic.prompt(a), a.max_new)
        tr = Track(arrival=a, req=req, t=t_sched, t_submit=now)
        self.tracks.append(tr)
        self._open[req.rid] = tr
        self._by_rid[req.rid] = tr

    def _submit_due(self, now: float) -> None:
        if self.backlog is not None:
            while (len(self.eng.scheduler.pending) < self.backlog
                   and self._next < len(self.arrivals)):
                self._submit(self.arrivals[self._next], now, now)
                self._next += 1
            return
        while (self._next < len(self.arrivals)
               and self.arrivals[self._next].t <= now):
            a = self.arrivals[self._next]
            self._submit(a, a.t, now)
            self._next += 1

    def _observe(self, t: float) -> None:
        done = []
        for rid, tr in self._open.items():
            new = len(tr.req.generated) - len(tr.token_t)
            if new > 0:
                tr.token_t.extend([t] * new)
            if tr.req.done:
                tr.t_done = t
                done.append(rid)
        for rid in done:
            del self._open[rid]

    def run(self, until: float) -> None:
        """Drive the loop until ``until`` seconds from the origin."""
        sched = self.eng.scheduler
        while True:
            now = self.now()
            if now >= until:
                return
            self._submit_due(now)
            if sched.has_work:
                with self._span("bench.step"):
                    self.eng.step()
                self._observe(self.now())
            else:
                nxt = (self.arrivals[self._next].t
                       if self.backlog is None
                       and self._next < len(self.arrivals) else until)
                wait = min(nxt, until) - self.now()
                if wait > 0:
                    with self._span("bench.wait"):
                        time.sleep(wait)

    def start(self, preroll_s: float) -> None:
        """Set the clock so the window starts ``preroll_s`` from now."""
        self.origin = time.perf_counter() + preroll_s

    def admit_prefix(self, prefix: List[int]) -> None:
        """Serve the shared prefix once, as a deployment's cache would
        hold it, before any traffic (untracked)."""
        self.eng.submit(list(prefix), 1)
        while self.eng.scheduler.has_work:
            self.eng.step()

    # -------------------------------------------------------- readings
    def summary(self, end: float) -> Dict[str, Any]:
        """What the client saw in the window ``[0, end]``.

        ``due`` are the requests scheduled inside the window; a request
        with no first token by ``end`` counts with the time it waited,
        and so does one not yet admitted, for the queue wait."""
        due = [tr for tr in self.tracks if 0.0 <= tr.t < end]
        ttft, qwait = [], []
        for tr in due:
            first = tr.token_t[0] if tr.token_t else end
            ttft.append(first - tr.t)
            qwait.append((tr.t_admit if tr.t_admit is not None else end)
                         - tr.t)
        gaps, tokens = [], 0
        for tr in self.tracks:
            ts = tr.token_t
            tokens += sum(1 for x in ts if 0.0 <= x <= end)
            gaps += [b - a for a, b in zip(ts, ts[1:]) if 0.0 <= b <= end]
        late = [tr.t_submit - tr.t for tr in self.tracks if tr.t >= 0.0]
        return {
            "window_s": end,
            "attempted": len(due),
            "failed": sum(1 for tr in due
                          if tr.req.shed_reason is not None),
            "finished_in_window": sum(1 for tr in due
                                      if tr.t_done is not None),
            "ttft_s": ttft,
            "queue_wait_s": qwait,
            "itl_s": gaps,
            "output_tokens": tokens,
            "submit_late_s": late,
        }

    def finished(self, end: float) -> List[Track]:
        """Requests whose last token came inside the window."""
        return [tr for tr in self.tracks
                if tr.t_done is not None and 0.0 <= tr.t_done <= end]
