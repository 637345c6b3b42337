"""The load loops: they send a mix's requests to the engine and watch what
comes back, on the host's clock, as a streaming client would.

After every `engine.step()` the loop reads each live request's token list
and stamps the tokens that appeared since the last look: that is when a
client sees them. Times are taken from `time.monotonic`, the engine's own
clock, so its admission stamps compare with the due times here.

Open loop: every request is due at its planned time, whether or not earlier
ones finished; time to first token counts from that due time, so a stall
that delays later submissions counts against them. Arrivals stop at the end
of the window; the loop then runs on until every request due in the window
has its first token or has failed (at most `DRAIN_S`).

Closed loop: each client sends its first request before the window and its
next one as soon as the previous finishes; the window opens once every slot
is busy. After the window no client sends again, and the loop runs on
until every request sent has its first token and `finished` of them have
finished (at most `DRAIN_S`), so that there are served requests to check
even where a request outlasts the window.

A traced run starts the profiler at a fixed offset into the window and
marks a span of fixed length from there (`Record.trace_span`); the
profiler is stopped when the window closes, before any drain, so the
seconds it takes to collect its trace fall outside the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, List, Optional

DRAIN_S = 60.0


@dataclasses.dataclass
class Seen:
    """What a client saw of one request."""
    plan: object                   # traffic.Planned
    req: object = None             # the engine's Request
    due: float = float("nan")      # absolute, host clock
    first: float = float("nan")    # first token seen
    last: float = float("nan")     # latest token seen
    t_done: float = float("nan")   # finished or failed, seen
    n_seen: int = 0
    done: bool = False
    failed: bool = False


@dataclasses.dataclass
class Record:
    """One run's observations."""
    seen: List[Seen]
    w0: float = 0.0                # window start
    w1: float = 0.0                # window end: first look at or after w0 + T
    end: float = 0.0               # end of the drain
    gaps: List[float] = dataclasses.field(default_factory=list)  # in window
    tokens_in_window: int = 0
    steps: int = 0                 # step() calls in the window
    decode_steps: int = 0          # ... that dispatched a pooled decode
    decode_times: List[float] = dataclasses.field(default_factory=list)
    active_per_decode: List[int] = dataclasses.field(default_factory=list)
    decoded: List[tuple] = dataclasses.field(default_factory=list)  # (t, n)
    lateness: List[float] = dataclasses.field(default_factory=list)
    compiles: int = -1
    trace_span: Optional[tuple] = None   # (start, stop) of the traced part
    trace_start_s: float = 0.0           # what starting the profiler took
    # the longest step() calls in the window: (seconds, start offset into
    # the window, prefill calls in it, active slots after it)
    longest: List[tuple] = dataclasses.field(default_factory=list)
    gc_pauses: List[tuple] = dataclasses.field(default_factory=list)


class Loop:
    """Shared bookkeeping of both loops."""

    def __init__(self, eng, prog, annotate: bool, clock: Callable = time.monotonic):
        self.eng, self.prog, self.clock = eng, prog, clock
        self.live: List[Seen] = []
        self.rec = Record(seen=[])
        self._ann = annotate
        if annotate:
            from jax.profiler import TraceAnnotation
            self._span = TraceAnnotation
        self.window_open = False

    def span(self, name: str):
        return self._span(name) if self._ann else contextlib.nullcontext()

    def submit(self, s: Seen) -> None:
        with self.span("bench.submit"):
            s.req = self.prog.make_request(s.plan)
            self.eng.submit_request(s.req)
        self.rec.seen.append(s)
        self.live.append(s)

    def step(self) -> float:
        eng, rec = self.eng, self.rec
        d0 = self.prog.decode_steps(eng)
        p0 = self.prog.prefill_calls(eng)
        t0 = self.clock()
        with self.span("bench.step"):
            eng.step()
        t = self.clock()
        if self.window_open:
            rec.longest.append((t - t0, t0 - rec.w0,
                                self.prog.prefill_calls(eng) - p0,
                                eng.n_active))
            if len(rec.longest) > 64:
                rec.longest = sorted(rec.longest, reverse=True)[:5]
            rec.steps += 1
            if self.prog.decode_steps(eng) > d0:
                rec.decode_steps += 1
                rec.decode_times.append(t)
                rec.active_per_decode.append(eng.n_active)
        self.observe(t)
        return t

    def observe(self, t: float) -> None:
        rec, keep, decoded = self.rec, [], 0
        inside = self.window_open
        for s in self.live:
            n = len(s.req.tokens)
            if n > s.n_seen:
                if s.n_seen == 0:
                    s.first = t
                    new_gaps = n - 1
                else:
                    new_gaps = n - s.n_seen
                if inside:
                    rec.tokens_in_window += n - s.n_seen
                    if s.n_seen:
                        rec.gaps.append(t - s.last)
                        new_gaps -= 1
                    rec.gaps.extend([0.0] * new_gaps)
                decoded += n - max(s.n_seen, 1)
                s.n_seen, s.last = n, t
            if self.prog.is_finished(s.req):
                s.done, s.t_done = True, t
            elif self.prog.is_failed(s.req):
                s.done = s.failed = True
                s.t_done = t
            else:
                keep.append(s)
        self.live = keep
        if decoded:
            rec.decoded.append((t, decoded))


def open_loop(eng, prog, planned, seconds: float, *, annotate=False,
              trace=None) -> Record:
    """Open-loop run. `trace` is an optional (offset_s, length_s, begin,
    end, stop): `begin` starts the profiler `offset_s` into the window,
    `end` closes the traced span `length_s` later, `stop` stops the
    profiler when the window closes."""
    lp = Loop(eng, prog, annotate)
    clock, rec = lp.clock, lp.rec
    plan = sorted((p for p in planned if p.due_s < seconds),
                  key=lambda p: p.due_s)
    rec.w0 = clock() - plan[0].due_s
    rec.w1 = rec.w0 + seconds
    tr = _TraceWindow(trace, rec)
    due_in_window: List[Seen] = []
    i, n, draining = 0, len(plan), False
    while True:
        now = clock()
        while i < n and rec.w0 + plan[i].due_s <= now:
            s = Seen(plan[i], due=rec.w0 + plan[i].due_s)
            rec.lateness.append(now - s.due)
            if plan[i].due_s >= 0.0:
                due_in_window.append(s)
            lp.submit(s)
            i += 1
        lp.window_open = not draining and now >= rec.w0
        tr.poll(now)
        if not draining and now >= rec.w1:
            draining, lp.window_open, rec.w1 = True, False, now
            tr.close()
        if draining and (now > rec.w1 + DRAIN_S or all(
                s.failed or not math.isnan(s.first) for s in due_in_window)):
            break
        if eng.has_work:
            lp.step()
        elif i < n:
            time.sleep(max(0.0, min(1e-3, rec.w0 + plan[i].due_s - clock())))
    rec.end = clock()
    tr.close()
    return rec


def closed_loop(eng, prog, planned, seconds: float, n_slots: int,
                finished: int, *, annotate=False, trace=None) -> Record:
    lp = Loop(eng, prog, annotate)
    clock, rec = lp.clock, lp.rec
    queues = {}
    for p in planned:
        queues.setdefault(p.client, []).append(p)
    current = {}

    def send(c: int, now: float) -> None:
        p = queues[c].pop(0)
        s = Seen(p, due=now)
        current[c] = s
        lp.submit(s)

    now = clock()
    for c in sorted(queues):
        send(c, now)
    while eng.n_active < n_slots:                 # ramp: fill every slot
        lp.step()
    rec.w0 = clock()
    rec.w1 = rec.w0 + seconds
    lp.window_open = True
    tr = _TraceWindow(trace, rec)
    while True:
        now = lp.step()
        tr.poll(now)
        if now >= rec.w1:
            rec.w1 = now
            break
        for c, s in current.items():
            if s.done and queues[c]:
                send(c, now)
    lp.window_open = False
    tr.close()
    while clock() < rec.w1 + DRAIN_S and eng.has_work and (
            sum(s.done and not s.failed for s in rec.seen) < finished
            or any(math.isnan(s.first) and not s.failed for s in rec.seen)):
        lp.step()
    rec.end = clock()
    return rec


class _TraceWindow:
    """Opens the traced span once, at a fixed offset into the window, and
    closes it a fixed length later."""

    def __init__(self, trace, rec: Record):
        self.spec, self.rec, self.state = trace, rec, 0

    def poll(self, now: float) -> None:
        if self.spec is None:
            return
        offset, length, begin = self.spec[:3]
        if self.state == 0 and now >= self.rec.w0 + offset:
            begin()
            self.t0 = time.monotonic()
            self.rec.trace_start_s = self.t0 - now
            self.state = 1
        elif self.state == 1 and now >= self.t0 + length:
            self._end()

    def _end(self) -> None:
        self.rec.trace_span = (self.t0, time.monotonic())
        self.spec[3]()
        self.state = 2

    def close(self) -> None:
        """At the window's close: end the span if it is open, stop the
        profiler if it was started."""
        if self.spec is not None and self.state == 1:
            self._end()
        if self.spec is not None and self.state == 2:
            self.spec[4]()
            self.state = 3
