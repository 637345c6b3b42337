"""Span-based request-lifecycle tracer for the serving engine.

The engine opens a *phase* span at every boundary of its host loop (the
names are `SPANS`, below) and emits *request* spans at each request's
terminal transition (queue -> prefill -> decode -> retire), built from the
engine's own recorded timestamps so the exported trace reconstructs a
request's measured TTFT and end-to-end latency exactly. Recovery events
(quarantine, re-prefill, engine demotion, ...) land as instant events on the
affected request's track, so a faulted request's timeline shows *why* it was
slow.

Every phase span, whether or not a `Tracer` is bound, does three things:

  * enters ``jax.profiler.TraceAnnotation("serve.<name>")`` (the tick uses
    ``StepTraceAnnotation`` with its tick number), so a profiler capture of
    the run carries the engine's phases on the same clock as the device
    ops. A capture is the switch: with none running an annotation costs
    about a microsecond;
  * adds its duration in seconds to the engine's per-phase counter
    ``phase_s_<name>`` (dots as ``_``) through the `stat` callback;
  * with a `Tracer` bound, records a complete event into the tracer's ring.

Design constraints (the observability overhead gate in
benchmarks/check_regression.py holds tracing + metrics to <= 2% of
saturated-decode throughput, with zero steady-state compiles):

  * everything is host-side Python — no device work, no jit, no recompiles;
  * recording one span costs two clock reads, one annotation and one
    counter update, plus one deque append with a tracer bound; events are
    compact tuples until export;
  * the event store is a bounded ring (``capacity`` events, oldest dropped,
    drops counted) so a long-running serve cannot grow without limit;
  * the disabled path is ``NULL_TRACER`` — a singleton whose recording
    methods are no-ops; its spans still annotate and count.

A phase span's duration is host time: JAX dispatch is async, so a span
around a jitted call measures its enqueue, and only the spans around a
host fetch (``retire.wait``, ``admit.wait``) measure the host blocked on
the device.

Export is Chrome-trace JSON (``to_chrome_trace()`` / ``save(path)``): open
the file in Perfetto (https://ui.perfetto.dev) or chrome://tracing. The host
loop renders as pid 0 / tid 0; each request renders as its own track (pid 1,
tid = rid).
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

# event tuples: (ph, name, cat, pid, tid, t0, dur, args)
#   ph "X" = complete span (dur in seconds), "i" = instant (dur ignored)
HOST_PID = 0        # host-loop phase spans
REQUEST_PID = 1     # per-request lifecycle tracks (tid = rid)

# Every phase span the engines open. Nesting within a tick: `dispatch`
# holds `decode_step` or `spec_round`; `retire` holds `retire.wait` (the
# fetch of the dispatched tokens and health); `admit` holds `prefill`,
# `prefill_chunk` and `admit.wait` (the fetch of the first tokens);
# `drift_check` holds a `retire`'s body. `warmup` lies outside any tick.
SPANS = ("tick", "faults", "drift_check", "dispatch", "decode_step",
         "spec_round", "retire", "retire.wait", "deadline_sweep", "admit",
         "prefill", "prefill_chunk", "admit.wait", "warmup")
ANNOTATION_PREFIX = "serve."


def stat_key(name: str) -> str:
    """The engine's per-phase seconds counter for span `name`."""
    return "phase_s_" + name.replace(".", "_")


_STAT_KEYS = {n: stat_key(n) for n in SPANS}


class _Span:
    """One phase span: a profiler annotation, a seconds counter and, with
    a tracer bound, a complete ("X") event in the ring."""

    __slots__ = ("_tr", "_name", "_cat", "_args", "_stat", "_step", "_ann",
                 "_t0")

    def __init__(self, tr, name: str, cat: str, args,
                 stat: Optional[Callable[[str, float], None]],
                 step: Optional[int]):
        self._tr = tr
        self._name = name
        self._cat = cat
        self._args = args
        self._stat = stat
        self._step = step

    def __enter__(self):
        label = ANNOTATION_PREFIX + self._name
        self._ann = (TraceAnnotation(label) if self._step is None
                     else StepTraceAnnotation(label, step_num=self._step))
        self._ann.__enter__()
        self._t0 = self._tr._clock()
        return self

    def __exit__(self, *exc):
        tr = self._tr
        dur = tr._clock() - self._t0
        self._ann.__exit__(*exc)
        if self._stat is not None:
            self._stat(_STAT_KEYS.get(self._name) or stat_key(self._name),
                       dur)
        if tr.enabled:
            tr._emit(("X", self._name, self._cat, HOST_PID, 0, self._t0,
                      dur, self._args))
        return False


class Tracer:
    """Bounded in-memory trace recorder (see module docstring).

    `clock` must match the engine's clock (both default to time.monotonic)
    so span timestamps and the engine's request timestamps share one
    timebase.
    """

    enabled = True

    def __init__(self, capacity: int = 65536,
                 clock: Callable[[], float] = time.monotonic):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._clock = clock
        self._events: deque = deque(maxlen=int(capacity))
        self._epoch = clock()
        self.total = 0          # events ever emitted (ring drops the oldest)
        self.dropped = 0

    # -- recording -----------------------------------------------------
    def _emit(self, ev: Tuple) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
        self._events.append(ev)
        self.total += 1

    def span(self, name: str, cat: str = "phase", *,
             stat: Optional[Callable[[str, float], None]] = None,
             step: Optional[int] = None, **args):
        """Phase span context manager (pid 0 / tid 0; see `_Span`).
        `stat(key, seconds)` receives its duration under `stat_key(name)`;
        `step` makes the annotation a StepTraceAnnotation."""
        return _Span(self, name, cat, args or None, stat, step)

    def complete(self, name: str, t0: float, t1: float, *,
                 cat: str = "request", rid: Optional[int] = None,
                 **args) -> None:
        """Record a span from already-measured timestamps (the scheduler
        uses the Request's own t_submit/t_admitted/... so the trace agrees
        exactly with the measured TTFT/latency)."""
        pid, tid = (REQUEST_PID, rid) if rid is not None else (HOST_PID, 0)
        self._emit(("X", name, cat, pid, tid, t0, max(t1 - t0, 0.0),
                    args or None))

    def instant(self, name: str, *, cat: str = "event",
                rid: Optional[int] = None, ts: Optional[float] = None,
                **args) -> None:
        pid, tid = (REQUEST_PID, rid) if rid is not None else (HOST_PID, 0)
        t = self._clock() if ts is None else ts
        self._emit(("i", name, cat, pid, tid, t, 0.0, args or None))

    # -- inspection / export -------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> List[Dict[str, Any]]:
        """Decoded events (dicts with seconds-based timestamps), oldest
        first. For tests and ad-hoc inspection; export uses Chrome JSON."""
        out = []
        for ph, name, cat, pid, tid, t0, dur, args in self._events:
            out.append({"ph": ph, "name": name, "cat": cat, "pid": pid,
                        "tid": tid, "ts": t0, "dur": dur,
                        "args": dict(args) if args else {}})
        return out

    def request_timeline(self, rid: int) -> List[Dict[str, Any]]:
        """All events on one request's track, ordered by timestamp."""
        evs = [e for e in self.events()
               if e["pid"] == REQUEST_PID and e["tid"] == rid]
        return sorted(evs, key=lambda e: (e["ts"], e["ts"] + e["dur"]))

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace/Perfetto JSON object (timestamps in µs relative to
        the tracer's epoch)."""
        evs: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": HOST_PID, "tid": 0,
             "args": {"name": "serve host loop"}},
            {"ph": "M", "name": "process_name", "pid": REQUEST_PID, "tid": 0,
             "args": {"name": "requests"}},
        ]
        named_reqs = set()
        for ph, name, cat, pid, tid, t0, dur, args in self._events:
            if pid == REQUEST_PID and tid not in named_reqs:
                named_reqs.add(tid)
                evs.append({"ph": "M", "name": "thread_name",
                            "pid": pid, "tid": tid,
                            "args": {"name": f"request {tid}"}})
            ev: Dict[str, Any] = {
                "ph": ph, "name": name, "cat": cat, "pid": pid, "tid": tid,
                "ts": (t0 - self._epoch) * 1e6,
            }
            if ph == "X":
                ev["dur"] = dur * 1e6
            else:
                ev["s"] = "t"
            if args:
                ev["args"] = dict(args)
            evs.append(ev)
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "total_events": self.total}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class NullTracer:
    """Disabled tracer: same surface as Tracer, near-zero cost."""

    enabled = False
    total = 0
    dropped = 0

    _clock = staticmethod(time.monotonic)

    def span(self, name, cat="phase", *, stat=None, step=None, **args):
        """Annotation and seconds counter only: nothing is recorded."""
        return _Span(self, name, cat, None, stat, step)

    def complete(self, name, t0, t1, *, cat="request", rid=None, **args):
        pass

    def instant(self, name, *, cat="event", rid=None, ts=None, **args):
        pass

    def __len__(self) -> int:
        return 0

    def events(self):
        return []

    def request_timeline(self, rid):
        return []

    def to_chrome_trace(self):
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


NULL_TRACER = NullTracer()
