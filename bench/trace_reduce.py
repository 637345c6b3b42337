"""From a profiler trace to the numbers the per-layer metrics read.

`jax.profiler` writes an `.xplane.pb`; `jax.profiler.ProfileData` reads it.
Each TPU is a plane named `/device:TPU:<n>` whose line `XLA Ops` holds one
event per operation run on the device and whose line `XLA Modules` holds
one event per executable run. Host threads are the other planes; the
harness's own `TraceAnnotation` spans (`bench.*`) land there, on the same
clock. `extract` turns a trace into plain tuples, `reduce` does the
arithmetic on them, so the arithmetic is tested on a recorded trace without
the profiler.

Busy time is the union of the operation intervals of a device, clipped to
the traced window (the span `bench.window`); the idle share is one minus
busy over the window. An idle gap is a stretch of the window in which no
operation ran; it is labelled with the harness span that covers most of it
(`bench.step`, `bench.submit`), or `host: outside the engine` when none does.

Control flow nests on the ops line: a `while` or `conditional` event
covers the events of its body. An op's time here is its self time, what
its event covers less its children's, so op times add up to the busy time
and a loop is not counted with what it runs. An executable run is known by
its name and by the op names it holds (the ops that start inside it).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, FrozenSet, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NO_SPAN = "host: outside the engine"

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Events:
    """A trace as plain tuples: per device its ops and module runs, and
    the host spans whose name starts with `bench.`."""
    ops: Dict[int, List[Interval]]
    modules: Dict[int, List[Interval]]
    host: List[Interval]

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        tup = lambda xs: [tuple(x) for x in xs]
        return cls({int(k): tup(v) for k, v in d["ops"].items()},
                   {int(k): tup(v) for k, v in d["modules"].items()},
                   tup(d["host"]))


def extract(path: str) -> Events:
    """Read an .xplane.pb into Events. Device op and module names are kept
    as `stable_name` gives them: XLA names an op event by its whole HLO
    instruction, operands and all."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(stable_name(e.name), float(e.start_ns),
                            float(e.end_ns)) for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[dev] = evs
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.end_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return Events(ops, modules, host)


def save(ev: Events, path: str, keep_s: float = 0.5) -> None:
    """Write the first `keep_s` of the traced span's events, gzipped JSON:
    a small recorded trace for the tests."""
    import gzip
    import json
    lo, hi = window_of(ev)
    hi = min(hi, lo + keep_s * 1e9)
    cut = Events({k: clip(v, lo, hi) for k, v in ev.ops.items()},
                 {k: clip(v, lo, hi) for k, v in ev.modules.items()},
                 [(WINDOW, lo, hi)] + [x for x in clip(ev.host, lo, hi)
                                       if x[0] != WINDOW])
    with gzip.open(path, "wt") as f:
        json.dump(cut.to_json(), f)


def load(path: str) -> Events:
    import gzip
    import json
    with gzip.open(path, "rt") as f:
        return Events.from_json(json.load(f))


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv if e > lo and s < hi]


def merged(iv: List[Interval]) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(iv, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Tuple[float, float], spans: List[Interval]) -> str:
    """The host span overlapping most of the gap."""
    best, best_ov = NO_SPAN, 0.0
    for n, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = n, ov
    return best


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the devices
    op_s: Dict[str, float]              # device seconds per op name, summed
    op_n: Dict[str, int]                # calls per op name
    runs: List[Tuple[str, float, FrozenSet[str]]]  # executable runs:
                                        # (name, seconds, op names held)
    gaps: List[Tuple[str, float]]       # (label, seconds), longest first

    def runs_of(self, name: Optional[str] = None, holding: Optional[str] = None,
                lacking: Optional[str] = None) -> Tuple[float, int]:
        """(seconds, runs) of the executable runs whose name matches `name`
        and that hold an op matching `holding` and none matching
        `lacking` (each pattern optional)."""
        has = lambda ops, pat: any(re.search(pat, o) for o in ops)
        sel = [sec for n, sec, ops in self.runs
               if (name is None or re.search(name, n))
               and (holding is None or has(ops, holding))
               and (lacking is None or not has(ops, lacking))]
        return sum(sel), len(sel)

    def ops_of(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of the operations whose name matches."""
        rx = re.compile(pattern)
        keys = [k for k in self.op_s if rx.search(k)]
        return (sum(self.op_s[k] for k in keys),
                sum(self.op_n[k] for k in keys))


def window_of(ev: Events) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in ev.host if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return spans[0]


def reduce(ev: Events, devices: List[int]) -> Reduced:
    lo, hi = window_of(ev)
    spans = [x for x in ev.host if x[0] != WINDOW]
    busy, op_s, op_n, runs, all_gaps = 0.0, {}, {}, [], []
    for dev in devices:
        ops = sorted(clip(ev.ops.get(dev, []), lo, hi),
                     key=lambda x: (x[1], -x[2]))
        union = merged(ops)
        busy += sum(e - s for s, e in union)
        for (n, s, e), own in zip(ops, self_times(ops)):
            op_s[n] = op_s.get(n, 0.0) + own * 1e-9
            op_n[n] = op_n.get(n, 0) + 1
        starts = [s for _, s, _ in ops]
        for n, s, e in clip(ev.modules.get(dev, []), lo, hi):
            held = ops[bisect.bisect_left(starts, s):
                       bisect.bisect_left(starts, e)]
            runs.append((n, (e - s) * 1e-9, frozenset(o[0] for o in held)))
        all_gaps += [(label(g, spans), (g[1] - g[0]) * 1e-9)
                     for g in gaps(union, lo, hi)]
    all_gaps.sort(key=lambda x: -x[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy * 1e-9 / max(len(devices), 1),
                   op_s=op_s, op_n=op_n, runs=runs, gaps=all_gaps)


def self_times(ops: List[Interval]) -> List[float]:
    """Each event's duration less that of the events directly inside it;
    `ops` sorted by start, the longer first where two start together."""
    own = [e - s for _, s, e in ops]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][2]) - s
        stack.append(i)
    return [max(x, 0.0) for x in own]


def stable_name(name: str) -> str:
    """An op or executable name without its operands and XLA's numbering
    (`%fusion.123 = f32[8]{0} fusion(...)` -> `fusion`,
    `jit_prefill(7)` -> `jit_prefill`), so names compare across builds."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$|\(\d+\)$", "", name)


def describe(path: str, n: int = 5) -> dict:
    """Planes, their lines, event counts and a few event names of an
    .xplane.pb: what a reduction can match on."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = {}
            for e in line.events:
                k = stable_name(e.name)
                names[k] = names.get(k, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:n]
            lines[line.name] = {"events": sum(names.values()), "top": top}
        out[plane.name] = lines
    return out


def breakdown(red: Reduced, n: int = 10) -> dict:
    ops: Dict[str, float] = {}
    for k, v in red.op_s.items():
        ops[stable_name(k)] = ops.get(stable_name(k), 0.0) + v
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:n]
    by_label: Dict[str, float] = {}
    for lab, s in red.gaps:
        by_label[lab] = by_label.get(lab, 0.0) + s
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[lab, s] for lab, s in red.gaps[:n]],
            "idle_by_host_span": by_label}
