#!/usr/bin/env python3
"""One run of one benchmark cell on the chip(s) of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json at the root of the
checkout: the cell names a configuration (`bench/configs/<config>.json`)
and a traffic mix (`bench/traffic/<traffic>.json`); its correctness limits
are `bench/limits/<cell>.json`; each metric is read by
`bench/metrics/<metric>.py`. The configuration names its architecture
(key `"arch"`), the module `bench/archs/<arch>.py`, and the harness reaches
the architecture only through it: the program's model configuration, the
weights and their dtype, the plain reference, the FLOP and byte counts,
the kernels and executable runs to find in the trace, the state's dtype and
the faults. A new cell, metric or architecture is new files and entries.

A run: weights from the seed on the device (one jitted call), the engine
that `launch/serve.py --stream --mode distilled` builds, warmup of the
cell's prompt buckets (set-up ends here), the mix's load for `--seconds`
(open loop at a fixed rate, or closed loop), then the check of what the
window served against the architecture's float32 reference. With
`--trace 1` the profiler records a few seconds near the end of the window,
and the line holds the per-layer metrics and the device's busy time;
without, the end-to-end metrics.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `compared`: each number checked beside its limit).
The numbers compared are also the last lines of standard error. A machine
without a TPU, or with fewer chips than the cell asks for, exits with code
3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import drive  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

ROOT = HERE.parent
NO_CHIP_EXIT = 3


class NoChip(RuntimeError):
    pass


def load_module(path: Path, name: str):
    """The Python file at `path`, run as a module named `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(root: Path, name: str):
    """The architecture module `bench/archs/<name>.py` under `root`."""
    return load_module(Path(root) / "bench" / "archs" / f"{name}.py",
                       "bench_arch_" + name)


class Cell:
    """A cell's files, found by name."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {c["name"]: c for c in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                           f"{sorted(cells)}")
        self.cell = cells[name]
        self.name = name
        conf = {c["name"]: c for c in self.bench["configs"]}[self.cell["config"]]
        self.cfg = self._json(conf["file"])
        if "arch" not in self.cfg:
            raise KeyError(f"{conf['file']} names no architecture (\"arch\")")
        self.arch = load_arch(self.root, self.cfg["arch"])
        self.mix = self._json(f"bench/traffic/{self.cell['traffic']}.json")
        self.limits = self._json(f"bench/limits/{name}.json")
        self.chips = int(self.cell["chips"])

    def _json(self, rel: str) -> dict:
        return json.loads((self.root / rel).read_text())

    @property
    def vocab(self) -> int:
        return self.arch.model_config(self.cfg).vocab

    def metrics(self, kind: str) -> list:
        """This cell's `end_to_end` or `per_layer` entries."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))


def check_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices {devs}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


def seconds_since_process_start() -> float:
    """From the kernel's start time of this process (Linux), else from the
    first line of this module."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - T_START


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it."""
    s = sorted(xs)
    return float(s[max(0, math.ceil(q / 100.0 * len(s)) - 1)])


class Context:
    """What a metric reader is given."""

    percentile = staticmethod(percentile)

    def __init__(self, cell: Cell, rec, seconds, setup_s, warmup_s, peak,
                 state_itemsize, red=None):
        self.cfg, self.mix, self.chips = cell.cfg, cell.mix, cell.chips
        self.arch = cell.arch
        self.n_slots = cell.mix["slots"]
        self.record, self.seconds = rec, seconds
        self.setup_s, self.warmup_s = setup_s, warmup_s
        self.peak, self.state_itemsize, self.trace = peak, state_itemsize, red

    def due_in_window(self):
        rec = self.record
        return [s for s in rec.seen if rec.w0 <= s.due < rec.w0 + self.seconds]

    def ttft_s(self) -> list:
        """Time to first token of each request due in the window; one that
        failed or saw none counts to the end of the drain."""
        rec = self.record
        return [(rec.end if s.failed or s.first != s.first else s.first)
                - s.due for s in self.due_in_window()]

    def attempted(self):
        """The requests the window dealt with: due in it (open loop), or in
        flight at some time in it (closed loop)."""
        rec = self.record
        if self.mix["loop"] == "open":
            return self.due_in_window()
        return [s for s in rec.seen
                if s.due < rec.w1 and not s.t_done < rec.w0]

    def host_span(self) -> tuple:
        """Where host-clock per-layer readings are taken: the traced span
        in a traced run, else the whole window."""
        rec = self.record
        return rec.trace_span or (rec.w0, rec.w1)

    def tick_ms(self):
        """Host seconds per step() that dispatched a pooled decode."""
        lo, hi = self.host_span()
        n = sum(lo <= t < hi for t in self.record.decode_times)
        return 1e3 * (hi - lo) / n if n else None

    def queue_wait_ms(self):
        """Mean of the engine's admission stamp minus the due time, over
        the admitted requests due in the span."""
        lo, hi = self.host_span()
        waits = [s.req.t_admitted - s.due for s in self.record.seen
                 if lo <= s.due < hi and s.req is not None
                 and s.req.t_admitted == s.req.t_admitted]
        return 1e3 * sum(waits) / len(waits) if waits else None

    # --- the traced part of the window ---
    def _in_trace(self, t: float) -> bool:
        span = self.record.trace_span
        return span is not None and span[0] <= t < span[1]

    def prompts_admitted_in_trace(self) -> list:
        return [len(s.plan.prompt) for s in self.record.seen
                if s.req is not None and self._in_trace(s.req.t_admitted)]

    def decoded_tokens_in_trace(self) -> int:
        return sum(n for t, n in self.record.decoded if self._in_trace(t))

    def decode_ms(self):
        """Device milliseconds per pooled decode run (the runs the
        architecture's `DECODE_RUNS` picks)."""
        if self.trace is None or self.arch.DECODE_RUNS is None:
            return None
        sec, n = self.trace.runs_of(**self.arch.DECODE_RUNS)
        return 1e3 * sec / n if n else None

    def prefill_ms_per_ktok(self):
        """Device milliseconds of bucketed prefill runs (`PREFILL_RUNS`)
        per 1,000 prompt tokens admitted in the traced span."""
        if self.trace is None or self.arch.PREFILL_RUNS is None:
            return None
        sec, n = self.trace.runs_of(**self.arch.PREFILL_RUNS)
        toks = sum(self.prompts_admitted_in_trace())
        return 1e3 * sec / (toks / 1e3) if n and toks else None

    def kernel_roofline(self, kernel: str):
        """The kernel's share of its roofline: the least time its counted
        work needs on this chip, per call, over its mean device time."""
        if self.trace is None or kernel not in self.arch.KERNELS:
            return None
        op, count = self.arch.KERNELS[kernel]
        sec, n = self.trace.ops_of(op)
        if not n:
            return None
        least, _ = work.roofline_s(
            count(self.n_slots, self.cfg, self.state_itemsize), self.peak)
        return 100.0 * n * least / sec

    def idle_share(self):
        red = self.trace
        if red is None or red.window_s <= 0:
            return None
        return 100.0 * (1.0 - red.busy_s / red.window_s)

    def step_mfu(self):
        red = self.trace
        if red is None or red.window_s <= 0:
            return None
        arch = self.arch
        flops = (self.decoded_tokens_in_trace()
                 * arch.decode_flops_per_token(self.cfg)
                 + sum(arch.prefill_flops(self.cfg, T)
                       for T in self.prompts_admitted_in_trace()))
        if flops <= 0:
            return None
        return 100.0 * flops / (red.window_s * self.chips
                                * self.peak["bf16_flops_per_s"])


def pick_checked(rec, mix: dict, seed: int) -> tuple:
    """Finished requests to check, drawn from the seed: (greedy, sampled).
    Of each kind the one with the most tokens, and more drawn at random,
    up to the mix's `check.requests` greedy and `check.sampled` sampled."""
    import numpy as np
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 17])
    done = [s for s in rec.seen if s.done and not s.failed
            and len(s.req.tokens) > 0]

    def draw(pool, want):
        if not pool or want <= 0:
            return []
        longest = max(pool, key=lambda s: (len(s.plan.prompt)
                                           + len(s.req.tokens), s.plan.idx))
        rest = [s for s in pool if s is not longest]
        k = min(len(rest), want - 1)
        return [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                        replace=False)]

    greedy = draw([s for s in done if s.plan.temperature <= 0.0],
                  mix["check"]["requests"])
    sampled = draw([s for s in done if s.plan.temperature > 0.0],
                   mix["check"].get("sampled", 0))
    return greedy, sampled


def _reference_rows(arch, w, cfg, mix, s, precision="f32"):
    """The reference's logits at the positions of a served request's
    tokens (teacher-forced: prompt, then the served tokens)."""
    import jax.numpy as jnp
    import numpy as np
    max_len, n_out = mix["max_len"], mix["output"]["max"]
    toks = np.asarray(s.req.tokens, np.int32)
    T, n = len(s.plan.prompt), len(toks)
    seq = np.zeros(max_len, np.int32)
    seq[:T] = s.plan.prompt
    seq[T:T + n - 1] = toks[:-1]
    served = np.zeros(n_out, np.int32)
    served[:n] = toks
    ref = arch.logits_at(w, jnp.asarray(seq), T, cfg=cfg, max_len=max_len,
                         n_out=n_out, precision=precision)
    return ref, jnp.asarray(served), n


def check_served(arch, w: dict, cfg: dict, mix: dict, greedy: list,
                 sampled: list = (), control: bool = False) -> dict:
    """Readings of what the window served, against the architecture's
    float32 reference (`arch.logits_at`).

    Greedy requests: the widest gap by which a served token's logit lies
    below the reference's best (`max_gap`). Sampled requests, each at its
    own temperature and top-p: the largest mass of more likely tokens above
    a served token, less top-p (`nucleus_excess`: above 0, the token lies
    outside the reference's nucleus), and how many standard deviations the
    served tokens' summed surprise lies from what sampling from the
    reference's nucleus gives (`surprise_z`, its size). With `control`,
    also `control_gap`: `max_gap` for the tokens that the reference in
    float8 puts first at the same positions, the control in the program's
    place."""
    import jax
    import jax.numpy as jnp
    out = {"max_gap": 0.0, "tokens": 0, "requests": len(greedy),
           "sampled_requests": len(sampled), "sampled_tokens": 0,
           "nucleus_excess": -1.0, "surprise_z": 0.0}
    if control:
        out["control_gap"] = 0.0
    for s in greedy:
        ref, served, n = _reference_rows(arch, w, cfg, mix, s)
        out["max_gap"] = max(out["max_gap"],
                             float(jnp.max(reference.gaps(ref, served, n))))
        out["tokens"] += n
        if control:
            c, _, _ = _reference_rows(arch, w, cfg, mix, s, "fp8")
            cg = reference.gaps(ref, jnp.argmax(c, axis=-1).astype(jnp.int32),
                                n)
            out["control_gap"] = max(out["control_gap"], float(jnp.max(cg)))
            del c
        del ref
    dev, var = 0.0, 0.0
    for s in sampled:
        ref, served, n = _reference_rows(arch, w, cfg, mix, s)
        st = jax.device_get(reference.sampled(
            ref, served, n, jnp.float32(s.plan.temperature),
            jnp.float32(s.plan.top_p)))
        out["nucleus_excess"] = max(out["nucleus_excess"],
                                    float(st["above"][:n].max())
                                    - s.plan.top_p)
        dev += float(st["surprise"].sum() - st["mean"].sum())
        var += float(st["var"].clip(0.0).sum())
        out["sampled_tokens"] += n
        del ref
    if var > 0.0:
        out["surprise_z"] = abs(dev) / var ** 0.5
    jax.effects_barrier()
    return out


def compare(chk: dict, lim: dict, failed: int) -> dict:
    """Each number checked beside its limit. A name ending in `_at_least`
    passes at or above its limit, every other at or below."""
    out = {"max_gap": {"value": chk["max_gap"], "limit": lim["max_gap"]},
           "checked_tokens_at_least": {"value": chk["tokens"],
                                       "limit": lim["min_checked_tokens"]}}
    if "nucleus_excess" in lim:
        out["nucleus_excess"] = {"value": chk["nucleus_excess"],
                                 "limit": lim["nucleus_excess"]}
        out["surprise_z"] = {"value": chk["surprise_z"],
                             "limit": lim["surprise_z"]}
        out["sampled_tokens_at_least"] = {
            "value": chk["sampled_tokens"],
            "limit": lim["min_sampled_tokens"]}
    out["failed"] = {"value": failed, "limit": 0}
    return out


def control_compared(chk: dict, lim: dict) -> dict:
    """The numbers compared with the control in the program's place: the
    greedy gap of the float8 reference's tokens (`check_served` with
    `control`)."""
    return compare(dict(chk, max_gap=chk["control_gap"]), lim, 0)


def verdict(compared: dict) -> bool:
    return all(c["value"] >= c["limit"] if name.endswith("_at_least")
               else c["value"] <= c["limit"]
               for name, c in compared.items())


def memory_peak(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks) if peaks else 0


class Profiler:
    """The profiler, into a temporary directory: started inside the window,
    the traced span marked by `bench.window`, stopped after the run. It
    records device operations and host annotations, not every Python
    call (`python_tracer_level` 0)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.span = None
        self.stop_s = 0.0

    def begin(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.span.__enter__()

    def end(self):
        self.span.__exit__(None, None, None)

    def stop(self):
        import jax
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        self.stop_s = time.monotonic() - t0

    def xplane(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        return sorted(found)[-1]

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def drive_cell(eng, cell: Cell, planned, seconds: float, prof=None):
    mix = cell.mix
    trace = None
    if prof is not None:
        # the traced span ends a second before the window does: the
        # profiler records until it is stopped as the window closes, and
        # stopping takes seconds for every second it recorded
        length = min(float(mix["trace_s"]), seconds)
        trace = (max(0.0, seconds - length - 1.0), length, prof.begin,
                 prof.end, prof.stop)
    pauses, began = [], []

    def on_gc(phase, info):
        if phase == "start":
            began[:] = [time.monotonic()]
        elif began:
            pauses.append((time.monotonic() - began[0], began[0],
                           info["generation"]))

    gc.callbacks.append(on_gc)
    try:
        rec = _drive(eng, cell, planned, seconds, prof, trace)
    finally:
        gc.callbacks.remove(on_gc)
    rec.gc_pauses = pauses
    return rec


def _drive(eng, cell, planned, seconds, prof, trace):
    mix = cell.mix
    with program.count_compiles() as cc:
        if mix["loop"] == "open":
            rec = drive.open_loop(eng, program, planned, seconds,
                                  annotate=prof is not None, trace=trace)
        else:
            rec = drive.closed_loop(eng, program, planned, seconds,
                                    mix["slots"], mix["check"]["requests"],
                                    annotate=prof is not None, trace=trace)
    rec.compiles = cc.compiles
    return rec


def summary_lines(rec, seconds: float, ctx: "Context") -> list:
    """Earlier lines of standard error: how late the generator ran,
    compiles in the window, p50s, requests per phase."""
    late = sorted(rec.lateness) or [0.0]
    phases = {"ramp": lambda s: s.due < rec.w0,
              "window": lambda s: rec.w0 <= s.due < rec.w0 + seconds,
              "after": lambda s: s.due >= rec.w0 + seconds}
    lines = [f"generator lateness: p50 {1e3 * percentile(late, 50):.3f} ms, "
             f"max {1e3 * late[-1]:.3f} ms over {len(rec.lateness)} sends",
             f"compiles inside the window: {rec.compiles}",
             f"window {rec.w1 - rec.w0:.3f} s, drain {rec.end - rec.w1:.3f} s,"
             f" {rec.steps} steps, {rec.decode_steps} decode dispatches, "
             f"{rec.tokens_in_window} tokens"]
    for name, inside in phases.items():
        ss = [s for s in rec.seen if inside(s)]
        lines.append(f"requests {name}: sent {len(ss)}, first token "
                     f"{sum(s.first == s.first for s in ss)}, finished "
                     f"{sum(s.done and not s.failed for s in ss)}, failed "
                     f"{sum(s.failed for s in ss)}")
    ttft = ctx.ttft_s()
    if ttft:
        lines.append("ttft p50 %.3f ms, p95 %.3f ms" % (
            1e3 * percentile(ttft, 50), 1e3 * percentile(ttft, 95)))
    if rec.gaps:
        lines.append("itl p50 %.3f ms, p95 %.3f ms" % (
            1e3 * percentile(rec.gaps, 50), 1e3 * percentile(rec.gaps, 95)))
    if rec.decode_steps:
        lines.append("tick over the window %.3f ms" % (
            1e3 * (rec.w1 - rec.w0) / rec.decode_steps))
    lines.append("longest steps in the window (s, at s, prefills, active): "
                 + json.dumps(sorted(rec.longest, reverse=True)[:5]))
    gcw = [p for p in rec.gc_pauses if rec.w0 <= p[1] < rec.w1]
    lines.append("garbage collections in the window: %d, %.3f s in all, "
                 "longest %s" % (len(gcw), sum(p[0] for p in gcw),
                                 max(gcw, default=None)))
    if rec.trace_span is not None:
        lines.append("traced span %.3f s from %.3f s into the window, "
                     "starting the profiler took %.3f s; tick over the span "
                     "%s ms, queue wait %s ms" % (
                         rec.trace_span[1] - rec.trace_span[0],
                         rec.trace_span[0] - rec.w0, rec.trace_start_s,
                         ctx.tick_ms(), ctx.queue_wait_ms()))
    return lines


def setup(cell: Cell, seed: int, planned, require_tpu: bool = True):
    """Weights from the seed, the engine, warmup of the planned prompts'
    buckets. Returns (devices, weights, engine, warmup seconds)."""
    devs = check_devices(cell.chips, require_tpu)
    import jax
    if require_tpu:
        program.enable_compile_cache()
    arch = cell.arch
    w = arch.make_weights(cell.cfg, seed)
    jax.block_until_ready(w)
    eng = program.make_engine(arch.to_program(w), arch.model_config(cell.cfg),
                              cell.mix, seed)
    t0 = time.monotonic()
    eng.warmup(sorted({len(p.prompt) for p in planned}))
    return devs, w, eng, time.monotonic() - t0


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, save_trace: str = None) -> dict:
    planned = traffic.generate(cell.mix, seed, cell.vocab, seconds)
    devs, w, eng, warmup_s = setup(cell, seed, planned, require_tpu)
    setup_s = seconds_since_process_start()
    state_itemsize = cell.arch.state_itemsize(eng)
    prof = Profiler() if trace else None
    try:
        rec = drive_cell(eng, cell, planned, seconds, prof)
        mem = memory_peak(devs[:cell.chips])
        red = None
        if prof is not None:
            ev = trace_reduce.extract(prof.xplane())
            if save_trace:
                trace_reduce.save(ev, save_trace)
                Path(save_trace + ".lines.json").write_text(json.dumps(
                    trace_reduce.describe(prof.xplane()), indent=1))
            red = trace_reduce.reduce(ev, [d.id for d in devs[:cell.chips]])
    finally:
        if prof is not None:
            prof.close()
    peak = work.peaks(devs[0].device_kind,
                      cell.root / "bench" / "peaks.json")
    ctx = Context(cell, rec, seconds, setup_s, warmup_s, peak,
                  state_itemsize, red)
    metrics, info = {}, []
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        v = cell.reader(m["name"]).read(ctx)
        if v is None:
            info.append(f"metric {m['name']} found nothing to read")
        else:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    info += summary_lines(rec, seconds, ctx)
    info.append("engine counters: " + json.dumps(
        {k: v for k, v in eng.stats.items() if isinstance(v, (int, float))})
        + " faults and recoveries: " + json.dumps(program.health(eng)))
    if prof is not None:
        info.append(f"stopping the profiler took {prof.stop_s:.3f} s")
    del eng
    gc.collect()
    t0 = time.monotonic()
    chk = check_served(cell.arch, w, cell.cfg, cell.mix,
                       *pick_checked(rec, cell.mix, seed))
    due = ctx.attempted()
    failed = sum(s.failed or s.first != s.first for s in due)
    compared = compare(chk, cell.limits, failed)
    info.append(f"checked {chk['requests']} greedy requests, {chk['tokens']}"
                f" served tokens, and {chk['sampled_requests']} sampled ones,"
                f" {chk['sampled_tokens']} tokens, in "
                f"{time.monotonic() - t0:.3f} s")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": verdict(compared), "attempted": len(due),
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        bd = trace_reduce.breakdown(red)
        info.append("idle by host span: " + json.dumps(bd.pop("idle_by_host_span")))
        out["breakdown"] = bd
    out["compared"] = compared
    return {"result": out, "info": info}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> int:
    a = parse(argv)
    try:
        cell = Cell(root, a.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: cannot load workload {a.workload!r}: {e}",
              file=sys.stderr)
        return 2
    try:
        res = run(cell, a.seed, a.seconds, bool(a.trace), require_tpu)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP_EXIT
    for line in res["info"]:
        print(f"bench: {line}", file=sys.stderr)
    for name, c in res["result"]["compared"].items():
        print(f"bench: compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
