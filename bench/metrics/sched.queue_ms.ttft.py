"""Scheduler admission: mean milliseconds a request waited in the engine's
queue (`t_dequeued - t_submit`), over the admitted requests submitted in
the traced span. With `sched.admit_host_ms.ttft` and
`sched.admit_wait_ms.ttft` it splits `t_admitted - t_submit` in three.
A program without the stamps gives nothing to read."""
import math

UNIT = "ms"
NAN = float("nan")


def admitted(ctx) -> list:
    """Requests submitted in the span whose admission stamps are all set."""
    lo, hi = ctx.host_span()
    reqs = [s.req for s in ctx.record.seen if s.req is not None]
    return [r for r in reqs if lo <= r.t_submit < hi
            and not math.isnan(getattr(r, "t_prefill_enqueued", NAN))
            and not math.isnan(r.t_admitted)]


def read(ctx):
    reqs = admitted(ctx)
    if not reqs:
        return None
    return 1e3 * sum(r.t_dequeued - r.t_submit for r in reqs) / len(reqs)
