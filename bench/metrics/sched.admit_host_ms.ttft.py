"""Scheduler admission: mean milliseconds of host work between a request
leaving the queue and every device op of its admission being enqueued
(`t_prefill_enqueued - t_dequeued`), over the admitted requests submitted
in the traced span. A program without the stamps gives nothing to read."""
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
    return 1e3 * sum(r.t_prefill_enqueued - r.t_dequeued
                     for r in reqs) / len(reqs)
