"""Scheduler admission: mean milliseconds the host waited for a request's
first token once its admission was enqueued (`t_admitted -
t_prefill_enqueued`): the decode in flight, the sampler, the prefill and
the first-token draw. Over the admitted requests submitted in the traced
span. A program without the stamps gives nothing to read."""
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
    return 1e3 * sum(r.t_admitted - r.t_prefill_enqueued
                     for r in reqs) / len(reqs)
