"""Jitted steps: prompt tokens as a share of the tokens the bucketed
prefill computed for them, in %. A prefill call computes
`prefills_per_step` rows of its padded length, dummy rows included; each
request is charged its call's tokens over the real rows in it
(`prefill_bucket`, `prefill_rows`). Over the admitted requests submitted
in the traced span. A program without the stamps gives nothing to read."""
import math

UNIT = "%"
NAN = float("nan")


def admitted(ctx) -> list:
    """Requests submitted in the span whose admission stamps are all set."""
    lo, hi = ctx.host_span()
    reqs = [s.req for s in ctx.record.seen if s.req is not None]
    return [r for r in reqs if lo <= r.t_submit < hi
            and not math.isnan(getattr(r, "t_prefill_enqueued", NAN))
            and not math.isnan(r.t_admitted)]


def read(ctx):
    reqs = [r for r in admitted(ctx) if r.prefill_rows > 0]
    rows = ctx.mix["prefills_per_step"]
    computed = sum(rows * r.prefill_bucket / r.prefill_rows for r in reqs)
    if not computed:
        return None
    return 100.0 * sum(len(r.prompt) for r in reqs) / computed
