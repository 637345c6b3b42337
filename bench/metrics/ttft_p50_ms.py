"""Median time to first token over the requests due in the window: first
token seen by the client minus the time the request was due. A request
that failed or saw no first token by the end of the drain counts with the
time to the end of the drain."""
UNIT = "ms"


def read(ctx):
    vals = ctx.ttft_s()
    return 1e3 * ctx.percentile(vals, 50) if vals else None
