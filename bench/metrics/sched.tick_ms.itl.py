"""The scheduler's host loop: seconds of the traced span per `step()` call
in it that dispatched a pooled decode (host clock)."""
UNIT = "ms"


def read(ctx):
    return ctx.tick_ms()
