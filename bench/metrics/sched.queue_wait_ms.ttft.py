"""Scheduler admission: mean of the engine's admission stamp minus the due
time, over the admitted requests due in the traced span."""
UNIT = "ms"


def read(ctx):
    return ctx.queue_wait_ms()
