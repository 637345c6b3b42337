"""Process start to the end of warmup: weights made on the device, the
engine built and every shape of the cell's traffic compiled or loaded from
the persistent cache. The traffic's ramp is not counted."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
