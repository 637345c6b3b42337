"""Device: one minus the union of device-operation intervals over the
traced window."""
UNIT = "%"


def read(ctx):
    return ctx.idle_share()
