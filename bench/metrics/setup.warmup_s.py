"""Host seconds of `ContinuousBatchingEngine.warmup` over the cell's
prompt buckets: the part of set-up that compiles or loads executables."""
UNIT = "s"


def read(ctx):
    return ctx.warmup_s
