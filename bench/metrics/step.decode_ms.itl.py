"""Jitted steps: device milliseconds per run of the pooled decode
executable (guarded or not: the engine's executable run that holds the
`ssm_decode_pallas` kernel), from the profiler trace."""
UNIT = "ms"


def read(ctx):
    return ctx.decode_ms()
