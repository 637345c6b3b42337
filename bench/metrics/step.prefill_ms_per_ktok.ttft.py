"""Jitted steps: device milliseconds of the bucketed prefill executable
runs in the traced span (the engine's runs that hold no decode kernel) per
1,000 prompt tokens admitted in it (bucket padding is not counted as
tokens)."""
UNIT = "ms"


def read(ctx):
    return ctx.prefill_ms_per_ktok()
