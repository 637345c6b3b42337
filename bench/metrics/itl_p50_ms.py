"""Median of every gap between consecutive tokens of every request, as a
streaming client sees them, over the gaps that end in the window. Tokens
that appear together count a gap of 0 after the first."""
UNIT = "ms"


def read(ctx):
    gaps = ctx.record.gaps
    return 1e3 * ctx.percentile(gaps, 50) if gaps else None
