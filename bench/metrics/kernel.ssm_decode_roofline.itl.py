"""Kernels: the Pallas `ssm_decode` kernel's share of its roofline, the
least time its bytes and FLOPs need on this chip (bench/work.py, from the
algorithm's shapes; bound by bytes) over its device time in the trace."""
UNIT = "%"


def read(ctx):
    return ctx.ssm_decode_roofline()
