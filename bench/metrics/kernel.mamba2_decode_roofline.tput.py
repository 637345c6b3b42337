"""Kernels: the Pallas `mamba2_decode` kernel's share of its roofline, the
least time its bytes and FLOPs need on this chip (the architecture's count
in `KERNELS`, from the algorithm's shapes; bound by bytes) over its device
time in the trace."""
UNIT = "%"


def read(ctx):
    return ctx.kernel_roofline("mamba2_decode")
