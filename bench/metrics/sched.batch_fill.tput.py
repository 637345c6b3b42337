"""Scheduler batching: mean active slots per decode tick in the window, as
a share of the slot pool."""
UNIT = "%"


def read(ctx):
    act = ctx.record.active_per_decode
    return 100.0 * sum(act) / len(act) / ctx.n_slots if act else None
