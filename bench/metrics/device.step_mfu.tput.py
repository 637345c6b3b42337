"""Device, whole step: model FLOPs of the tokens served in the traced
window (decoded tokens and prompts admitted in it, counted from shapes by
bench/work.py) over the window times the chips times the bf16 peak."""
UNIT = "%"


def read(ctx):
    return ctx.step_mfu()
