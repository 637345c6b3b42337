"""Output tokens delivered in the window over the window's length."""
UNIT = "tokens/s"


def read(ctx):
    rec = ctx.record
    return rec.tokens_in_window / (rec.w1 - rec.w0)
