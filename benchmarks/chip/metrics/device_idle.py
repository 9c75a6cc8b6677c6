"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    if not ctx.red.ops:
        return None
    return 100.0 * (1.0 - ctx.red.busy_ns / ctx.red.window_ns)
