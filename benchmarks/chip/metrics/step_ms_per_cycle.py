"""Device time of whole runner calls (the `runner` programs of the
trace's module line) per simulated cycle of those calls."""


def read(ctx):
    runs = [d for name, _, d in ctx.red.modules if "runner" in name]
    if not runs:
        return None
    return sum(runs) / (len(runs) * ctx.config["cycles"]) / 1e6
