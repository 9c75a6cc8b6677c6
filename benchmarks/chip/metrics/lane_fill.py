"""Share of the executed lanes that are live work: over the engine's
calls (`sweep.group` spans), live specs x their nodes x live rates
against padded spec lanes x padded nodes x padded rates."""
import re


def read(ctx):
    live = padded = 0
    for _, _, _, args in ctx.red.spans_named("sweep.group"):
        n_pad = int(re.search(r"\bn=(\d+)", args["shape"]).group(1))
        live += args["s_live"] * ctx.config["n"] * args["r_live"]
        padded += args["s_pad"] * n_pad * args["r_pad"]
    return 100.0 * live / padded if padded else None
