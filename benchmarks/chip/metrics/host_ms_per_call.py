"""Host time per runner call: the traced passes' wall time less the
time spent waiting on the device (`sim.wait` spans), over the runner
calls (`sim.dispatch` spans).  Planning, stacking, dispatch and result
handling on the host all land here."""


def read(ctx):
    calls = len(ctx.red.spans_named("sim.dispatch"))
    if not calls:
        return None
    wait_ns = sum(s[2] for s in ctx.red.spans_named("sim.wait"))
    return (ctx.window_wall_ns - wait_ns) / calls / 1e6
