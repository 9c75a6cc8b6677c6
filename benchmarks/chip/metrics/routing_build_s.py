"""Host seconds the process spent building routings (up*/down* tables,
`repro.core.routing`, on a routing-cache miss), from the program's
`routing.build_s` counter in `repro.obs.metrics`.  The traced pass
hits the routing cache, so this is set-up.  Read only where the device
trace ran the runners those routings feed."""


def read(ctx):
    if not any("runner" in name for name, _, _ in ctx.red.modules):
        return None
    from repro.obs import metrics
    o = metrics.snapshot().get("routing.build_s")
    return o["sum"] if isinstance(o, dict) else None
