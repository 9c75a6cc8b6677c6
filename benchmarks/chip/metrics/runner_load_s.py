"""Host seconds the process spent getting each simulator runner's
executable: its load from the persistent compile cache, or the XLA
compile on a miss, from the program's compile-pipeline counters
(`jit.compile_s:<fun>` of every `runner` function in
`repro.obs.metrics`).  The counters cover the whole process; the traced
pass compiles nothing, so this is set-up.  Read only where the device
trace ran the runners."""


def read(ctx):
    if not any("runner" in name for name, _, _ in ctx.red.modules):
        return None
    from repro.obs import metrics
    secs = [v["sum"] for k, v in metrics.snapshot().items()
            if k.startswith("jit.compile_s:")
            and "runner" in k.split(":", 1)[1]]
    return sum(secs) if secs else None
