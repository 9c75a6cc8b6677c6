"""Host seconds the process spent building the simulator's runners: the
jaxpr trace and the MLIR lowering of every `runner` function, from the
program's compile-pipeline counters (`jit.trace_s:<fun>`,
`jit.lower_s:<fun>` in `repro.obs.metrics`).  The counters cover the
whole process; the traced pass compiles nothing, so this is set-up.
Read only where the device trace ran the runners."""


def read(ctx):
    if not any("runner" in name for name, _, _ in ctx.red.modules):
        return None
    from repro.obs import metrics
    secs = [v["sum"] for k, v in metrics.snapshot().items()
            if k.startswith(("jit.trace_s:", "jit.lower_s:"))
            and "runner" in k.split(":", 1)[1]]
    return sum(secs) if secs else None
