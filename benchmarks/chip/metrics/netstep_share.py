"""Share of the device's busy time spent in the netstep allocation
kernel (its ops found by name in the trace)."""


def is_netstep(name: str) -> bool:
    return "netstep" in name


def read(ctx):
    kernel = ctx.red.op_time_ns(is_netstep)
    if not kernel or not ctx.red.busy_ns:
        return None
    return 100.0 * kernel / ctx.red.busy_ns
