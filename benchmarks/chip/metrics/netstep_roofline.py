"""The netstep kernel's share of its memory roofline: the least time the
HBM bytes of the logical allocation take at the chip's peak bandwidth,
over the kernel's device time.

Per lane and simulated cycle the allocation reads `op_slot` (int32) and
`eligible` (1 byte) of shape [N, PI, V] and writes `win_mask` (1 byte,
[N, PI, V]), `vc_choice` and `out_req` (int32, [N, PI]), at the padded
N and PI of each engine call.  Its integer work has no published peak,
so only the bytes bound it."""
import re


def allocation_bytes(n: int, pi: int, v: int) -> int:
    """HBM bytes of one lane-cycle of the allocation."""
    return n * pi * v * (4 + 1 + 1) + n * pi * (4 + 4)


def is_netstep(name: str) -> bool:
    return "netstep" in name


def read(ctx):
    kernel_ns = ctx.red.op_time_ns(is_netstep)
    if not kernel_ns:
        return None
    total = 0
    for _, _, _, args in ctx.red.spans_named("sweep.group"):
        n = int(re.search(r"\bn=(\d+)", args["shape"]).group(1))
        p = int(re.search(r"\bp=(\d+)", args["shape"]).group(1))
        lanes = args["s_pad"] * args["r_pad"]
        total += lanes * ctx.config["cycles"] * allocation_bytes(
            n, p + 1, ctx.config["n_vcs"])
    least_s = total / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ns / 1e9)
