"""The flight recorder's share of its memory roofline: the least time
its logical HBM bytes take at the chip's peak bandwidth, over the device
time of the ops in `step_flight` (`benchmarks/chip/step_phases.py`).

Per padded lane and simulated cycle the step reads and writes (int32)
each flight counter element it updates: `tel_busy` and `tel_stall`
[C+1], `tel_occ` [C+1, V], `tel_inj` and `tel_eject` [N], `tel_hist`
[16]; with `telemetry_windows` W > 0 the same again, less the
histogram, in the cycle's window slice.  C, N are each engine call's
padded channels and nodes (`sweep.group` spans).  A lower bound of the
bytes the scatters move, so the share cannot exceed the truth."""
import re

from benchmarks.chip.step_phases import flight_ns

LAT_HIST_BINS = 16


def flight_bytes(n: int, c: int, v: int, windows: int) -> int:
    """HBM bytes of one lane-cycle of the flight recorder: one int32
    read and one write of each counter element the step updates."""
    per_slice = (c + 1) * (2 + v) + 2 * n
    elements = per_slice + LAT_HIST_BINS + (per_slice if windows else 0)
    return 2 * 4 * elements


def read(ctx):
    flight = flight_ns(ctx)
    if flight is None:
        return None
    total = 0
    for _, _, _, args in ctx.red.spans_named("sweep.group"):
        n = int(re.search(r"\bn=(\d+)", args["shape"]).group(1))
        c = int(re.search(r"\bc=(\d+)", args["shape"]).group(1))
        lanes = args["s_pad"] * args["r_pad"]
        total += lanes * ctx.config["cycles"] * flight_bytes(
            n, c, ctx.config["n_vcs"], ctx.config.get("telemetry_windows", 0))
    if not total:
        return None
    least_s = total / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (flight / 1e9)
