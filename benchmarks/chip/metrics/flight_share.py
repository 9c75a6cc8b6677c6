"""Share of the device's busy time spent in the flight recorder: the
leaf ops that the program's phase map (`repro.obs.phases`) puts in the
step's `step_flight` phase, tied to their runner as
`benchmarks/chip/step_phases.py` says.  Read only where the
configuration turns the flight recorder on."""
from benchmarks.chip.step_phases import flight_ns


def read(ctx):
    flight = flight_ns(ctx)
    if flight is None or not ctx.red.busy_ns:
        return None
    return 100.0 * flight / ctx.red.busy_ns
