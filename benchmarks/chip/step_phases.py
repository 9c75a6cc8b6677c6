"""Device time of the simulator's step phases in a traced window.

Each leaf op of the window is tied to the runner program (a `runner`
event of the trace's `XLA Modules` line) whose interval holds it.  Each
such program is tied to the phase map (`repro.obs.phases`) of the one
runner of the process whose compiled instructions include every op name
seen in it; the map then names each op's phase.  Ops that no runner
program holds ran outside the scan and count as `outside`.
"""
from __future__ import annotations

import bisect

OUTSIDE = "outside"


def runner_maps() -> list:
    """The process's runner phase maps; none where the program keeps no
    map (a program older than `repro.obs.phases`)."""
    try:
        from repro.obs.phases import phase_maps
    except ImportError:
        return []
    return phase_maps()


def programs(red) -> list:
    """[(start, end, {op name: ns})] of the window's runner programs, in
    time order, with the leaf ops each holds; ops outside every runner
    program under start and end None, last."""
    mods = sorted((s, s + d) for name, s, d in red.modules
                  if "runner" in name)
    starts = [s for s, _ in mods]
    held = [{} for _ in mods]
    rest: dict = {}
    for name, s, d in red.ops:
        k = bisect.bisect_right(starts, s) - 1
        into = held[k] if k >= 0 and s < mods[k][1] else rest
        into[name] = into.get(name, 0.0) + d
    return [(s, e, ops) for (s, e), ops in zip(mods, held)] + \
        [(None, None, rest)]


def match(ops: dict, maps: list):
    """The one map whose instructions include every op name in `ops`;
    None where no map or several do."""
    found = [m for m in maps if all(name in m.phases for name in ops)]
    return found[0] if len(found) == 1 else None


def phase_ns(red, maps: list) -> dict | None:
    """{phase, `OUTSIDE` or None (in the scan, no phase): device ns} over
    the window's leaf ops; None where no runner program ran, or where one
    matches no map or several."""
    progs = programs(red)
    if len(progs) == 1 or not maps:
        return None
    out: dict = {}
    for start, _, ops in progs:
        if not ops:
            continue
        if start is None:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + sum(ops.values())
            continue
        m = match(ops, maps)
        if m is None:
            return None
        for name, ns in ops.items():
            label = m.phases.get(name)
            out[label] = out.get(label, 0.0) + ns
    return out


def flight_ns(ctx) -> float | None:
    """Device ns of the ops in `step_flight`; None where the
    configuration has the flight recorder off, or the ops cannot be tied
    to a phase map, or none of them is the flight recorder's."""
    if not ctx.config.get("telemetry"):
        return None
    by_phase = phase_ns(ctx.red, runner_maps())
    if by_phase is None:
        return None
    return by_phase.get("step_flight") or None
