"""Reduction of a profiler trace (`.xplane.pb`) and the program's spans to
the numbers the per-layer metrics read.

The traced window is the host annotation `WINDOW` that the harness opens
around the traced passes.  Device time is read from the first TPU
device plane: its `XLA Ops` line (every operation the chip ran, each
loop iteration apart) and its `XLA Modules` line (whole program calls).
On a TPU an op's event name is its whole HLO instruction
(`%fusion.197 = s32[229376]{...} fusion(...), ...`); ops are named here
by the instruction's own name (`fusion.197`), as the trace viewer shows
it.  Control-flow ops such as the scan's `while` hold the ops of their
body on the same line: they count towards busy time, but the op totals
(`op_time_ns`, `top_ops`) take only ops that hold no other op.
The program's own spans (`repro.obs.trace`, perf_counter clock) are put
on the trace's clock by the offset between the window's annotation and
the perf_counter reading taken as it opened.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    """What the metric readers see of one traced window (ns on the
    trace's clock; host spans already shifted onto it)."""
    window: tuple            # (start, end)
    ops: list                # [(name, start, dur)] leaf device ops in window
    modules: list            # [(name, start, dur)] device programs
    busy: list               # merged [(start, end)] of all ops, clipped
    spans: list              # [(name, start, dur, args)] program spans

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)

    def op_time_ns(self, match) -> float:
        """Summed device time of the ops whose name `match` accepts."""
        return sum(d for name, _, d in self.ops if match(name))

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]


def read_planes(path: str) -> dict:
    """{(plane, line): [(name, start_ns, dur_ns)]} of an .xplane.pb file."""
    from jax.profiler import ProfileData
    return planes_of(ProfileData.from_file(path))


def op_name(event_name: str) -> str:
    """`fusion.197` of `%fusion.197 = s32[...] fusion(...)`; other names
    as they are."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def planes_of(profile) -> dict:
    """{(plane, line): [(name, start_ns, dur_ns)]} of a `ProfileData`:
    every host line, and the device lines the reduction reads."""
    out = {}
    for plane in profile.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            out[(plane.name, line.name)] = [
                (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                for e in line.events]
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(ops) -> list:
    """The ops that hold no other op (a `while` holds its body's ops).
    Sorted by start, longest first, an op that holds others is followed
    by one that lies inside it."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))

    def holds(a, b):
        return b[1] < a[1] + a[2] and b[1] + b[2] <= a[1] + a[2]

    return [e for i, e in enumerate(ops)
            if i + 1 == len(ops) or not holds(e, ops[i + 1])]


def _device_plane(planes: dict) -> str | None:
    names = sorted({p for p, _ in planes if p.startswith("/device:TPU:")})
    return names[0] if names else None


def reduce(planes: dict, spans=(), perf_at_window_ns: float | None = None
           ) -> Reduced:
    """Cut the trace to the window and merge the device op intervals.

    `spans` are the program's spans (objects with name, ts, dur, args;
    perf_counter ns) and `perf_at_window_ns` the perf_counter reading
    taken as the window annotation opened."""
    wins = [(s, d) for (p, _), evs in planes.items() if p.startswith("/host")
            for name, s, d in evs if name == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w0 = min(s for s, _ in wins)
    w1 = max(s + d for s, d in wins)
    dev = _device_plane(planes)
    ops = [e for e in planes.get((dev, OPS_LINE), []) if w0 <= e[1] < w1]
    modules = [e for e in planes.get((dev, MODULES_LINE), [])
               if w0 <= e[1] < w1]
    busy = merge((max(s, w0), min(s + d, w1)) for _, s, d in ops)
    ops = leaves(ops)
    shift = 0.0 if perf_at_window_ns is None else w0 - perf_at_window_ns
    host = [(sp.name, sp.ts + shift, float(sp.dur), dict(sp.args))
            for sp in spans]
    return Reduced(window=(w0, w1), ops=ops, modules=modules, busy=busy,
                   spans=host)


def top_ops(red: Reduced, k: int = 10) -> list:
    """[[name, seconds]] of the k device ops that took most time."""
    tot: dict = {}
    for name, _, d in red.ops:
        tot[name] = tot.get(name, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(red: Reduced, k: int = 10) -> list:
    """[[host span, seconds]] of the k longest device-idle gaps in the
    window, each named by the innermost program span open at its middle
    (`host` where none is)."""
    edges = [red.window[0]] + [x for iv in red.busy for x in iv] + \
        [red.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        open_ = [sp for sp in red.spans if sp[1] <= mid < sp[1] + sp[2]]
        name = min(open_, key=lambda sp: sp[2])[0] if open_ else "host"
        out.append([name, (e - s) / 1e9])
    return out
