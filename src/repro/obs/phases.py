"""Which step phase each compiled instruction of a runner belongs to
(DESIGN.md §13).

The simulator's step names its phases with `jax.named_scope`
(`step_arrivals` ... `step_flight`).  A device trace names an op by its
HLO instruction (`fusion.264`, `netstep.8`), not by its scope, and the
fusions XLA makes of scatters carry no scope of their own.  The compiled
runner's HLO text does carry the scopes, in the `op_name` metadata of
its instructions and of the instructions inside each fusion.  So:

  * `hlo_phases(text)` maps every instruction of a compiled module's
    sequential computations (the entry, loop bodies and conditions) to
    the phase it belongs to:
      - an unfused instruction takes the innermost `step_*` scope of its
        own `op_name`;
      - a fusion takes the phase its inner instructions' `op_name`s
        name (its own where they name none); where they name several it
        takes its root's, walking back from the root to the first
        instruction that names one, and is counted as mixed;
      - an instruction with no phase outside every loop body is
        `OUTSIDE`; one inside the scan's body with no phase maps to
        None;
  * `phase_maps()` builds the map of every runner `run_batch` noted in
    `obs.profile`'s registry (it notes while profiling or span tracing
    is on: its key, padded shape, `SimConfig`, phase-schedule width and
    the abstract arguments of its call), only when asked:
    `runner.lower(...).compile()` then finds the traced, lowered and
    compiled program in JAX's caches (a persistent-cache load included)
    and compiles nothing.  The compile-pipeline counters of
    `repro.obs.metrics` are muted while it does, so building a map is
    never counted as set-up.  Ask outside any timed window.
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["PHASES", "OUTSIDE", "RunnerPhases", "hlo_phases",
           "scope_phase", "phase_maps"]

#: the step's phases, in the order the step runs them
PHASES = ("step_arrivals", "step_credits", "step_inject", "step_route",
          "step_alloc", "step_move", "step_flight")
#: the label of instructions that run outside the scan's body
OUTSIDE = "outside"

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|to_apply|body|condition)=%?([\w.\-]+)")
_LOOP = ("body", "condition")


@dataclasses.dataclass
class _Instr:
    name: str
    opcode: str
    root: bool
    op_name: str | None
    operands: list
    called: list            # [(attribute, computation)]


def _operands(rest: str, start: int) -> list:
    """Names in the balanced parentheses that open at `rest[start]`."""
    depth = 0
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                return _OPERAND.findall(rest[start:i])
    return _OPERAND.findall(rest[start:])


def _parse(text: str) -> tuple:
    """({computation: [_Instr]}, entry computation name)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        op = _OPCODE.search(rest)
        if op is None:
            continue
        opn = _OP_NAME.search(rest)
        cur.append(_Instr(name=m.group(2), opcode=op.group(1),
                          root=bool(m.group(1)),
                          op_name=opn.group(1) if opn else None,
                          operands=_operands(rest, op.end() - 1),
                          called=_CALLED.findall(rest)))
    return comps, entry


def scope_phase(op_name: str | None) -> str | None:
    """The innermost `step_*` scope of an `op_name`, or None."""
    if not op_name:
        return None
    found = [p for p in op_name.split("/") if p in PHASES]
    return found[-1] if found else None


def hlo_phases(text: str) -> tuple:
    """({instruction: phase, `OUTSIDE` or None}, frozenset of mixed
    fusions) of a compiled module's HLO text (`Compiled.as_text()`);
    see the module docstring for the rules."""
    comps, entry = _parse(text)
    inner_memo: dict = {}
    root_memo: dict = {}

    def inner(comp: str) -> set:
        """Phases that the instructions of `comp` (and of what they call)
        name."""
        if comp not in inner_memo:
            inner_memo[comp] = set()
            out = set()
            for ins in comps.get(comp, ()):
                p = scope_phase(ins.op_name)
                if p:
                    out.add(p)
                for _, c in ins.called:
                    out |= inner(c)
            inner_memo[comp] = out
        return inner_memo[comp]

    def root_phase(comp: str) -> str | None:
        """The phase of `comp`'s root: the first instruction that names
        one, walking back from the root through operands."""
        if comp in root_memo:
            return root_memo[comp]
        root_memo[comp] = None
        by_name = {ins.name: ins for ins in comps.get(comp, ())}
        queue = [ins for ins in comps.get(comp, ()) if ins.root]
        seen = set()
        while queue:
            ins = queue.pop(0)
            if ins.name in seen:
                continue
            seen.add(ins.name)
            p = scope_phase(ins.op_name) or next(
                (root_phase(c) for _, c in ins.called if root_phase(c)),
                None)
            if p:
                root_memo[comp] = p
                return p
            queue += [by_name[o] for o in ins.operands if o in by_name]
        return None

    # the computations that run in sequence: the entry, and the bodies
    # and conditions of the loops it runs
    loops, stack = set(), [entry]
    while stack:
        for ins in comps.get(stack.pop(), ()):
            for attr, c in ins.called:
                if attr in _LOOP and c not in loops:
                    loops.add(c)
                    stack.append(c)

    phases, mixed = {}, set()
    for comp in [entry, *loops] if entry else []:
        for ins in comps[comp]:
            fused = [c for attr, c in ins.called if attr == "calls"]
            named = set().union(*(inner(c) for c in fused))
            if len(named) > 1:
                mixed.add(ins.name)
                phase = next((root_phase(c) for c in fused
                              if root_phase(c)), None)
            elif named:
                phase = named.pop()
            else:
                phase = scope_phase(ins.op_name)
            if phase is None and comp not in loops:
                phase = OUTSIDE
            phases[ins.name] = phase
    return phases, frozenset(mixed)


@dataclasses.dataclass(frozen=True)
class RunnerPhases:
    """The phase map of one compiled runner."""
    shape: str              # str(PadShape) of its calls
    cfg: object             # its SimConfig
    kmax: int               # phase-schedule width (0: static runner)
    phases: dict            # {instruction: phase | OUTSIDE | None}
    mixed: frozenset        # fusions whose inner instructions name several


def phase_maps() -> list:
    """[RunnerPhases] of every runner noted while profiling or span
    tracing was on (`obs.profile`) and still alive; builds the missing
    maps, so call it off any timed path."""
    from .metrics import compile_events_muted
    from .profile import noted_runners
    out = []
    for entry in noted_runners():
        runner = entry["runner"]()
        if runner is None:
            continue
        if entry.get("map") is None:
            with compile_events_muted():
                text = runner.lower(*entry["args"]).compile().as_text()
            phases, mixed = hlo_phases(text)
            entry["map"] = RunnerPhases(
                shape=entry["shape"], cfg=entry["cfg"], kmax=entry["kmax"],
                phases=phases, mixed=mixed)
        out.append(entry["map"])
    return out
