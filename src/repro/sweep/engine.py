"""Batched multi-topology sweep engine (DESIGN.md §6).

`SweepEngine` turns "evaluate K topologies x R injection rates" from a
per-topology recompile loop into a handful of batched compiled programs:

  1. specs are grouped by *bucketed* padded shape (dims rounded up to
     configurable multiples, batch size rounded up by replicating the
     last spec, rate rows rounded up by repeating the last rate),
  2. shape groups then merge while one call at the merged shape costs no
     more padded device work than the calls apart (`SweepEngine.group`,
     estimated by `lane_cost`), so the inert tail lanes of one group
     carry another group's specs instead,
  3. adding one more topology or rate to a sweep often re-runs the SAME
     executable (`repro.core.simulator.get_batch_runner` caches per
     padded shape; jit caches per batch shape) — though a new spec can
     change which groups merge, and so which executables run: merging
     trades that reuse for never adding padded work, and
  4. padding invariance (see `repro.sweep.padding`) guarantees results
     are bitwise-equal to the single-spec `simulate` path.

Case-level evaluation moved to the declarative experiment API
(`repro.experiments`, DESIGN.md §10): describe a grid of `Scenario`s,
`plan` it, `execute` it, get a `ResultFrame`.  The old case-level entry
points here (`evaluate_cases`, `evaluate_workload_cases`) remain as
deprecation shims forwarding to that pipeline; `run_specs` /
`run_workloads` stay first-class — they are the primitive layer the
experiment executor lowers onto.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import warnings
from typing import Hashable, NamedTuple, Sequence

import numpy as np

from repro.core import simulator as sim
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.routing import cached_routing
from repro.core.simulator import SimConfig, SimSpec
from repro.obs.metrics import cache_counters, metrics
from repro.obs.trace import trace

from .padding import PadShape


class SweepCase(NamedTuple):
    """One (topology, size, substrate, traffic) evaluation cell."""
    name: str
    n: int
    substrate: str = "organic"
    pattern: str = "uniform"
    area: float = 74.0
    roles: str = "homogeneous"

    def build(self) -> tuple:
        """(routing, traffic matrix) for this cell, via the shared cache."""
        topo, routing = cached_routing(self.name, self.n, self.substrate,
                                       self.area, self.roles)
        return routing, TR.PATTERNS[self.pattern](topo)

    @property
    def valid(self) -> bool:
        return T.valid_n(self.name, self.n)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m if m > 1 else x


def lane_cost(shape: PadShape) -> int:
    """Estimated per-cycle device work of one spec lane padded to `shape`.

    The step is dominated by its [N, P+1, V] port-grid phases (route
    lookup, allocation, move) and by per-channel arrivals and link
    writes; V and the buffer depth are the same in every call of one
    config, and the link ring is read one slot per cycle, so depth D
    does not enter (DESIGN.md §6)."""
    return shape.n * (shape.p + 1) + shape.c


class SpecGroup(NamedTuple):
    """One engine call: specs `idxs` padded to `shape` and `k_pad`.
    `tag` is what all members share: the planner's (kind, R, routing),
    None inside one engine call."""
    tag: Hashable
    shape: PadShape      # bucketed padded shape of the call
    k_pad: int           # bucketed phase axis (0 = static)
    idxs: tuple          # member positions, in input order


@dataclasses.dataclass
class SweepEngine:
    """Padded-batch sweep runner with a compiled-executable cache.

    bucket=False disables shape rounding (every distinct max-shape gets
    its own executable); the default buckets favour executable reuse when
    topologies are added incrementally.
    """
    cfg: SimConfig = SimConfig()
    bucket: bool = True
    s_round: int = 4         # batch axis rounded up to a multiple of this
    r_round: int = 4         # rate axis rounded up to a multiple of this
    n_mult: int = 8          # node-dim bucket
    c_mult: int = 32         # channel-dim bucket
    d_mult: int = 4          # link-ring bucket
    k_round: int = 2         # phase axis (workload mode) bucket

    def __post_init__(self):
        self.stats = dict(runs=0, groups=0, specs=0, compiles=0, reuses=0)

    # ---- shape policy --------------------------------------------------
    def bucket_shape(self, shape: PadShape) -> PadShape:
        if not self.bucket:
            return shape
        return PadShape(n=_round_up(shape.n, self.n_mult),
                        p=shape.p,
                        c=_round_up(shape.c, self.c_mult),
                        d=_round_up(shape.d, self.d_mult))

    def k_bucket(self, k: int) -> int:
        return _round_up(k, self.k_round) if self.bucket and k else k

    def call_cost(self, shape: PadShape, s_live: int) -> int:
        """Padded device work per cycle and rate of one call of `s_live`
        specs at `shape`: its padded spec lanes times `lane_cost`."""
        s_pad = _round_up(s_live, self.s_round) if self.bucket else s_live
        return s_pad * lane_cost(shape)

    def group(self, shapes: Sequence[PadShape],
              ks: Sequence[int] | None = None,
              tags: Sequence[Hashable] | None = None,
              single_program: bool = False) -> list[SpecGroup]:
        """Partition specs into engine calls — the one grouping policy,
        shared by `run_specs`/`run_workloads` and `experiments.plan`.

        shapes: each spec's own `PadShape`; ks: its phase count (0 for a
        static spec); tags: what members of one call must share (the
        planner's kind, rate-grid length and routing mode).  Specs first
        group by (tag, bucketed shape, bucketed phase count).  With
        `single_program` each tag's groups become one call.  Otherwise,
        when bucketing, a tag's groups merge greedily — largest saving
        first, ties merged (one compile fewer) — while the merged call
        costs no more padded work than the two apart:

            call_cost(max(a, b), s_a + s_b)
                <= call_cost(a, s_a) + call_cost(b, s_b)

        with `max` elementwise and the phase axis padded to the larger.
        The merges depend only on the multiset of (shape, phase count,
        live count) groups, never on the specs' order.  Groups come back
        in order of their first member.
        """
        n = len(shapes)
        ks = ks if ks is not None else [0] * n
        tags = tags if tags is not None else [None] * n
        base: dict = {}
        for i, (shape, k, tag) in enumerate(zip(shapes, ks, tags)):
            key = (tag, self.bucket_shape(shape), self.k_bucket(k))
            base.setdefault(key, []).append(i)
        by_tag: dict = {}
        for (tag, shape, k), idxs in base.items():
            by_tag.setdefault(tag, []).append((shape, k, idxs))
        out = []
        for tag, groups in by_tag.items():
            if single_program:
                groups = [(PadShape.of([g[0] for g in groups]),
                           max(g[1] for g in groups),
                           [i for g in groups for i in g[2]])]
            elif self.bucket and len(groups) > 1:
                merged = self._merge(groups)
                metrics.inc("sweep.merged_groups", len(groups) - len(merged))
                groups = merged
            out += [SpecGroup(tag, shape, k, tuple(sorted(idxs)))
                    for shape, k, idxs in groups]
        return sorted(out, key=lambda g: g.idxs[0])

    def _merge(self, groups: list) -> list:
        """Greedy cost-aware merging of (shape, k_pad, idxs) groups (see
        `group`).  Candidate merges sit in a heap keyed by (-saving, the
        pair's (shape, k_pad, live count)), so ties break on the groups'
        contents, never on their order."""
        def canon(g):
            return g[0], g[1], len(g[2])

        alive = dict(enumerate(sorted(groups, key=canon)))
        heap: list = []

        def push(a: int, b: int) -> None:
            ga, gb = alive[a], alive[b]
            saving = (self.call_cost(ga[0], len(ga[2]))
                      + self.call_cost(gb[0], len(gb[2]))
                      - self.call_cost(PadShape.of([ga[0], gb[0]]),
                                       len(ga[2]) + len(gb[2])))
            if saving >= 0:
                heapq.heappush(heap, (-saving, sorted((canon(ga), canon(gb))),
                                      a, b))

        for a, b in itertools.combinations(list(alive), 2):
            push(a, b)
        nxt = len(alive)
        while heap:
            _, _, a, b = heapq.heappop(heap)
            if a not in alive or b not in alive:
                continue                       # stale: a side merged since
            ga, gb = alive.pop(a), alive.pop(b)
            alive[nxt] = (PadShape.of([ga[0], gb[0]]), max(ga[1], gb[1]),
                          ga[2] + gb[2])
            for c in list(alive)[:-1]:
                push(c, nxt)
            nxt += 1
        return list(alive.values())

    # ---- core entry points ---------------------------------------------
    def run_specs(self, specs: Sequence[SimSpec], rates,
                  single_program: bool = False,
                  cfg: SimConfig | None = None) -> list[dict]:
        """Run heterogeneous specs through few batched programs.

        rates: [R] shared or [S, R] per-spec.  Returns one result dict
        per spec (same keys as `simulator.run_batch`), in input order.
        single_program=True pads every spec to one global shape so the
        whole sweep is exactly one compiled program (at the cost of
        padding small-radix topologies to the largest radix present).
        `cfg` overrides the engine's SimConfig for this call only (the
        experiment executor uses it for per-scenario routing modes,
        DESIGN.md §15); the runner cache keys on the config, so
        overrides coexist with the engine default.
        """
        return self._run_grouped(specs, rates, None, single_program, cfg)

    def run_workloads(self, specs: Sequence[SimSpec], schedules, rates,
                      single_program: bool = False,
                      cfg: SimConfig | None = None) -> list[dict]:
        """Run (spec, phase-schedule) pairs through few batched programs.

        schedules: one `simulator.SchedSpec` (or compilable
        `workloads.Schedule`) per spec.  Groups also bucket the phase
        axis (`k_round`) so workloads with similar phase counts share
        executables.  Result dicts gain the per-phase counters of
        `run_batch(..., schedules=...)`.  `cfg` as in `run_specs`.
        """
        if len(schedules) != len(specs):
            raise ValueError(
                f"schedules {len(schedules)} != specs {len(specs)}")
        schedules = [s.compile() if hasattr(s, "compile") else s
                     for s in schedules]
        return self._run_grouped(specs, rates, schedules, single_program,
                                 cfg)

    # keys whose leading axis is NOT the rate grid (never trimmed)
    # result keys whose leading axis is NOT the rate axis — never
    # sliced back to n_rates when rate-padding is trimmed
    _PER_PHASE_KEYS = ("phase_cycles", "window_cycles")

    def _run_grouped(self, specs, rates, schedules, single_program,
                     cfg: SimConfig | None = None):
        cfg = cfg or self.cfg
        s = len(specs)
        rates = np.asarray(rates, np.float32)
        if rates.ndim == 1:
            rates = np.broadcast_to(rates, (s, rates.shape[0])).copy()
        n_rates = rates.shape[1]
        r_pad = _round_up(n_rates, self.r_round) if self.bucket else n_rates
        own = [PadShape(n=sp.n, p=sp.p, c=sp.c, d=sp.d) for sp in specs]
        ks = [sc.k for sc in schedules] if schedules is not None \
            else [0] * s
        groups = self.group(own, ks, single_program=single_program)

        # compile accounting via the metrics registry's monotonic cache
        # counters (DESIGN.md §13): a runner-cache *miss* delta counts
        # new compiled programs exactly.  The old before/after subtraction
        # of sum(entries.values()) shrank when the LRU evicted a runner
        # between the two reads and misattributed compiles.
        before = cache_counters()["cache.runner.misses"]
        results: list = [None] * s
        for _, shape, k_pad, idxs in groups:
            idxs = list(idxs)
            g_specs = [specs[i] for i in idxs]
            g_scheds = [schedules[i] for i in idxs] \
                if schedules is not None else None
            g_rates = rates[idxs]
            if r_pad > n_rates:
                g_rates = np.concatenate(
                    [g_rates,
                     np.repeat(g_rates[:, -1:], r_pad - n_rates, axis=1)],
                    axis=1)
            s_live = len(g_specs)
            s_pad = _round_up(s_live, self.s_round) \
                if self.bucket else s_live
            shapes = len({(self.bucket_shape(own[i]), self.k_bucket(ks[i]))
                          for i in idxs})
            while len(g_specs) < s_pad:           # replicate an inert tail
                g_specs.append(g_specs[-1])
                g_rates = np.concatenate([g_rates, g_rates[-1:]], axis=0)
                if g_scheds is not None:
                    g_scheds.append(g_scheds[-1])
            # bucket-fill attrs (DESIGN.md §16): live vs padded batch
            # rows/rates — with the per-spec pad_fill fractions on the
            # results, the complete pad-waste picture for this dispatch.
            # `shapes` counts the shape groups this call carries;
            # work_live / work_pad (Σ `lane_cost` of the live specs at
            # their own shapes / of every padded lane at the call's) add
            # the port and channel padding that s_live / s_pad miss
            with trace("sweep.group", cat="sweep", specs=len(g_specs),
                       shape=str(shape), k_pad=k_pad,
                       s_live=s_live, s_pad=s_pad,
                       r_live=n_rates, r_pad=g_rates.shape[1],
                       shapes=shapes,
                       work_live=sum(lane_cost(own[i]) for i in idxs),
                       work_pad=s_pad * lane_cost(shape),
                       kind="static" if g_scheds is None else "workload"):
                out = sim.run_batch(g_specs, g_rates, cfg,
                                    pad_shape=shape, schedules=g_scheds,
                                    k_pad=k_pad or None)
            metrics.observe("sweep.bucket_fill", s_live / s_pad)
            for j, i in enumerate(idxs):
                results[i] = {
                    k: (v[:n_rates] if isinstance(v, np.ndarray)
                        and k not in self._PER_PHASE_KEYS else v)
                    for k, v in out[j].items()}
        compiled = cache_counters()["cache.runner.misses"] - before
        self.stats["runs"] += 1
        self.stats["groups"] += len(groups)
        self.stats["specs"] += s
        self.stats["compiles"] += compiled
        self.stats["reuses"] += max(len(groups) - compiled, 0)
        metrics.inc("sweep.runs")
        metrics.inc("sweep.groups", len(groups))
        metrics.inc("sweep.specs", s)
        metrics.inc("sweep.compiles", compiled)
        return results

    # ---- case-level deprecation shims ----------------------------------
    # Case-level evaluation was redesigned into the declarative
    # experiment API (repro.experiments, DESIGN.md §10).  These shims
    # forward to it and reshape the ResultFrame into the legacy
    # list-of-dicts; they will be removed once nothing imports them.

    def _experiment_frame(self, scenarios):
        from repro import experiments as X
        exp = X.Experiment(scenarios, cfg=self.cfg, name="legacy_shim")
        return X.execute(X.plan(exp, engine=self), engine=self)

    def evaluate_cases(self, cases: Sequence[SweepCase],
                       n_rates: int = 6) -> list[dict | None]:
        """DEPRECATED: use `repro.experiments.run` on an `Experiment` of
        static `Scenario`s (see README migration table).

        Simulated saturation for many cells; invalid cells yield None.
        """
        warnings.warn(
            "SweepEngine.evaluate_cases is deprecated; build an "
            "Experiment of Scenarios and call repro.experiments.run",
            DeprecationWarning, stacklevel=2)
        from repro import experiments as X
        frame = self._experiment_frame(
            [X.scenario_from_case(c, rates=X.SaturationGrid(n_rates))
             for c in cases])
        out = []
        for i, case in enumerate(cases):
            res = frame.case_result(i)
            if res is not None:
                res["case"] = case
            out.append(res)
        return out

    def evaluate_workload_cases(self, cases: Sequence[SweepCase],
                                workloads: Sequence, n_rates: int = 5,
                                fit: bool = True) -> list[dict | None]:
        """DEPRECATED: use `repro.experiments.run` on an `Experiment`
        whose Scenarios carry the workloads as their `traffic` (see
        README migration table).

        Returns len(cases) * len(workloads) rows in case-major order;
        invalid cases yield None rows.
        """
        warnings.warn(
            "SweepEngine.evaluate_workload_cases is deprecated; build "
            "an Experiment of workload Scenarios and call "
            "repro.experiments.run", DeprecationWarning, stacklevel=2)
        from repro import experiments as X
        frame = self._experiment_frame(
            [dataclasses.replace(
                X.scenario_from_case(case, traffic=wl,
                                     rates=X.SaturationGrid(n_rates)),
                fit_schedule=fit)
             for case in cases for wl in workloads])
        out = []
        for ci, case in enumerate(cases):
            for wi in range(len(workloads)):
                res = frame.workload_result(ci * len(workloads) + wi)
                if res is not None:
                    res["case"] = case
                out.append(res)
        return out

    def sweep(self, names: Sequence[str], n: int, substrate: str = "organic",
              pattern: str = "uniform", area: float = 74.0,
              roles: str = "homogeneous", n_rates: int = 6) -> list[dict]:
        """Evaluate several topologies at one size in one batched sweep
        (a thin convenience over `repro.experiments.run`)."""
        from repro import experiments as X
        frame = self._experiment_frame(
            [X.Scenario(name, n, substrate, pattern, area, roles,
                        rates=X.SaturationGrid(n_rates))
             for name in names])
        rows = []
        for i, name in enumerate(names):
            res = frame.case_result(i)
            if res is None:
                continue
            rows.append(dict(topology=name, n=n, substrate=substrate,
                             pattern=pattern,
                             sim_saturation=res["sim_saturation"],
                             analytic_saturation=res["analytic_saturation"],
                             latency_at_sat=res["latency_at_sat"]))
        return rows


def default_engine() -> SweepEngine:
    """Process-wide engine for the default SimConfig.  Forwards to the
    experiment executor's per-config registry so legacy callers and the
    declarative pipeline share one engine (and its stats)."""
    from repro.experiments import engine_for
    return engine_for(SimConfig())
