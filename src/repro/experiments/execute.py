"""Experiment executor (DESIGN.md §10): Plan -> batched runs -> frame.

Runs each plan bucket through the shared `SweepEngine` — static buckets
via `run_specs`, workload buckets via `run_workloads`, analytic buckets
without any simulation — and assembles a `ResultFrame` with one row per
scenario in experiment order.

Scale/robustness knobs:

  * `chunk_size` streams a bucket in chunks of that many scenarios
    instead of one monolithic batch — bounds device memory for huge
    grids and gives `progress` callbacks something to report between
    compiled runs (the engine's executable cache makes chunks of one
    padded shape share one compiled program);
  * `on_error="skip"` isolates partial failures: a chunk that raises
    marks only its own scenarios `status="failed"` (with the error
    message in the row), logs an `execute.chunk_failed` metrics event
    with the skip reason (`repro.obs.metrics`), and the rest of the
    experiment completes;
  * engines are shared per `SimConfig` (`engine_for`), so every
    experiment, benchmark and deprecation shim in a process reuses one
    compiled-executable cache.

Observability (DESIGN.md §13): execution is span-traced (`execute` /
per-chunk `execute.chunk` spans nest over the engine's `sweep.group`
and the simulator's `sim.dispatch`/`sim.wait` spans), and the progress
callback can opt into per-chunk timing: a 4-parameter callback
`progress(done, total, key, info)` receives an `info` dict with
`elapsed_s`, `compiled` (runner-cache misses this chunk), `scenarios`
and `status`; the historical 3-parameter `progress(done, total, key)`
form keeps working unchanged.
"""
from __future__ import annotations

import inspect
import time
from typing import Callable

import numpy as np

from repro.core.simulator import SimConfig
from repro.obs.metrics import cache_counters, metrics
from repro.obs.trace import trace
from repro.sweep.engine import SweepEngine

from .frame import ResultFrame, _identity_row, scenario_row
from .plan import Bucket, Plan, plan as make_plan
from .scenario import Experiment

_ENGINES: dict[SimConfig, SweepEngine] = {}


def engine_for(cfg: SimConfig = SimConfig()) -> SweepEngine:
    """Process-wide engine per SimConfig (shared executable cache)."""
    if cfg not in _ENGINES:
        _ENGINES[cfg] = SweepEngine(cfg=cfg)
    return _ENGINES[cfg]


def _chunks(items: list, size: int | None):
    if not size or size >= len(items):
        yield items
        return
    for i in range(0, len(items), size):
        yield items[i:i + size]


def _progress_arity(cb) -> int:
    """How many positional args `cb` accepts (legacy callbacks take 3:
    done, total, key; observability-aware ones take 4: ..., info)."""
    try:
        params = [p for p in inspect.signature(cb).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY,
                                p.POSITIONAL_OR_KEYWORD)]
        var = any(p.kind == p.VAR_POSITIONAL
                  for p in inspect.signature(cb).parameters.values())
        return 4 if var or len(params) >= 4 else 3
    except (TypeError, ValueError):      # builtins / C callables
        return 3


def _run_chunk(engine: SweepEngine, bucket: Bucket, chunk: list) -> list:
    """One engine call for `chunk`; returns raw result dicts in order.

    The plan's bucket is already one engine call (`SweepEngine.group`),
    so the engine runs it as such (`single_program`), never re-splitting
    a merged bucket: a whole bucket runs at its key's shape, a chunk of
    one at its own members' padded maximum."""
    if bucket.key.kind == "analytic":
        return [None] * len(chunk)
    rates = np.stack([ps.rates for ps in chunk]).astype(np.float32)
    specs = [ps.spec for ps in chunk]
    # per-scenario routing overrides (Scenario.routing, DESIGN.md §15):
    # the bucket key carries the effective mode, so one engine serves
    # both — only the SimConfig handed to run_batch changes, and the
    # engine's runner cache keys on it
    cfg = engine.cfg if bucket.key.routing == engine.cfg.routing \
        else engine.cfg._replace(routing=bucket.key.routing)
    if bucket.key.kind == "workload":
        return engine.run_workloads(specs, [ps.sched_spec for ps in chunk],
                                    rates, single_program=True, cfg=cfg)
    return engine.run_specs(specs, rates, single_program=True, cfg=cfg)


def execute(pl: Plan, engine: SweepEngine | None = None,
            chunk_size: int | None = None,
            progress: Callable[[int, int, object], None] | None = None,
            on_error: str = "raise") -> ResultFrame:
    """Run a plan and return the `ResultFrame` (scenario order)."""
    if on_error not in ("raise", "skip"):
        raise ValueError(f"on_error must be 'raise' or 'skip', "
                         f"got {on_error!r}")
    exp = pl.experiment
    engine = engine or engine_for(exp.cfg)
    n = len(exp.scenarios)
    results: list = [None] * n
    planned: list = [None] * n
    rows: list = [None] * n
    errors: list = []
    for i, reason in pl.skipped:
        rows[i] = _identity_row(exp, exp.scenarios[i], "invalid", reason,
                                diag_code=pl.skip_codes.get(i, ""))
    total, done = pl.n_planned, 0
    arity = _progress_arity(progress) if progress is not None else 0
    with trace("experiment.execute", cat="experiments",
               experiment=exp.name, scenarios=n,
               buckets=len(pl.buckets)):
        for bucket in pl.buckets:
            for chunk in _chunks(bucket.items, chunk_size):
                t0 = time.perf_counter()
                misses0 = cache_counters()["cache.runner.misses"]
                status = "ok"
                with trace("execute.chunk", cat="experiments",
                           kind=bucket.key.kind,
                           scenarios=len(chunk)) as sp:
                    try:
                        out = _run_chunk(engine, bucket, chunk)
                    except Exception as e:   # noqa: BLE001 — isolate chunk
                        if on_error == "raise":
                            raise
                        status = "failed"
                        msg = f"{type(e).__name__}: {e}"
                        sp.set(error=msg)
                        # a skipped chunk is never silent: the skip
                        # reason lands in the metrics event log too
                        metrics.event(
                            "execute.chunk_failed", experiment=exp.name,
                            reason=msg, scenarios=len(chunk),
                            bucket=str(bucket.key),
                            indices=[ps.index for ps in chunk])
                        for ps in chunk:
                            planned[ps.index] = ps
                            errors.append((ps.index, msg))
                            rows[ps.index] = _identity_row(
                                exp, ps.scenario, "failed", msg,
                                diag_code="EX001")
                        out = None
                if out is not None:
                    for ps, res in zip(chunk, out):
                        planned[ps.index] = ps
                        results[ps.index] = res
                        rows[ps.index] = scenario_row(exp, ps, res)
                done += len(chunk)
                if progress is not None:
                    if arity >= 4:
                        info = dict(
                            elapsed_s=time.perf_counter() - t0,
                            compiled=cache_counters()
                            ["cache.runner.misses"] - misses0,
                            scenarios=len(chunk), status=status)
                        progress(done, total, bucket.key, info)
                    else:
                        progress(done, total, bucket.key)
    return ResultFrame(experiment=exp, rows=rows, results=results,
                       planned=planned, errors=errors)


def run(experiment: Experiment, engine: SweepEngine | None = None,
        chunk_size: int | None = None,
        progress: Callable[[int, int, object], None] | None = None,
        on_error: str = "raise",
        single_program: bool = False) -> ResultFrame:
    """The one front door: plan + execute in one call.

        frame = repro.experiments.run(Experiment([...], cfg=...))

    See `plan()` to inspect bucketing (and `single_program`) first,
    `execute()` for the streaming/failure knobs.
    """
    engine = engine or engine_for(experiment.cfg)
    return execute(make_plan(experiment, engine,
                             single_program=single_program),
                   engine=engine, chunk_size=chunk_size,
                   progress=progress, on_error=on_error)
