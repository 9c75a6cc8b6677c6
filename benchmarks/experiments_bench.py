"""Experiment-API benchmark: one mixed static+workload grid, one call.

    PYTHONPATH=src python -m benchmarks.experiments_bench [--smoke]

Exercises the whole declarative pipeline (DESIGN.md §10) the way the
paper's grids use it: a single `Experiment` mixing static patterns and
time-varying workloads over Table-III topologies x substrates, planned
into shape/phase buckets, executed in streamed chunks with progress
reporting, and written as a schema-stamped `ResultFrame` CSV
(results/experiments_grid.csv).  Reports plan shape, wall-clock split
(plan vs execute) and the engine's compile/reuse stats.
"""
from __future__ import annotations

import argparse
import os
import time
from functools import partial

import repro.experiments as X
import repro.workloads as W
from repro.core.simulator import SimConfig
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR

SMOKE = dict(names=("mesh", "folded_torus", "folded_hexa_torus"),
             n=16, n_rates=3, cycles=360, warmup=120)
DEFAULT = dict(names=("mesh", "folded_torus", "hexamesh",
                      "folded_hexa_torus", "octamesh"),
               n=36, n_rates=5, cycles=1500, warmup=500)


def build_experiment(params: dict) -> X.Experiment:
    cfg = SimConfig(cycles=params["cycles"], warmup=params["warmup"])
    alt = W.Workload("alt:tornado-uniform",
                     partial(W.phase_alternating, repeats=1))
    traffics = ("uniform", "tornado", alt)
    return X.Experiment.grid(
        topologies=params["names"], sizes=[params["n"]],
        substrates=("organic", "glass"), traffics=traffics,
        roles=("hetero_cmi",), rates=X.SaturationGrid(params["n_rates"]),
        cfg=cfg, name="experiments_grid")


def bench(params: dict, chunk_size: int | None = None) -> dict:
    exp = build_experiment(params)
    engine = X.engine_for(exp.cfg)
    t0 = time.time()
    pl = X.plan(exp, engine)
    plan_s = time.time() - t0
    print(pl.describe())
    ticks: list = []
    t0 = time.time()
    frame = X.execute(pl, engine=engine, chunk_size=chunk_size,
                      progress=lambda done, total, key:
                      ticks.append((done, total)))
    exec_s = time.time() - t0
    frame.to_csv(os.path.join(RESULTS_DIR, "experiments_grid.csv"))
    static_rows = [r for r in frame.ok() if r["kind"] == "static"]
    wl_rows = [r for r in frame.ok() if r["kind"] == "workload"]
    out = dict(scenarios=len(exp), planned=pl.n_planned,
               buckets=len(pl.buckets), static_rows=len(static_rows),
               workload_rows=len(wl_rows),
               progress_ticks=len(ticks),
               plan_s=round(plan_s, 3), execute_s=round(exec_s, 3),
               engine_stats=dict(engine.stats))
    for k, v in out.items():
        print(f"{k}: {v}")

    from .harness import BenchRun
    run = BenchRun("experiments",
                   mode="smoke" if len(exp) <= 40 else "full")
    run.metrics(dict(plan_s=out["plan_s"], execute_s=out["execute_s"]))
    run.metric("scenarios", len(exp), direction="higher")
    run.metric("buckets", len(pl.buckets))
    run.metric("compiles", engine.stats["compiles"])
    run.extra(engine_stats=dict(engine.stats))
    run.finish()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid (CI-sized, well under a minute)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="stream buckets in chunks of this many cells")
    args = ap.parse_args(argv)
    use_compile_cache()
    bench(SMOKE if args.smoke else DEFAULT, chunk_size=args.chunk_size)


if __name__ == "__main__":
    main()
