"""Workload sweep benchmark — the headline time-varying result.

    PYTHONPATH=src python -m benchmarks.workload_bench [--smoke|--full]

Evaluates Table-III topologies on organic AND glass substrates under
three workload families (DESIGN.md §9):

  * an LLM-training collective workload derived from a sharded qwen3
    step (`repro.workloads.collective_workload` — TP all-reduce waves,
    FSDP gather/reduce-scatter, mapped onto chiplet positions),
  * a replayed Netrace-like region trace with ON/OFF memory bursts
    (`trace_workload("fluidanimate")`),
  * an adversarial tornado<->uniform phase alternation.

The whole (topology x substrate) x workload grid is ONE declarative
`Experiment` — workloads ride in the Scenarios' `traffic` field — run
through `repro.experiments.run` (DESIGN.md §10), which lowers it onto
batched `SweepEngine` programs (the engine `stats` record how many).
Results land in results/workload_sweep.csv (schema-stamped), one row
per (cell, phase) plus an ALL summary row per cell.
"""
from __future__ import annotations

import argparse
import os
import time
from functools import partial

import numpy as np

import repro.experiments as X
import repro.workloads as W
from repro.core.simulator import SimConfig
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR, write_csv

SUBSTRATES = ("organic", "glass")

SMOKE = dict(names=("mesh", "folded_torus", "folded_hexa_torus"),
             n=16, n_rates=3, cycles=360, warmup=120, roles="hetero_cmi")
DEFAULT = dict(names=("mesh", "folded_torus", "hexamesh",
                      "folded_hexa_torus"),
               n=36, n_rates=5, cycles=1500, warmup=500,
               roles="hetero_cmi")
# all Table-III topologies (invalid N-constraint cells are skipped by
# the planner, e.g. cluscross at odd grids)
FULL = dict(names="ALL", n=64, n_rates=6, cycles=2000, warmup=700,
            roles="hetero_cmi")


def workload_suite(arch: str = "qwen3_1_7b") -> list[W.Workload]:
    cfg = W.arch_sizes(arch)
    return [
        W.Workload(f"collective:{cfg.name}",
                   partial(W.collective_workload, cfg)),
        W.Workload("trace:fluidanimate",
                   partial(W.trace_workload, trace="fluidanimate")),
        W.Workload("alt:tornado-uniform", W.phase_alternating),
    ]


def bench_workloads(params: dict, arch: str = "qwen3_1_7b") -> list[dict]:
    cfg = SimConfig(cycles=params["cycles"], warmup=params["warmup"])
    names = params["names"]
    if names == "ALL":
        from repro.core import topology as T
        names = tuple(T.GENERATORS)
    workloads = workload_suite(arch)
    exp = X.Experiment(
        [X.Scenario(name, params["n"], substrate, traffic=wl,
                    roles=params["roles"],
                    rates=X.SaturationGrid(params["n_rates"]))
         for name in names for substrate in SUBSTRATES
         for wl in workloads],
        cfg=cfg, name="workload_sweep")
    engine = X.engine_for(cfg)
    t0 = time.time()
    frame = X.run(exp, engine=engine)
    wall = time.time() - t0
    rows = []
    for i, row in enumerate(frame.rows):
        if row["status"] != "ok":
            continue
        res = frame.workload_result(i)
        # relative saturation is substrate-blind at these link lengths;
        # the substrate story is the absolute rate the wires sustain
        base = dict(topology=row["topology"], n=row["n"],
                    substrate=row["substrate"], workload=res["workload"],
                    sim_saturation=round(res["sim_saturation"], 4),
                    abs_throughput_gbps=round(row["abs_throughput_gbps"],
                                              1),
                    analytic_saturation=round(res["analytic_saturation"],
                                              4),
                    latency_at_sat=round(res["latency_at_sat"], 2))
        rows.append(dict(base, phase="ALL",
                         phase_cycles=int(res["phase_cycles"].sum()),
                         throughput=base["sim_saturation"],
                         latency=base["latency_at_sat"]))
        for k, label in enumerate(res["phase_labels"]):
            rows.append(dict(
                base, phase=label,
                phase_cycles=int(res["phase_cycles"][k]),
                throughput=round(float(res["throughput_ph"][k]), 4),
                latency=round(float(res["latency_ph"][k]), 2)))
    write_csv(os.path.join(RESULTS_DIR, "workload_sweep.csv"), rows)
    n_cells = len(names) * len(SUBSTRATES)
    print(f"[workload_bench] {n_cells} cells x {len(workloads)} "
          f"workloads in {wall:.1f}s; engine stats: {engine.stats}")
    _print_headline(rows)

    from .harness import BenchRun
    run = BenchRun("workload", mode="smoke" if params is SMOKE else "full")
    pf = [r["pad_fill"]["phase"] for r in frame.results if r is not None]
    run.metrics(dict(wall_s=round(wall, 4)))
    run.metric("cells", n_cells, direction="higher")
    run.metric("rows", len(rows), direction="higher")
    run.metric("pad_fill_phase", round(float(np.mean(pf)), 4)
               if pf else None, direction="higher")
    run.metric("compiles", engine.stats["compiles"])
    run.finish()
    return rows


def _print_headline(rows: list[dict]):
    """Collective-workload saturation by topology, organic vs glass."""
    coll = [r for r in rows if r["phase"] == "ALL"
            and r["workload"].startswith("collective:")]
    if not coll:
        return
    print("\nLLM-collective workload saturation "
          "(rel flits/node/cycle | abs Tb/s):")
    names = sorted({r["topology"] for r in coll})
    print(f"  {'topology':20s} " +
          " ".join(f"{s:>16s}" for s in SUBSTRATES))
    for name in names:
        cells = {r["substrate"]: r for r in coll if r["topology"] == name}
        vals = " ".join(
            f"{cells[s]['sim_saturation']:6.3f}|"
            f"{cells[s]['abs_throughput_gbps'] / 1e3:6.2f} Tb"
            if s in cells else f"{'—':>16s}" for s in SUBSTRATES)
        print(f"  {name:20s} {vals}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid (CI-sized, well under a minute)")
    ap.add_argument("--full", action="store_true",
                    help="10 topologies at N=64 (slow)")
    ap.add_argument("--arch", default="qwen3_1_7b",
                    help="architecture for the collective workload")
    args = ap.parse_args(argv)
    use_compile_cache()
    params = SMOKE if args.smoke else (FULL if args.full else DEFAULT)
    bench_workloads(params, arch=args.arch)


if __name__ == "__main__":
    main()
