"""Fault injection / graceful degradation benchmark (DESIGN.md §12).

    PYTHONPATH=src python -m benchmarks.fault_bench [--smoke|--full]

Measures how Table-III topologies degrade as links die: for each
(topology, substrate) cell at N=36 we draw seeded random fault sets of
k in {0, 1, 2, 4} failed links, re-route the surviving structure
(up*/down* on the masked edge list, via the structural-hash routing
cache) and sweep offered load to saturation.  Reported per cell:

  * absolute saturation throughput through the substrate wires (Tb/s,
    the §V-B cost model at the simulated plateau), and
  * zero-load latency in ns (cycle time is 1 ns at the paper's clock),

i.e. the two ends of the degradation curve in results/
fault_degradation.csv.  A second, smaller grid superimposes a serving
tenant on an LLM-training collective step (`workloads.mixed_tenant`)
and pushes the mixed schedule through the *same* fault masks — the
"serve traffic through dead links" scenario the paper never measures.

The whole grid is one declarative `Experiment`; degraded cells whose
fault set cannot be applied (e.g. the draw would disconnect the
survivors) are skipped by the sampler with a printed reason, never
silently dropped.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

import repro.experiments as X
import repro.workloads as W
from repro.core import topology as T
from repro.core.simulator import SimConfig, zero_load_latency
from repro.faults import FaultError, sample_faults
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR, write_csv

SUBSTRATES = ("organic", "glass")
#: mixed-tenant grid: the paper's headline pair + the hexagonal family
MIXED_NAMES = ("mesh", "torus", "hexamesh", "folded_hexa_torus")

SMOKE = dict(names=("mesh", "torus", "folded_hexa_torus"), n=16,
             substrates=("organic",), ks=(0, 2), n_rates=3,
             cycles=360, warmup=120, mixed_names=("folded_hexa_torus",),
             mixed_ks=(0, 2))
DEFAULT = dict(names="ALL", n=36, substrates=SUBSTRATES,
               ks=(0, 1, 2, 4), n_rates=5, cycles=1500, warmup=500,
               mixed_names=MIXED_NAMES, mixed_ks=(0, 1, 2, 4))
FULL = dict(names="ALL", n=64, substrates=SUBSTRATES, ks=(0, 1, 2, 4, 8),
            n_rates=6, cycles=2000, warmup=700,
            mixed_names=MIXED_NAMES, mixed_ks=(0, 2, 4, 8))


def fault_grid(names, n: int, substrates, ks, *, kind: str = "random",
               seed: int = 0):
    """[(name, substrate, k, FaultSet | None)] for every valid cell.

    k=0 carries `faults=None` so the pristine path is the untouched
    zero-fault code path (bitwise identical to a fault-free Scenario).
    Cells whose topology is invalid at N, or where the sampler cannot
    draw k survivable links, are dropped with a printed reason.
    """
    cells, dropped = [], []
    for name in names:
        if name in T.N_CONSTRAINTS and not T.N_CONSTRAINTS[name](n):
            dropped.append(f"{name}: unsupported N={n} "
                           f"(topology.N_CONSTRAINTS)")
            continue
        for substrate in substrates:
            topo = T.build(name, n, substrate=substrate)
            for k in ks:
                if k == 0:
                    cells.append((name, substrate, 0, None))
                    continue
                try:
                    fs = sample_faults(topo, k, kind, seed=seed)
                except FaultError as e:
                    dropped.append(f"{name}/{substrate}/k={k}: {e}")
                    continue
                cells.append((name, substrate, k, fs))
    for msg in dropped:
        print(f"[fault_bench] drop {msg}")
    return cells


def bench_faults(params: dict, arch: str = "qwen3_1_7b") -> list[dict]:
    cfg = SimConfig(cycles=params["cycles"], warmup=params["warmup"])
    names = params["names"]
    if names == "ALL":
        names = tuple(T.GENERATORS)
    rates = X.SaturationGrid(params["n_rates"])
    n = params["n"]

    cells = fault_grid(names, n, params["substrates"], params["ks"])
    scenarios = [
        X.Scenario(name, n, substrate, traffic="uniform", faults=fs,
                   rates=rates,
                   tags=(("k_failed", k), ("suite", "static")))
        for name, substrate, k, fs in cells]

    mixed = W.mixed_tenant(W.arch_sizes(arch), serve_frac=0.3)
    mixed_cells = fault_grid(params["mixed_names"], n,
                             params["substrates"], params["mixed_ks"])
    scenarios += [
        X.Scenario(name, n, substrate, traffic=mixed, faults=fs,
                   rates=rates,
                   tags=(("k_failed", k), ("suite", "mixed")))
        for name, substrate, k, fs in mixed_cells]

    exp = X.Experiment(scenarios, cfg=cfg, name="fault_degradation")
    engine = X.engine_for(cfg)
    t0 = time.time()
    frame = X.run(exp, engine=engine)
    wall = time.time() - t0

    rows = []
    for i, row in enumerate(frame.rows):
        if row["status"] != "ok":
            continue
        ps = frame.planned[i]
        rows.append(dict(
            topology=row["topology"], n=row["n"],
            substrate=row["substrate"], suite=row["suite"],
            traffic=row["traffic"], k_failed=row["k_failed"],
            faults=row["faults"], failed_links=row["failed_links"],
            sim_saturation=round(row["sim_saturation"], 4),
            analytic_saturation=round(row["analytic_saturation"], 4),
            abs_throughput_gbps=round(row["abs_throughput_gbps"], 1),
            abs_throughput_tbps=round(
                row["abs_throughput_gbps"] / 1e3, 3),
            zero_load_ns=round(
                float(zero_load_latency(ps.routing, ps.traffic)), 2),
            latency_ns=round(row["latency_ns"], 2)))
    write_csv(os.path.join(RESULTS_DIR, "fault_degradation.csv"), rows)
    print(f"[fault_bench] {len(scenarios)} scenarios "
          f"({len(frame.ok())} ok) in {wall:.1f}s; "
          f"engine stats: {engine.stats}")
    _print_headline(rows, params["ks"])

    from .harness import BenchRun
    run = BenchRun("fault", mode="smoke" if params is SMOKE else "full")
    run.metrics(dict(wall_s=round(wall, 4)))
    run.metric("scenarios", len(scenarios), direction="higher")
    run.metric("ok_rows", len(frame.ok()), direction="higher")
    run.metric("compiles", engine.stats["compiles"])
    run.finish()
    return rows


def _print_headline(rows: list[dict], ks):
    """Static-uniform degradation: abs Tb/s retained vs k failed links."""
    stat = [r for r in rows if r["suite"] == "static"
            and r["substrate"] == "organic"]
    if not stat:
        return
    print("\nuniform-traffic degradation, organic "
          "(abs Tb/s at saturation; % of k=0 in parens):")
    names = sorted({r["topology"] for r in stat})
    print(f"  {'topology':20s} " + " ".join(f"{f'k={k}':>15s}"
                                            for k in ks))
    for name in names:
        by_k = {r["k_failed"]: r for r in stat if r["topology"] == name}
        if 0 not in by_k:
            continue
        base = by_k[0]["abs_throughput_tbps"]
        vals = []
        for k in ks:
            if k not in by_k:
                vals.append(f"{'—':>15s}")
                continue
            t = by_k[k]["abs_throughput_tbps"]
            vals.append(f"{t:7.2f} ({100 * t / max(base, 1e-9):4.0f}%)")
        print(f"  {name:20s} " + " ".join(vals))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid (CI-sized, well under a minute)")
    ap.add_argument("--full", action="store_true",
                    help="all topologies at N=64, k up to 8 (slow)")
    ap.add_argument("--arch", default="qwen3_1_7b",
                    help="architecture for the mixed-tenant workload")
    args = ap.parse_args(argv)
    use_compile_cache()
    params = SMOKE if args.smoke else (FULL if args.full else DEFAULT)
    bench_faults(params, arch=args.arch)


if __name__ == "__main__":
    main()
