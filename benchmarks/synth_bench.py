"""Topology-synthesis benchmark: search the design space, report the
Pareto front -> results/synth_pareto.csv.

    PYTHONPATH=src python -m benchmarks.synth_bench [--smoke] [--seed S]

Runs the full DESIGN.md §11 pipeline (generate -> feasibility filter
-> analytic rank -> cycle-accurate verify -> Pareto) at the paper's
scale point (N=48, organic substrate) and reports the two headline
numbers the subsystem exists to produce:

  * whether `folded_hexa_torus` lands on (or within 5 % of) the Pareto
    front of the search's own candidate pool, and
  * the prefilter ratio — feasible candidates per cycle-accurate
    simulation (how much the analytic stage cut the simulation bill).

`--smoke` runs a seeded mini-search (N=16, one generation, short
simulations) that finishes well under 60 s for CI; it writes the same
CSV schema.
"""
from __future__ import annotations

import argparse
import os
import time

from repro.core.simulator import SimConfig
from repro.experiments import io as xio
from repro.synth import SearchConfig, run_search
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR

SMOKE = SearchConfig(n=16, n_random=8, generations=1, offspring=8,
                     sim_top=4, n_rates=3,
                     cfg=SimConfig(cycles=360, warmup=120))
DEFAULT = SearchConfig(n=48, cfg=SimConfig(cycles=1500, warmup=500))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seeded mini-search (<60 s) for CI")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(RESULTS_DIR,
                                                  "synth_pareto.csv"))
    args = ap.parse_args()
    use_compile_cache()
    import dataclasses
    cfg = dataclasses.replace(SMOKE if args.smoke else DEFAULT,
                              seed=args.seed)

    t0 = time.time()
    res = run_search(cfg, progress=lambda g, G, s: print(
        f"[synth] generation {g}/{G}: {s['n_feasible']} feasible "
        f"of {s['n_generated']} generated", flush=True))
    wall = time.time() - t0

    xio.write_csv(args.out, res.rows())
    s = res.stats
    print(f"[synth] N={cfg.n} {cfg.substrate} seed={cfg.seed}: "
          f"{s['n_generated']} generated, {s['n_infeasible']} infeasible, "
          f"{s['n_duplicate']} duplicate, {s['n_feasible']} feasible, "
          f"{s['n_simulated']} simulated in {wall:.1f}s")
    print(f"[synth] prefilter ratio: {res.prefilter_ratio:.1f}x "
          f"(feasible / cycle-sim evaluations)")
    front = [c.topo.name for c in res.front()]
    print(f"[synth] Pareto front (abs Tb/s, zero-load ns, wire-mm): "
          f"{front}")
    fht = res.on_front("folded_hexa_torus", eps=0.0)
    fht5 = res.on_front("folded_hexa_torus", eps=0.05)
    print(f"[synth] folded_hexa_torus on front: {fht} "
          f"(within 5%: {fht5})")
    from .harness import BenchRun
    run = BenchRun("synth", mode="smoke" if args.smoke else "full")
    run.metrics(dict(wall_s=round(wall, 4)))
    run.metric("generated", s["n_generated"], direction="higher")
    run.metric("feasible", s["n_feasible"], direction="higher")
    run.metric("simulated", s["n_simulated"])
    run.metric("prefilter_ratio", round(res.prefilter_ratio, 2),
               direction="higher")
    run.metric("front_size", len(front), direction="higher")
    run.finish()

    if not args.smoke:
        assert fht5, "FHT fell off its own Pareto front — regression"
        assert res.prefilter_ratio >= 5.0, \
            f"prefilter ratio {res.prefilter_ratio:.1f}x < 5x"


if __name__ == "__main__":
    main()
