"""Static-vs-adaptive routing gain grid (DESIGN.md §15).

    PYTHONPATH=src python -m benchmarks.adaptive_bench [--smoke|--full]

The question none of the paper's predecessors answer: does FHT's flat
channel-load distribution still translate to a throughput edge when
routing can route *around* congestion?  This bench runs Table-III
topologies x organic/glass x {uniform, hotspot_drift, bursty,
mixed-tenant} x {static, adaptive} at N=36 through ONE declarative
`Experiment` — the routing mode rides in `Scenario(routing=...)`, so
the planner splits the modes into their own compiled programs and the
engine batches everything else.

Results land in results/adaptive_gain.csv: one row per (topology,
substrate, workload) with both modes' saturation and the relative
adaptive gain.  The headline printout reports the hotspot-drift gains
— the drifting-hotspot schedule is where adaptivity should pay, and
where the mesh/torus family is expected to gain the most (FHT's static
load is already flat, so its gain is the interesting number).
"""
from __future__ import annotations

import argparse
import os
import time
from functools import partial

import numpy as np

import repro.experiments as X
import repro.workloads as W
from repro.configs import get_config
from repro.core.simulator import SimConfig
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR, write_csv
from .harness import BenchRun

SUBSTRATES = ("organic", "glass")
ROUTINGS = ("static", "adaptive")

SMOKE = dict(names=("mesh", "torus", "folded_hexa_torus"), n=16,
             n_rates=4, cycles=400, warmup=150,
             workloads=("uniform", "hotspot_drift"))
DEFAULT = dict(names="ALL", n=36, n_rates=4, cycles=1000, warmup=300,
               workloads=("uniform", "hotspot_drift", "bursty",
                          "mixed_tenant"))
FULL = dict(names="ALL", n=36, n_rates=6, cycles=2000, warmup=700,
            workloads=("uniform", "hotspot_drift", "bursty",
                       "mixed_tenant"))


def traffic_suite(names, arch: str = "qwen3_1_7b") -> list:
    """Traffic sources by name: the uniform static pattern plus the
    time-varying workloads adaptivity is supposed to help with."""
    out = []
    for w in names:
        if w == "uniform":
            out.append("uniform")
        elif w == "hotspot_drift":
            out.append(W.Workload("hotspot_drift", partial(
                W.hotspot_drift, n_phases=4, dwell=250, seed=2)))
        elif w == "bursty":
            out.append(W.Workload("bursty", partial(
                W.bursty_uniform, on=20, off=60)))
        elif w == "mixed_tenant":
            out.append(W.mixed_tenant(get_config(arch)))
        else:
            raise KeyError(f"unknown workload {w!r}")
    return out


def bench_adaptive(params: dict, arch: str = "qwen3_1_7b") -> list[dict]:
    cfg = SimConfig(cycles=params["cycles"], warmup=params["warmup"])
    names = params["names"]
    if names == "ALL":
        from repro.core import topology as T
        names = tuple(T.GENERATORS)
    traffics = traffic_suite(params["workloads"], arch)
    exp = X.Experiment(
        [X.Scenario(name, params["n"], substrate, traffic=tr,
                    routing=routing,
                    rates=X.SaturationGrid(params["n_rates"]))
         for name in names for substrate in SUBSTRATES
         for tr in traffics for routing in ROUTINGS],
        cfg=cfg, name="adaptive_gain")
    engine = X.engine_for(cfg)
    t0 = time.time()
    frame = X.run(exp, engine=engine)
    wall = time.time() - t0

    # pair the (static, adaptive) rows — they are adjacent by
    # construction (routing is the innermost loop)
    rows = []
    for i in range(0, len(frame.rows), 2):
        st, ad = frame.rows[i], frame.rows[i + 1]
        if st["status"] != "ok" or ad["status"] != "ok":
            continue
        assert (st["routing"], ad["routing"]) == ROUTINGS
        s, a = st["sim_saturation"], ad["sim_saturation"]
        rows.append(dict(
            topology=st["topology"], n=st["n"],
            substrate=st["substrate"], workload=st["traffic"],
            analytic_saturation=round(st["analytic_saturation"], 4),
            static_saturation=round(s, 4),
            adaptive_saturation=round(a, 4),
            adaptive_gain=round(a / s - 1.0, 4) if s > 0 else "",
            static_latency_ns=round(st["latency_ns"], 2),
            adaptive_latency_ns=round(ad["latency_ns"], 2),
            abs_adaptive_gbps=round(ad["abs_throughput_gbps"], 1)))
    write_csv(os.path.join(RESULTS_DIR, "adaptive_gain.csv"), rows)
    print(f"[adaptive_bench] {len(rows)} cells "
          f"({len(names)} topologies x {len(SUBSTRATES)} substrates x "
          f"{len(traffics)} workloads) in {wall:.1f}s; "
          f"engine stats: {engine.stats}")
    _print_headline(rows)

    # BENCH json: warm observed pass for spans + XLA profiles; the gain
    # metrics guard the adaptive-routing win itself against regressions
    run = BenchRun("adaptive", mode="smoke" if params is SMOKE else "full")
    frame2 = run.observed_pass(lambda: X.run(exp, engine=engine))
    split = run.device_host_split()
    pf = [r["pad_fill"]["state"] for r in frame2.results if r is not None]
    hd = [r["adaptive_gain"] for r in rows
          if r["workload"] == "hotspot_drift"
          and isinstance(r["adaptive_gain"], float)]
    run.metrics(dict(cold_wall_s=round(wall, 4),
                     warm_device_s=split["device_s"],
                     warm_stack_s=split["stack_s"]))
    run.metric("cells", len(rows), direction="higher")
    run.metric("pad_fill_state", round(float(np.mean(pf)), 4)
               if pf else None, direction="higher")
    if hd:
        run.metric("drift_gain_mean", round(float(np.mean(hd)), 4),
                   direction="higher")
        run.metric("drift_gain_max", round(float(max(hd)), 4),
                   direction="higher")
    run.extra(workloads=list(params["workloads"]), n=params["n"])
    run.finish()
    return rows


def _print_headline(rows: list[dict]):
    """Hotspot-drift adaptive gain by topology (organic substrate)."""
    hd = [r for r in rows if r["workload"] == "hotspot_drift"
          and r["substrate"] == "organic"]
    if not hd:
        return
    print("\nhotspot-drift: static vs adaptive saturation "
          "(rel flits/node/cycle):")
    for r in sorted(hd, key=lambda r: -r["adaptive_gain"]):
        print(f"  {r['topology']:20s} static {r['static_saturation']:6.3f}"
              f"  adaptive {r['adaptive_saturation']:6.3f}"
              f"  gain {r['adaptive_gain']:+7.1%}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid (CI-sized, well under a minute)")
    ap.add_argument("--full", action="store_true",
                    help="Table III at N=36, long measurement windows")
    ap.add_argument("--arch", default="qwen3_1_7b",
                    help="architecture for the mixed-tenant workload")
    args = ap.parse_args(argv)
    use_compile_cache()
    params = SMOKE if args.smoke else (FULL if args.full else DEFAULT)
    bench_adaptive(params, arch=args.arch)


if __name__ == "__main__":
    main()
