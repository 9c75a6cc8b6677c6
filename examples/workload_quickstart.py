"""Workload-engine quickstart: time-varying traffic through the
declarative experiment API (DESIGN.md §9 + §10).

    PYTHONPATH=src python examples/workload_quickstart.py

Builds three workloads — a qwen3-style LLM-training collective
schedule, a replayed fluidanimate trace with ON/OFF bursts, and an
adversarial tornado<->uniform alternation — crosses them with Mesh vs
FoldedHexaTorus in ONE `Experiment`, and runs the grid through
`repro.experiments.run` (the workloads ride in each Scenario's
`traffic` field; the planner lowers them onto batched engine programs).
"""
import os
from functools import partial

import numpy as np

import repro.experiments as X
import repro.workloads as W
from repro.core.simulator import SimConfig


def main():
    cfg = W.arch_sizes("qwen3_1_7b")
    workloads = [
        W.Workload(f"collective:{cfg.name}",
                   partial(W.collective_workload, cfg)),
        W.Workload("trace:fluidanimate",
                   partial(W.trace_workload, trace="fluidanimate")),
        W.Workload("alt:tornado-uniform", W.phase_alternating),
    ]
    exp = X.Experiment(
        [X.Scenario(name, 16, traffic=wl, roles="hetero_cmi",
                    rates=X.SaturationGrid(4))
         for name in ("mesh", "folded_hexa_torus") for wl in workloads],
        cfg=SimConfig(cycles=800, warmup=300), name="workload_quickstart")
    frame = X.run(exp)
    print("=== workloads x topologies, one declarative experiment ===")
    for i, row in enumerate(frame.rows):
        if row["status"] != "ok":
            continue
        res = frame.workload_result(i)
        phases = ", ".join(
            f"{lbl}={thr:.3f}" for lbl, thr in
            zip(res["phase_labels"], res["throughput_ph"]))
        print(f"{row['topology']:18s} {res['workload']:24s} "
              f"sat={res['sim_saturation']:.3f} "
              f"lat={res['latency_at_sat']:5.1f}cy  per-phase [{phases}]")
    frame.to_csv(os.path.join(os.path.dirname(__file__), "..",
                              "results", "workload_quickstart.csv"))

    print("\n=== anatomy of the collective schedule on FHT-16 ===")
    from repro.core.topology import build
    topo = build("folded_hexa_torus", 16)
    sched = W.collective_workload(cfg, topo)
    for p in sched.phases:
        burst = f" burst {p.burst_on}/{p.burst_off}" if p.burst_on else ""
        print(f"  {p.label:12s} {p.duration:4d}cy intensity="
              f"{p.intensity:.3f}{burst} peak-row="
              f"{np.asarray(p.traffic).sum(1).max():.3g} bytes")


if __name__ == "__main__":
    main()
