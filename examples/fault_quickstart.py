"""Fault-injection quickstart: serve traffic through dead links
(DESIGN.md §12).

    PYTHONPATH=src python examples/fault_quickstart.py

Draws seeded link faults on FoldedHexaTorus-36, shows the degraded
topology re-routing deadlock-free through the experiment pipeline,
compares the degradation against Mesh, and runs a mixed-tenant
schedule (serving traffic superimposed on a training step) through the
same fault masks.  A disconnecting fault set is shown being rejected.
"""
import os

import repro.experiments as X
import repro.faults as F
import repro.workloads as W
from repro.core.simulator import SimConfig
from repro.core.topology import build


def main():
    cfg = SimConfig(cycles=800, warmup=300)
    names = ("mesh", "folded_hexa_torus")
    ks = (0, 1, 2, 4)

    print("=== uniform-traffic degradation, N=36 organic ===")
    scenarios = []
    for name in names:
        topo = build(name, 36)
        for k in ks:
            fs = F.sample_faults(topo, k, "random", seed=0) if k else None
            scenarios.append(X.Scenario(
                name, 36, faults=fs, rates=X.SaturationGrid(4),
                tags=(("k_failed", k),)))
    frame = X.run(X.Experiment(scenarios, cfg=cfg,
                               name="fault_quickstart"))
    for row in frame.ok():
        print(f"  {row['topology']:18s} k={row['k_failed']} "
              f"faults={row['faults']:16s} "
              f"sat={row['sim_saturation']:.3f} "
              f"abs={row['abs_throughput_gbps'] / 1e3:.2f} Tb/s")

    print("\n=== mixed tenant (train collectives + 30% serving) "
          "through the same masks ===")
    mixed = W.mixed_tenant(W.arch_sizes("qwen3_1_7b"), serve_frac=0.3)
    topo = build("folded_hexa_torus", 36)
    scenarios = [X.Scenario("folded_hexa_torus", 36, traffic=mixed,
                            faults=F.sample_faults(topo, k, "random",
                                                   seed=0) if k else None,
                            rates=X.SaturationGrid(3),
                            tags=(("k_failed", k),))
                 for k in (0, 2)]
    mf = X.run(X.Experiment(scenarios, cfg=cfg, name="fault_mixed"))
    for i, row in enumerate(mf.ok()):
        res = mf.workload_result(i)
        print(f"  k={row['k_failed']} sat={res['sim_saturation']:.3f} "
              f"lat={res['latency_at_sat']:.1f}cy "
              f"({len(res['phase_labels'])} phases)")

    print("\n=== partitioned packages are outages, not data points ===")
    import numpy as np
    mesh = build("mesh", 16)
    e = np.sort(np.asarray(mesh.edges), axis=1)
    cut = tuple(tuple(int(x) for x in lk) for lk in e[(e == 0).any(1)])
    try:
        F.FaultSet(links=cut).apply(mesh)
    except F.DisconnectedFaultError as err:
        print(f"  rejected: {err}")

    frame.to_csv(os.path.join(os.path.dirname(__file__), "..",
                              "results", "fault_quickstart.csv"))


if __name__ == "__main__":
    main()
