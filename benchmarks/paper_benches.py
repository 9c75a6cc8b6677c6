"""One benchmark per paper table/figure (see DESIGN.md §7).

Every grid-shaped figure is described as a `repro.experiments`
Experiment — a list of declarative Scenarios sharing one SimConfig —
and evaluated through the one `run` front door (DESIGN.md §10):
analytically by default, with the cycle-accurate simulator under
--sim.  CSVs are the experiment `ResultFrame`s (tidy rows, stable
columns, `schema_version` stamped)."""
from __future__ import annotations

import os
from functools import partial

import numpy as np

import repro.experiments as X
from repro.core import linkmodel as lm
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.collectives import build_ici_model

from .common import (RESULTS_DIR, SIZES, SIZES_FULL, run_cells,
                     write_csv)

PRINCIPLED = ["mesh", "folded_torus", "hexamesh", "folded_hexa_torus",
              "octamesh", "folded_octa_torus"]
ALL_TOPOLOGIES = list(T.GENERATORS)


def _figure_frame(scenarios, use_sim, name, csv):
    frame = run_cells(scenarios, use_sim=use_sim, name=name)
    frame.to_csv(os.path.join(RESULTS_DIR, csv))
    return frame


def fig2_linkmodel(sizes=None):
    """Fig. 2: data rate vs link length for all substrates."""
    rows = []
    for sub in ("organic", "glass", "passive_interposer"):
        for length in np.linspace(0, 75, 76):
            rows.append(dict(substrate=sub, length_mm=float(length),
                             rate_frac=float(lm.rate_fraction(length, sub)),
                             rate_gbps=float(lm.rate_gbps(length, sub))))
    write_csv(os.path.join(RESULTS_DIR, "fig2.csv"), rows)
    return rows[-1]["rate_frac"]


def fig4_principles(sizes=None, use_sim=False):
    """Fig. 4: principled topologies x 3 chiplet sizes, organic."""
    sizes = sizes or SIZES
    scens = [X.Scenario(name, n, "organic", "uniform", area=area)
             for area in (37.0, 74.0, 148.0)
             for name in PRINCIPLED
             for n in sizes]
    frame = _figure_frame(scens, use_sim, "fig4", "fig4.csv")
    # headline: FHT wins throughput at N=256, 74mm^2
    return frame.best("abs_throughput_gbps", n=max(sizes),
                      area_mm2=74.0)["topology"]


def table1_area(sizes=None):
    """Table I: chiplet area relative to Mesh."""
    frame = run_cells([X.Scenario(name, 64, "organic", area=area)
                       for area in (37.0, 74.0, 148.0)
                       for name in PRINCIPLED], name="table1")
    rows = []
    for area in (37.0, 74.0, 148.0):
        base = frame.select(topology="mesh",
                            area_mm2=area)[0]["chiplet_area_mm2"]
        for r in frame.select(area_mm2=area):
            rows.append(dict(topology=r["topology"], area_mm2=area,
                             chiplet_area_mm2=r["chiplet_area_mm2"],
                             rel_vs_mesh_pct=100 * (
                                 r["chiplet_area_mm2"] / base - 1)))
    write_csv(os.path.join(RESULTS_DIR, "table1.csv"), rows)
    fht74 = [r for r in rows if r["topology"] == "folded_hexa_torus"
             and r["area_mm2"] == 74.0][0]
    return fht74["rel_vs_mesh_pct"]


def table2_power(sizes=None):
    """Table II: power at saturation relative to Mesh (mean over sizes)."""
    sizes = sizes or SIZES
    frame = run_cells([X.Scenario(name, n, "organic", area=area)
                       for area in (37.0, 74.0, 148.0)
                       for name in PRINCIPLED for n in sizes],
                      name="table2")
    rows = []
    for area in (37.0, 74.0, 148.0):
        for name in PRINCIPLED:
            rels = [100 * (r["power_w"] /
                           frame.select(topology="mesh", n=r["n"],
                                        area_mm2=area)[0]["power_w"] - 1)
                    for r in frame.select(topology=name, area_mm2=area)]
            rows.append(dict(topology=name, area_mm2=area,
                             power_rel_mean_pct=float(np.mean(rels)),
                             power_rel_std_pct=float(np.std(rels))))
    write_csv(os.path.join(RESULTS_DIR, "table2.csv"), rows)
    return [r["power_rel_mean_pct"] for r in rows
            if r["topology"] == "folded_hexa_torus"][1]


def table3_properties(sizes=None):
    """Table III: measured diameter/radix/link-range for all topologies."""
    rows = []
    for name in ALL_TOPOLOGIES:
        for n in (64, 256):
            if name in T.N_CONSTRAINTS and not T.N_CONSTRAINTS[name](n):
                continue
            t = T.build(name, n)
            rows.append(dict(topology=name, n=n, diameter=t.diameter,
                             radix=t.radix,
                             max_link_range=int(t.link_ranges().max()),
                             max_link_mm=round(t.max_link_length_mm(), 1)))
    write_csv(os.path.join(RESULTS_DIR, "table3.csv"), rows)
    return len(rows)


def fig7_main(sizes=None, use_sim=False):
    """Fig. 7: all topologies x {homo,hetero} x {organic,glass}."""
    sizes = sizes or SIZES
    scens = [X.Scenario(name, n, substrate, pattern, roles=roles)
             for substrate in ("organic", "glass")
             for roles, pattern in (("homogeneous", "uniform"),
                                    ("hetero_cm", "hetero_mix"))
             for name in ALL_TOPOLOGIES
             for n in sizes]
    frame = _figure_frame(scens, use_sim, "fig7", "fig7.csv")
    best = {}
    for n in sizes:
        best[n] = frame.best("abs_throughput_gbps", n=n,
                             substrate="organic",
                             traffic="uniform")["topology"]
    return best


def fig8_patterns(sizes=None, use_sim=False):
    """Fig. 8: permutation / tornado / neighbor on glass, homogeneous."""
    sizes = sizes or SIZES
    scens = [X.Scenario(name, n, "glass", pattern)
             for pattern in ("permutation", "tornado", "neighbor")
             for name in ALL_TOPOLOGIES
             for n in sizes]
    frame = _figure_frame(scens, use_sim, "fig8", "fig8.csv")
    return len(frame.ok())


def fig10_traces(sizes=None, use_sim=False):
    """Fig. 10: synthetic Netrace-like traces, C/M/I placement, organic."""
    sizes = sizes or [64, 144]
    scens = []
    for profile in ("blackscholes", "fluidanimate"):
        for region in range(5):
            intensity = TR.TRACE_PROFILES[profile][region][0]
            tr = X.CustomTraffic(
                f"{profile}:r{region}",
                partial(lambda topo, p, r: TR.trace_region_traffic(
                    topo, p, r)[0], p=profile, r=region))
            for name in ("mesh", "folded_torus", "hexamesh",
                         "folded_hexa_torus", "kite_medium", "sid_mesh",
                         "double_butterfly", "octamesh"):
                for n in sizes:
                    scens.append(X.Scenario(
                        name, n, "organic", tr, roles="hetero_cmi",
                        tags=(("profile", profile), ("region", region),
                              ("intensity", intensity))))
    frame = _figure_frame(scens, use_sim, "fig10", "fig10.csv")
    return len(frame.ok())


def collectives_bridge(sizes=None):
    """Framework bridge: collective time under each ICI topology."""
    rows = []
    for name in ("mesh", "hexamesh", "folded_torus", "folded_hexa_torus"):
        for n in (64, 256):
            m = build_ici_model(name, n, "organic")
            for s in (2 ** 24, 2 ** 30):
                rows.append(dict(
                    topology=name, n=n, bytes=s,
                    allreduce_ms=1e3 * m.collective_time_s("all_reduce", s),
                    allgather_ms=1e3 * m.collective_time_s("all_gather", s),
                    b_eff_gbps=m.b_eff_gbps))
    write_csv(os.path.join(RESULTS_DIR, "collectives.csv"), rows)
    fht = [r for r in rows if r["topology"] == "folded_hexa_torus"
           and r["n"] == 64 and r["bytes"] == 2 ** 30][0]
    mesh = [r for r in rows if r["topology"] == "mesh"
            and r["n"] == 64 and r["bytes"] == 2 ** 30][0]
    return mesh["allreduce_ms"] / fht["allreduce_ms"]


def sweep_speedup(sizes=None):
    """Batched-vs-looped simulator sweep wall-clock (DESIGN.md §6/§7)."""
    from .sweep_bench import bench_speedup
    out = bench_speedup(smoke=True)
    return (f"batched {out['batched_cold_s']:.1f}s vs looped "
            f"{out['looped_cold_s']:.1f}s cold "
            f"({out['cold_speedup']:.2f}x), bitwise_equal="
            f"{out['bitwise_equal']}")


BENCHES = {
    "fig2_linkmodel": fig2_linkmodel,
    "table3_properties": table3_properties,
    "table1_area": table1_area,
    "fig4_principles": fig4_principles,
    "table2_power": table2_power,
    "fig7_main": fig7_main,
    "fig8_patterns": fig8_patterns,
    "fig10_traces": fig10_traces,
    "collectives_bridge": collectives_bridge,
    "sweep_speedup": sweep_speedup,
}
