"""Observability benchmark (DESIGN.md §13): link-load heatmaps + traces.

    PYTHONPATH=src python -m benchmarks.obs_bench [--smoke]

Three artifacts, all from the flight recorder + span tracer:

  * results/link_load_heatmap.csv — per-directed-channel busy/util/
    stall/occupancy rows for every Table-III topology x substrate at
    N=36 under uniform traffic at the saturation plateau, plus
    results/link_load_summary.csv with the distribution stats
    (p50/p95/max channel load, Gini imbalance) per cell.  This is the
    paper's central mechanism made measurable: folding *spreads*
    channel load where Mesh/Torus concentrate it.
  * results/fault_link_load.csv — the same per-link view for the
    FoldedHexaTorus k=2 failed-links cell of results/
    fault_degradation.csv (same seeded draw), with the dead links as
    explicit `status="dead"` rows — showing where the surviving
    channels pick up the rerouted load.
  * results/sweep_phases.trace.json — a Chrome-trace/Perfetto span
    breakdown of one cold and one warm sweep over the same grid
    (plan -> chunk -> sweep.group -> sim.dispatch/sim.wait), with the
    compile-vs-run wall-clock split printed from the span tree.

The bench also *asserts* the flight-recorder conservation invariants
on every cell (sum(inj_node) == accepted_n, sum(eject_node) ==
delivered, sum(lat_hist) == delivered) — the telemetry cross-check of
the acceptance criteria.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

import repro.experiments as X
from repro.core import topology as T
from repro.core.simulator import SimConfig
from repro.faults import FaultError, sample_faults
import repro.workloads as W
from repro.obs import metrics
from repro.obs.report import write_link_reports, write_window_reports
from repro.obs.trace import (clear_trace, disable_tracing, enable_tracing,
                             get_spans, save_chrome_trace, trace)
from repro.sweep.engine import SweepEngine
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR
from .harness import BenchRun

SUBSTRATES = ("organic", "glass")

SMOKE = dict(names=("mesh", "torus", "folded_hexa_torus"), n=16,
             substrates=("organic",), n_rates=3, cycles=360, warmup=120)
DEFAULT = dict(names="ALL", n=36, substrates=SUBSTRATES, n_rates=5,
               cycles=1500, warmup=500)


def _scenarios(params: dict):
    names = params["names"]
    if names == "ALL":
        names = tuple(T.GENERATORS)
    rates = X.SaturationGrid(params["n_rates"])
    n = params["n"]
    out = []
    for name in names:
        if name in T.N_CONSTRAINTS and not T.N_CONSTRAINTS[name](n):
            print(f"[obs_bench] drop {name}: unsupported N={n}")
            continue
        for substrate in params["substrates"]:
            out.append(X.Scenario(name, n, substrate, traffic="uniform",
                                  rates=rates))
    return out


def check_conservation(frame: X.ResultFrame) -> int:
    """Assert the exact flight-recorder invariants on every ok cell."""
    checked = 0
    for i, row in enumerate(frame.rows):
        res = frame.results[i]
        if row["status"] != "ok" or res is None:
            continue
        np.testing.assert_array_equal(
            res["inj_node"].sum(axis=1), res["accepted_n"],
            err_msg=f"{row['topology']}: inj_node vs accepted_n")
        np.testing.assert_array_equal(
            res["eject_node"].sum(axis=1), res["delivered"],
            err_msg=f"{row['topology']}: eject_node vs delivered")
        np.testing.assert_array_equal(
            res["lat_hist"].sum(axis=1), res["delivered"],
            err_msg=f"{row['topology']}: lat_hist vs delivered")
        checked += 1
    return checked


def _phase_split(spans) -> dict:
    """Wall-clock (ms) per span kind, compile vs run split included."""
    ms = lambda sel: sum(s.dur for s in spans if sel(s)) / 1e6
    return dict(
        plan=ms(lambda s: s.name == "experiment.plan"),
        stack=ms(lambda s: s.name == "sim.stack"),
        dispatch_cold=ms(lambda s: s.name == "sim.dispatch"
                         and s.args.get("cold")),
        dispatch_warm=ms(lambda s: s.name == "sim.dispatch"
                         and not s.args.get("cold")),
        wait=ms(lambda s: s.name == "sim.wait"),
        total=ms(lambda s: s.name == "experiment.execute")
        + ms(lambda s: s.name == "experiment.plan"))


def bench_obs(params: dict) -> None:
    cfg = SimConfig(cycles=params["cycles"], warmup=params["warmup"],
                    telemetry=True)
    scenarios = _scenarios(params)
    exp = X.Experiment(scenarios, cfg=cfg, name="link_load")
    engine = SweepEngine(cfg=cfg)

    enable_tracing()
    clear_trace()
    metrics.event("obs_bench.start", n=params["n"],
                  scenarios=len(scenarios))

    # ---- cold pass: compiles included ---------------------------------
    t0 = time.time()
    with trace("bench.cold_run", cat="bench"):
        frame = X.run(exp, engine=engine)
    cold_wall = time.time() - t0
    cold_spans = get_spans()

    # ---- warm pass: same grid, executables reused ---------------------
    clear_trace()
    t0 = time.time()
    with trace("bench.warm_run", cat="bench"):
        X.run(exp, engine=engine)
    warm_wall = time.time() - t0
    warm_spans = get_spans()

    # one Perfetto-loadable document holding both passes
    clear_trace()
    for s in cold_spans:
        s.set(run="cold")
    for s in warm_spans:
        s.set(run="warm")
    from repro.obs.trace import TRACER
    for s in cold_spans + warm_spans:
        TRACER._record(s)
    save_chrome_trace(
        os.path.join(RESULTS_DIR, "sweep_phases.trace.json"),
        metadata=dict(bench="obs_bench", n=params["n"],
                      scenarios=len(scenarios),
                      cold_wall_s=round(cold_wall, 3),
                      warm_wall_s=round(warm_wall, 3)))
    disable_tracing()

    checked = check_conservation(frame)
    print(f"[obs_bench] conservation exact on {checked} cells "
          f"(inj==accepted, eject==delivered, hist==delivered)")

    rows = frame.all_link_rows()
    summary = write_link_reports(
        os.path.join(RESULTS_DIR, "link_load_heatmap.csv"),
        os.path.join(RESULTS_DIR, "link_load_summary.csv"), rows)

    cold = _phase_split(cold_spans)
    warm = _phase_split(warm_spans)
    print(f"[obs_bench] cold pass {cold_wall:.2f}s "
          f"(compile-dispatch {cold['dispatch_cold'] / 1e3:.2f}s, "
          f"device wait {cold['wait'] / 1e3:.2f}s); "
          f"warm pass {warm_wall:.2f}s "
          f"(compile-dispatch {warm['dispatch_cold'] / 1e3:.2f}s, "
          f"device wait {warm['wait'] / 1e3:.2f}s)")
    print(f"[obs_bench] engine stats: {engine.stats}")

    _print_headline(summary)
    _fault_companion(params, cfg)
    drift_gini = _window_companion(params, cfg)

    # BENCH json (DESIGN.md §16): one extra warm pass with profiling on
    # captures the XLA cost/memory analysis; pad_fill rides on results
    run = BenchRun("obs", mode="smoke" if params is SMOKE else "full")
    frame3 = run.observed_pass(lambda: X.run(exp, engine=engine))
    pf = [r["pad_fill"]["state"] for r in frame3.results if r is not None]
    run.metrics(dict(cold_wall_s=round(cold_wall, 4),
                     warm_wall_s=round(warm_wall, 4),
                     cold_dispatch_s=round(cold["dispatch_cold"] / 1e3, 4),
                     cold_wait_s=round(cold["wait"] / 1e3, 4),
                     warm_wait_s=round(warm["wait"] / 1e3, 4)))
    run.metric("conservation_cells", checked, direction="higher")
    run.metric("pad_fill_state", round(float(np.mean(pf)), 4),
               direction="higher")
    if drift_gini is not None:
        run.metric("drift_gini_spread", drift_gini, direction="higher")
    run.extra(scenarios=len(scenarios), n=params["n"])
    run.finish()


def _window_companion(params: dict, cfg: SimConfig) -> float | None:
    """Time-windowed telemetry on a drifting-hotspot workload: the
    per-window heatmap/summary CSVs that make hotspot migration visible
    (DESIGN.md §16).  Returns the spread of per-window Gini (max - min)
    at the plateau rate — ~0 under steady uniform load, clearly positive
    while a hotspot drifts."""
    n = params["n"]
    wcfg = cfg._replace(telemetry_windows=6)
    wl = W.Workload("hotspot_drift",
                    lambda topo: W.hotspot_drift(topo, n_phases=6,
                                                 dwell=200))
    exp = X.Experiment(
        [X.Scenario("folded_hexa_torus", n, traffic=wl,
                    rates=X.SaturationGrid(params["n_rates"]))],
        cfg=wcfg, name="window_heatmap")
    frame = X.run(exp, engine=SweepEngine(cfg=wcfg))
    rows = frame.all_window_rows()
    if not rows:
        print("[obs_bench] window companion produced no rows")
        return None
    summary = write_window_reports(
        os.path.join(RESULTS_DIR, "window_heatmap.csv"),
        os.path.join(RESULTS_DIR, "window_summary.csv"), rows)
    ginis = [s["gini"] for s in summary]
    spread = round(max(ginis) - min(ginis), 4)
    print(f"[obs_bench] windowed drift companion: {len(summary)} windows, "
          f"gini {min(ginis):.3f}..{max(ginis):.3f} (spread {spread})")
    return spread


def _print_headline(summary: list[dict]) -> None:
    """Load-distribution table: the flatter the channel-load histogram
    (lower Gini / p95), the better folding does its job."""
    for substrate in sorted({s["substrate"] for s in summary}):
        rows = sorted((s for s in summary
                       if s["substrate"] == substrate),
                      key=lambda s: s["gini"])
        print(f"\nchannel-load distribution at saturation, {substrate} "
              f"(lower Gini = flatter load):")
        print(f"  {'topology':20s} {'links':>5s} {'p50':>7s} {'p95':>7s} "
              f"{'max':>7s} {'gini':>7s}")
        for s in rows:
            print(f"  {s['topology']:20s} {s['n_links']:5d} "
                  f"{s['util_p50']:7.3f} {s['util_p95']:7.3f} "
                  f"{s['util_max']:7.3f} {s['gini']:7.3f}")


def _fault_companion(params: dict, cfg: SimConfig) -> None:
    """Per-link telemetry for the FHT k=2 failed-links cell of
    results/fault_degradation.csv (same `sample_faults` seed)."""
    n = params["n"]
    topo = T.build("folded_hexa_torus", n)
    try:
        fs = sample_faults(topo, 2, "random", seed=0)
    except FaultError as e:
        print(f"[obs_bench] fault companion skipped: {e}")
        return
    rates = X.SaturationGrid(params["n_rates"])
    exp = X.Experiment(
        [X.Scenario("folded_hexa_torus", n, "organic", faults=None,
                    rates=rates, tags=(("k_failed", 0),)),
         X.Scenario("folded_hexa_torus", n, "organic", faults=fs,
                    rates=rates, tags=(("k_failed", 2),))],
        cfg=cfg, name="fault_link_load")
    frame = X.run(exp, engine=SweepEngine(cfg=cfg))
    check_conservation(frame)
    rows = frame.all_link_rows()
    frame.to_link_csv(os.path.join(RESULTS_DIR, "fault_link_load.csv"))
    dead = [r for r in rows if r["status"] == "dead"]
    ok2 = [r for r in rows if r["status"] == "ok"
           and r.get("k_failed") == 2]
    ok0 = [r for r in rows if r["status"] == "ok"
           and r.get("k_failed") == 0]
    hot0 = max(r["util"] for r in ok0) if ok0 else 0.0
    hot2 = max(r["util"] for r in ok2) if ok2 else 0.0
    print(f"[obs_bench] FHT k=2 companion: {len(dead)} dead directed "
          f"links ({fs.name}); hottest surviving channel util "
          f"{hot2:.3f} vs {hot0:.3f} pristine")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid (CI-sized, well under a minute)")
    args = ap.parse_args(argv)
    use_compile_cache()
    bench_obs(SMOKE if args.smoke else DEFAULT)


if __name__ == "__main__":
    main()
