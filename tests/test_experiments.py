"""Declarative experiment API tests (DESIGN.md §10).

The acceptance property: `repro.experiments.run` reproduces the legacy
case-level paths BITWISE — a mixed static+workload Experiment over
Table-III topologies yields metrics identical to `evaluate_cases` /
`evaluate_workload_cases` on the same grid, and both are pinned to the
independent single-spec oracle (`saturation_throughput` / single-spec
`run_batch`) so the equality is not vacuous.  Plus: planning semantics
(validation, bucketing, rate policies), chunked/progress/partial-
failure execution, the versioned writers, the analytic-vs-simulated
saturation cross-check, and the deprecation contracts of the legacy
entry points.
"""
import warnings

import numpy as np
import pytest

import repro.experiments as X
import repro.workloads as W
from repro.core import topology as T
from repro.core.simulator import (SimConfig, run_batch,
                                  saturation_throughput)
from repro.sweep.engine import SweepCase, SweepEngine

CFG = SimConfig(cycles=300, warmup=100)
RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")

STATIC_CASES = [SweepCase("mesh", 16), SweepCase("folded_hexa_torus", 16),
                SweepCase("hexamesh", 16), SweepCase("hypercube", 15)]

WORKLOADS = [W.Workload("alt", lambda t: W.phase_alternating(
                 t, phase_cycles=60, repeats=1)),
             W.Workload("trace", lambda t: W.trace_workload(
                 t, "blackscholes", region_cycles=40))]


def _quiet_legacy(fn, *args, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*args, **kw)


# ---------------------------------------------------------------------
# acceptance: bitwise reproduction of the legacy paths
# ---------------------------------------------------------------------

def test_mixed_experiment_bitwise_equals_legacy_paths():
    """THE acceptance criterion: one mixed static+workload Experiment
    == evaluate_cases + evaluate_workload_cases on the same grid, and
    both == the single-spec oracle."""
    eng = SweepEngine(cfg=CFG)
    static_scens = [X.scenario_from_case(c, rates=X.SaturationGrid(4))
                    for c in STATIC_CASES]
    wl_cases = [SweepCase("mesh", 16, roles="hetero_cmi"),
                SweepCase("folded_hexa_torus", 16, "glass",
                          roles="hetero_cmi")]
    wl_scens = [X.scenario_from_case(c, traffic=wl,
                                     rates=X.SaturationGrid(3))
                for c in wl_cases for wl in WORKLOADS]
    exp = X.Experiment(static_scens + wl_scens, cfg=CFG, name="mixed")
    frame = X.run(exp, engine=eng)

    legacy_static = _quiet_legacy(eng.evaluate_cases, STATIC_CASES,
                                  n_rates=4)
    legacy_wl = _quiet_legacy(eng.evaluate_workload_cases, wl_cases,
                              WORKLOADS, n_rates=3)

    ns = len(static_scens)
    for i, case in enumerate(STATIC_CASES):
        got, want = frame.case_result(i), legacy_static[i]
        if not case.valid:
            assert got is None and want is None
            assert frame.rows[i]["status"] == "invalid"
            continue
        assert got["sim_saturation"] == want["sim_saturation"]
        assert got["analytic_saturation"] == want["analytic_saturation"]
        assert got["latency_at_sat"] == want["latency_at_sat"]
        for k in RAW:
            np.testing.assert_array_equal(got["sweep"][k],
                                          want["sweep"][k], err_msg=k)
        # ...and the independent oracle agrees (equality is not vacuous)
        routing, tm = case.build()
        oracle = saturation_throughput(routing, tm, CFG, n_rates=4)
        assert got["sim_saturation"] == oracle["sim_saturation"]
        assert got["latency_at_sat"] == oracle["latency_at_sat"]
    for j in range(len(wl_scens)):
        got = frame.workload_result(ns + j)
        want = legacy_wl[j]
        assert got["sim_saturation"] == want["sim_saturation"]
        assert got["workload"] == want["workload"]
        assert got["phase_labels"] == want["phase_labels"]
        np.testing.assert_array_equal(got["phase_cycles"],
                                      want["phase_cycles"])
        np.testing.assert_array_equal(got["throughput_ph"],
                                      want["throughput_ph"])
        for k in RAW:
            np.testing.assert_array_equal(got["sweep"][k],
                                          want["sweep"][k], err_msg=k)


def test_workload_scenario_bitwise_equals_single_spec_oracle():
    """A workload scenario's sweep == the raw run_batch single-spec
    path fed the identical fitted schedule + rate grid."""
    scen = X.Scenario("mesh", 16, roles="hetero_cmi",
                      traffic=WORKLOADS[0], rates=X.SaturationGrid(3))
    frame = X.run(X.Experiment([scen], cfg=CFG), engine=SweepEngine(
        cfg=CFG))
    ps = frame.planned[0]
    single = run_batch([ps.spec], ps.rates[None, :], CFG,
                       schedules=[ps.sched_spec])[0]
    for k in RAW + ("delivered_ph", "lat_sum_ph"):
        np.testing.assert_array_equal(single[k], frame.results[0][k],
                                      err_msg=k)


# ---------------------------------------------------------------------
# satellite: analytic-vs-simulated saturation cross-check (Table III)
# ---------------------------------------------------------------------

def test_saturation_crosscheck_all_table3_topologies():
    """For every Table-III topology at n=16, the simulated saturation
    from a SaturationGrid scenario lands within tolerance of the
    analytic `paths_channel_loads` bound (the bound is an upper bound;
    the sim plateau must reach a sane fraction of it)."""
    names = [n for n in T.GENERATORS
             if X.Scenario(n, 16).valid]
    assert len(names) >= 15          # the Table-III roster
    exp = X.Experiment([X.Scenario(name, 16,
                                   rates=X.SaturationGrid(4))
                        for name in names],
                       cfg=CFG, name="crosscheck")
    frame = X.run(exp)
    for i, name in enumerate(names):
        row = frame.rows[i]
        assert row["status"] == "ok", name
        analytic = row["analytic_saturation"]
        routing = frame.planned[i].routing
        # the frame's analytic bound IS the channel-load bound
        assert analytic == pytest.approx(
            routing.saturation_rate(frame.planned[i].traffic))
        assert row["sim_saturation"] <= 1.15 * analytic, name
        assert row["sim_saturation"] >= 0.30 * analytic, name


# ---------------------------------------------------------------------
# planning semantics
# ---------------------------------------------------------------------

def test_plan_validates_and_buckets():
    exp = X.Experiment(
        [X.Scenario("mesh", 16),                       # static
         X.Scenario("folded_hexa_torus", 16),          # static, same R
         X.Scenario("hypercube", 15),                  # invalid
         X.Scenario("mesh", 16, traffic=WORKLOADS[0]),
         X.Scenario("mesh", 16, rates=X.ExplicitRates((0.1, 0.2)))],
        cfg=CFG)
    pl = X.plan(exp)
    assert pl.n_planned == 4
    assert [i for i, _ in pl.skipped] == [2]
    kinds = sorted(b.key.kind for b in pl.buckets)
    assert "workload" in kinds and "static" in kinds
    # the explicit-rate scenario has R=2, so it cannot share a bucket
    rs = sorted(b.key.n_rates for b in pl.buckets)
    assert 2 in rs
    assert "skip #2" in pl.describe()
    # workload buckets carry a padded phase axis
    wl = [b for b in pl.buckets if b.key.kind == "workload"][0]
    assert wl.key.k_pad >= wl.items[0].sched_spec.k


def test_single_program_plan_merges_buckets_bitwise():
    """single_program=True coalesces same-(kind, R) buckets into one
    compiled program without changing any counter."""
    exp = X.Experiment([X.Scenario(name, 16, traffic=pattern,
                                   rates=X.SaturationGrid(3))
                        for name in ("mesh", "folded_hexa_torus")
                        for pattern in ("uniform", "tornado", "neighbor",
                                        "permutation")], cfg=CFG)
    eng = SweepEngine(cfg=CFG)
    base = X.run(exp, engine=eng)
    # two full P4 / P6 groups: one call at P6 would pad 4 more lanes,
    # so the cost rule keeps them apart
    assert len(X.plan(exp, eng).buckets) == 2
    pl = X.plan(exp, eng, single_program=True)
    assert len(pl.buckets) == 1 and pl.single_program
    one = X.execute(pl, engine=eng)
    for a, b in zip(base.results, one.results):
        for k in RAW:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_rate_policies():
    grid = X.SaturationGrid(5).resolve(0.4)
    assert len(grid) == 5 and grid[-1] <= 1.0
    ex = X.ExplicitRates((0.3, 0.1))
    np.testing.assert_allclose(ex.resolve(123.0), [0.3, 0.1])
    assert "0.3" in ex.describe()
    with pytest.raises(ValueError):
        X.ExplicitRates(())
    with pytest.raises(KeyError):
        X.plan(X.Experiment([X.Scenario("mesh", 16,
                                        traffic="nonesuch")], cfg=CFG))
    # a bare topo -> matrix callable is a usage error with a clear fix
    from repro.core import traffic as TR
    with pytest.raises(TypeError, match="CustomTraffic"):
        X.plan(X.Experiment([X.Scenario("mesh", 16,
                                        traffic=TR.uniform)], cfg=CFG))


def test_analytic_backend_rows_match_sim_identity():
    """Analytic backend: no simulation, rows carry the channel-load
    bound and zero-load latency through the same cost model."""
    exp = X.Experiment([X.Scenario("mesh", 16),
                        X.Scenario("hypercube", 15)],
                       cfg=CFG, backend="analytic")
    frame = X.run(exp)
    assert frame.results[0] is None          # nothing simulated
    row = frame.rows[0]
    assert row["sim_saturation"] is None
    assert row["rel_throughput"] == pytest.approx(
        row["analytic_saturation"])
    assert row["abs_throughput_gbps"] > 0
    assert frame.rows[1]["status"] == "invalid"


# ---------------------------------------------------------------------
# execution: chunking, progress, partial-failure isolation
# ---------------------------------------------------------------------

def test_chunked_execution_bitwise_and_progress():
    exp = X.Experiment([X.Scenario(n, 16, rates=X.SaturationGrid(3))
                        for n in ("mesh", "folded_hexa_torus",
                                  "hexamesh", "honeycomb_mesh")],
                       cfg=CFG)
    eng = SweepEngine(cfg=CFG)
    whole = X.run(exp, engine=eng)
    ticks = []
    chunked = X.run(exp, engine=eng, chunk_size=1,
                    progress=lambda done, total, key:
                    ticks.append((done, total)))
    for a, b in zip(whole.results, chunked.results):
        for k in RAW:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert ticks[-1][0] == ticks[-1][1] == 4
    assert len(ticks) == 4               # one tick per 1-scenario chunk


class _FailingEngine(SweepEngine):
    """Raises for any chunk containing the poisoned topology size."""
    poison_n: int = 0

    def run_specs(self, specs, rates, single_program=False, cfg=None):
        if any(s.n == self.poison_n for s in specs):
            raise RuntimeError("injected failure")
        return super().run_specs(specs, rates, single_program, cfg=cfg)


def test_partial_failure_isolation():
    eng = _FailingEngine(cfg=CFG)
    eng.poison_n = 36
    exp = X.Experiment([X.Scenario("mesh", 16),
                        X.Scenario("mesh", 36),      # poisoned
                        X.Scenario("folded_hexa_torus", 16)],
                       cfg=CFG)
    with pytest.raises(RuntimeError):
        X.run(exp, engine=eng)                       # default: raise
    frame = X.run(exp, engine=eng, chunk_size=1, on_error="skip")
    statuses = [r["status"] for r in frame.rows]
    assert statuses == ["ok", "failed", "ok"]
    assert "injected failure" in frame.rows[1]["error"]
    assert frame.errors and frame.errors[0][0] == 1
    assert frame.results[0] is not None
    # ok scenarios are bitwise-unaffected by their failed neighbour
    clean = X.run(X.Experiment([X.Scenario("mesh", 16)], cfg=CFG),
                  engine=SweepEngine(cfg=CFG))
    for k in RAW:
        np.testing.assert_array_equal(frame.results[0][k],
                                      clean.results[0][k], err_msg=k)


# ---------------------------------------------------------------------
# deprecation contracts
# ---------------------------------------------------------------------

def test_legacy_entry_points_warn_and_work():
    eng = SweepEngine(cfg=CFG)
    cases = [SweepCase("mesh", 16)]
    with pytest.warns(DeprecationWarning, match="evaluate_cases"):
        out = eng.evaluate_cases(cases, n_rates=3)
    assert out[0]["sim_saturation"] > 0
    with pytest.warns(DeprecationWarning,
                      match="evaluate_workload_cases"):
        grid = eng.evaluate_workload_cases(cases, WORKLOADS[:1],
                                           n_rates=3)
    assert grid[0]["phase_cycles"].sum() == CFG.cycles - CFG.warmup
    from benchmarks.common import evaluate_many
    with pytest.warns(DeprecationWarning, match="evaluate_many"):
        rows = evaluate_many([("mesh", 16)], sim_cfg=CFG)
    assert rows[0]["topology"] == "mesh" and not rows[0]["sim"]


# ---------------------------------------------------------------------
# versioned writers + frame plumbing
# ---------------------------------------------------------------------

def test_write_csv_schema_and_stable_columns(tmp_path):
    path = str(tmp_path / "out.csv")
    rows = [dict(b=1, a=2), None, dict(a=3, b=4, c=5)]
    cols = X.write_csv(path, rows)
    assert cols == ["schema_version", "b", "a", "c"]
    lines = open(path).read().splitlines()
    assert lines[0] == "schema_version,b,a,c"
    assert lines[1] == f"{X.SCHEMA_VERSION},1,2,"
    assert len(lines) == 3                   # None row dropped
    # cells containing commas/quotes are RFC-4180 quoted, not split
    X.write_csv(path, [dict(r="rates(0.1,0.2)", q='say "hi"')])
    body = open(path).read().splitlines()[1]
    assert body == f'{X.SCHEMA_VERSION},"rates(0.1,0.2)","say ""hi"""'


def test_write_json_roundtrip(tmp_path):
    path = str(tmp_path / "out.json")
    X.write_json(path, [dict(x=np.float32(1.5),
                             y=np.arange(3))], meta=dict(tag="t"))
    doc = X.read_json(path)
    assert doc["schema_version"] == X.SCHEMA_VERSION
    assert doc["tag"] == "t"
    assert doc["rows"][0] == {"x": 1.5, "y": [0, 1, 2]}


def test_frame_csv_and_selects(tmp_path):
    exp = X.Experiment([X.Scenario("mesh", 16,
                                   tags=(("flavour", "plain"),)),
                        X.Scenario("hypercube", 15)],
                       cfg=CFG, backend="analytic")
    frame = X.run(exp)
    assert frame.columns[:3] == ("experiment", "backend", "status")
    assert "flavour" in frame.columns
    path = str(tmp_path / "frame.csv")
    frame.to_csv(path)
    lines = open(path).read().splitlines()
    assert lines[0].startswith("schema_version,experiment")
    assert len(lines) == 2                   # invalid row excluded
    frame.to_csv(path, include_failures=True)
    assert len(open(path).read().splitlines()) == 3
    assert frame.select(topology="mesh")[0]["flavour"] == "plain"
    assert len(frame) == 2 and len(list(iter(frame))) == 2
    # tags may not shadow reserved result columns
    with pytest.raises(ValueError, match="reserved"):
        X.Scenario("mesh", 16, tags=(("status", "phase1"),))


# ---------------------------------------------------------------------
# fault injection through the pipeline (DESIGN.md §12)
# ---------------------------------------------------------------------

def test_empty_faultset_bitwise_identical_to_no_faults():
    """Regression: `faults=FaultSet()` must be byte-for-byte the
    no-faults path — same routing cache entry, same sweep counters —
    for static AND workload traffic."""
    import repro.faults as F
    eng = SweepEngine(cfg=CFG)
    mk = lambda fs: [
        X.Scenario("mesh", 16, faults=fs, rates=X.SaturationGrid(3)),
        X.Scenario("folded_hexa_torus", 16, faults=fs,
                   traffic=WORKLOADS[0], rates=X.SaturationGrid(3))]
    base = X.run(X.Experiment(mk(None), cfg=CFG), engine=eng)
    empty = X.run(X.Experiment(mk(F.FaultSet()), cfg=CFG), engine=eng)
    for i in range(2):
        assert empty.planned[i].routing is base.planned[i].routing
        assert empty.planned[i].topo is base.planned[i].topo
        for k in RAW:
            np.testing.assert_array_equal(empty.results[i][k],
                                          base.results[i][k], err_msg=k)
        assert empty.rows[i]["faults"] == "none"
        assert empty.rows[i]["failed_links"] == 0


def test_degraded_scenarios_flow_through_pipeline():
    """Link/chiplet fault sets run in the same padded batches; columns
    report the fault identity; disconnecting sets are skipped with an
    actionable reason, not crashed on."""
    import repro.faults as F
    topo = T.build("folded_hexa_torus", 16)
    fs = F.sample_faults(topo, 2, "random", seed=0)
    chip = F.sample_faults(topo, 1, "chiplets", seed=0)
    e = np.sort(np.asarray(T.build("mesh", 16).edges), axis=1)
    cut = F.FaultSet(links=tuple(
        tuple(int(x) for x in lk) for lk in e[(e == 0).any(1)]))
    exp = X.Experiment(
        [X.Scenario("folded_hexa_torus", 16, rates=X.SaturationGrid(3)),
         X.Scenario("folded_hexa_torus", 16, faults=fs,
                    rates=X.SaturationGrid(3)),
         X.Scenario("folded_hexa_torus", 16, faults=chip,
                    rates=X.SaturationGrid(3)),
         X.Scenario("mesh", 16, faults=cut,
                    rates=X.SaturationGrid(3))], cfg=CFG)
    pl = X.plan(exp)
    assert pl.n_planned == 3
    assert len(pl.skipped) == 1
    i, reason = pl.skipped[0]
    assert i == 3 and "fault set rejected" in reason \
        and "islands" in reason
    frame = X.run(exp)
    assert [r["status"] for r in frame.rows] == ["ok", "ok", "ok",
                                                 "invalid"]
    pristine, degraded, dead_chip = frame.rows[:3]
    assert degraded["faults"] == fs.name
    assert degraded["failed_links"] == 2 and degraded["failed_chiplets"] == 0
    assert dead_chip["failed_chiplets"] == 1
    assert degraded["sim_saturation"] <= pristine["sim_saturation"] + 1e-9
    # the degraded cell routed a genuinely different structure
    assert frame.planned[1].routing is not frame.planned[0].routing
    assert len(frame.planned[1].topo.edges) == \
        len(frame.planned[0].topo.edges) - 2
    # dead chiplet neither injects nor receives in the resolved traffic
    dead = chip.chiplets[0]
    assert frame.planned[2].traffic[dead].sum() == 0
    assert frame.planned[2].traffic[:, dead].sum() == 0
    # scenario labels and Scenario.degraded reflect the fault identity
    assert exp.scenarios[1].degraded and not exp.scenarios[0].degraded
    assert fs.name in exp.scenarios[1].label


def test_workload_scenario_with_chiplet_faults_masks_every_phase():
    """A schedule run under chiplet faults carries masked phases and the
    whole (degraded topo, masked schedule) pair stays bitwise equal to
    the single-spec oracle."""
    import repro.faults as F
    from repro.core.simulator import run_batch
    topo = T.build("mesh", 16)
    chip = F.sample_faults(topo, 1, "chiplets", seed=3)
    scen = X.Scenario("mesh", 16, traffic=WORKLOADS[0], faults=chip,
                      rates=X.SaturationGrid(3))
    frame = X.run(X.Experiment([scen], cfg=CFG),
                  engine=SweepEngine(cfg=CFG))
    assert frame.rows[0]["status"] == "ok"
    ps = frame.planned[0]
    dead = chip.chiplets[0]
    for p in ps.schedule.phases:
        m = np.asarray(p.traffic)
        assert m[dead].sum() == 0 and m[:, dead].sum() == 0
    single = run_batch([ps.spec], ps.rates[None, :], CFG,
                       schedules=[ps.sched_spec])[0]
    for k in RAW:
        np.testing.assert_array_equal(single[k], frame.results[0][k],
                                      err_msg=k)


def test_scenario_faults_type_error():
    with pytest.raises(TypeError, match="FaultSet"):
        X.Scenario("mesh", 16, faults=[(0, 1)])


# ---------------------------------------------------------------------
# per-scenario routing modes (DESIGN.md §15)
# ---------------------------------------------------------------------

def test_scenario_routing_validation():
    X.Scenario("mesh", 16, routing="adaptive")
    X.Scenario("mesh", 16, routing=None)
    with pytest.raises(ValueError, match="routing"):
        X.Scenario("mesh", 16, routing="wild")
    s = X.Scenario("mesh", 16)
    assert s.effective_routing(CFG) == "static"
    assert s.effective_routing(CFG._replace(routing="adaptive")) \
        == "adaptive"
    so = X.Scenario("mesh", 16, routing="adaptive")
    assert so.effective_routing(CFG) == "adaptive"


def test_plan_buckets_split_by_routing():
    """Static and adaptive scenarios of the same shape land in
    different buckets (different compiled programs), and the bucket key
    carries the effective mode."""
    exp = X.Experiment(
        [X.Scenario("mesh", 16, rates=X.ExplicitRates((0.1, 0.3))),
         X.Scenario("mesh", 16, rates=X.ExplicitRates((0.1, 0.3)),
                    routing="adaptive")], cfg=CFG)
    pl = X.plan(exp)
    keys = sorted(b.key.routing for b in pl.buckets)
    assert keys == ["adaptive", "static"]
    # single_program mode must NOT merge across routing modes
    pl2 = X.plan(exp, single_program=True)
    assert len(pl2.buckets) == 2


def test_execute_routing_override_matches_direct():
    """A routing="adaptive" scenario produces exactly the counters of a
    direct adaptive run; the static sibling stays on the engine default."""
    rates = (0.1, 0.4)
    exp = X.Experiment(
        [X.Scenario("mesh", 16, rates=X.ExplicitRates(rates)),
         X.Scenario("mesh", 16, rates=X.ExplicitRates(rates),
                    routing="adaptive")], cfg=CFG)
    frame = X.run(exp)
    assert [r["routing"] for r in frame.rows] == ["static", "adaptive"]
    from repro.core.routing import cached_routing
    from repro.core import traffic as TR
    from repro.core.simulator import make_spec
    topo, routing = cached_routing("mesh", 16, "organic", 74.0,
                                   "homogeneous")
    spec = make_spec(routing, TR.uniform(topo))
    rr = np.asarray(rates, np.float32)[None, :]
    for i, mode in enumerate(("static", "adaptive")):
        direct = run_batch([spec], rr, CFG._replace(routing=mode))[0]
        got = frame.results[i]
        for k in RAW:
            np.testing.assert_array_equal(
                np.asarray(got[k]), np.asarray(direct[k]),
                err_msg=f"{mode}/{k}")


def test_saturation_grid_routing_headroom():
    """SaturationGrid resolves a wider ceiling for adaptive scenarios;
    explicit headroom pins it for both modes."""
    from repro.core.simulator import saturation_rate_grid
    g = X.SaturationGrid(n_rates=5)
    np.testing.assert_array_equal(
        g.resolve(0.3), saturation_rate_grid(0.3, 5))
    ad = g.resolve(0.3, routing="adaptive")
    assert ad[-1] > g.resolve(0.3)[-1]
    pinned = X.SaturationGrid(n_rates=5, headroom=2.5)
    np.testing.assert_array_equal(
        pinned.resolve(0.3, routing="static"),
        pinned.resolve(0.3, routing="adaptive"))
    assert "x2.5" in pinned.describe()


def test_routing_column_in_frame_csv(tmp_path):
    exp = X.Experiment(
        [X.Scenario("mesh", 16, rates=X.ExplicitRates((0.1,)),
                    routing="adaptive")], cfg=CFG)
    frame = X.run(exp)
    p = tmp_path / "out.csv"
    frame.to_csv(str(p))
    head = p.read_text().splitlines()
    assert "routing" in head[0].split(",")
    i = head[0].split(",").index("routing")
    assert head[1].split(",")[i] == "adaptive"
