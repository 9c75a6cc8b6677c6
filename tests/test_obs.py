"""Observability tests (DESIGN.md §13).

The acceptance properties of the telemetry layer:

  * **off is bitwise free** — `SimConfig(telemetry=True)` must not
    change a single shared counter vs the telemetry-off run, on static,
    workload AND fault-degraded scenarios (the flight recorder is a
    pure observer);
  * **conservation** — the per-node/per-link counters reconcile exactly
    with the aggregate counters the simulator already reports
    (sum(inj_node) == accepted_n, sum(eject_node) == delivered,
    sum(lat_hist) == delivered);
  * **padding-invariant** — telemetry sliced from a larger padded batch
    is bitwise-equal to the tight run, and never names a sacrificial or
    padded slot.

Plus unit coverage of the host half: tracer semantics, Chrome-trace
export, the metrics registry, the executor's backwards-compatible
progress callback, and the engine's eviction-proof compile accounting.
"""
import json

import numpy as np
import pytest

import repro.experiments as X
import repro.faults as F
import repro.workloads as W
from repro.core import topology as T
from repro.core import traffic as TR
from repro.core.routing import build_routing
from repro.core.simulator import (LAT_HIST_BINS, TELEMETRY_KEYS,
                                  TELEMETRY_WINDOW_KEYS, SimConfig,
                                  make_spec, run_batch,
                                  telemetry_window_cycles)
from repro.obs.metrics import (MetricsRegistry, cache_counters,
                               metrics as METRICS)
from repro.obs.report import gini, link_load_summary, window_summary
from repro.obs.trace import (Tracer, clear_trace, disable_tracing,
                             enable_tracing, get_spans, span_summary,
                             trace)
from repro.sweep.engine import SweepEngine
from repro.sweep.padding import PadShape

CFG = SimConfig(cycles=300, warmup=100)
TCFG = CFG._replace(telemetry=True)
MEAS = CFG.cycles - CFG.warmup
RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")
RATES = np.array([0.05, 0.2, 0.5], np.float32)

HETERO = [("mesh", 16), ("folded_hexa_torus", 36)]


@pytest.fixture(scope="module")
def specs():
    out = []
    for name, n in HETERO:
        r = build_routing(T.build(name, n))
        out.append(make_spec(r, TR.uniform(r.topo)))
    return out


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the process tracer disabled."""
    disable_tracing()
    clear_trace()
    yield
    disable_tracing()
    clear_trace()


# ---------------------------------------------------------------------
# flight recorder: bitwise-off, conservation, padding
# ---------------------------------------------------------------------

def test_telemetry_off_bitwise_identical_static(specs):
    """Turning the recorder on must not perturb any shared counter."""
    off = run_batch(specs, RATES, CFG)
    on = run_batch(specs, RATES, TCFG)
    for o, t in zip(off, on):
        for k in RAW:
            np.testing.assert_array_equal(o[k], t[k], err_msg=k)
        np.testing.assert_array_equal(o["throughput"], t["throughput"])
        np.testing.assert_array_equal(o["latency"], t["latency"])
        assert all(k in t for k in TELEMETRY_KEYS)
        assert not any(k in o for k in TELEMETRY_KEYS)


def test_telemetry_off_bitwise_identical_workload():
    topo = T.build("folded_hexa_torus", 16)
    r = build_routing(topo)
    sched = W.phase_alternating(topo, phase_cycles=60, repeats=1).fit(MEAS)
    spec = make_spec(r, sched.mean_traffic())
    eng_off = SweepEngine(cfg=CFG)
    eng_on = SweepEngine(cfg=TCFG)
    off = eng_off.run_workloads([spec], [sched], RATES)[0]
    on = eng_on.run_workloads([spec], [sched], RATES)[0]
    for k in RAW + ("delivered_ph", "lat_sum_ph"):
        np.testing.assert_array_equal(off[k], on[k], err_msg=k)
    assert "link_busy" in on and "link_busy" not in off


def test_telemetry_off_bitwise_identical_faults():
    topo = T.build("folded_hexa_torus", 36)
    fs = F.sample_faults(topo, 2, "random", seed=0)
    mk = lambda cfg: X.Experiment(
        [X.Scenario("folded_hexa_torus", 36, faults=fs,
                    rates=X.ExplicitRates((0.1, 0.3)))], cfg=cfg)
    off = X.run(mk(CFG), engine=SweepEngine(cfg=CFG))
    on = X.run(mk(TCFG), engine=SweepEngine(cfg=TCFG))
    for k in RAW:
        np.testing.assert_array_equal(off.results[0][k], on.results[0][k],
                                      err_msg=k)


def test_telemetry_conservation(specs):
    """Flight counters reconcile EXACTLY with the aggregate counters."""
    out = run_batch(specs, RATES, TCFG)
    for spec, res in zip(specs, out):
        np.testing.assert_array_equal(res["inj_node"].sum(axis=1),
                                      res["accepted_n"])
        np.testing.assert_array_equal(res["eject_node"].sum(axis=1),
                                      res["delivered"])
        np.testing.assert_array_equal(res["lat_hist"].sum(axis=1),
                                      res["delivered"])
        # each delivered flit traversed >= 1 link; busy counts them all
        assert (res["link_busy"].sum(axis=1) >= res["delivered"]).all()
        util = res["link_util"]
        assert (util >= 0).all() and (util <= 1).all()
        assert (res["link_stall"] >= 0).all()
        assert res["lat_hist"].shape == (len(RATES), LAT_HIST_BINS)


def test_telemetry_padding_invariant(specs):
    """Telemetry sliced from a fat padded batch == the tight batch, and
    its leaves are sized to the spec's own (c, n) — pad slots and the
    sacrificial row can never leak into a report."""
    tight = run_batch(specs, RATES, TCFG)
    shape = PadShape.of(specs)
    fat = PadShape(n=shape.n + 7, p=shape.p + 2, c=shape.c + 19,
                   d=shape.d + 3)
    padded = run_batch(specs, RATES, TCFG, pad_shape=fat)
    for spec, a, b in zip(specs, tight, padded):
        for k in TELEMETRY_KEYS:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        assert b["link_busy"].shape == (len(RATES), spec.c)
        assert b["inj_node"].shape == (len(RATES), spec.n)
        assert b["link_occ_sum"].shape[:2] == (len(RATES), spec.c)


def test_link_rows_and_frame_columns(tmp_path):
    """Tidy per-link rows cover exactly the routed channels, tidy rows
    gain the distribution columns, and the CSV writers round-trip."""
    exp = X.Experiment([X.Scenario("mesh", 16,
                                   rates=X.ExplicitRates((0.1, 0.4))),
                        X.Scenario("folded_hexa_torus", 16,
                                   rates=X.ExplicitRates((0.1, 0.4)))],
                       cfg=TCFG, name="obs_unit")
    frame = X.run(exp, engine=SweepEngine(cfg=TCFG))
    for i in range(2):
        rows = frame.link_rows(i)
        routing = frame.planned[i].routing
        assert len(rows) == len(routing.ch_src)          # all ok, no dead
        assert all(r["status"] == "ok" for r in rows)
        assert {r["channel"] for r in rows} == set(range(len(rows)))
        srcs = {(r["src"], r["dst"]) for r in rows}
        want = {(int(s), int(d)) for s, d in
                zip(routing.ch_src, routing.ch_dst)}
        assert srcs == want
        assert frame.rows[i]["link_gini"] is not None
        assert 0.0 <= frame.rows[i]["link_gini"] <= 1.0
        assert frame.rows[i]["link_util_max"] >= \
            frame.rows[i]["link_util_p95"]
    path = str(tmp_path / "links.csv")
    frame.to_link_csv(path)
    header = open(path).readline().strip().split(",")
    assert header[0] == "schema_version" and "util" in header
    # summary distribution stats per topology cell
    summary = link_load_summary(frame.all_link_rows())
    assert len(summary) == 2
    for s in summary:
        assert s["n_dead"] == 0 and s["util_max"] >= s["util_p95"]


def test_link_rows_report_dead_links():
    topo = T.build("folded_hexa_torus", 36)
    fs = F.sample_faults(topo, 2, "random", seed=0)
    exp = X.Experiment([X.Scenario("folded_hexa_torus", 36, faults=fs,
                                   rates=X.ExplicitRates((0.1, 0.3)))],
                       cfg=TCFG)
    frame = X.run(exp, engine=SweepEngine(cfg=TCFG))
    rows = frame.link_rows(0)
    dead = [r for r in rows if r["status"] == "dead"]
    ok = [r for r in rows if r["status"] == "ok"]
    assert len(dead) == 2 * fs.n_links          # both directions
    assert {(r["src"], r["dst"]) for r in dead} == \
        {(u, v) for a, b in fs.links for u, v in ((a, b), (b, a))}
    assert all(r["busy"] == 0 and r["channel"] == -1 for r in dead)
    # surviving channels are the degraded routing's channels
    assert len(ok) == len(frame.planned[0].routing.ch_src)
    # no dead link appears among the surviving directed channels
    assert not ({(r["src"], r["dst"]) for r in ok}
                & {(r["src"], r["dst"]) for r in dead})


def test_link_rows_require_telemetry():
    exp = X.Experiment([X.Scenario("mesh", 16,
                                   rates=X.ExplicitRates((0.1,)))],
                       cfg=CFG)
    frame = X.run(exp, engine=SweepEngine(cfg=CFG))
    with pytest.raises(ValueError, match="telemetry"):
        frame.link_rows(0)


def test_gini():
    assert gini([1, 1, 1, 1]) == pytest.approx(0.0)
    assert gini([0, 0, 0, 8]) == pytest.approx(0.75)
    assert gini([]) == 0.0
    assert gini([0.0, 0.0]) == 0.0


# ---------------------------------------------------------------------
# host half: tracer + metrics
# ---------------------------------------------------------------------

def test_tracer_records_spans_and_attrs():
    tr = Tracer()
    with tr.trace("outer", cat="test", a=1):
        with tr.trace("inner") as sp:
            sp.set(cold=True)
    assert not tr.spans()                      # disabled: nothing kept
    tr.enable()
    with tr.trace("outer", cat="test", a=1):
        with tr.trace("inner") as sp:
            sp.set(cold=True)
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.args["cold"] is True and outer.args["a"] == 1
    assert outer.dur >= inner.dur >= 0
    assert outer.ts <= inner.ts <= inner.ts + inner.dur \
        <= outer.ts + outer.dur


def test_tracer_records_exceptions():
    tr = Tracer()
    tr.enable()
    with pytest.raises(RuntimeError):
        with tr.trace("boom"):
            raise RuntimeError("x")
    (sp,) = tr.spans()
    assert sp.args["error"] == "RuntimeError"


def test_chrome_trace_export(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.trace("phase", cat="test", shape="(1, 2)"):
        pass
    path = str(tmp_path / "trace.json")
    n = tr.save_chrome_trace(path, metadata=dict(run="unit"))
    assert n == 1
    doc = json.load(open(path))
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "phase"
    assert ev["args"]["shape"] == "(1, 2)"
    assert doc["metadata"]["run"] == "unit"


def test_simulator_emits_spans_when_tracing(specs):
    enable_tracing()
    run_batch(specs[:1], RATES, CFG)
    names = [s.name for s in get_spans()]
    assert "sim.stack" in names and "sim.dispatch" in names \
        and "sim.wait" in names
    disp = [s for s in get_spans() if s.name == "sim.dispatch"]
    assert all("cold" in s.args for s in disp)


def test_metrics_registry(tmp_path):
    m = MetricsRegistry()
    m.inc("a")
    m.inc("a", 2)
    assert m.get("a") == 3
    m.observe("lat", 1.0)
    m.observe("lat", 3.0)
    snap = m.snapshot()
    assert snap["a"] == 3
    assert snap["lat"] == dict(count=2, sum=4.0, min=1.0, max=3.0)
    assert "cache.runner.misses" in snap        # absorbed LRU counters
    sink = str(tmp_path / "events.jsonl")
    m.set_sink(sink)
    m.event("chunk_failed", reason="boom", n=2)
    m.event("other")
    assert [e["reason"] for e in m.events("chunk_failed")] == ["boom"]
    lines = [json.loads(x) for x in open(sink)]
    assert len(lines) == 2 and lines[0]["event"] == "chunk_failed"
    out = str(tmp_path / "log.jsonl")
    assert m.save_jsonl(out) == 2
    m.reset()
    assert m.get("a") == 0 and not m.events()


def test_cache_counters_monotonic():
    before = cache_counters()
    r = build_routing(T.build("mesh", 16))
    run_batch([make_spec(r, TR.uniform(r.topo))],
              np.array([0.1], np.float32), CFG)
    after = cache_counters()
    for k in ("cache.runner.misses", "cache.runner.hits",
              "cache.routing.misses"):
        assert after[k] >= before[k]


# ---------------------------------------------------------------------
# executor + engine plumbing
# ---------------------------------------------------------------------

def test_progress_callback_three_and_four_arg():
    exp = X.Experiment([X.Scenario("mesh", 16, rates=X.SaturationGrid(3)),
                        X.Scenario("folded_hexa_torus", 16,
                                   rates=X.SaturationGrid(3))], cfg=CFG)
    eng = SweepEngine(cfg=CFG)
    legacy, rich = [], []
    X.run(exp, engine=eng, chunk_size=1,
          progress=lambda done, total, key: legacy.append((done, total)))
    X.run(exp, engine=eng, chunk_size=1,
          progress=lambda done, total, key, info:
          rich.append((done, total, info)))
    assert [x[:2] for x in legacy] == [x[:2] for x in rich]
    for _, _, info in rich:
        assert info["status"] == "ok" and info["scenarios"] == 1
        assert info["elapsed_s"] >= 0 and info["compiled"] >= 0
    # warm second run: the engine reused its executables
    assert sum(info["compiled"] for _, _, info in rich) == 0


class _FailingEngine(SweepEngine):
    poison_n: int = 0

    def run_specs(self, specs, rates, single_program=False, cfg=None):
        if any(s.n == self.poison_n for s in specs):
            raise RuntimeError("injected failure")
        return super().run_specs(specs, rates, single_program, cfg=cfg)


def test_failed_chunk_logs_metrics_event():
    eng = _FailingEngine(cfg=CFG)
    eng.poison_n = 36
    exp = X.Experiment([X.Scenario("mesh", 16),
                        X.Scenario("mesh", 36)], cfg=CFG,
                       name="obs_fail_unit")
    n0 = len(METRICS.events("execute.chunk_failed"))
    infos = []
    frame = X.run(exp, engine=eng, chunk_size=1, on_error="skip",
                  progress=lambda d, t, k, info: infos.append(info))
    assert [r["status"] for r in frame.rows] == ["ok", "failed"]
    evs = METRICS.events("execute.chunk_failed")[n0:]
    assert len(evs) == 1
    assert evs[0]["experiment"] == "obs_fail_unit"
    assert "injected failure" in evs[0]["reason"]
    assert evs[0]["indices"] == [1]
    assert [i["status"] for i in infos] == ["ok", "failed"]


def test_engine_compile_stats_survive_evictions():
    """Satellite regression: compile accounting is a monotonic
    miss-delta, so an LRU eviction between groups cannot make the
    engine report fewer (or negative) compiles."""
    from repro.core import simulator as sim
    tiny = SimConfig(cycles=80, warmup=20)
    rates = np.array([0.1, 0.3], np.float32)
    specs = []
    for name, n in HETERO:
        r = build_routing(T.build(name, n))
        specs.append(make_spec(r, TR.uniform(r.topo)))
    old_max = sim.runner_cache_info()["max_size"]
    sim._RUNNER_CACHE.clear()
    eng = SweepEngine(cfg=tiny, bucket=False)
    try:
        sim.set_runner_cache_limit(1)   # every group evicts the other
        # bucket=False never merges: 2 shapes -> 2 compiles
        eng.run_specs(specs, rates)
        assert eng.stats["compiles"] == 2
        eng.run_specs(specs, rates)     # both cold again (evicted)
        assert eng.stats["compiles"] == 4
        assert eng.stats["reuses"] == 0
    finally:
        sim.set_runner_cache_limit(old_max)


def test_engine_emits_sweep_group_spans(specs):
    enable_tracing()
    clear_trace()
    SweepEngine(cfg=CFG).run_specs(specs, RATES)
    groups = [s for s in get_spans() if s.name == "sweep.group"]
    assert groups and all(s.args["kind"] == "static" for s in groups)


def test_experiment_pipeline_emits_plan_execute_spans():
    enable_tracing()
    clear_trace()
    exp = X.Experiment([X.Scenario("mesh", 16,
                                   rates=X.ExplicitRates((0.1,)))],
                       cfg=CFG)
    X.run(exp, engine=SweepEngine(cfg=CFG))
    names = [s.name for s in get_spans()]
    for want in ("experiment.plan", "experiment.execute",
                 "execute.chunk", "sweep.group", "sim.dispatch"):
        assert want in names, want


# ---------------------------------------------------------------------
# windowed flight recorder (DESIGN.md §16)
# ---------------------------------------------------------------------

WCFG = TCFG._replace(telemetry_windows=5)
WKEYS_RAW = ("link_busy_w", "link_stall_w", "link_occ_w",
             "inj_node_w", "eject_node_w")
#: (windowed key, aggregate it must sum to over the window axis)
WSUM = (("link_busy_w", "link_busy"), ("link_stall_w", "link_stall"),
        ("link_occ_w", "link_occ_sum"), ("inj_node_w", "inj_node"),
        ("eject_node_w", "eject_node"))


def test_windowed_off_by_default(specs):
    """telemetry_windows=0 leaves results without any windowed key, and
    enabling it perturbs no aggregate counter (it only *bins*)."""
    plain = run_batch(specs, RATES, TCFG)
    windowed = run_batch(specs, RATES, WCFG)
    for p, w in zip(plain, windowed):
        assert not any(k in p for k in TELEMETRY_WINDOW_KEYS)
        assert all(k in w for k in TELEMETRY_WINDOW_KEYS)
        for k in RAW + TELEMETRY_KEYS:
            np.testing.assert_array_equal(p[k], w[k], err_msg=k)


@pytest.mark.parametrize("routing", ["static", "adaptive"])
def test_windowed_conservation(specs, routing):
    """Each windowed tensor sums over its window axis EXACTLY to the
    aggregate counter, in both routing modes."""
    cfg = WCFG._replace(routing=routing)
    for res in run_batch(specs, RATES, cfg):
        for wk, ak in WSUM:
            np.testing.assert_array_equal(
                res[wk].sum(axis=1), res[ak],
                err_msg=f"{routing}: {wk} vs {ak}")
        wc = res["window_cycles"]
        assert wc.sum() == MEAS and len(wc) == 5
        util = res["link_util_w"]
        assert (util >= 0).all() and (util <= 1).all()


def test_windowed_conservation_workload():
    """Windowed counters reconcile on the phase-schedule path too."""
    topo = T.build("folded_hexa_torus", 16)
    r = build_routing(topo)
    sched = W.phase_alternating(topo, phase_cycles=60, repeats=1).fit(MEAS)
    spec = make_spec(r, sched.mean_traffic())
    res = SweepEngine(cfg=WCFG).run_workloads([spec], [sched], RATES)[0]
    for wk, ak in WSUM:
        np.testing.assert_array_equal(res[wk].sum(axis=1), res[ak],
                                      err_msg=wk)
    # and the two decompositions of accepted agree: windows vs phases
    np.testing.assert_array_equal(
        res["inj_node_w"].sum(axis=(1, 2)),
        res["accepted_ph"].sum(axis=1))


@pytest.mark.parametrize("routing", ["static", "adaptive"])
def test_windowed_padding_invariant(specs, routing):
    """Windowed telemetry sliced from a FAT padded batch is bitwise
    equal to the tight batch, in both routing modes (the fat-pad
    regression test of the acceptance criteria)."""
    cfg = WCFG._replace(routing=routing)
    tight = run_batch(specs, RATES, cfg)
    shape = PadShape.of(specs)
    fat = PadShape(n=shape.n + 7, p=shape.p + 2, c=shape.c + 19,
                   d=shape.d + 3)
    padded = run_batch(specs, RATES, cfg, pad_shape=fat)
    for spec, a, b in zip(specs, tight, padded):
        for k in WKEYS_RAW + ("link_util_w", "window_cycles"):
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"{routing}: {k}")
        W_ = cfg.telemetry_windows
        assert b["link_busy_w"].shape == (len(RATES), W_, spec.c)
        assert b["inj_node_w"].shape == (len(RATES), W_, spec.n)


def test_window_validation_errors(specs):
    with pytest.raises(ValueError, match="telemetry=True"):
        run_batch(specs, RATES, CFG._replace(telemetry_windows=4))
    with pytest.raises(ValueError, match="exceeds the measured"):
        run_batch(specs, RATES,
                  TCFG._replace(telemetry_windows=MEAS + 1))
    with pytest.raises(ValueError):
        run_batch(specs, RATES, TCFG._replace(telemetry_windows=-1))


def test_telemetry_window_cycles_partition():
    """The host-side window grid partitions the measured span exactly,
    even when W does not divide it."""
    cfg = SimConfig(cycles=307, warmup=100, telemetry=True,
                    telemetry_windows=6)
    wc = telemetry_window_cycles(cfg)
    assert wc.sum() == 207 and len(wc) == 6
    assert wc.min() >= 207 // 6 and wc.max() <= 207 // 6 + 1
    with pytest.raises(ValueError):
        telemetry_window_cycles(cfg._replace(telemetry_windows=0))


def test_window_rows_summary_and_csv(tmp_path):
    """Tidy per-(window, link) rows + time-heatmap CSV round-trip, and
    the per-window summary tracks a drifting hotspot's imbalance."""
    wl = W.Workload("hotspot_drift",
                    lambda topo: W.hotspot_drift(topo, n_phases=5,
                                                 dwell=40))
    exp = X.Experiment(
        [X.Scenario("folded_hexa_torus", 16, traffic=wl,
                    rates=X.ExplicitRates((0.1, 0.3)))],
        cfg=WCFG, name="win")
    frame = X.run(exp, engine=SweepEngine(cfg=WCFG))
    rows = frame.window_rows(0)
    spec = frame.planned[0].spec
    W_ = WCFG.telemetry_windows
    assert len(rows) == W_ * spec.c
    # the window grid tiles the measured span
    starts = sorted({r["t_start"] for r in rows})
    ends = sorted({r["t_end"] for r in rows})
    assert starts[0] == 0 and ends[-1] == MEAS
    assert starts[1:] == ends[:-1]
    # summary: one row per window, busy total conserved vs link rows
    summ = window_summary(rows)
    assert [s["window"] for s in summ] == list(range(W_))
    assert sum(s["busy_total"] for s in summ) == \
        sum(r["busy"] for r in rows)
    path = tmp_path / "win.csv"
    frame.to_window_csv(str(path))
    header = path.read_text().splitlines()[0].split(",")
    assert header[0] == "schema_version"
    from repro.obs.flight import WINDOW_COLUMNS
    assert list(WINDOW_COLUMNS) == header[1:1 + len(WINDOW_COLUMNS)]


def test_window_rows_require_windowed_telemetry(specs):
    from repro.obs.flight import window_rows
    exp = X.Experiment([X.Scenario("mesh", 16,
                                   rates=X.ExplicitRates((0.1,)))],
                       cfg=TCFG)
    frame = X.run(exp, engine=SweepEngine(cfg=TCFG))
    with pytest.raises(ValueError, match="windowed telemetry"):
        window_rows(frame.planned[0], frame.results[0])


# ---------------------------------------------------------------------
# pad-waste accounting (DESIGN.md §16)
# ---------------------------------------------------------------------

def test_pad_fill_on_results(specs):
    """Every result carries its live-work fraction; padding fatter
    shrinks it, and a tight single-spec batch is fill 1.0."""
    tight = run_batch([specs[0]], RATES, CFG)[0]
    assert tight["pad_fill"] == dict(state=1.0, chan=1.0, depth=1.0,
                                     phase=1.0)
    both = run_batch(specs, RATES, CFG)
    shape = PadShape.of(specs)
    for spec, res in zip(specs, both):
        pf = res["pad_fill"]
        assert 0 < pf["state"] <= 1.0 and pf["chan"] == spec.c / shape.c
        assert pf["phase"] == 1.0
    fat = PadShape(n=shape.n + 7, p=shape.p + 2, c=shape.c + 19,
                   d=shape.d + 3)
    fatter = run_batch(specs, RATES, CFG, pad_shape=fat)
    for res, fres in zip(both, fatter):
        assert fres["pad_fill"]["state"] < res["pad_fill"]["state"]


def test_pad_fill_in_frame_rows():
    """Tidy ResultFrame rows surface the pad-fill columns (schema v6)."""
    exp = X.Experiment(
        [X.Scenario(name, 16, rates=X.ExplicitRates((0.1,)))
         for name in ("mesh", "folded_hexa_torus")],
        cfg=CFG)
    frame = X.run(exp, engine=SweepEngine(cfg=CFG))
    for row in frame.ok():
        assert 0 < row["pad_fill_state"] <= 1.0
        assert 0 < row["pad_fill_chan"] <= 1.0
        assert row["pad_fill_phase"] == 1.0
    assert any(r["pad_fill_chan"] < 1.0 for r in frame.ok())


def test_sweep_group_span_reports_bucket_fill(specs):
    enable_tracing()
    clear_trace()
    SweepEngine(cfg=CFG, s_round=4).run_specs(specs, RATES)
    groups = [s for s in get_spans() if s.name == "sweep.group"]
    assert groups
    for sp in groups:
        assert sp.args["s_live"] <= sp.args["s_pad"]
        assert sp.args["r_live"] <= sp.args["r_pad"]
    disp = [s for s in get_spans() if s.name == "sim.dispatch"]
    assert disp and all("fill_state" in s.args for s in disp)


# ---------------------------------------------------------------------
# metrics sink isolation (DESIGN.md §16 satellite)
# ---------------------------------------------------------------------

def test_metrics_buffered_sink_flush_and_close(tmp_path):
    reg = MetricsRegistry()
    sink = tmp_path / "ev.jsonl"
    reg.set_sink(str(sink), buffered=True)
    reg.event("a", x=1)
    reg.event("b", x=2)
    assert not sink.exists() or sink.read_text() == ""
    assert reg.flush() == 2
    assert len(sink.read_text().splitlines()) == 2
    reg.event("c")
    reg.close_sink()
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [e["event"] for e in lines] == ["a", "b", "c"]
    reg.event("after_close")          # no sink: memory only
    assert len(sink.read_text().splitlines()) == 3


def test_metrics_reset_detaches_sink(tmp_path):
    """reset() flushes + detaches the sink, so a later run cannot leak
    events into a file an earlier test attached."""
    reg = MetricsRegistry()
    sink = tmp_path / "run1.jsonl"
    reg.set_sink(str(sink), buffered=True)
    reg.inc("n")
    reg.event("run1.ev")
    reg.reset()
    assert [json.loads(ln)["event"]
            for ln in sink.read_text().splitlines()] == ["run1.ev"]
    assert reg.get("n") == 0 and reg.events() == []
    reg.event("run2.ev")              # post-reset events stay in memory
    assert len(sink.read_text().splitlines()) == 1


def test_metrics_sink_switch_flushes_old(tmp_path):
    reg = MetricsRegistry()
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    reg.set_sink(str(a), buffered=True)
    reg.event("one")
    reg.set_sink(str(b))              # unbuffered from here
    assert len(a.read_text().splitlines()) == 1
    reg.event("two")
    assert json.loads(b.read_text())["event"] == "two"


# ---------------------------------------------------------------------
# tracer edge cases (DESIGN.md §16 satellite)
# ---------------------------------------------------------------------

def test_tracer_empty_export(tmp_path):
    t = Tracer()
    t.enable()
    assert t.chrome_events() == []
    path = tmp_path / "empty.trace.json"
    assert t.save_chrome_trace(str(path)) == 0
    doc = json.loads(path.read_text())
    assert doc["traceEvents"] == []


def test_tracer_concurrent_threads():
    import threading
    t = Tracer()
    t.enable()

    def worker(i):
        for j in range(20):
            with t.trace(f"w{i}", cat="thr", j=j):
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    spans = t.spans()
    assert len(spans) == 80           # no span lost to a race
    # every span carries its recording thread's id (ids may be recycled
    # once a thread exits, so count per worker, not distinct tids)
    assert all(s.tid for s in spans)
    by_name = span_summary(spans)
    assert all(by_name[f"w{i}"]["count"] == 20 for i in range(4))
    for i in range(4):
        tids = {s.tid for s in spans if s.name == f"w{i}"}
        assert len(tids) == 1         # one worker -> one tid


def test_nested_span_parent_attribution():
    """Chrome events come out start-sorted with parents before children
    (spans RECORD innermost-first; export must not)."""
    t = Tracer()
    t.enable()
    with t.trace("parent", cat="t"):
        with t.trace("child", cat="t"):
            with t.trace("grandchild", cat="t"):
                pass
    assert [s.name for s in t.spans()] == ["grandchild", "child",
                                           "parent"]
    ev = t.chrome_events()
    assert [e["name"] for e in ev] == ["parent", "child", "grandchild"]
    p, c, g = ev
    assert p["ts"] <= c["ts"] <= g["ts"]
    assert p["ts"] + p["dur"] >= c["ts"] + c["dur"] \
        >= g["ts"] + g["dur"]


def test_span_summary_aggregates():
    t = Tracer()
    t.enable()
    for _ in range(3):
        with t.trace("x"):
            pass
    with t.trace("y"):
        pass
    summ = span_summary(t.spans())
    assert summ["x"]["count"] == 3 and summ["y"]["count"] == 1
    assert summ["x"]["total_s"] >= summ["x"]["max_s"] >= 0


# ---------------------------------------------------------------------
# set-up counters: compile pipeline and routing builds (always on)
# ---------------------------------------------------------------------

def _jit_obs():
    return {k: v for k, v in METRICS.snapshot().items()
            if k.startswith("jit.")}


def test_fresh_jit_records_each_compile_stage_under_its_name():
    import jax

    @jax.jit
    def obs_probe_inner(x):
        return x * 3

    @jax.jit
    def obs_probe_outer(x):
        return obs_probe_inner(x) + 1

    obs_probe_outer(np.arange(5, dtype=np.int32)).block_until_ready()
    snap = METRICS.snapshot()
    for key in ("jit.trace_s:obs_probe_outer",
                "jit.lower_s:jit(obs_probe_outer)",
                "jit.compile_s:jit(obs_probe_outer)",
                "jit.trace_s:obs_probe_inner"):
        assert snap[key]["count"] == 1 and snap[key]["sum"] >= 0, key
    # the nested jit is traced inside its caller and never compiled alone
    assert "jit.lower_s:jit(obs_probe_inner)" not in snap
    assert "jit.compile_s:jit(obs_probe_inner)" not in snap


def test_warm_calls_record_no_compile_observation(specs):
    import jax
    f = jax.jit(lambda x: x - 1)
    x = np.ones(7, np.float32)
    f(x).block_until_ready()
    run_batch(specs[:1], RATES, CFG)
    before = _jit_obs()
    assert any(k.startswith("jit.compile_s:") for k in before)
    f(x).block_until_ready()
    run_batch(specs[:1], RATES, CFG)
    assert _jit_obs() == before


def test_compile_listeners_install_once():
    import importlib

    import jax
    import repro.obs
    importlib.reload(repro.obs)
    repro.obs.install_compile_listeners()

    @jax.jit
    def obs_probe_once(x):
        return x + 2

    obs_probe_once(np.zeros(3, np.int32)).block_until_ready()
    snap = METRICS.snapshot()
    assert snap["jit.trace_s:obs_probe_once"]["count"] == 1
    assert snap["jit.compile_s:jit(obs_probe_once)"]["count"] == 1


def test_routing_build_time_observed_on_a_miss_only():
    from repro.core import routing as RT
    topo = T.build("hexamesh", 19)
    RT.routing_cache_clear()
    n0 = METRICS.snapshot().get("routing.build_s", {}).get("count", 0)
    RT.routing_for(topo)
    after_miss = METRICS.snapshot()["routing.build_s"]
    assert after_miss["count"] == n0 + 1 and after_miss["sum"] > 0
    RT.routing_for(topo)
    assert METRICS.snapshot()["routing.build_s"] == after_miss


def test_runner_carries_each_step_phase_scope():
    """Each phase of the step is a named scope, so the compiled program's
    instructions say which phase they belong to."""
    from repro.core import simulator as sim
    from repro.sweep.padding import stack_specs
    r = build_routing(T.build("mesh", 16))
    batch, shape = stack_specs([make_spec(r, TR.uniform(r.topo))])
    runner = sim._make_batch_runner(shape.n, shape.p, shape.c, shape.d,
                                    TCFG._replace(cycles=40, warmup=10),
                                    "jnp")
    hlo = runner.lower(batch, np.zeros((1, 2), np.float32)
                       ).compile().as_text()
    for scope in ("step_arrivals", "step_credits", "step_inject",
                  "step_route", "step_alloc", "step_move", "step_flight"):
        assert f'/{scope}/' in hlo, scope


def test_merged_call_reports_shapes_work_and_merges(specs):
    """A merged engine call names the shape groups it carries and its
    live vs padded lane work; `sweep.merged_groups` counts each group a
    merge absorbs, once, in the planner or in the engine."""
    from repro.sweep.engine import lane_cost
    enable_tracing()
    clear_trace()
    eng = SweepEngine(cfg=CFG)
    m0 = METRICS.get("sweep.merged_groups")
    eng.run_specs(specs, RATES)
    assert METRICS.get("sweep.merged_groups") - m0 == 1
    (sp,) = [s for s in get_spans() if s.name == "sweep.group"]
    call = eng.bucket_shape(PadShape.of(specs))
    assert sp.args["shape"] == str(call)
    assert sp.args["shapes"] == 2
    # mesh16: 16*5 + 48; folded_hexa_torus36: 36*7 + 208
    assert sp.args["work_live"] == 128 + 460 == sum(
        lane_cost(PadShape.of([s])) for s in specs)
    assert sp.args["work_pad"] == 4 * (40 * 7 + 224) == 4 * lane_cost(call)
    exp = X.Experiment([X.Scenario(name, n, rates=X.ExplicitRates((0.1,)))
                        for name, n in HETERO], cfg=CFG)
    m1 = METRICS.get("sweep.merged_groups")
    pl = X.plan(exp, eng)
    assert len(pl.buckets) == 1
    assert METRICS.get("sweep.merged_groups") - m1 == 1
    X.execute(pl, engine=eng)             # runs the bucket as one call
    assert METRICS.get("sweep.merged_groups") - m1 == 1
