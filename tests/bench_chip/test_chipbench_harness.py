"""The on-chip benchmark's harness, run on the CPU: cells, configurations,
mixes, layouts and metric readers found by name; the copied rate grid,
traffic patterns and layouts; the padding share of the flagship plan; the peaks
table; the refusal to run without a TPU; and one whole run at a tiny
size through `run_cell`."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import patterns as PT  # noqa: E402
from benchmarks.chip.peaks import peaks_for  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
LAYOUTS = sorted(p.name for p in (ROOT / "benchmarks/chip/layouts")
                 .glob("*.json"))


def program_layout(topology: str, n: int) -> tuple:
    """The program's own layout, for sizes the benchmark pins none of."""
    from repro.core import topology as T
    topo = T.build(topology, n)
    return topo.pos, topo.edges


@pytest.fixture
def tiny_layouts(monkeypatch):
    monkeypatch.setattr(H, "load_layout", program_layout)


def tiny_cell(**mix) -> H.Cell:
    """A 16-chiplet, 60-cycle cell of three topologies and two patterns."""
    cfg = json.loads((ROOT / "benchmarks/chip/configs/paper_n64.json")
                     .read_text())
    cfg.update(n=16, cycles=60, warmup=20)
    m = dict(topologies=["mesh", "folded_hexa_torus", "kite_small"],
             substrates=["glass"], patterns=["uniform", "permutation"],
             n_rates=8, headroom=2.0, pattern_seed=7, check_scenarios=3)
    m.update(mix)
    return H.Cell("tiny", cfg, m, BENCH["end_to_end"], BENCH["per_layer"])


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(name):
    cell = H.load_cell(name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.mix == json.loads(
        (ROOT / f"benchmarks/chip/mixes/{w['traffic']}.json").read_text())
    assert cell.config["n"] in (64, 256)
    assert {m["name"] for m in cell.end_to_end} == {"router_cycles_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]}
    for topology in cell.mix["topologies"]:
        pos, _ = H.load_layout(topology, cell.config["n"])
        assert len(pos) == cell.config["n"]


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(KeyError, match="no workload"):
        H.load_cell("paper_n64.nope")
    with pytest.raises(KeyError, match="no mixes/nope.json"):
        H._load_json("mixes", "nope")
    with pytest.raises(KeyError, match="no layouts/mesh.n7.json"):
        H.load_layout("mesh", 7)
    with pytest.raises(KeyError, match="no per-layer metric"):
        H.load_metric("nope")
    bench = dict(BENCH, workloads=[dict(BENCH["workloads"][0],
                                        config="nope")])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(KeyError, match="unknown config"):
        H.load_cell(bench["workloads"][0]["name"], root=tmp_path)


def test_benchmark_files_are_where_it_says():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    for m in BENCH["per_layer"]:
        assert callable(H.load_metric(m["name"]))
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "paper_n256.fht_patterns", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("analytic", [1e-4, 0.013, 0.2, 0.4195, 0.5, 0.9])
def test_rate_grid_is_the_programs(analytic):
    from repro.core.simulator import saturation_rate_grid
    np.testing.assert_array_equal(PT.rate_grid(analytic, 8, 2.0),
                                  saturation_rate_grid(analytic, 8))


@pytest.mark.parametrize("pattern", sorted(PT.PATTERNS))
@pytest.mark.parametrize("topology,n", [("folded_hexa_torus", 64),
                                        ("kite_medium", 64),
                                        ("hexamesh", 256)])
def test_patterns_are_the_programs(pattern, topology, n):
    from repro.core import topology as T
    from repro.core import traffic as TR
    topo = T.build(topology, n)
    seed = 2 ** 31 + 5
    kw = dict(seed=seed) if pattern == "permutation" else {}
    np.testing.assert_array_equal(PT.PATTERNS[pattern](n, topo.pos, seed),
                                  TR.PATTERNS[pattern](topo, **kw))


@pytest.mark.parametrize("fname", LAYOUTS)
def test_pinned_layouts_are_the_programs(fname):
    topology, n = fname[:-len(".json")].rsplit(".n", 1)
    pos, edges = H.load_layout(topology, int(n))
    want_pos, want_edges = program_layout(topology, int(n))
    np.testing.assert_array_equal(pos, want_pos)
    np.testing.assert_array_equal(edges, want_edges)


def test_table3_lane_fill_is_38_of_40():
    """The flagship plan: 38 live spec lanes of 40 run in 7 calls, all at
    N=64 with 8 of 8 rates, as the engine's `sweep.group` spans would
    report."""
    import repro.experiments as X
    from repro.sweep.engine import _round_up
    cell = H.load_cell("paper_n64.table3_uniform")
    exp = H.experiment(cell, H.plan_cell(cell, seed=11))
    eng = X.engine_for(exp.cfg)
    buckets = X.plan(exp, eng).buckets
    spans = [("sweep.group", 0, 0, dict(
        shape=str(b.key.shape), s_live=len(b.items),
        s_pad=_round_up(len(b.items), eng.s_round), r_live=8, r_pad=8))
        for b in buckets]
    ctx = H.MetricContext(red=H.TRD.Reduced((0, 1), [], [], [], spans),
                          config=cell.config, peak={},
                          window_wall_ns=1.0)
    assert len(buckets) == 7
    assert sum(len(b.items) for b in buckets) == 38
    assert sum(s[3]["s_pad"] for s in spans) == 40
    assert H.load_metric("lane_fill")(ctx) == pytest.approx(95.0)
    assert H.padded_scenarios(exp)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_sim_config_is_unchanged(config):
    """Every committed configuration gets the `SimConfig` it always had
    (so the same runners and compile-cache keys)."""
    from repro.core.simulator import SimConfig
    cfg = json.loads((ROOT / f"benchmarks/chip/configs/{config}.json")
                     .read_text())
    assert H.sim_config(cfg) == SimConfig(
        n_vcs=cfg["n_vcs"], buf_depth=cfg["buf_depth"],
        cycles=cfg["cycles"], warmup=cfg["warmup"], seed=cfg["sim_seed"],
        alloc=cfg["alloc"], telemetry=cfg["telemetry"],
        routing=cfg["routing"])
    assert H.sim_config(cfg).telemetry_windows == 0
    assert H.counter_keys(cfg) == H.RAW


def test_sim_config_takes_every_field():
    cfg = dict(tiny_cell().config, telemetry=True, telemetry_windows=4,
               routing="adaptive", seed=99)
    sc = H.sim_config(cfg)
    assert (sc.telemetry, sc.telemetry_windows, sc.routing, sc.seed) == \
        (True, 4, "adaptive", cfg["sim_seed"])
    assert H.counter_keys(cfg) == H.RAW + H.FLIGHT + H.FLIGHT_W
    assert H.counter_keys(dict(cfg, telemetry_windows=0)) == \
        H.RAW + H.FLIGHT


def test_peaks_reject_unknown_devices():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_check_sample_takes_a_padded_scenario():
    cell = tiny_cell()
    planned = list(range(6))
    for seed in (1, 2, 3, 2 ** 31 + 7):
        s = H.check_sample(cell, planned, {4}, seed)
        assert s[0] == 4 and len(s) == 3 and len(set(s)) == 3


def test_tiny_run_end_to_end_on_cpu(tiny_layouts):
    """Set-up, window and check of a whole run; the counters of every
    pass match the plain reference."""
    res = H.run_cell(tiny_cell(), 2 ** 31 + 99, 0.5, False,
                     time.perf_counter(), jax.devices(), log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert res["checks"]["counter_mismatches"] == {"value": 0, "limit": 0}
    assert res["failed"] == 0 and res["attempted"] % (6 * 8) == 0
    assert set(res["metrics"]) == {"router_cycles_per_s", "setup_s"}
    assert res["metrics"]["router_cycles_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_tiny_traced_run_end_to_end_on_cpu(tiny_layouts, monkeypatch):
    """The `--trace 1` path: one pass under the profiler and the span
    tracer, reduced to the per-layer metrics.  The CPU trace has no TPU
    plane, so only the span metrics are read; the peaks are the v5e's."""
    monkeypatch.setattr(H, "peaks_for", lambda kind: peaks_for("TPU v5e"))
    res = H.run_cell(tiny_cell(), 2 ** 31 + 98, 0.5, True,
                     time.perf_counter(), jax.devices(), log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"host_ms_per_call", "lane_fill"}
    assert res["metrics"]["lane_fill"]["value"] == pytest.approx(75.0)
    assert res["device"]["window_s"] > 0
    assert res["breakdown"]["idle_gaps"]
    assert list(res)[-1] == "checks"


def test_tiny_flight_recorder_run_on_cpu(tiny_layouts):
    """A configuration with the flight recorder on and 4 windows runs
    through `run_cell` as it stands; every flight-recorder counter is
    compared with the reference, element by element."""
    cell = tiny_cell(patterns=["uniform"])
    cell.config.update(telemetry=True, telemetry_windows=4)
    res = H.run_cell(cell, 2 ** 31 + 97, 0.2, False, time.perf_counter(),
                     jax.devices(), log=lambda *_: None)
    assert res["correct"], (res["checks"], res["compared"])
    assert set(res["compared"]) == set(H.RAW + H.FLIGHT + H.FLIGHT_W)
    for k, (elements, differing) in res["compared"].items():
        assert elements > 0 and differing == 0, k
    assert list(res)[-1] == "checks"


def test_channels_are_the_programs_link_rows(tiny_layouts):
    """The channel ends a pass carries are those of the program's own
    per-link rows, channel by channel."""
    import repro.experiments as X
    cell = tiny_cell(patterns=["uniform"])
    cell.config.update(telemetry=True)
    exp = H.experiment(cell, H.plan_cell(cell, 5))
    frame = X.run(exp, on_error="skip")
    for i, got in enumerate(H.run_pass(exp)):
        rows = frame.link_rows(i, rate_index=0)
        np.testing.assert_array_equal(
            got[H.CHANNELS], [(r["src"], r["dst"]) for r in rows])
