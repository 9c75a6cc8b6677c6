"""The flight-recorder cell (`paper_n64.telemetry_windows`) and its two
readers, run on the CPU: the cell loads by name with the flight recorder
on at W=8 and compares every windowed counter; a 16-chiplet shrink of
its configuration reads `correct` against the plain reference; and
`flight_share` / `flight_roofline` read a hand-made trace through a
hand-made phase map exactly, and read nothing where they cannot tie an
op to a phase."""
import json
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import step_phases as SP  # noqa: E402
from benchmarks.chip.peaks import peaks_for  # noqa: E402

CELL = "paper_n64.telemetry_windows"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FLIGHT_METRICS = ("flight_share", "flight_roofline")
V5E = peaks_for("TPU v5e")


def _json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


# ---------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------

def test_flight_cell_loads_with_the_recorder_on_at_eight_windows():
    from repro.core.simulator import SimConfig
    cell = H.load_cell(CELL)
    assert cell.chips == 1
    assert H.sim_config(cell.config) == SimConfig(
        n_vcs=4, buf_depth=4, cycles=400, warmup=140, seed=0, alloc="auto",
        telemetry=True, routing="static", telemetry_windows=8)
    assert H.counter_keys(cell.config) == H.RAW + H.FLIGHT + H.FLIGHT_W


def test_flight_config_is_paper_n64_with_the_recorder_on():
    base = _json("benchmarks/chip/configs/paper_n64.json")
    flight = _json("benchmarks/chip/configs/paper_n64_flight.json")
    assert flight.pop("telemetry") is True
    assert flight.pop("telemetry_windows") == 8
    extra = flight.pop("guarantees")
    assert extra[:len(base["guarantees"])] == base["guarantees"]
    assert len(extra) == len(base["guarantees"]) + 1
    source = flight.pop("source")
    assert source.startswith(base["source"].split(";")[0])
    assert "flight recorder" in source
    for key in ("telemetry", "guarantees", "source"):
        base.pop(key)
    assert flight == base
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "paper_n64_flight")
    assert entry["reduced"] == ["cycles", "warmup"]


def test_flight_config_names_its_own_source():
    """A configuration is new only where its source or its cuts differ
    from every other's: the flight config names the part of its source
    that defines it."""
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "paper_n64_flight")
    others = [(c["source"], c["reduced"]) for c in BENCH["configs"]
              if c["name"] != entry["name"]]
    assert (entry["source"], entry["reduced"]) not in others
    assert entry["source"].startswith("https://arxiv.org/abs/2504.19878 ")
    assert "flight recorder" in entry["source"]
    assert len(entry["source"]) <= 200


def test_flight_mix_is_table3_on_organic():
    mix = _json("benchmarks/chip/mixes/table3_organic_uniform.json")
    table3 = _json("benchmarks/chip/mixes/table3_uniform.json")
    assert mix == dict(table3, substrates=["organic"])
    assert (len(mix["topologies"]), mix["n_rates"], mix["headroom"],
            mix["check_scenarios"], mix["pattern_seed"]) == \
        (19, 8, 2.0, 6, 2504198780)


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 3 * 2 ** 30 + 5])
def test_flight_cell_shrunk_to_16_chiplets_is_correct(seed, monkeypatch):
    """The cell's own configuration at N=16 and 60 cycles, three of its
    topologies: every counter key it compares, windowed ones included,
    matches the reference element for element."""
    from repro.core import topology as T

    def program_layout(topology, n):
        topo = T.build(topology, n)
        return topo.pos, topo.edges

    monkeypatch.setattr(H, "load_layout", program_layout)
    cell = H.load_cell(CELL)
    cell.config.update(n=16, cycles=60, warmup=20)
    cell.mix.update(topologies=["mesh", "folded_hexa_torus", "kite_small"],
                    check_scenarios=2)
    res = H.run_cell(cell, seed, 0.2, False, time.perf_counter(),
                     jax.devices(), log=lambda *_: None)
    assert res["correct"], (res["checks"], res["compared"])
    assert set(res["compared"]) == set(H.counter_keys(cell.config))
    for k in H.FLIGHT_W:
        elements, differing = res["compared"][k]
        assert elements > 0 and differing == 0, k


#: the per-layer metrics whose readers read the flight cell too
READ_IN_THE_CELL = ("host_ms_per_call", "lane_fill", "step_ms_per_cycle",
                    "netstep_share", "netstep_roofline", "device_idle",
                    "runner_build_s", "runner_load_s", "routing_build_s")


def test_flight_metrics_list_the_cell_alone():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in FLIGHT_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert (m["unit"], m["source"], m["moves"]) == \
            ("%", "device_trace", "router_cycles_per_s")


@pytest.mark.parametrize("name", READ_IN_THE_CELL)
def test_metrics_read_in_the_cell_list_it(name):
    m = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert CELL in m.get("workloads", [CELL])


# ---------------------------------------------------------------------
# the readers, on a hand-made trace and phase map
# ---------------------------------------------------------------------

def _map(phases: dict):
    from repro.obs.phases import RunnerPhases
    return RunnerPhases(shape="PadShape(n=16, p=4, c=48, d=5)", cfg=None,
                        kmax=0, phases=phases, mixed=frozenset())


#: two runners whose instruction names overlap, with phases that differ
MAP_A = _map({"fusion.1": "step_flight", "fusion.2": "step_route",
              "netstep.8": "step_alloc", "copy.4": None,
              "while.9": "outside"})
MAP_B = _map({"fusion.1": "step_route", "fusion.3": "step_flight",
              "fusion.2": "step_move"})
SHAPE = "PadShape(n=16, p=4, c=48, d=5)"
CONFIG = dict(_json("benchmarks/chip/configs/paper_n64_flight.json"),
              n=16, cycles=60)


def _red(flight_a=100.0, flight_b=50.0):
    """Two runner programs (A's ops, then B's) and a small program after
    them; busy 810 ns."""
    modules = [("jit_runner(11)", 0.0, 400.0), ("jit_runner(12)", 500.0,
                                                  400.0),
               ("jit_convert(3)", 950.0, 10.0)]
    ops = [("fusion.1", 10.0, flight_a), ("fusion.2", 200.0, 150.0),
           ("netstep.8", 360.0, 30.0), ("copy.4", 395.0, 5.0),
           ("fusion.1", 510.0, 200.0), ("fusion.3", 720.0, flight_b),
           ("convert.1", 950.0, 10.0)]
    spans = [("sweep.group", 0.0, 0.0, dict(shape=SHAPE, s_pad=4, r_pad=8)),
             ("sweep.group", 0.0, 0.0, dict(shape=SHAPE, s_pad=8, r_pad=8))]
    return H.TRD.Reduced(window=(0.0, 1000.0), ops=ops, modules=modules,
                         busy=[(0.0, 400.0), (500.0, 900.0),
                               (950.0, 960.0)], spans=spans)


def _ctx(red=None, **config):
    return H.MetricContext(red=red or _red(), config=dict(CONFIG, **config),
                           peak=V5E, window_wall_ns=1000.0)


@pytest.fixture
def maps(monkeypatch):
    def use(*maps):
        monkeypatch.setattr(SP, "runner_maps", lambda: list(maps))
    use(MAP_A, MAP_B)
    return use


def test_each_program_is_tied_to_the_one_map_holding_its_ops(maps):
    assert SP.phase_ns(_red(), [MAP_A, MAP_B]) == {
        "step_flight": 150.0, "step_route": 350.0, "step_alloc": 30.0,
        None: 5.0, "outside": 10.0}


def test_flight_share_is_exact(maps):
    assert H.load_metric("flight_share")(_ctx()) == \
        pytest.approx(100.0 * 150.0 / 810.0)


def _roofline_module():
    from importlib import util
    spec = util.spec_from_file_location(
        "flight_roofline", ROOT / "benchmarks/chip/metrics/flight_roofline.py")
    mod = util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flight_bytes_by_hand():
    mod = _roofline_module()
    # c=48, n=16, V=4: busy 49 + stall 49 + occ 49*4 + inj 16 + eject 16
    # + hist 16 = 342 elements; the cycle's window slice holds the same
    # less the histogram, 326; one int32 read and one write of each
    assert mod.flight_bytes(16, 48, 4, 8) == (342 + 326) * 4 * 2
    assert mod.flight_bytes(16, 48, 4, 0) == 342 * 4 * 2


def test_flight_roofline_is_exact(maps):
    mod = _roofline_module()
    total = (4 * 8 + 8 * 8) * 60 * mod.flight_bytes(16, 48, 4, 8)
    least_ns = total / V5E["hbm_bytes_per_s"] * 1e9
    assert H.load_metric("flight_roofline")(_ctx()) == \
        pytest.approx(100.0 * least_ns / 150.0)


@pytest.mark.parametrize("slower", [1.0, 1.25, 7.0, 1e4])
def test_flight_roofline_stays_at_or_under_100(maps, slower):
    """At any flight op time at or above the bytes' least time at the
    chip's peak, the share is at most 100%."""
    mod = _roofline_module()
    total = (4 * 8 + 8 * 8) * 60 * mod.flight_bytes(16, 48, 4, 8)
    least_ns = total / V5E["hbm_bytes_per_s"] * 1e9
    red = _red(flight_a=least_ns * slower - 50.0, flight_b=50.0)
    share = H.load_metric("flight_roofline")(_ctx(red))
    assert share == pytest.approx(100.0 / slower)
    assert share <= 100.0 * (1 + 1e-12)


@pytest.mark.parametrize("name", FLIGHT_METRICS)
def test_flight_readers_read_nothing_with_the_recorder_off(maps, name):
    assert H.load_metric(name)(_ctx(telemetry=False,
                                    telemetry_windows=0)) is None


@pytest.mark.parametrize("name", FLIGHT_METRICS)
@pytest.mark.parametrize("cell", ["paper_n64.table3_uniform",
                                  "paper_n256.fht_patterns",
                                  "paper_n256.main4_uniform"])
def test_flight_readers_read_nothing_in_the_other_cells(maps, name, cell):
    ctx = H.MetricContext(red=_red(), config=H.load_cell(cell).config,
                          peak=V5E, window_wall_ns=1000.0)
    assert H.load_metric(name)(ctx) is None


@pytest.mark.parametrize("name", FLIGHT_METRICS)
@pytest.mark.parametrize("held", [(MAP_B,), (), (MAP_A, _map(
    dict(MAP_A.phases, **{"fusion.3": "step_flight"})), MAP_B)],
    ids=["no_map_holds_a_program", "no_map", "two_maps_hold_a_program"])
def test_flight_readers_read_nothing_without_one_map(maps, name, held):
    maps(*held)
    assert H.load_metric(name)(_ctx()) is None


@pytest.mark.parametrize("name", FLIGHT_METRICS)
def test_flight_readers_read_nothing_without_a_runner_program(maps, name):
    red = _red()
    red.modules = [m for m in red.modules if "runner" not in m[0]]
    assert H.load_metric(name)(_ctx(red)) is None


@pytest.mark.parametrize("name", FLIGHT_METRICS)
def test_flight_readers_read_nothing_from_a_program_with_no_map(
        monkeypatch, name):
    """A program older than `repro.obs.phases` (the parent's) keeps no
    map: the readers return None and do not raise."""
    monkeypatch.setitem(sys.modules, "repro.obs.phases", None)
    assert SP.runner_maps() == []
    assert H.load_metric(name)(_ctx()) is None
