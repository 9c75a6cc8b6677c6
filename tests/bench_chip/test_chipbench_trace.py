"""The reduction from a profiler trace and the program's spans to the
per-layer metrics, on a small trace in the layout of a TPU v5e profile
(`data/window_trace.pbtxt`): the window cut, the busy union, ops named
as the trace viewer names them, the scan's `while` left out of the op
totals, kernel ops found by name, idle gaps named by the open span, and
every reader's number worked out by hand."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import trace_reduce as TRD  # noqa: E402

US = 1000.0
PERF0 = 5e9          # perf_counter ns as the window opened (trace: 1000 us)


def _span(name, start_us, end_us, **args):
    return SimpleNamespace(name=name, ts=PERF0 + (start_us - 1000) * US,
                           dur=(end_us - start_us) * US, args=args)


SPANS = [
    _span("experiment.execute", 1000, 10900),
    _span("experiment.plan", 1000, 1400),
    _span("sweep.group", 1400, 5700, shape="PadShape(n=16, p=4, c=64, d=12)",
          s_live=1, s_pad=4, r_live=8, r_pad=8),
    _span("sim.dispatch", 1400, 1500),
    _span("sim.wait", 1500, 5600),
    _span("sim.stack", 5700, 5900),
    _span("sweep.group", 5700, 9200, shape="PadShape(n=16, p=6, c=96, d=12)",
          s_live=2, s_pad=4, r_live=8, r_pad=8),
    _span("sim.dispatch", 5900, 6000),
    _span("sim.wait", 6000, 9100),
]


@pytest.fixture(scope="module")
def red():
    text = (Path(__file__).parent / "data" / "window_trace.pbtxt").read_text()
    planes = TRD.planes_of(ProfileData.from_text_proto(text))
    return TRD.reduce(planes, SPANS, PERF0)


def test_window_and_busy_union(red):
    assert red.window == (1000 * US, 11000 * US)
    # the op before the window is dropped; overlapping ops merge
    assert red.busy == [(1500 * US, 4500 * US), (6000 * US, 8500 * US),
                        (9500 * US, 9700 * US)]
    assert red.busy_ns == 5700 * US
    assert red.op_time_ns(lambda n: "netstep" in n) == 1000 * US
    assert [m[0] for m in red.modules].count("jit_runner(1)") == 2
    assert "while.10" not in {name for name, _, _ in red.ops}


@pytest.mark.parametrize("event,name", [
    ("%fusion.197 = s32[229376]{0:T(1024)S(1)} fusion(s32[4,8] %b), "
     "kind=kCustom", "fusion.197"),
    ("%netstep_pallas.8 = (s32[4]) custom-call(s32[4] %c)",
     "netstep_pallas.8"),
    ("jit_runner(8751335778159574291)", "jit_runner(8751335778159574291)"),
    ("chipbench.window", "chipbench.window")])
def test_op_names_are_the_instructions_own(event, name):
    assert TRD.op_name(event) == name


def test_leaves_drop_only_ops_that_hold_others():
    ops = [("while", 0.0, 10.0), ("a", 0.0, 4.0), ("b", 4.0, 6.0),
           ("c", 12.0, 3.0), ("d", 14.0, 3.0)]
    assert [e[0] for e in TRD.leaves(ops)] == ["a", "b", "c", "d"]


def test_spans_land_on_the_trace_clock(red):
    plan = red.spans_named("experiment.plan")[0]
    assert plan[1] == 1000 * US and plan[2] == 400 * US


def test_breakdown(red):
    assert TRD.top_ops(red) == [["fusion.1", 0.0035], ["fusion.2", 0.0015],
                                ["netstep_pallas.8", 0.001], ["copy.3", 0.0002]]
    assert TRD.idle_gaps(red) == [["sim.wait", 0.0015],
                                  ["experiment.execute", 0.0013],
                                  ["sim.wait", 0.001],
                                  ["experiment.plan", 0.0005]]


@pytest.mark.parametrize("name,expected", [
    ("host_ms_per_call", (10000 - 7200) * US / 2 / 1e6),
    ("lane_fill", 100 * (16 * 8 * 3) / (16 * 8 * 8)),
    ("step_ms_per_cycle", 7000 * US / 20 / 1e6),
    ("netstep_share", 100 * 1000 / 5700),
    ("netstep_roofline",
     100 * (320 * (16 * 5 * 4 * 6 + 16 * 5 * 8) + 320 * (16 * 7 * 4 * 6 +
                                                       16 * 7 * 8))
     / 819e9 / 1e-3),
    ("device_idle", 100 * (1 - 5700 / 10000)),
])
def test_metric_readers(red, name, expected):
    ctx = H.MetricContext(red=red, config=dict(n=16, cycles=10, n_vcs=4),
                          peak=dict(hbm_bytes_per_s=819e9),
                          window_wall_ns=10000 * US)
    assert H.load_metric(name)(ctx) == pytest.approx(expected, rel=1e-12)


def test_readers_find_nothing_in_an_empty_window():
    red = TRD.Reduced(window=(0.0, 1e9), ops=[], modules=[], busy=[],
                      spans=[])
    ctx = H.MetricContext(red=red, config=dict(n=16, cycles=10, n_vcs=4),
                          peak=dict(hbm_bytes_per_s=819e9),
                          window_wall_ns=1e9)
    for name in ("host_ms_per_call", "lane_fill", "step_ms_per_cycle",
                 "netstep_share", "netstep_roofline", "device_idle"):
        assert H.load_metric(name)(ctx) is None


def test_merge_and_missing_window():
    assert TRD.merge([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == [(1, 4.5), (5, 6)]
    with pytest.raises(ValueError, match="chipbench.window"):
        TRD.reduce({("/host:CPU", "python"): [("other", 0.0, 1.0)]})
