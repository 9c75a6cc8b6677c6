"""The benchmark's plain reference against the program, on the CPU, and
its control.

The reference rebuilds ports, hop latencies, the up*/down* routing, the
analytic bound and the cycle-by-cycle simulation from a layout alone;
here it must agree with the program exactly.  The control is the
reference with the allocator's rotating priority frozen (one guarantee
of the configuration broken): put in the program's place, the harness's
comparison must find it wrong."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import patterns as PT  # noqa: E402
from benchmarks.chip import reference as R  # noqa: E402


@pytest.mark.parametrize("topology,n,substrate", [
    ("mesh", 16, "organic"), ("folded_hexa_torus", 64, "glass"),
    ("flattened_butterfly", 64, "organic"), ("kite_large", 64, "glass"),
    ("hypercube", 64, "organic"), ("honeycomb_torus", 64, "glass")])
def test_network_and_bound_are_the_programs(topology, n, substrate):
    from repro.core import linkmodel as lm
    from repro.core import traffic as TR
    from repro.core.routing import cached_routing
    topo, rt = cached_routing(topology, n, substrate, 74.0)
    net = R.build_network(topo.pos, topo.edges, substrate, 74.0)
    np.testing.assert_array_equal(net.table, rt.table)
    np.testing.assert_array_equal(net.out_ch, rt.out_ch)
    np.testing.assert_array_equal(net.in_ch, rt.in_ch)
    np.testing.assert_array_equal(
        net.depth, np.maximum(lm.hop_latency_cycles(rt.ch_len_mm,
                                                    substrate), 1))
    for tm in (TR.uniform(topo), TR.tornado(topo)):
        assert R.analytic_bound(net, tm) == rt.saturation_rate(tm)


@pytest.mark.parametrize("topology,pattern", [
    ("mesh", "uniform"), ("folded_hexa_torus", "tornado"),
    ("flattened_butterfly", "permutation"), ("hexamesh", "neighbor")])
def test_simulation_is_the_programs(topology, pattern):
    from repro.core import topology as T
    from repro.core.routing import cached_routing
    from repro.core.simulator import SimConfig, make_spec, run_batch
    topo = T.build(topology, 16, substrate="glass")
    _, rt = cached_routing(topology, 16, "glass", 74.0)
    tm = PT.PATTERNS[pattern](16, topo.pos, 9)
    net = R.build_network(topo.pos, topo.edges, "glass", 74.0)
    rates = PT.rate_grid(R.analytic_bound(net, tm), 8, 2.0)
    got = run_batch([make_spec(rt, tm)], rates[None].astype(np.float32),
                    SimConfig(cycles=120, warmup=40, alloc="jnp"))[0]
    ref = R.simulate(net, tm, rates, cycles=120, warmup=40, n_vcs=4,
                     buf_depth=4, seed=0)
    assert ref["delivered"].sum() > 0
    for k in H.RAW:
        np.testing.assert_array_equal(np.asarray(got[k]), ref[k])


@pytest.mark.parametrize("seed", [3, 17, 2 ** 31 + 11])
def test_control_is_found_wrong(seed, monkeypatch):
    """Frozen rotating priority, read by the harness's own comparison."""
    from repro.core import topology as T
    monkeypatch.setattr(H, "load_layout", lambda t, n: (
        T.build(t, n).pos, T.build(t, n).edges))
    cfg = dict(n=16, chiplet_area_mm2=74.0, roles="homogeneous", n_vcs=4,
               buf_depth=4, sim_seed=0, cycles=120, warmup=40)
    mix = dict(topologies=["mesh", "folded_hexa_torus", "kite_small"],
               substrates=["organic"], patterns=["uniform", "tornado"],
               n_rates=8, headroom=2.0, pattern_seed=7, check_scenarios=6)
    cell = H.Cell("control", cfg, mix, [], [])
    planned = H.plan_cell(cell, seed)
    sample = H.check_sample(cell, planned, set(), seed)
    refs = {i: H.reference_counters(cell, planned[i]) for i in sample}
    control = {i: H.reference_counters(cell, planned[i], rotate=False)
               for i in sample}
    sound, _ = H.compare([refs], refs)
    assert sound["counter_mismatches"]["value"] == 0
    wrong, _ = H.compare([control], refs)
    assert wrong["counter_mismatches"]["value"] > 0


def _tiny_net(topology: str, pattern: str):
    """A 16-chiplet glass layout of the program's, its traffic and rates."""
    from repro.core import topology as T
    topo = T.build(topology, 16, substrate="glass")
    tm = PT.PATTERNS[pattern](16, topo.pos, 9)
    net = R.build_network(topo.pos, topo.edges, "glass", 74.0)
    return net, tm, PT.rate_grid(R.analytic_bound(net, tm), 8, 2.0)


def test_simulate_without_telemetry_returns_the_four_counters():
    net, tm, rates = _tiny_net("kite_small", "uniform")
    ref = R.simulate(net, tm, rates, cycles=60, warmup=20, n_vcs=4,
                     buf_depth=4, seed=0)
    assert set(ref) == set(H.RAW)
    with pytest.raises(ValueError, match="telemetry=True"):
        R.simulate(net, tm, rates, cycles=60, warmup=20, n_vcs=4,
                   buf_depth=4, seed=0, windows=4)


@pytest.mark.parametrize("topology,pattern,windows", [
    ("mesh", "uniform", 0), ("folded_hexa_torus", "tornado", 7),
    ("kite_small", "permutation", 4), ("hexamesh", "neighbor", 80)])
def test_flight_recorder_is_the_programs(topology, pattern, windows):
    """Every flight-recorder counter, whole and in windows (7 does not
    divide the 80 measured cycles; 80 is one window per cycle), equal
    to the program's channel by channel."""
    from repro.core.routing import cached_routing
    from repro.core.simulator import SimConfig, make_spec, run_batch
    net, tm, rates = _tiny_net(topology, pattern)
    _, rt = cached_routing(topology, 16, "glass", 74.0)
    got = run_batch([make_spec(rt, tm)], rates[None].astype(np.float32),
                    SimConfig(cycles=120, warmup=40, alloc="jnp",
                              telemetry=True,
                              telemetry_windows=windows))[0]
    ref = R.simulate(net, tm, rates, cycles=120, warmup=40, n_vcs=4,
                     buf_depth=4, seed=0, telemetry=True, windows=windows)
    want = H.RAW + H.FLIGHT + (H.FLIGHT_W if windows else ())
    assert set(ref) == set(want)
    assert ref["link_stall"].sum() > 0 and ref["lat_hist"][:, 1:].sum() > 0
    got[H.CHANNELS] = np.stack([rt.ch_src, rt.ch_dst], 1)
    ref[H.CHANNELS] = np.stack([net.ch_src, net.ch_dst], 1)
    checks, per_key = H.compare([{0: got}], {0: ref})
    assert checks["counter_mismatches"]["value"] == 0, per_key
    assert set(per_key) == set(want)


def test_channels_are_matched_by_their_chiplets():
    """The program's channels in another order compare equal; a channel
    with other chiplets, or a second channel of one pair, does not."""
    net, tm, rates = _tiny_net("folded_hexa_torus", "uniform")
    ref = R.simulate(net, tm, rates, cycles=60, warmup=20, n_vcs=4,
                     buf_depth=4, seed=0, telemetry=True, windows=4)
    ends = np.stack([net.ch_src, net.ch_dst], 1)
    ref[H.CHANNELS] = ends
    perm = np.random.default_rng(5).permutation(net.c)
    got = {k: (np.take(v, perm, axis=H.CHANNEL_AXIS[k])
               if k in H.CHANNEL_AXIS else v) for k, v in ref.items()}
    got[H.CHANNELS] = ends[perm]
    checks, _ = H.compare([{0: got}], {0: ref})
    assert checks["counter_mismatches"]["value"] == 0
    pairs = set(map(tuple, ends.tolist()))
    src = int(ends[perm][0, 0])
    elsewhere = next(d for d in range(net.n)
                     if d != src and (src, d) not in pairs)
    moved = ends[perm].copy()
    moved[0, 1] = elsewhere
    checks, per_key = H.compare([{0: dict(got, **{H.CHANNELS: moved})}],
                                {0: ref})
    assert per_key["link_busy"] == [ref["link_busy"].size] * 2
    assert per_key["delivered"][1] == 0
    twice = ends[perm].copy()
    twice[1] = twice[0]
    with pytest.raises(ValueError, match="two channels from chiplet"):
        H.compare([{0: dict(got, **{H.CHANNELS: twice})}], {0: ref})


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 13])
def test_control_mismatches_flight_counters(seed, monkeypatch):
    """The control with the flight recorder on differs from the
    reference in the recorder's own counters, not only the raw four."""
    from repro.core import topology as T
    monkeypatch.setattr(H, "load_layout", lambda t, n: (
        T.build(t, n).pos, T.build(t, n).edges))
    cfg = dict(n=16, chiplet_area_mm2=74.0, roles="homogeneous", n_vcs=4,
               buf_depth=4, sim_seed=0, cycles=60, warmup=20,
               telemetry=True, telemetry_windows=4)
    mix = dict(topologies=["mesh", "folded_hexa_torus", "kite_small"],
               substrates=["organic"], patterns=["uniform"], n_rates=8,
               headroom=2.0, pattern_seed=7, check_scenarios=3)
    cell = H.Cell("control", cfg, mix, [], [])
    planned = H.plan_cell(cell, seed)
    sample = H.check_sample(cell, planned, set(), seed)
    refs = {i: H.reference_counters(cell, planned[i]) for i in sample}
    control = {i: H.reference_counters(cell, planned[i], rotate=False)
               for i in sample}
    _, per_key = H.compare([control], refs)
    for k in H.FLIGHT + H.FLIGHT_W:
        if k not in ("inj_node", "inj_node_w", "window_cycles"):
            assert per_key[k][1] > 0, (k, per_key[k])
