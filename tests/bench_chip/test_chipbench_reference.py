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
    sound = H.compare([refs], refs)
    assert sound["counter_mismatches"]["value"] == 0
    wrong = H.compare([control], refs)
    assert wrong["counter_mismatches"]["value"] > 0
