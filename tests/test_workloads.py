"""Workload-engine tests (DESIGN.md §9).

The acceptance property: a phase schedule with a single uniform phase
reproduces the static-traffic simulator counters BITWISE — the workload
path is a strict generalization of the static path.  Plus: padding
invariance of the phase pointer (spec-, rate- and phase-axis padding),
ON/OFF burst semantics, the collective/trace/synthetic generators, and
the engine's workloads x topologies batching."""
import numpy as np
import pytest

import repro.workloads as W
from repro.core import topology as T, traffic as TR
from repro.core.collectives import (collective_flow, mesh_axis_groups,
                                    mesh_coords)
from repro.core.routing import build_routing
from repro.core.simulator import (SimConfig, make_sched_spec, make_spec,
                                  phase_measured_cycles, run_batch)
from repro.sweep.engine import SweepCase, SweepEngine
from repro.workloads.collective import step_collective_ops

CFG = SimConfig(cycles=300, warmup=100)
RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")


@pytest.fixture(scope="module")
def fht16():
    return build_routing(T.build("folded_hexa_torus", 16))


@pytest.fixture(scope="module")
def mesh16():
    return build_routing(T.build("mesh", 16))


# ---------------------------------------------------------------------
# acceptance: static equivalence + padding invariance
# ---------------------------------------------------------------------

def test_single_uniform_phase_bitwise_equals_static(fht16):
    """THE acceptance criterion: one uniform unit-intensity phase ==
    the static simulator, counter for counter, bit for bit."""
    u = TR.uniform(fht16.topo)
    spec = make_spec(fht16, u)
    rates = np.array([0.05, 0.2, 0.6], np.float32)[None, :]
    static = run_batch([spec], rates, CFG)[0]
    sched = W.static_schedule(u, CFG.cycles).compile()
    wl = run_batch([spec], rates, CFG, schedules=[sched])[0]
    for k in RAW:
        np.testing.assert_array_equal(static[k], wl[k], err_msg=k)
    np.testing.assert_array_equal(static["throughput"], wl["throughput"])
    np.testing.assert_array_equal(static["latency"], wl["latency"])
    # the single phase carries all delivery
    np.testing.assert_array_equal(wl["delivered_ph"][:, 0],
                                  wl["delivered"])


def test_workload_batch_padding_invariance(fht16, mesh16):
    """Heterogeneous (spec, schedule) pairs padded into one program are
    bitwise-equal to each pair run alone — phase pointer, per-phase
    counters and all."""
    pairs = []
    for r in (mesh16, fht16):
        u, t = TR.uniform(r.topo), TR.tornado(r.topo)
        sched = W.Schedule([W.Phase(u, 1.0, 120),
                            W.Phase(t, 0.8, 180, 10, 30)]).compile()
        pairs.append((make_spec(r, u), sched))
    # a third pair with a different phase count forces K padding
    r = build_routing(T.build("honeycomb_mesh", 16))
    u = TR.uniform(r.topo)
    pairs.append((make_spec(r, u),
                  W.static_schedule(u, CFG.cycles).compile()))
    specs = [p[0] for p in pairs]
    scheds = [p[1] for p in pairs]
    rates = np.array([0.1, 0.4], np.float32)
    batched = run_batch(specs, rates, CFG, schedules=scheds)
    for (spec, sched), b in zip(pairs, batched):
        single = run_batch([spec], rates[None, :], CFG,
                           schedules=[sched])[0]
        for k in RAW + ("delivered_ph", "offered_ph", "accepted_ph",
                        "lat_sum_ph"):
            np.testing.assert_array_equal(single[k], b[k], err_msg=k)


def test_phase_axis_padding_is_inert(fht16):
    u, t = TR.uniform(fht16.topo), TR.tornado(fht16.topo)
    spec = make_spec(fht16, u)
    sched = W.Schedule([W.Phase(u, 1.0, 150),
                        W.Phase(t, 0.5, 150)]).compile()
    rates = np.array([0.3], np.float32)[None, :]
    a = run_batch([spec], rates, CFG, schedules=[sched])[0]
    b = run_batch([spec], rates, CFG, schedules=[sched], k_pad=7)[0]
    for k in RAW + ("delivered_ph", "lat_sum_ph"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------
# phase semantics
# ---------------------------------------------------------------------

def test_phase_counters_partition_totals(fht16):
    u, t = TR.uniform(fht16.topo), TR.tornado(fht16.topo)
    spec = make_spec(fht16, u)
    sched = W.Schedule([W.Phase(u, 1.0, 100), W.Phase(t, 0.7, 100),
                        W.Phase(u, 0.4, 100)]).compile()
    res = run_batch([spec], np.array([[0.2, 0.8]], np.float32), CFG,
                    schedules=[sched])[0]
    for ph_key, tot_key in (("delivered_ph", "delivered"),
                            ("offered_ph", "offered_n"),
                            ("accepted_ph", "accepted_n"),
                            ("lat_sum_ph", "lat_sum")):
        np.testing.assert_array_equal(res[ph_key].sum(axis=1),
                                      res[tot_key], err_msg=ph_key)
    assert phase_measured_cycles(sched, CFG).sum() == \
        CFG.cycles - CFG.warmup


def test_zero_intensity_phase_offers_nothing(fht16):
    u = TR.uniform(fht16.topo)
    spec = make_spec(fht16, u)
    sched = W.Schedule([W.Phase(u, 1.0, 150),
                        W.Phase(u, 0.0, 150)]).compile()
    res = run_batch([spec], np.array([[0.5]], np.float32), CFG,
                    schedules=[sched])[0]
    assert res["offered_ph"][0, 0] > 0
    assert res["offered_ph"][0, 1] == 0     # exact: gain 0 gates injection
    assert res["accepted_ph"][0, 1] == 0


def test_burst_modulation_preserves_mean_and_bursts(fht16):
    """ON/OFF modulation: same mean offered load as unmodulated, but
    injection happens only inside ON windows."""
    u = TR.uniform(fht16.topo)
    spec = make_spec(fht16, u)
    cfg = SimConfig(cycles=1200, warmup=200)
    rate = np.array([[0.2]], np.float32)
    plain = run_batch([spec], rate, cfg, schedules=[
        W.static_schedule(u, cfg.cycles).compile()])[0]
    burst = run_batch([spec], rate, cfg, schedules=[W.Schedule(
        [W.Phase(u, 1.0, cfg.cycles, burst_on=20, burst_off=20)]
    ).compile()])[0]
    assert burst["offered_n"][0] == pytest.approx(plain["offered_n"][0],
                                                  rel=0.15)
    # extreme bursts (gain > 1 inside ON) must cap at the rate ceiling:
    # offered can never exceed one flit per node per ON cycle
    assert burst["offered_n"][0] <= spec.n * (cfg.cycles - cfg.warmup)


def test_schedule_replays_cyclically(fht16):
    """A schedule shorter than the simulation wraps: phase 0 of the
    second replay sees the same traffic as the first."""
    u = TR.uniform(fht16.topo)
    spec = make_spec(fht16, u)
    short = W.Schedule([W.Phase(u, 1.0, 90), W.Phase(u, 0.0, 30)])
    res = run_batch([spec], np.array([[0.3]], np.float32), CFG,
                    schedules=[short.compile()])[0]
    cyc = phase_measured_cycles(short.compile(), CFG)
    assert cyc.sum() == CFG.cycles - CFG.warmup
    assert cyc[0] > 90        # phase 0 measured across >1 replay
    assert res["offered_ph"][0, 0] > 0


def test_schedule_fit_is_exact():
    topo = T.build("mesh", 16)
    s = W.phase_alternating(topo, phase_cycles=333, repeats=1)
    for target in (200, 1000, 777):
        f = s.fit(target)
        assert f.total_cycles == target
        assert len(f.phases) == len(s.phases)
    # many 1-cycle phases: the negative rounding residual exceeds any
    # single phase's slack and must be spread across phases
    u = TR.uniform(topo)
    tiny = W.Schedule([W.Phase(u, 1.0, 1) for _ in range(10)])
    f = tiny.fit(15)
    assert f.total_cycles == 15
    assert min(p.duration for p in f.phases) >= 1
    with pytest.raises(ValueError):
        tiny.fit(9)


# ---------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------

def test_mesh_groups_partition_and_are_contiguous():
    topo = T.build("mesh", 16)
    shape = {"data": 4, "model": 4}
    coords = mesh_coords(topo, shape)
    assert sorted(coords) == ["data", "model"]
    for axis in shape:
        groups = mesh_axis_groups(topo, shape, axis)
        flat = sorted(i for g in groups for i in g)
        assert flat == list(range(16))          # partition
        assert all(len(g) == 4 for g in groups)
    # model groups are physically contiguous runs along x
    for g in mesh_axis_groups(topo, shape, "model"):
        ys = topo.pos[g, 1]
        assert np.ptp(ys) == 0
        assert (np.diff(topo.pos[g, 0]) > 0).all()


def test_collective_flow_conserves_payload():
    groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
    for kind, factor in (("all_reduce", 2 * 3 / 4), ("all_gather", 3 / 4),
                         ("reduce_scatter", 3 / 4),
                         ("collective_permute", 1.0), ("all_to_all", 3 / 4)):
        m = collective_flow(8, kind, groups, 100.0)
        assert m.shape == (8, 8) and (np.diag(m) == 0).all()
        np.testing.assert_allclose(m.sum(axis=1), 100.0 * factor)
    with pytest.raises(KeyError):
        collective_flow(8, "broadcastish", groups, 1.0)


#: phase -> (kind, mesh axis) of the ops `step_collective_ops` issues
PHASE_OPS = {
    "fsdp_gather": ("all_gather", "data"),
    "fwd_tp": ("all_reduce", "model"),
    "moe_a2a": ("all_to_all", "model"),
    "bwd_tp": ("all_reduce", "model"),
    "grad_reduce": ("reduce_scatter", "data"),
}

#: pinned op lists, per architecture and "data x model" mesh: each
#: op's (phase, bytes per chip) in order, at seq_len 2048 and 4
#: sequences per data shard (`collective_workload`'s sizing)
COLLECTIVE_OPS_PINNED = {
    "qwen3_1_7b": {
        "8x8": (("fsdp_gather", 507904000), ("fwd_tp", 1879048192),
                ("bwd_tp", 1879048192), ("grad_reduce", 507904000)),
        "16x4": (("fsdp_gather", 1015808000), ("fwd_tp", 1879048192),
                 ("bwd_tp", 1879048192), ("grad_reduce", 1015808000)),
        "64x1": (("fsdp_gather", 4063232000), ("grad_reduce", 4063232000)),
    },
    "gemma3_1b": {
        "8x8": (("fsdp_gather", 325435392), ("fwd_tp", 981467136),
                ("bwd_tp", 981467136), ("grad_reduce", 325435392)),
        "16x4": (("fsdp_gather", 650870784), ("fwd_tp", 981467136),
                 ("bwd_tp", 981467136), ("grad_reduce", 650870784)),
        "64x1": (("fsdp_gather", 2603483136), ("grad_reduce", 2603483136)),
    },
    "starcoder2_3b": {
        "8x8": (("fsdp_gather", 1078198272), ("fwd_tp", 3019898880),
                ("bwd_tp", 3019898880), ("grad_reduce", 1078198272)),
        "16x4": (("fsdp_gather", 2156396544), ("fwd_tp", 3019898880),
                 ("bwd_tp", 3019898880), ("grad_reduce", 2156396544)),
        "64x1": (("fsdp_gather", 8625586176), ("grad_reduce", 8625586176)),
    },
    "minicpm3_4b": {
        "8x8": (("fsdp_gather", 1262192640), ("fwd_tp", 5200936960),
                ("bwd_tp", 5200936960), ("grad_reduce", 1262192640)),
        "16x4": (("fsdp_gather", 2524385280), ("fwd_tp", 5200936960),
                 ("bwd_tp", 5200936960), ("grad_reduce", 2524385280)),
        "64x1": (("fsdp_gather", 10097541120), ("grad_reduce", 10097541120)),
    },
    "seamless_m4t_medium": {
        "8x8": (("fsdp_gather", 181509120), ("fwd_tp", 402653184),
                ("bwd_tp", 402653184), ("grad_reduce", 181509120)),
        "16x4": (("fsdp_gather", 363018240), ("fwd_tp", 402653184),
                 ("bwd_tp", 402653184), ("grad_reduce", 363018240)),
        "64x1": (("fsdp_gather", 1452072960), ("grad_reduce", 1452072960)),
    },
    "qwen3_moe_235b_a22b": {
        "8x8": (("fsdp_gather", 58760888320), ("fwd_tp", 12616466432),
                ("moe_a2a", 50465865728), ("bwd_tp", 12616466432),
                ("grad_reduce", 58760888320)),
        "16x4": (("fsdp_gather", 117521776640), ("fwd_tp", 12616466432),
                 ("moe_a2a", 50465865728), ("bwd_tp", 12616466432),
                 ("grad_reduce", 117521776640)),
        "64x1": (("fsdp_gather", 470087106560), ("grad_reduce", 470087106560)),
    },
    "grok_1_314b": {
        "8x8": (("fsdp_gather", 79121350656), ("fwd_tp", 12884901888),
                ("moe_a2a", 12884901888), ("bwd_tp", 12884901888),
                ("grad_reduce", 79121350656)),
        "16x4": (("fsdp_gather", 158242701312), ("fwd_tp", 12884901888),
                 ("moe_a2a", 12884901888), ("bwd_tp", 12884901888),
                 ("grad_reduce", 158242701312)),
        "64x1": (("fsdp_gather", 632970805248), ("grad_reduce", 632970805248)),
    },
    "chameleon_34b": {
        "8x8": (("fsdp_gather", 8573157376), ("fwd_tp", 12884901888),
                ("bwd_tp", 12884901888), ("grad_reduce", 8573157376)),
        "16x4": (("fsdp_gather", 17146314752), ("fwd_tp", 12884901888),
                 ("bwd_tp", 12884901888), ("grad_reduce", 17146314752)),
        "64x1": (("fsdp_gather", 68585259008), ("grad_reduce", 68585259008)),
    },
    "jamba_v0_1_52b": {
        "8x8": (("fsdp_gather", 12448694272), ("fwd_tp", 4294967296),
                ("moe_a2a", 2147483648), ("bwd_tp", 4294967296),
                ("grad_reduce", 12448694272)),
        "16x4": (("fsdp_gather", 24897388544), ("fwd_tp", 4294967296),
                 ("moe_a2a", 2147483648), ("bwd_tp", 4294967296),
                 ("grad_reduce", 24897388544)),
        "64x1": (("fsdp_gather", 99589554176), ("grad_reduce", 99589554176)),
    },
    "mamba2_1_3b": {
        "8x8": (("fsdp_gather", 57778176), ("fwd_tp", 3221225472),
                ("bwd_tp", 3221225472), ("grad_reduce", 57778176)),
        "16x4": (("fsdp_gather", 115556352), ("fwd_tp", 3221225472),
                 ("bwd_tp", 3221225472), ("grad_reduce", 115556352)),
        "64x1": (("fsdp_gather", 462225408), ("grad_reduce", 462225408)),
    },
}


@pytest.mark.parametrize("mesh", ["8x8", "16x4", "64x1"])
@pytest.mark.parametrize("arch", list(COLLECTIVE_OPS_PINNED))
def test_collective_ops_pinned(arch, mesh):
    """The collectives of one training step of each published
    architecture, op for op and byte for byte."""
    dm, tm = (int(x) for x in mesh.split("x"))
    cfg = W.arch_sizes(arch)
    ops = step_collective_ops(cfg, {"data": dm, "model": tm},
                              seq_len=2048, global_batch=4 * dm)
    want = [(ph, *PHASE_OPS[ph], b)
            for ph, b in COLLECTIVE_OPS_PINNED[arch][mesh]]
    assert [(o.phase, o.kind, o.axis, o.bytes_per_chip) for o in ops] \
        == want
    assert all(type(o.bytes_per_chip) is float for o in ops)


def test_arch_sizes_lookup():
    """Published name and canonical key find the same entry."""
    assert list(W.ARCHS) == list(COLLECTIVE_OPS_PINNED)
    for key, cfg in W.ARCHS.items():
        assert W.arch_sizes(key) is cfg
        assert W.arch_sizes(cfg.name) is cfg
    assert W.arch_sizes("qwen3-1.7b").d_model == 2048
    with pytest.raises(KeyError, match="unknown architecture"):
        W.arch_sizes("qwen4-1b")


@pytest.mark.parametrize("arch", list(COLLECTIVE_OPS_PINNED))
def test_collective_workload_phases(fht16, arch):
    cfg = W.arch_sizes(arch)
    sched = W.collective_workload(cfg, fht16.topo, step_cycles=800)
    labels = [p.label for p in sched.phases]
    # MoE archs add the all-to-all phase
    moe = ["moe_a2a"] if cfg.n_experts else []
    assert labels == ["fsdp_gather", "fwd_tp", *moe, "bwd_tp",
                      "grad_reduce"]
    assert max(p.intensity for p in sched.phases) == 1.0
    for p in sched.phases:
        m = np.asarray(p.traffic)
        assert (m >= 0).all() and np.abs(np.diag(m)).max() == 0
        assert p.duration >= 1
    if arch != "qwen3_1_7b":
        return
    # and the whole thing simulates
    spec = make_spec(fht16, sched.mean_traffic())
    res = run_batch([spec], np.array([[0.3]], np.float32), CFG,
                    schedules=[sched.fit(CFG.cycles - CFG.warmup)
                               .compile()])[0]
    assert res["delivered"][0] > 0


def test_trace_roundtrip_and_workload(tmp_path, fht16):
    tr = W.builtin_traces(region_cycles=100)["fluidanimate"]
    path = str(tmp_path / "t.json")
    tr.save(path)
    tr2 = W.load_trace(path)
    assert tr2.name == tr.name and tr2.regions == tr.regions
    topo = T.build("folded_hexa_torus", 16, roles_scheme="hetero_cmi")
    sched = W.trace_workload(topo, path)
    assert len(sched.phases) == 5
    assert sched.phases[0].burst_on == 25    # fluidanimate memory waves
    # intensities come straight from the legacy profile
    legacy = [i for i, _ in TR.TRACE_PROFILES["fluidanimate"]]
    assert [p.intensity for p in sched.phases] == legacy


def test_synthetic_generators(fht16):
    topo = fht16.topo
    alt = W.phase_alternating(topo, repeats=1)
    assert [p.label for p in alt.phases] == ["tornado", "uniform"]
    hot = W.hotspot_drift(topo, n_phases=3, seed=1)
    assert len(hot.phases) == 3
    for p in hot.phases:
        assert np.abs(np.diag(p.traffic)).max() == 0
    assert W.bursty_uniform(topo).phases[0].burst_off == 60


# ---------------------------------------------------------------------
# engine batching
# ---------------------------------------------------------------------

def test_engine_run_workloads_matches_singles(fht16, mesh16):
    rates = np.array([0.1, 0.35], np.float32)
    specs, scheds = [], []
    for r in (mesh16, fht16):
        u = TR.uniform(r.topo)
        specs.append(make_spec(r, u))
        scheds.append(W.phase_alternating(r.topo, phase_cycles=100,
                                          repeats=1).compile())
    eng = SweepEngine(cfg=CFG)
    out = eng.run_workloads(specs, scheds, rates)
    for spec, sched, got in zip(specs, scheds, out):
        single = run_batch([spec], rates[None, :], CFG,
                           schedules=[sched])[0]
        for k in RAW + ("delivered_ph", "lat_sum_ph"):
            np.testing.assert_array_equal(single[k], got[k], err_msg=k)
        np.testing.assert_array_equal(single["phase_cycles"],
                                      got["phase_cycles"])
    # same shapes again -> no new compilation
    before = eng.stats["compiles"]
    eng.run_workloads(specs, scheds, rates)
    assert eng.stats["compiles"] == before


def test_engine_workload_cases_grid():
    cases = [SweepCase("mesh", 16, roles="hetero_cmi"),
             SweepCase("hypercube", 15),     # invalid N
             SweepCase("folded_hexa_torus", 16, "glass",
                       roles="hetero_cmi")]
    workloads = [W.Workload("alt", lambda t: W.phase_alternating(
                     t, phase_cycles=60, repeats=1)),
                 W.Workload("trace", lambda t: W.trace_workload(
                     t, "blackscholes", region_cycles=40))]
    eng = SweepEngine(cfg=CFG)
    grid = eng.evaluate_workload_cases(cases, workloads, n_rates=3)
    assert len(grid) == 6
    assert grid[2] is None and grid[3] is None
    for row in (grid[0], grid[1], grid[4], grid[5]):
        assert row["sim_saturation"] > 0
        assert len(row["phase_labels"]) == len(row["throughput_ph"])
        # fitted: one replay covers the measurement window exactly
        assert row["phase_cycles"].sum() == CFG.cycles - CFG.warmup
    assert grid[0]["workload"].startswith("alt")
    assert grid[1]["workload"].startswith("trace:")
