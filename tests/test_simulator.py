"""Cycle-accurate simulator tests: conservation, plateau behaviour,
consistency with the analytic channel-load bound."""
import numpy as np
import pytest

from repro.core import topology as T, traffic as TR
from repro.core.routing import build_routing
from repro.core.simulator import SimConfig, simulate, \
    saturation_throughput, zero_load_latency

CFG = SimConfig(cycles=1500, warmup=500)


@pytest.fixture(scope="module")
def fht16():
    topo = T.build("folded_hexa_torus", 16)
    return build_routing(topo)


def test_low_load_delivery(fht16):
    """At 10 % of saturation, delivered == offered (no loss)."""
    u = TR.uniform(fht16.topo)
    res = simulate(fht16, u, [0.05], CFG)
    assert res["throughput"][0] >= 0.85 * res["offered"][0]


def test_throughput_plateaus(fht16):
    u = TR.uniform(fht16.topo)
    res = simulate(fht16, u, [0.2, 0.5, 0.9, 1.0], CFG)
    thr = res["throughput"]
    # monotone-ish up to plateau; last two within 15 %
    assert thr[1] > thr[0]
    assert abs(thr[3] - thr[2]) < 0.15 * max(thr[2], 1e-6)


def test_sim_below_analytic_bound(fht16):
    """The analytic channel-load rate is an upper bound for the sim."""
    u = TR.uniform(fht16.topo)
    out = saturation_throughput(fht16, u, CFG, n_rates=5)
    assert out["sim_saturation"] <= out["analytic_saturation"] * 1.1
    assert out["sim_saturation"] >= 0.4 * out["analytic_saturation"]


def test_latency_grows_with_load(fht16):
    u = TR.uniform(fht16.topo)
    res = simulate(fht16, u, [0.1, 1.0], CFG)
    assert res["latency"][1] > res["latency"][0]


def test_zero_load_latency_close_to_sim(fht16):
    """Sim latency at very low load ~ analytic zero-load latency."""
    u = TR.uniform(fht16.topo)
    zl = zero_load_latency(fht16, u)
    res = simulate(fht16, u, [0.02], CFG)
    assert res["latency"][0] == pytest.approx(zl, rel=0.35)


def test_mesh_vs_fht_simulated():
    """Fig. 4: FHT sustains higher simulated throughput than Mesh.

    (N=16 is the paper's smallest, tightest-margin point — Fig. 7 even
    shows other topologies edging FHT there; we assert strictly higher.)"""
    out = {}
    for name in ("mesh", "folded_hexa_torus"):
        r = build_routing(T.build(name, 16))
        out[name] = saturation_throughput(
            r, TR.uniform(r.topo), CFG, n_rates=5)["sim_saturation"]
    assert out["folded_hexa_torus"] > 1.05 * out["mesh"]


def test_hetero_traffic_runs():
    topo = T.build("folded_hexa_torus", 16, roles_scheme="hetero_cm")
    r = build_routing(topo)
    m = TR.hetero_mix(topo)
    res = simulate(r, m, [0.2], CFG)
    assert res["throughput"][0] > 0


# ---------------------------------------------------------------------
# bitwise pin: routing="static" vs the pre-adaptive simulator
# (DESIGN.md §15).  The counters below were captured from the simulator
# BEFORE the adaptive-routing branch existed; any drift in the static
# path — across plain, workload, telemetry-on and faulted configs —
# fails here with the exact counter that moved.
# ---------------------------------------------------------------------

_PIN_CFG = SimConfig(cycles=300, warmup=100)
_PIN_RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")
_PIN_RATES = np.array([0.05, 0.2, 0.6], np.float32)

GOLDEN = {
    'static:mesh16': {'delivered': [165, 669, 1119], 'offered_n': [161, 653, 1948], 'accepted_n': [161, 653, 1186], 'lat_sum': [3497, 14991, 64092]},
    'static:fht36': {'delivered': [359, 1424, 3536], 'offered_n': [363, 1442, 4393], 'accepted_n': [363, 1442, 3690], 'lat_sum': [7406, 29483, 122185]},
    'workload:fht16_drift': {'delivered': [156, 621, 705], 'offered_n': [155, 634, 1874], 'accepted_n': [155, 618, 718], 'lat_sum': [2171, 26593, 57100], 'delivered_ph': [0, 73, 83, 0, 304, 317, 0, 281, 424]},
    'telemetry:fht16': {'delivered': [163, 654, 1950], 'offered_n': [161, 653, 1948], 'accepted_n': [161, 653, 1935], 'lat_sum': [2240, 9071, 32384]},
    'telemetry:fht16:tel': {'link_busy': 4787, 'link_stall': 929, 'inj_node': 2749, 'eject_node': 2767},
    'faulted:mesh16_k2': {'delivered': [164, 666, 964], 'offered_n': [161, 653, 1948], 'accepted_n': [161, 653, 990], 'lat_sum': [3695, 16056, 66809]},
    # captured from the step that still read head flits, credits and
    # winners by element gathers, before the one-hot selects replaced them
    'adaptive:fht16_drift': {'delivered': [156, 633, 730], 'offered_n': [155, 634, 1874], 'accepted_n': [155, 624, 833], 'lat_sum': [2163, 26149, 55315], 'delivered_ph': [0, 73, 83, 0, 314, 319, 0, 297, 433]},
}


def _pin_check(tag, res, keys=_PIN_RAW):
    for k in keys:
        got = [int(x) for x in np.asarray(res[k]).ravel()]
        assert got == GOLDEN[tag][k], f"{tag}/{k}: {got} != {GOLDEN[tag][k]}"


def test_static_pin_plain():
    from repro.core.simulator import make_spec, run_batch
    mesh = build_routing(T.build("mesh", 16))
    fht = build_routing(T.build("folded_hexa_torus", 36))
    specs = [make_spec(mesh, TR.uniform(mesh.topo)),
             make_spec(fht, TR.uniform(fht.topo))]
    res = run_batch(specs, _PIN_RATES, _PIN_CFG)
    _pin_check("static:mesh16", res[0])
    _pin_check("static:fht36", res[1])


def test_static_pin_workload():
    import repro.workloads as W
    from repro.core.simulator import make_spec, run_batch
    fht16 = build_routing(T.build("folded_hexa_torus", 16))
    sched = W.hotspot_drift(fht16.topo, n_phases=3, dwell=100,
                            seed=1).fit(_PIN_CFG.cycles).compile()
    spec = make_spec(fht16, TR.uniform(fht16.topo))
    res = run_batch([spec], _PIN_RATES[None, :], _PIN_CFG,
                    schedules=[sched])[0]
    _pin_check("workload:fht16_drift", res, _PIN_RAW + ("delivered_ph",))


def test_static_pin_telemetry():
    from repro.core.simulator import make_spec, run_batch
    fht16 = build_routing(T.build("folded_hexa_torus", 16))
    spec = make_spec(fht16, TR.uniform(fht16.topo))
    res = run_batch([spec], _PIN_RATES[None, :],
                    _PIN_CFG._replace(telemetry=True))[0]
    _pin_check("telemetry:fht16", res)
    for k, want in GOLDEN["telemetry:fht16:tel"].items():
        assert int(np.asarray(res[k]).sum()) == want, k
    # the new escape/adaptive split is a pure host-side view of occ_sum
    occ = np.asarray(res["link_occ_sum"])
    assert np.array_equal(np.asarray(res["link_occ_escape"]),
                          occ[:, :, 0])
    assert np.array_equal(np.asarray(res["link_occ_adaptive"]),
                          occ[:, :, 1:].sum(axis=-1))


def test_static_pin_faulted():
    import repro.faults as F
    from repro.core.simulator import make_spec, run_batch
    mesh = build_routing(T.build("mesh", 16))
    fs = F.sample_faults(mesh.topo, 2, "random", seed=3)
    rdeg = build_routing(fs.apply(mesh.topo))
    spec = make_spec(rdeg, fs.mask_traffic(TR.uniform(mesh.topo)))
    res = run_batch([spec], _PIN_RATES[None, :], _PIN_CFG)[0]
    _pin_check("faulted:mesh16_k2", res)


def test_adaptive_pin_workload():
    """The adaptive route lookup (escape credit, candidate credit and
    the downstream VC of each winner) under a drifting hotspot."""
    import repro.workloads as W
    from repro.core.simulator import make_spec, run_batch
    fht16 = build_routing(T.build("folded_hexa_torus", 16))
    sched = W.hotspot_drift(fht16.topo, n_phases=3, dwell=100,
                            seed=1).fit(_PIN_CFG.cycles).compile()
    spec = make_spec(fht16, TR.uniform(fht16.topo))
    res = run_batch([spec], _PIN_RATES[None, :],
                    _PIN_CFG._replace(routing="adaptive"),
                    schedules=[sched])[0]
    _pin_check("adaptive:fht16_drift", res, _PIN_RAW + ("delivered_ph",))


# ---------------------------------------------------------------------
# the step's one-hot reads
# ---------------------------------------------------------------------

@pytest.mark.parametrize("case", ["head", "credit", "winner"])
def test_select_last_matches_gathers(case):
    """`_select_last` reads what `take_along_axis` and fancy indexing
    read, bitwise, at the step's three kinds of index: a head slot of
    the B live buffer slots, a clipped output slot of the credit table
    (its last column INF), and the winning VC."""
    import jax.numpy as jnp
    from repro.core.simulator import INF, _select_last
    rng = np.random.default_rng(7)
    n, pi, v, b = 5, 4, 3, 4
    big = np.iinfo(np.int32)
    if case == "head":
        buf = rng.integers(big.min, big.max, (n, pi, v, b + 1), np.int32)
        head = rng.integers(0, b, (n, pi, v), np.int32)
        head[0], head[1] = 0, b - 1
        got = _select_last(jnp.asarray(buf)[..., :b], jnp.asarray(head))
        want = np.take_along_axis(buf, head[..., None], axis=3)[..., 0]
    elif case == "credit":
        p = pi - 1
        cred = rng.integers(-3, 5, (n, p, v), np.int32)
        cred_pad = np.concatenate(
            [cred, np.full((n, 1, v), int(INF), np.int32)], axis=1)
        op_slot = rng.integers(-3, pi, (n, pi, v), np.int32)
        op_slot[0], op_slot[1] = p, -1
        slot = np.clip(op_slot, 0, p)
        got = _select_last(jnp.swapaxes(jnp.asarray(cred_pad), 1, 2)[:, None],
                           jnp.asarray(slot))
        want = cred_pad[np.arange(n)[:, None, None], slot,
                        np.arange(v)[None, None, :]]
        assert (want == int(INF)).any() and (slot == 0).any()
    else:
        head_dst = rng.integers(big.min, big.max, (n, pi, v), np.int32)
        wvc = rng.integers(0, v, (n, pi), np.int32)
        wvc[0], wvc[1] = 0, v - 1
        got = _select_last(jnp.asarray(head_dst), jnp.asarray(wvc))
        want = head_dst[np.arange(n)[:, None], np.arange(pi)[None, :], wvc]
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------
# adaptive mode (DESIGN.md §15)
# ---------------------------------------------------------------------

def test_adaptive_runs_and_conserves():
    """Adaptive mode delivers traffic and obeys flit conservation."""
    from repro.core.simulator import make_spec, run_batch
    r = build_routing(T.build("mesh", 16))
    spec = make_spec(r, TR.uniform(r.topo))
    cfg = _PIN_CFG._replace(routing="adaptive")
    res = run_batch([spec], _PIN_RATES[None, :], cfg)[0]
    d = np.asarray(res["delivered"])
    a = np.asarray(res["accepted_n"])
    o = np.asarray(res["offered_n"])
    assert (d > 0).all()
    # conservation up to warmup in-flight drain: the measured window can
    # deliver flits accepted during warmup, but never more than the
    # network could plausibly hold (node buffers at every node)
    slack = spec.n * cfg.n_vcs * cfg.buf_depth
    assert (d <= a + slack).all()
    assert (a <= o).all()            # acceptance never exceeds offers


def test_adaptive_rejects_single_vc():
    from repro.core.simulator import make_spec, run_batch
    r = build_routing(T.build("mesh", 16))
    spec = make_spec(r, TR.uniform(r.topo))
    cfg = _PIN_CFG._replace(routing="adaptive", n_vcs=1)
    with pytest.raises(ValueError, match="n_vcs"):
        run_batch([spec], _PIN_RATES[None, :], cfg)


def test_unknown_routing_mode_rejected():
    from repro.core.simulator import make_spec, run_batch
    r = build_routing(T.build("mesh", 16))
    spec = make_spec(r, TR.uniform(r.topo))
    with pytest.raises(ValueError, match="routing"):
        run_batch([spec], _PIN_RATES[None, :],
                  _PIN_CFG._replace(routing="exotic"))


def test_rate_grid_headroom():
    """Satellite regression: adaptive grids extend past the analytic
    bound, static grids are bitwise-unchanged from the historical 2x."""
    from repro.core.simulator import (ADAPTIVE_HEADROOM, STATIC_HEADROOM,
                                      routing_headroom,
                                      saturation_rate_grid)
    analytic = 0.31
    legacy = np.linspace(max(analytic * 0.25, 1e-3),
                         min(1.0, 2.0 * analytic), 8)
    assert np.array_equal(saturation_rate_grid(analytic), legacy)
    assert np.array_equal(
        saturation_rate_grid(analytic, headroom=STATIC_HEADROOM), legacy)
    ad = saturation_rate_grid(analytic, headroom=ADAPTIVE_HEADROOM)
    assert ad[-1] > analytic and ad[-1] > legacy[-1]
    assert routing_headroom("adaptive") == ADAPTIVE_HEADROOM
    assert routing_headroom("static") == STATIC_HEADROOM
    # the ceiling still clips at 1.0 flits/node/cycle
    assert saturation_rate_grid(0.9, headroom=3.0)[-1] == 1.0


def test_adaptive_beats_static_on_hotspot_drift():
    """The headline claim (ISSUE acceptance): minimal-adaptive routing
    outruns static table routing on the drifting-hotspot schedule for
    the mesh family."""
    import repro.workloads as W
    from repro.core.simulator import make_spec, run_batch
    cfg = SimConfig(cycles=1000, warmup=300)
    r = build_routing(T.build("mesh", 36))
    spec = make_spec(r, TR.uniform(r.topo))
    sched = W.hotspot_drift(r.topo, n_phases=4, dwell=250,
                            seed=2).fit(cfg.cycles).compile()
    rr = np.linspace(0.05, 0.9, 8).astype(np.float32)[None, :]
    st = run_batch([spec], rr, cfg, schedules=[sched])[0]
    ad = run_batch([spec], rr, cfg._replace(routing="adaptive"),
                   schedules=[sched])[0]
    s = float(np.max(np.asarray(st["throughput"])))
    a = float(np.max(np.asarray(ad["throughput"])))
    assert a > 1.05 * s, f"adaptive {a:.4f} should beat static {s:.4f}"
