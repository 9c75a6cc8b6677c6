"""Sweep-engine tests (DESIGN.md §6): padding invariance — a padded
batch of heterogeneous topologies must be bitwise-equal to the
single-spec simulator path — plus executable-cache reuse and the
rate-grid plumbing."""
import numpy as np
import pytest

from repro.core import simulator as sim
from repro.core import topology as T, traffic as TR
from repro.core.routing import build_routing
from repro.core.simulator import (SimConfig, make_spec, run_batch,
                                  simulate)
from repro.sweep.engine import SweepCase, SweepEngine
from repro.sweep.padding import PadShape, stack_specs

CFG = SimConfig(cycles=300, warmup=100)
RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")

# deliberately heterogeneous: different N, radix/ports, channel counts
HETERO = [("mesh", 16), ("folded_hexa_torus", 36), ("honeycomb_mesh", 16),
          ("octamesh", 25)]


@pytest.fixture(scope="module")
def hetero_specs():
    specs = []
    for name, n in HETERO:
        r = build_routing(T.build(name, n))
        specs.append(make_spec(r, TR.uniform(r.topo)))
    return specs


def test_stack_specs_shapes(hetero_specs):
    batch, shape = stack_specs(hetero_specs)
    s = len(hetero_specs)
    assert shape == PadShape.of(hetero_specs)
    assert batch.table.shape == (s, shape.n, shape.n, shape.p + 1)
    assert batch.ch_src.shape == (s, shape.c)
    # padded nodes must be inert: no injection weight, no routes
    for i, spec in enumerate(hetero_specs):
        assert (batch.inj_weight[i, spec.n:] == 0).all()
        assert (batch.table[i, :, spec.n:, :] == -1).all()
        assert int(batch.pi[i]) == spec.p + 1


def test_pad_shape_must_cover(hetero_specs):
    from repro.sweep.padding import pad_spec
    small = PadShape(n=4, p=2, c=4, d=2)
    with pytest.raises(ValueError):
        pad_spec(hetero_specs[0], small)


def test_batched_bitwise_equals_single_spec(hetero_specs):
    """The acceptance property: >=4 topologies x >=4 rates through ONE
    batched compiled program, bitwise-equal per spec to the single-spec
    path."""
    rates = np.array([0.05, 0.15, 0.3, 0.6], np.float32)
    batched = run_batch(hetero_specs, rates, CFG)      # one program
    for spec, b in zip(hetero_specs, batched):
        single = run_batch([spec], rates[None, :], CFG)[0]
        for k in RAW:
            np.testing.assert_array_equal(single[k], b[k], err_msg=k)
        # derived floats come from identical ints -> identical too
        np.testing.assert_array_equal(single["throughput"],
                                      b["throughput"])
        np.testing.assert_array_equal(single["latency"], b["latency"])


def test_engine_bucketing_matches_and_reuses(hetero_specs):
    rates = np.array([0.05, 0.2, 0.5], np.float32)
    eng = SweepEngine(cfg=CFG)
    res1 = eng.run_specs(hetero_specs, rates)
    for spec, r in zip(hetero_specs, res1):
        single = run_batch([spec], rates[None, :], CFG)[0]
        for k in RAW:
            np.testing.assert_array_equal(single[k], r[k], err_msg=k)
    # a second sweep over the same shapes must not compile anything new
    compiles_before = eng.stats["compiles"]
    eng.run_specs(hetero_specs, rates)
    assert eng.stats["compiles"] == compiles_before


def test_engine_single_program_mode(hetero_specs):
    rates = np.array([0.1, 0.4], np.float32)
    eng = SweepEngine(cfg=CFG)
    res = eng.run_specs(hetero_specs, rates, single_program=True)
    assert eng.stats["groups"] == 1
    for spec, r in zip(hetero_specs, res):
        single = run_batch([spec], rates[None, :], CFG)[0]
        np.testing.assert_array_equal(single["delivered"], r["delivered"])


def test_run_batch_per_spec_rates(hetero_specs):
    """[S, R] rate rows pair each spec with its own grid."""
    specs = hetero_specs[:2]
    rates = np.array([[0.05, 0.2], [0.1, 0.3]], np.float32)
    out = run_batch(specs, rates, CFG)
    for i, spec in enumerate(specs):
        single = run_batch([spec], rates[i:i + 1], CFG)[0]
        np.testing.assert_array_equal(single["delivered"],
                                      out[i]["delivered"])
    with pytest.raises(ValueError):
        run_batch(specs, np.zeros((3, 2), np.float32), CFG)


def test_simulate_is_a_batch_of_one():
    topo = T.build("folded_hexa_torus", 16)
    r = build_routing(topo)
    u = TR.uniform(topo)
    rates = [0.05, 0.3]
    res = simulate(r, u, rates, CFG)
    spec = make_spec(r, u)
    raw = run_batch([spec], np.asarray(rates, np.float32)[None, :], CFG)[0]
    np.testing.assert_array_equal(res["throughput"], raw["throughput"])
    np.testing.assert_array_equal(res["latency"], raw["latency"])


def test_evaluate_cases_matches_saturation_throughput():
    """Engine case evaluation reports the same saturation as the
    single-spec `saturation_throughput` helper."""
    from repro.core.simulator import saturation_throughput
    cases = [SweepCase("mesh", 16), SweepCase("folded_hexa_torus", 16),
             SweepCase("hypercube", 15)]          # last one invalid
    eng = SweepEngine(cfg=CFG)
    out = eng.evaluate_cases(cases, n_rates=4)
    assert out[2] is None
    for case, res in zip(cases[:2], out[:2]):
        routing, tm = case.build()
        want = saturation_throughput(routing, tm, CFG, n_rates=4)
        assert res["sim_saturation"] == want["sim_saturation"]
        assert res["latency_at_sat"] == want["latency_at_sat"]


def test_alloc_pallas_interpret_matches_jnp():
    """The Pallas netstep allocator (interpret mode on CPU) drives the
    batched simulator to the same counters as the jnp oracle."""
    r = build_routing(T.build("mesh", 16))
    spec = make_spec(r, TR.uniform(r.topo))
    rates = np.array([0.1, 0.4], np.float32)[None, :]
    tiny = SimConfig(cycles=60, warmup=20)
    ref = run_batch([spec], rates, tiny)
    got = run_batch([spec], rates, tiny._replace(alloc="pallas"))
    for k in RAW:
        np.testing.assert_array_equal(ref[0][k], got[0][k], err_msg=k)


def test_runner_cache_lru_eviction_does_not_change_results():
    """Bounding the compiled-runner cache only costs recompiles: with a
    1-entry LRU, alternating two padded shapes evicts on every switch
    yet reproduces the unbounded-cache counters bitwise, and the
    hit/miss/eviction counters account for the traffic."""
    tiny = SimConfig(cycles=80, warmup=20)
    rates = np.array([0.1, 0.3], np.float32)
    specs = []
    for name, n in (("mesh", 16), ("folded_hexa_torus", 36)):
        r = build_routing(T.build(name, n))
        specs.append(make_spec(r, TR.uniform(r.topo)))
    want = [run_batch([s], rates[None, :], tiny)[0] for s in specs]

    old_max = sim.runner_cache_info()["max_size"]
    sim._RUNNER_CACHE.clear()
    before = sim.runner_cache_info()
    try:
        sim.set_runner_cache_limit(1)
        got = []
        for _ in range(2):
            for s in specs:                 # A, B, A, B -> evict each time
                got.append(run_batch([s], rates[None, :], tiny)[0])
        info = sim.runner_cache_info()
        assert info["size"] == 1 and info["max_size"] == 1
        assert info["misses"] - before["misses"] == 4
        assert info["evictions"] - before["evictions"] == 3
        assert info["hits"] == before["hits"]
    finally:
        sim.set_runner_cache_limit(old_max)
    for g, w in zip(got, want + want):
        for k in RAW:
            np.testing.assert_array_equal(g[k], w[k], err_msg=k)
    # the survivor (last-run shape) is still cached: re-run is a hit
    h0 = sim.runner_cache_info()["hits"]
    run_batch([specs[1]], rates[None, :], tiny)
    assert sim.runner_cache_info()["hits"] == h0 + 1


def test_hash_rng_invariant_to_padding():
    """The injection hash depends only on (seed, t, node, stream)."""
    import jax.numpy as jnp
    a = sim._node_bits(7, 13, jnp.arange(16), 1)
    b = sim._node_bits(7, 13, jnp.arange(64), 1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:16])
    # and distinct streams / cycles decorrelate
    c = sim._node_bits(7, 13, jnp.arange(16), 2)
    d = sim._node_bits(7, 14, jnp.arange(16), 1)
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(a), np.asarray(d))


# ---------------------------------------------------------------------
# adaptive routing through the batched engine (DESIGN.md §15)
# ---------------------------------------------------------------------

ACFG = CFG._replace(routing="adaptive")


def test_adaptive_batched_bitwise_equals_single_spec(hetero_specs):
    """Padding invariance holds for the adaptive branch too: the
    batched program delivers the same counters as each single-spec run."""
    rates = np.array([0.05, 0.2, 0.5], np.float32)
    batched = run_batch(hetero_specs, rates, ACFG)
    for spec, b in zip(hetero_specs, batched):
        single = run_batch([spec], rates[None, :], ACFG)[0]
        for k in RAW:
            np.testing.assert_array_equal(single[k], b[k], err_msg=k)


def test_adaptive_fat_pad_invariant(hetero_specs):
    """Fat-padding every axis (nodes, ports, channels, ring depth) does
    not change a single adaptive counter: the productive-ports mask's
    pad region is all-False, so adaptive selection never sees pad
    lanes."""
    specs = hetero_specs[:2]
    rates = np.array([0.1, 0.4], np.float32)
    tight = run_batch(specs, rates, ACFG)
    shape = PadShape.of(specs)
    fat = PadShape(n=shape.n + 7, p=shape.p + 2, c=shape.c + 19,
                   d=shape.d + 3)
    padded = run_batch(specs, rates, ACFG, pad_shape=fat)
    for a, b in zip(tight, padded):
        for k in RAW:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_static_fat_pad_invariant_with_prod_leaf(hetero_specs):
    """The new `prod` BatchSpec leaf must not disturb the static path's
    fat-pad invariance (it is dead code under routing='static')."""
    specs = hetero_specs[:2]
    rates = np.array([0.1, 0.4], np.float32)
    tight = run_batch(specs, rates, CFG)
    shape = PadShape.of(specs)
    fat = PadShape(n=shape.n + 5, p=shape.p + 1, c=shape.c + 9,
                   d=shape.d + 2)
    padded = run_batch(specs, rates, CFG, pad_shape=fat)
    for a, b in zip(tight, padded):
        for k in RAW:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_prod_leaf_padding_contract(hetero_specs):
    """Stacked productive-ports masks: real region matches each spec's
    own mask, pad region is all-False."""
    batch, shape = stack_specs(hetero_specs)
    for i, spec in enumerate(hetero_specs):
        pr = batch.prod[i]
        assert pr.shape == (shape.n, shape.n, shape.p)
        np.testing.assert_array_equal(
            pr[:spec.n, :spec.n, :spec.p], spec.prod)
        assert not pr[spec.n:].any()
        assert not pr[:, spec.n:].any()
        assert not pr[:, :, spec.p:].any()


def test_engine_cfg_override_routes_adaptively(hetero_specs):
    """`run_specs(..., cfg=...)` runs the override config; the engine's
    own default stays intact (per-scenario routing, DESIGN.md §15)."""
    specs = hetero_specs[:2]
    rates = np.array([0.1, 0.4], np.float32)
    eng = SweepEngine(cfg=CFG)
    via_engine = eng.run_specs(specs, rates, cfg=ACFG)
    direct = run_batch(specs, rates, ACFG)
    for a, b in zip(direct, via_engine):
        for k in RAW:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # and without the override the engine still runs static
    static = eng.run_specs(specs, rates)
    single = run_batch([specs[0]], rates[None, :], CFG)[0]
    np.testing.assert_array_equal(static[0]["delivered"],
                                  single["delivered"])


# ---------------------------------------------------------------------
# cost-aware merging of shape groups (DESIGN.md §6)
# ---------------------------------------------------------------------

# four N=16 topologies whose bucketed (p, c) all differ
MERGE4 = ("mesh", "folded_hexa_torus", "honeycomb_mesh", "octamesh")

# the bucketed shapes of the N=64 Table III grid (19 topologies x
# {organic, glass}), each with its live spec count
TABLE3 = [(PadShape(64, 3, 192, 12), 4), (PadShape(64, 4, 224, 12), 8),
          (PadShape(64, 4, 256, 12), 10), (PadShape(64, 5, 256, 12), 2),
          (PadShape(64, 6, 288, 12), 2), (PadShape(64, 6, 352, 12), 2),
          (PadShape(64, 6, 384, 12), 4), (PadShape(64, 8, 448, 12), 2),
          (PadShape(64, 8, 512, 12), 2), (PadShape(64, 14, 896, 12), 2)]


@pytest.fixture(scope="module")
def merge4_specs():
    specs = []
    for name in MERGE4:
        r = build_routing(T.build(name, 16))
        specs.append(make_spec(r, TR.uniform(r.topo)))
    return specs


def _calls(groups) -> list:
    return sorted((g.shape, g.k_pad, len(g.idxs)) for g in groups)


def test_single_spec_groups_merge_into_one_call_bitwise(merge4_specs):
    """Four single-spec groups of different (p, c) fill one call's four
    lanes; every counter equals the unbucketed run's, one call per
    shape."""
    eng = SweepEngine(cfg=CFG)
    own = [PadShape.of([s]) for s in merge4_specs]
    assert len({eng.bucket_shape(sh) for sh in own}) == 4
    (g,) = eng.group(own)
    assert g.idxs == (0, 1, 2, 3)
    assert g.shape == eng.bucket_shape(PadShape.of(merge4_specs))
    rates = np.array([0.05, 0.2, 0.5], np.float32)
    merged = eng.run_specs(merge4_specs, rates)
    assert eng.stats["groups"] == 1
    apart_eng = SweepEngine(cfg=CFG, bucket=False)
    apart = apart_eng.run_specs(merge4_specs, rates)
    assert apart_eng.stats["groups"] == 4
    for a, b in zip(apart, merged):
        for k in RAW:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_full_small_group_stays_apart_from_wide_one():
    """Four small specs fill their own call; one call at the wide shape
    would pad them into four more wide lanes.  Two small specs fit the
    wide call's inert lanes instead."""
    eng = SweepEngine()
    small, wide = PadShape(16, 3, 36, 9), PadShape(16, 14, 224, 9)
    apart = eng.group([small] * 4 + [wide] * 2)
    assert _calls(apart) == [(eng.bucket_shape(small), 0, 4),
                             (eng.bucket_shape(wide), 0, 2)]
    (g,) = eng.group([small] * 2 + [wide] * 2)
    assert g.shape == eng.bucket_shape(wide) and len(g.idxs) == 4


@pytest.mark.parametrize("seed", [0, 1, 2, 2 ** 31 + 5])
def test_merges_ignore_spec_order(seed):
    """The Table III grid's calls depend only on its shapes: 10 shape
    groups become 7 calls and 30848 units of padded work (from 40960),
    in any order of the specs."""
    eng = SweepEngine()
    shapes = [sh for sh, live in TABLE3 for _ in range(live)]
    order = np.random.default_rng(seed).permutation(len(shapes))
    groups = eng.group([shapes[i] for i in order])
    want = eng.group(shapes)
    assert _calls(groups) == _calls(want)
    members = sorted(sorted(shapes[order[i]] for i in g.idxs)
                     for g in groups)
    assert members == sorted(sorted(shapes[i] for i in g.idxs)
                             for g in want)
    assert len(groups) == 7
    assert sum(eng.call_cost(g.shape, len(g.idxs)) for g in groups) \
        == 30848
    assert sum(eng.call_cost(sh, live) for sh, live in TABLE3) == 40960


def test_merge_keeps_tags_apart_pads_phases_and_needs_bucketing():
    """Groups of different tags (kind, R, routing) never share a call;
    merged workload groups pad the phase axis to the larger; a
    bucket=False engine never merges."""
    a, b = PadShape(16, 4, 48, 9), PadShape(16, 6, 96, 9)
    eng = SweepEngine()
    assert len(eng.group([a, b], tags=["static", "adaptive"])) == 2
    (g,) = eng.group([a, b], ks=[2, 5])
    assert g.k_pad == 6 and g.idxs == (0, 1)
    assert len(SweepEngine(bucket=False).group([a, b])) == 2
    (one,) = SweepEngine(bucket=False).group([a, b], single_program=True)
    assert one.shape == PadShape.of([a, b])
