"""Static verification layer (DESIGN.md §14): diagnostics engine,
exhaustive routing certification, design-principle lint, JAX hazards.

The certification grid here is the acceptance bar: every Table III
topology on both substrates (and fault-degraded variants) must come
back deadlock-free with a full reachability certificate, a
deliberately-cyclic routing must yield a *real* CDG-cycle witness, and
the seeded int32-overflow / pad-slot-write configs must be flagged.
"""
from __future__ import annotations

import dataclasses
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.analysis as A
from repro.analysis.diagnostics import CODES, Report, diag
from repro.analysis.jaxpr_hazards import (check_dtype_promotions,
                                          check_host_sync,
                                          check_overflow,
                                          check_padding_contract,
                                          check_recompiles, iter_eqns)
from repro.analysis.routing_verify import (certify_routing, check_acyclic,
                                           dependency_edges,
                                           find_cdg_cycle)
from repro.core import topology as T
from repro.core import traffic as tr
from repro.core.routing import (Routing, dependency_graph_is_acyclic,
                                routing_for)
from repro.core.simulator import SimConfig, make_spec
from repro.sweep.padding import PadShape, stack_specs

CFG = SimConfig(cycles=120, warmup=40)


# ---------------------------------------------------------------------
# diagnostics engine
# ---------------------------------------------------------------------

def test_diagnostic_defaults_and_witness():
    d = diag("RT001", "cycle found", target="x", cycle=[1, 2, 3])
    assert d.severity == "error" and d.slug == "cdg-cycle"
    assert d.label == "RT001 cdg-cycle"
    assert d.witness_dict() == {"cycle": [1, 2, 3]}
    assert "RT001" in str(d) and "[x]" in str(d)
    with pytest.raises(KeyError):
        diag("ZZ999", "no such code")
    with pytest.raises(ValueError):
        diag("RT001", "bad sev", severity="fatal")


def test_code_registry_families():
    for code, (slug, sev, desc) in CODES.items():
        assert code[:2] in ("RT", "DP", "JX", "FT", "EX")
        assert sev in ("error", "warning", "info") and slug and desc
    # routing violations are errors; design principles are warnings
    # (Table III deliberately violates them)
    assert all(CODES[c][1] == "error" for c in CODES if c[:2] == "RT")
    assert all(CODES[c][1] == "warning" for c in CODES if c[:2] == "DP")


def test_report_gate_and_summary(tmp_path):
    rep = Report([diag("DP001", "w1"), diag("RT001", "e1")])
    rep.record("routing", "t1")
    assert not rep.ok and rep.gate() == 1
    assert rep.gate(fail_on="warning") == 1
    assert len(rep.errors()) == 1 and len(rep.warnings()) == 1
    assert rep.counts() == {"DP001": 1, "RT001": 1}
    assert "1 error(s)" in rep.summary()
    out = tmp_path / "diag.json"
    rep.to_json(str(out))
    import json
    doc = json.loads(out.read_text())
    assert doc["kind"] == "diagnostics" and doc["n_errors"] == 1
    assert doc["rows"][1]["code"] == "RT001"
    clean = Report()
    assert clean.ok and clean.gate() == 0


# ---------------------------------------------------------------------
# routing verifier: certification grid (the acceptance bar)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("substrate", ["organic", "glass"])
def test_all_table3_topologies_certify_deadlock_free(substrate):
    """Exhaustive certification: every builtin at N=36, both substrates."""
    for name in sorted(T.GENERATORS):
        n = T.nearest_valid_n(name, 36)
        r = routing_for(T.build(name, n, substrate=substrate),
                        certify=True)
        cert = r.cert
        assert cert is not None and cert.ok, \
            f"{name}/{substrate}: {[str(d) for d in cert.diagnostics]}"
        assert cert.acyclic and cert.complete and cert.declared
        # every ordered pair of a connected pristine topology is checked
        assert cert.n_pairs_checked == n * (n - 1)
        assert cert.n_dep_edges > 0 and cert.max_hops_seen >= 1


@pytest.mark.parametrize("substrate", ["organic", "glass"])
@pytest.mark.parametrize("name", sorted(T.GENERATORS))
def test_table3_certifies_at_n64(name, substrate):
    """Every builtin at N=64, the size the chip benchmark's N=64 cells
    run, certifies deadlock-free with every ordered pair checked."""
    assert T.valid_n(name, 64)
    cert = routing_for(T.build(name, 64, substrate=substrate),
                       certify=True).cert
    assert cert is not None and cert.ok, \
        f"{name}/{substrate}: {[str(d) for d in cert.diagnostics]}"
    assert cert.acyclic and cert.complete and cert.declared
    assert cert.n_pairs_checked == 64 * 63


@pytest.mark.parametrize("name", ["folded_hexa_torus", "mesh", "torus",
                                  "hexamesh"])
def test_fault_variants_certify(name):
    """Fault masks k<=2: degraded routings stay certified; pairs
    involving dead chiplets are exempt by construction."""
    from repro.faults import apply_variant, iter_fault_variants
    topo = T.build(name, 36)
    labels = []
    for label, fs in iter_fault_variants(topo, kmax=2,
                                         kinds=("random", "chiplets")):
        degraded = apply_variant(topo, fs)
        cert = routing_for(degraded, certify=True).cert
        assert cert.ok, f"{name}[{label}]: {cert.diagnostics}"
        labels.append(label)
        if label.startswith("chiplets"):
            k = int(label.split(":")[1][1:])
            live = 36 - k
            assert cert.n_pairs_checked == live * (live - 1)
    assert "pristine" in labels and len(labels) >= 3


def _ring_cyclic_routing(n: int) -> Routing:
    """A deliberately-cyclic routing: n-ring, everything forwarded
    clockwise with no turn prohibition — the textbook deadlock."""
    pos = np.stack([np.cos(np.linspace(0, 2 * np.pi, n, endpoint=False)),
                    np.sin(np.linspace(0, 2 * np.pi, n,
                                       endpoint=False))], axis=1) * 10
    edges = np.array([(i, (i + 1) % n) for i in range(n)])
    topo = T.make_topology(f"ring{n}", pos, edges)
    # one clockwise channel per node; port 0 at src, in_port 0 at dst
    ch_src = np.arange(n)
    ch_dst = (ch_src + 1) % n
    table = np.full((n, n, 2), -1, np.int16)
    for d in range(n):
        for v in range(n):
            table[d, v, :] = Routing.EJECT if v == d else 0
    return Routing(
        topo=topo, ch_src=ch_src, ch_dst=ch_dst,
        ch_len_mm=np.ones(n), ch_out_port=np.zeros(n, np.int64),
        ch_in_port=np.zeros(n, np.int64),
        out_ch=np.arange(n).reshape(n, 1),
        in_ch=((np.arange(n) - 1) % n).reshape(n, 1),
        n_ports=np.ones(n, np.int64), table=table,
        prohibited_turns=0, total_turns=n)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=3, max_value=9))
def test_cyclic_routing_witness_is_a_real_cdg_cycle(n):
    """The RT001 witness must be an actual cycle of dependency edges."""
    r = _ring_cyclic_routing(n)
    diags = check_acyclic(r)
    assert len(diags) == 1 and diags[0].code == "RT001"
    w = diags[0].witness_dict()
    cycle = w["cycle"]
    assert len(cycle) >= 2
    edge_set = {tuple(e) for e in dependency_edges(r).tolist()}
    for a, b in zip(cycle, cycle[1:] + cycle[:1]):
        assert (a, b) in edge_set, f"witness edge {(a, b)} not in CDG"
    cert = certify_routing(r)
    assert not cert.ok and not cert.acyclic
    # the ring routing delivers (clockwise all the way), so only the
    # cycle check fails
    assert cert.complete and cert.declared


def test_broken_table_yields_unreachable_and_undeclared():
    r = routing_for(T.build("mesh", 16))
    table = r.table.copy()
    # dead-end pair (3 -> 0): no out port at the injection column
    table[0, 3, r.max_ports] = -1
    bad = dataclasses.replace(r, table=table, cert=None)
    cert = certify_routing(bad)
    assert not cert.ok and not cert.complete
    rt2 = [d for d in cert.diagnostics if d.code == "RT002"]
    assert rt2 and rt2[0].witness_dict()["pair"] == (3, 0)
    # undeclared channel: route to a port with no channel behind it
    table2 = r.table.copy()
    p_bad = int(r.n_ports[5])           # first virtual port at node 5
    if p_bad < r.max_ports:
        table2[0, 5, 0] = p_bad
        bad2 = dataclasses.replace(r, table=table2, cert=None)
        cert2 = certify_routing(bad2)
        assert any(d.code == "RT003" for d in cert2.diagnostics)


def test_livelock_detected_as_rt004():
    # bounce a packet for dst 15 between nodes 0 and 1 forever
    r = routing_for(T.build("mesh", 16))
    c01 = int(np.flatnonzero((r.ch_src == 0) & (r.ch_dst == 1))[0])
    c10 = int(np.flatnonzero((r.ch_src == 1) & (r.ch_dst == 0))[0])
    table = r.table.copy()
    dst = 15
    table[dst, 0, r.max_ports] = r.ch_out_port[c01]        # inject 0->1
    table[dst, 1, r.ch_in_port[c01]] = r.ch_out_port[c10]  # 1 -> 0
    table[dst, 0, r.ch_in_port[c10]] = r.ch_out_port[c01]  # 0 -> 1
    bad = dataclasses.replace(r, table=table, cert=None)
    cert = certify_routing(bad)
    assert not cert.ok and not cert.complete
    d = [x for x in cert.diagnostics if x.code == "RT004"]
    assert d and d[0].witness_dict()["pair"] == (0, dst)


def test_certificate_cached_with_routing():
    from repro.core.routing import routing_cache_clear
    routing_cache_clear()
    topo = T.build("folded_hexa_torus", 16)
    r1 = routing_for(topo)              # plain: no certificate yet
    assert r1.cert is None
    r2 = routing_for(topo, certify=True)
    assert r2 is r1 and r2.cert is not None and r2.cert.ok
    r3 = routing_for(topo, certify=True)  # cached, not re-verified
    assert r3.cert is r2.cert


def test_deprecated_bool_shim_still_works():
    r = routing_for(T.build("folded_hexa_torus", 16))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert dependency_graph_is_acyclic(r) is True
        assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert dependency_graph_is_acyclic.__doc__.startswith("Deprecated")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert dependency_graph_is_acyclic(_ring_cyclic_routing(5)) \
            is False


# ---------------------------------------------------------------------
# design-principle lint (byte-identical to the legacy prefilter)
# ---------------------------------------------------------------------

def test_principle_messages_match_legacy_strings():
    from repro.synth.feasibility import FeasibilityCriteria, check
    crit = FeasibilityCriteria(max_radix=3, max_wire_cost_mm=1.0)
    topo = T.build("torus", 36)
    legacy = check(topo, crit)
    diags = A.diagnose(topo, crit)
    assert [d.message for d in diags] == legacy
    assert legacy[0] == "link-range 4 > 1 (Principle 2)"
    codes = [d.code for d in diags]
    assert codes == sorted(codes)       # DP001..DP005 in check order
    assert all(d.severity == "warning" for d in diags)


def test_rate_floor_diagnostic_on_glass_vs_organic():
    crit = A.FeasibilityCriteria(min_rate_fraction=0.95)
    topo_o = T.build("torus", 36, substrate="organic")
    dp2 = [d for d in A.diagnose(topo_o, crit) if d.code == "DP002"]
    assert dp2 and "organic rate floor 0.95" in dp2[0].message
    w = dp2[0].witness_dict()
    assert w["max_link_mm"] > w["cap_mm"]


def test_n_constraint_lint_matches_planner_string():
    assert A.check_n_constraint("mesh", 36) == []
    diags = A.check_n_constraint("hypercube", 36)
    assert diags[0].code == "DP006"
    assert diags[0].message == \
        "hypercube does not support N=36 (topology.N_CONSTRAINTS)"


def test_valid_n_and_nearest():
    assert T.valid_n("mesh", 17) and T.valid_n("hypercube", 32)
    assert not T.valid_n("hypercube", 36)
    assert T.nearest_valid_n("hypercube", 36) == 32
    assert T.nearest_valid_n("cluscross_v1", 36) == 36
    assert T.nearest_valid_n("mesh", 36) == 36


def test_synth_rejection_ledger_carries_codes():
    from repro.synth.search import SearchConfig, SearchState
    st_ = SearchState(config=SearchConfig(n=36, substrate="organic"))
    assert not st_.admit(T.build("torus", 36), origin="registry")
    rej = st_.rejected[0]
    assert rej["reasons"] == ["link-range 4 > 1 (Principle 2)"]
    assert rej["diag_codes"] == ["DP001"]


# ---------------------------------------------------------------------
# planner / frame diag_code plumbing
# ---------------------------------------------------------------------

def test_plan_skip_codes_and_frame_column():
    import repro.experiments as X
    exp = X.Experiment([X.Scenario("mesh", 16),
                        X.Scenario("hypercube", 15)], cfg=CFG,
                       backend="analytic")
    pl = X.plan(exp)
    # legacy 2-tuple shape is pinned; codes ride in skip_codes
    i, reason = pl.skipped[0]
    assert i == 1 and reason == \
        "hypercube does not support N=15 (topology.N_CONSTRAINTS)"
    assert pl.skip_codes == {1: "DP006"}
    frame = X.run(exp)
    assert frame.rows[1]["status"] == "invalid"
    assert frame.rows[1]["diag_code"] == "DP006"
    assert frame.rows[0]["diag_code"] in ("", None)
    assert "diag_code" in frame.columns


def test_fault_rejected_skip_code():
    import repro.experiments as X
    import repro.faults as F
    e = np.sort(np.asarray(T.build("mesh", 16).edges), axis=1)
    cut = F.FaultSet(links=tuple(
        tuple(int(x) for x in lk) for lk in e[(e == 0).any(1)]))
    pl = X.plan(X.Experiment(
        [X.Scenario("mesh", 16, faults=cut)], cfg=CFG,
        backend="analytic"))
    assert pl.skip_codes == {0: "FT001"}


def test_schema_v6():
    from repro.experiments.io import SCHEMA_VERSION
    assert SCHEMA_VERSION == 6


# ---------------------------------------------------------------------
# jaxpr hazards
# ---------------------------------------------------------------------

def test_seeded_int32_overflow_flagged():
    """The acceptance-criterion seeded config: long run overflows the
    summed-latency counter; the default config is clean."""
    hot = check_overflow(36, 4, SimConfig(cycles=50_000, warmup=1000))
    assert any(d.code == "JX001" for d in hot)
    lat = [d for d in hot if d.witness_dict()["counter"] == "lat_node"]
    assert lat and lat[0].severity == "error"
    assert lat[0].witness_dict()["bound"] >= 2 ** 31
    assert check_overflow(36, 4, SimConfig()) == []


def test_telemetry_counters_bounded_too():
    from repro.analysis.jaxpr_hazards import counter_bounds
    b = counter_bounds(36, 4, SimConfig(telemetry=True))
    assert "tel_occ" in b and "tel_hist" in b
    assert all(v < 2 ** 31 for v in b.values())


def test_seeded_pad_slot_write_flagged():
    """Corrupting a padded lane (the acceptance-criterion seed) must
    produce a JX002 with a concrete (spec, leaf, index) witness."""
    specs = [make_spec(routing_for(T.build(nm, n)),
                       tr.uniform(T.build(nm, n)))
             for nm, n in (("folded_hexa_torus", 36), ("mesh", 16))]
    batch, shape = stack_specs(specs)
    assert check_padding_contract(batch, specs) == []   # clean batch
    # seed 1: a padded out_ch points at a real channel -> a pad lane
    # could scatter a flit onto spec 1's live channel rows
    bad = batch._replace(out_ch=batch.out_ch.copy())
    bad.out_ch[1, specs[1].n + 1, 0] = 3
    d = check_padding_contract(bad, specs)
    assert d and all(x.code == "JX002" for x in d)
    w = d[0].witness_dict()
    assert w["spec"] == 1 and w["leaf"] == "out_ch"
    assert w["value"] == 3
    # seed 2: nonzero injection weight in the padded node tail -> pad
    # nodes would inject real flits
    bad2 = batch._replace(inj_weight=batch.inj_weight.copy())
    bad2.inj_weight[1, specs[1].n] = 0.5
    d2 = check_padding_contract(bad2, specs)
    assert any(x.witness_dict()["leaf"] == "inj_weight" for x in d2)


def test_out_of_range_declared_channel_flagged():
    spec = make_spec(routing_for(T.build("mesh", 16)),
                     tr.uniform(T.build("mesh", 16)))
    batch, _ = stack_specs([spec])
    bad = batch._replace(out_ch=batch.out_ch.copy())
    live = np.argwhere(bad.out_ch[0] >= 0)[0]
    bad.out_ch[0, live[0], live[1]] = spec.c + 5   # beyond this spec's C
    d = check_padding_contract(bad, [spec])
    assert any(x.code == "JX002" for x in d)


def test_recompile_hazard_reported_with_bucketing_hint():
    shapes = [PadShape(16, 4, 48, 4), PadShape(36, 4, 120, 4),
              PadShape(16, 4, 48, 4)]
    assert check_recompiles([shapes[0], shapes[0]]) == []
    d = check_recompiles(shapes, bucketed=[PadShape(40, 4, 128, 4)] * 3)
    assert d[0].code == "JX003"
    assert "2 distinct padded shapes" in d[0].message
    assert "reduce this to 1" in d[0].message
    assert d[0].witness_dict()["n_shapes"] == 2


def test_traced_step_is_clean_and_walker_finds_seeded_hazards():
    import jax
    import jax.numpy as jnp
    from repro.core.simulator import trace_batch
    topo = T.build("mesh", 16)
    spec = make_spec(routing_for(topo), tr.uniform(topo))
    jaxpr, shape, batch = trace_batch([spec], [0.1, 0.2], CFG)
    assert shape.n == 16
    assert len(list(iter_eqns(jaxpr))) > 50      # walker descends scan
    assert check_host_sync(jaxpr) == []
    assert check_dtype_promotions(jaxpr) == []
    # seeded host callback is found inside nested jaxprs
    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x * 2
    j2 = jax.make_jaxpr(jax.jit(noisy))(jnp.float32(1.0))
    hs = check_host_sync(j2)
    assert hs and hs[0].code == "JX004"
    # seeded 64-bit promotion is found
    try:
        from jax.experimental import enable_x64
        with enable_x64():
            j3 = jax.make_jaxpr(
                lambda x: x.astype(jnp.float64) + 1.0)(
                    np.float32(1.0))
        dp = check_dtype_promotions(j3)
        assert any(d.code == "JX005" for d in dp)
    except ImportError:
        pass


def test_analyze_batch_front_door():
    from repro.analysis.jaxpr_hazards import analyze_batch
    topos = [T.build("folded_hexa_torus", 16), T.build("mesh", 16)]
    specs = [make_spec(routing_for(t), tr.uniform(t)) for t in topos]
    rep = analyze_batch(specs, [0.1], CFG)
    assert rep.ok                        # no errors on the real path
    assert ("padding", "batch[2]") in rep.analyzed
    assert any(kind == "recompile" for kind, _ in rep.analyzed)


# ---------------------------------------------------------------------
# engine front door + CLI
# ---------------------------------------------------------------------

def test_analyze_front_door_and_metrics():
    from repro.obs.metrics import metrics
    before = metrics.with_prefix("analysis.").get("analysis.certified", 0)
    rep = A.analyze(names=["folded_hexa_torus", "hypercube"], n=36,
                    substrates=("organic",), fault_kmax=1)
    assert rep.ok
    # hypercube at 36 is linted DP006 and analyzed at 32 instead
    assert [d.code for d in rep if d.code == "DP006"] == ["DP006"]
    assert any("hypercube/n32" in lbl for _, lbl in rep.analyzed)
    after = metrics.with_prefix("analysis.")
    assert after["analysis.certified"] > before


def test_cli_all_builtin_gate(tmp_path, capsys):
    """The acceptance criterion: `--all-builtin` certifies every Table
    III topology on both substrates with zero error diagnostics."""
    from repro.analysis.__main__ import main
    out = tmp_path / "diagnostics.json"
    rc = main(["--all-builtin", "-n", "36", "-q", "-o", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "0 error(s)" in text
    import json
    doc = json.loads(out.read_text())
    assert doc["n_errors"] == 0
    # 19 builtins x 2 substrates, principles + >=1 routing cert each
    routings = [a for a in doc["analyzed"] if a[0] == "routing"]
    assert len(routings) >= 2 * len(T.GENERATORS)


def test_cli_fails_on_warning_threshold(capsys):
    from repro.analysis.__main__ import main
    rc = main(["torus", "-n", "36", "--substrate", "organic", "-q",
               "--fail-on", "warning"])
    assert rc == 1                       # DP001 link-range warning
    rc2 = main(["torus", "-n", "36", "--substrate", "organic", "-q"])
    assert rc2 == 0                      # warnings pass the error gate


# ---------------------------------------------------------------------
# RT005: escape certification for minimal-adaptive routing (§15)
# ---------------------------------------------------------------------

def test_rt005_registered():
    slug, sev, desc = CODES["RT005"]
    assert slug == "escape-unsafe" and sev == "error"


@pytest.mark.parametrize("name", ["folded_hexa_torus", "mesh", "torus",
                                  "hexamesh"])
def test_escape_certified_on_builtins(name):
    """Every Table III family certifies RT005-clean: the productive-
    ports mask is non-trivial and every adaptive choice keeps a
    deliverable escape."""
    from repro.analysis.routing_verify import check_escape
    r = routing_for(T.build(name, 36))
    diags, n_choices = check_escape(r)
    assert diags == [] and n_choices > 0
    cert = certify_routing(r)
    assert cert.ok and cert.escape_safe
    assert cert.n_adaptive_choices == n_choices


def test_productive_ports_structure():
    """Mask semantics: all-False on the diagonal, every True entry is a
    strictly minimal, escape-safe declared channel."""
    import scipy.sparse.csgraph as csg
    from repro.core.routing import productive_ports
    r = routing_for(T.build("folded_hexa_torus", 16))
    prod = productive_ports(r)
    n, P = r.topo.n, r.max_ports
    assert prod.shape == (n, n, P) and prod.dtype == bool
    assert not prod[np.arange(n), np.arange(n)].any()
    hops = csg.shortest_path(r.topo.adjacency(), unweighted=True)
    for d, u, p in np.argwhere(prod):
        c = int(r.out_ch[u, p])
        assert c >= 0
        w = int(r.ch_dst[c])
        assert hops[w, d] + 1 == hops[u, d]
        q = int(r.ch_in_port[c])
        assert w == d or r.table[d, w, q] >= 0
    # the mask is non-trivial; a (dst, node) MAY legitimately have no
    # escape-safe minimal port (up*/down* escape routes are not always
    # minimal) — those states simply ride the escape class
    assert prod.any()
    assert prod.sum() >= n * (n - 1) // 2


def test_rt005_flags_escape_unsafe_mask():
    """Hand-poisoning the productive-ports mask with a non-minimal (or
    escape-losing) entry must yield an RT005 witness naming it."""
    from repro.core.routing import productive_ports
    r = routing_for(T.build("mesh", 16))
    prod = productive_ports(r).copy()
    # add a port that walks AWAY from the destination: node 0's port to
    # node 1 while routing to node 1's far side... pick (d=0, u=0+1 hop)
    # any declared port at node 5 that is not already productive for d=0
    cand = [(5, p) for p in range(r.max_ports)
            if r.out_ch[5, p] >= 0 and not prod[0, 5, p]]
    assert cand, "mesh node 5 should have a non-minimal port for dst 0"
    u, p = cand[0]
    poisoned = dataclasses.replace(r, cert=None)
    poisoned.prod = prod
    prod[0, u, p] = True
    from repro.analysis.routing_verify import check_escape
    diags, _ = check_escape(poisoned)
    assert diags and all(d.code == "RT005" for d in diags)
    w = diags[0].witness_dict()
    assert w["choice"][0] == 0 and w["choice"][1] == u
    cert = certify_routing(poisoned)
    assert not cert.ok and not cert.escape_safe


@given(seed=st.integers(0, 10_000))
@settings(max_examples=12, deadline=None)
def test_escape_property_random_graphs(seed):
    """Satellite property (ISSUE #9): on random connected degree-
    bounded topologies the escape-class CDG is acyclic and every
    (src, dst) pair stays reachable when the adaptive function is in
    play — i.e. RT005 + RT002/RT004 certify clean."""
    import networkx as nx
    rng = np.random.default_rng(seed)
    n = int(rng.integers(8, 24))
    g = nx.gnm_random_graph(n, int(n * 1.8), seed=seed)
    if not nx.is_connected(g):
        g = nx.compose(g, nx.path_graph(n))
    edges = np.array(sorted(tuple(sorted(e)) for e in g.edges()),
                     dtype=np.int32)
    pos = rng.uniform(0, np.sqrt(n), size=(n, 2))
    topo = T.Topology(name="rand", n=n, pos=pos, edges=edges,
                      substrate="organic", chiplet_area_mm2=74.0)
    cert = certify_routing(routing_for(topo))
    assert cert.ok, [str(d) for d in cert.diagnostics]
    assert cert.escape_safe and cert.n_adaptive_choices > 0
    assert cert.n_pairs_checked == n * (n - 1)


@given(seed=st.integers(0, 5_000), k=st.integers(1, 2))
@settings(max_examples=10, deadline=None)
def test_escape_property_faulted(seed, k):
    """Same property under sampled fault masks k<=2 on a Table III
    topology: the degraded routing (and its productive-ports mask)
    still certifies RT005-clean."""
    from repro.faults import FaultError, sample_faults
    topo = T.build("folded_hexa_torus", 36)
    try:
        fs = sample_faults(topo, k, "random", seed=seed)
        degraded = fs.apply(topo)
    except FaultError:
        return                          # disconnecting mask: resampled
    cert = certify_routing(routing_for(degraded))
    assert cert.ok and cert.escape_safe, \
        [str(d) for d in cert.diagnostics]
