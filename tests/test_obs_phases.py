"""The runner phase map (`repro.obs.phases`, DESIGN.md §13) and the flight
recorder's host span and counters.

The map names, for each compiled instruction of a runner, the step phase
its `op_name` metadata names (`step_arrivals` ... `step_flight`); fusions
take the phase of the instructions inside them.  It is built on demand
from the jit's own caches, so building it compiles nothing and adds no
set-up counter samples.  `run_batch` notes a runner for its map only
while span tracing or profiling is on."""
import jax
import numpy as np
import pytest

from repro.core import routing as RT, simulator as sim, topology as T
from repro.core import traffic as TR
from repro.core.simulator import SimConfig, make_spec, run_batch
from repro.obs import metrics
from repro.obs.phases import (OUTSIDE, PHASES, hlo_phases, phase_maps,
                              scope_phase)
from repro.obs.profile import ProfileRegistry, noted_runners
from repro.obs.trace import (clear_trace, disable_tracing, enable_tracing,
                             get_spans)

OFF = SimConfig(cycles=47, warmup=11)
ON = OFF._replace(telemetry=True, telemetry_windows=4)
RATES = np.array([0.1, 0.4], np.float32)


@pytest.fixture(scope="module")
def spec():
    topo = T.build("folded_hexa_torus", 16)
    return make_spec(RT.routing_for(topo), TR.uniform(topo))


def traced_run(spec, cfg):
    """`run_batch` with span tracing on, as a traced benchmark pass."""
    enable_tracing()
    try:
        run_batch([spec], RATES, cfg)
    finally:
        disable_tracing()
        clear_trace()


@pytest.fixture(scope="module")
def maps(spec):
    """{telemetry: RunnerPhases} of the two runners this module calls."""
    for cfg in (OFF, ON):
        traced_run(spec, cfg)
    got = {m.cfg.telemetry: m for m in phase_maps() if m.cfg in (OFF, ON)}
    assert set(got) == {False, True}
    return got


def test_telemetry_runner_maps_every_phase(maps):
    named = set(maps[True].phases.values())
    assert set(PHASES) <= named
    assert OUTSIDE in named
    flight = [k for k, v in maps[True].phases.items() if v == "step_flight"]
    assert flight


def test_telemetry_off_runner_has_no_flight_phase(maps):
    named = set(maps[False].phases.values())
    assert "step_flight" not in named
    assert set(PHASES) - {"step_flight"} <= named


def test_map_keeps_the_runner_key(maps, spec):
    m = maps[True]
    assert m.cfg == ON and m.kmax == 0
    assert m.shape.startswith("PadShape(n=16,")


def test_building_a_map_adds_no_setup_samples_and_compiles_nothing(spec):
    """A runner of a shape of its own, called once; its map is built
    after, and JAX's compile pipeline records nothing for it."""
    cfg = ON._replace(cycles=41)
    traced_run(spec, cfg)
    runner = next(e["runner"]() for e in noted_runners() if e["cfg"] == cfg)
    compiled_before = runner._cache_size()
    events = []

    def listen(event, secs, **kw):
        events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    before = {k: v for k, v in metrics.snapshot().items()
              if k.startswith("jit.")}
    try:
        m = next(m for m in phase_maps() if m.cfg == cfg)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    after = {k: v for k, v in metrics.snapshot().items()
             if k.startswith("jit.")}
    assert after == before
    assert "/jax/core/compile/backend_compile_duration" not in events
    assert runner._cache_size() == compiled_before
    assert "step_flight" in m.phases.values()


def test_compile_events_outside_the_map_still_record(spec):
    key = "jit.trace_s:runner"
    n0 = metrics.snapshot().get(key, {}).get("count", 0)
    run_batch([spec], RATES, OFF._replace(cycles=43))
    assert metrics.snapshot()[key]["count"] == n0 + 1


def test_runners_are_noted_only_while_tracing_or_profiling(spec):
    cfg = OFF._replace(cycles=37)
    run_batch([spec], RATES, cfg)
    assert not [e for e in noted_runners() if e["cfg"] == cfg]
    traced_run(spec, cfg)
    assert len([e for e in noted_runners() if e["cfg"] == cfg]) == 1
    traced_run(spec, cfg)
    assert len([e for e in noted_runners() if e["cfg"] == cfg]) == 1


def test_registry_holds_runners_weakly():
    reg = ProfileRegistry()
    f = jax.jit(lambda x: x + 1)
    x = np.ones(3, np.float32)
    f(x)
    reg.note("key", f, (x,), shape="shape")
    (entry,) = reg.noted()
    assert entry["args"][0] == jax.ShapeDtypeStruct((3,), np.float32)
    del f
    import gc
    gc.collect()
    assert reg.noted() == []


# ---------------------------------------------------------------------
# the rules, on a hand-made module in a TPU compile's layout
# ---------------------------------------------------------------------

SCOPE = "jit(runner)/vmap(vmap())/while/body/closed_call"
HLO = f"""HloModule jit_runner, is_scheduled=true

%region_add (a: s32[], b: s32[]) -> s32[] {{
  %a = s32[] parameter(0), metadata={{op_name="scatter"}}
  %b = s32[] parameter(1), metadata={{op_name="scatter"}}
  ROOT %add.1 = s32[] add(%a, %b)
}}

%fused_scatter (param_0: s32[9], param_1: s32[4]) -> s32[9] {{
  %param_0 = s32[9]{{0}} parameter(0)
  %param_1 = s32[4]{{0}} parameter(1)
  ROOT %scatter.3 = s32[9]{{0}} scatter(%param_0, %param_1), to_apply=%region_add, metadata={{op_name="{SCOPE}/step_flight/scatter-add" stack_frame_id=7}}
}}

%fused_mixed (param_0.1: s32[8]) -> (s32[8], s32[8]) {{
  %param_0.1 = s32[8]{{0}} parameter(0)
  %gather.2 = s32[8]{{0}} gather(%param_0.1), metadata={{op_name="{SCOPE}/step_route/jit(take_along_axis)/gather"}}
  %mul.4 = s32[8]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{SCOPE}/step_flight/mul"}}
  %add.5 = s32[8]{{0}} add(%gather.2, %mul.4)
  ROOT %tuple.6 = (s32[8]{{0}}, s32[8]{{0}}) tuple(%add.5, %mul.4)
}}

%fused_bare (param_0.2: s32[8]) -> s32[8] {{
  %param_0.2 = s32[8]{{0}} parameter(0)
  ROOT %neg.7 = s32[8]{{0}} negate(%param_0.2)
}}

%body (arg: (s32[], s32[9], s32[8])) -> (s32[], s32[9], s32[8]) {{
  %arg = (s32[], s32[9]{{0}}, s32[8]{{0}}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %tel = s32[9]{{0}} get-tuple-element(%arg), index=1
  %buf = s32[8]{{0}} get-tuple-element(%arg), index=2
  %idx = s32[4]{{0}} slice(%buf), slice={{[0:4]}}, metadata={{op_name="{SCOPE}/step_move/jit(clip)/slice"}}
  %fusion.10 = s32[9]{{0:T(1024)}} fusion(%tel, %idx), kind=kLoop, calls=%fused_scatter
  %fusion.11 = (s32[8]{{0}}, s32[8]{{0}}) fusion(%buf), kind=kLoop, calls=%fused_mixed, metadata={{op_name="{SCOPE}/step_route/gather"}}
  %copy.12 = s32[8]{{0}} copy(%buf)
  %netstep.8 = s32[8]{{0}} custom-call(%copy.12), custom_call_target="tpu_custom_call", metadata={{op_name="{SCOPE}/step_alloc/jit(netstep_pallas)/netstep/pallas_call"}}
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %t = (s32[], s32[9]{{0}}, s32[8]{{0}}) tuple(%next, %fusion.10, %netstep.8)
}}

%cond (arg.1: (s32[], s32[9], s32[8])) -> pred[] {{
  %arg.1 = (s32[], s32[9]{{0}}, s32[8]{{0}}) parameter(0)
  %j = s32[] get-tuple-element(%arg.1), index=0
  %n = s32[] constant(400)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}}

ENTRY %main.66 (p0: s32[9], p1: s32[8]) -> s32[8] {{
  %p0 = s32[9]{{0}} parameter(0)
  %p1 = s32[8]{{0}} parameter(1)
  %fusion.20 = s32[8]{{0}} fusion(%p1), kind=kLoop, calls=%fused_bare
  %zero = s32[] constant(0)
  %init = (s32[], s32[9]{{0}}, s32[8]{{0}}) tuple(%zero, %p0, %fusion.20)
  %while.12 = (s32[], s32[9]{{0}}, s32[8]{{0}}) while(%init), condition=%cond, body=%body
  ROOT %out = s32[8]{{0}} get-tuple-element(%while.12), index=2
}}
"""


@pytest.fixture(scope="module")
def hand():
    return hlo_phases(HLO)


def test_scatter_fusion_without_its_own_op_name_takes_its_inner_phase(hand):
    phases, mixed = hand
    assert phases["fusion.10"] == "step_flight"
    assert "fusion.10" not in mixed


def test_mixed_fusion_takes_its_roots_phase(hand):
    """The root is a tuple with no metadata: the walk back from it meets
    `add.5` (none), then `mul.4`, which names `step_flight`; the
    fusion's own `op_name` (`step_route`) does not decide."""
    phases, mixed = hand
    assert phases["fusion.11"] == "step_flight"
    assert mixed == {"fusion.11"}


def test_unfused_instructions_take_their_innermost_scope(hand):
    phases, _ = hand
    assert phases["netstep.8"] == "step_alloc"
    assert phases["idx"] == "step_move"


def test_outside_the_scan_body_is_outside(hand):
    phases, _ = hand
    for name in ("fusion.20", "while.12", "init", "p0"):
        assert phases[name] == OUTSIDE
    # the loop's own bookkeeping names no phase
    assert phases["copy.12"] is None and phases["next"] is None
    assert phases["lt"] is None


def test_fused_computations_are_not_mapped(hand):
    phases, _ = hand
    assert not {"scatter.3", "gather.2", "mul.4", "add.1"} & set(phases)


@pytest.mark.parametrize("op_name,phase", [
    (f"{SCOPE}/step_route/jit(take_along_axis)/gather", "step_route"),
    (f"{SCOPE}/step_alloc/jit(netstep_pallas)/netstep/pallas_call",
     "step_alloc"),
    ("jit(runner)/vmap(vmap())/while/body/closed_call/jit(remainder)/rem",
     None),
    ("step_flightless/add", None),
    (None, None),
])
def test_scope_phase(op_name, phase):
    assert scope_phase(op_name) == phase


# ---------------------------------------------------------------------
# the flight recorder on the host: span and counters
# ---------------------------------------------------------------------

def test_flight_counters_count_the_bytes_copied(spec):
    c0 = metrics.get("flight.calls")
    b0 = metrics.get("flight.d2h_bytes")
    run_batch([spec, spec], RATES, ON)
    n, c = spec.n, spec.c
    lanes, v, w = 2 * len(RATES), ON.n_vcs, ON.telemetry_windows
    per_lane = (2 * (c + 1) + (c + 1) * v + 2 * n + sim.LAT_HIST_BINS) + \
        w * (2 * (c + 1) + (c + 1) * v + 2 * n)
    assert metrics.get("flight.calls") == c0 + 1
    assert metrics.get("flight.d2h_bytes") == b0 + 4 * lanes * per_lane
    run_batch([spec], RATES, OFF)
    assert metrics.get("flight.calls") == c0 + 1


def test_flight_span_only_with_the_recorder_on(spec):
    clear_trace()
    enable_tracing()
    try:
        run_batch([spec], RATES, ON)
        run_batch([spec], RATES, OFF)
    finally:
        disable_tracing()
    spans = get_spans()
    clear_trace()
    flight = [s for s in spans if s.name == "sim.flight"]
    assert len(flight) == 1 and flight[0].args["specs"] == 1
    assert len([s for s in spans if s.name == "sim.wait"]) == 2
