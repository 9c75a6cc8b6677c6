"""Compile (never run) the simulator's main path for a described TPU v5e.

The TPU compiler is installed with jaxlib, so it can compile for a chip
that is described and not attached.  These tests catch what the Pallas
interpreter cannot: lowerings Mosaic refuses, blocks that are not
tile-aligned under the runner's vmaps, and kernels that overflow VMEM.
The topology is described inside a fixture (never at import), so every
test worker collects the same tests and only the one given this file
loads the TPU library."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import simulator as sim, topology as T, traffic as TR
from repro.core.routing import build_routing
from repro.kernels.netstep import ops as netstep_ops
from repro.kernels.netstep.netstep import netstep_pallas
from repro.sweep.padding import stack_specs


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _alloc(op_slot, eligible, rr_vc, rr_port):
    return netstep_pallas(op_slot, eligible, (rr_vc, rr_port),
                          interpret=False)


@pytest.mark.parametrize("batched", [False, True],
                         ids=["plain", "spec_rate_vmap"])
@pytest.mark.parametrize("n,pi,v", [(64, 7, 4), (256, 31, 4)])
def test_netstep_compiles_for_v5e(one_chip, n, pi, v, batched):
    """The kernel at the main path's widths — PI=31 is FlattenedButterfly
    at N=256 — compiles natively, alone and under the runner's
    spec x rate vmaps."""
    lead = (4, 8) if batched else ()
    sds = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt,
                                                 sharding=one_chip)
    fn = jax.vmap(jax.vmap(_alloc)) if batched else _alloc
    compiled = jax.jit(fn).lower(
        sds((n, pi, v), jnp.int32), sds((n, pi, v), jnp.bool_),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_runner_compiles_with_kernel(one_chip, monkeypatch):
    """The whole jitted runner (vmap specs . vmap rates . scan step) at
    N=64 compiles for v5e with the netstep kernel native, not
    interpreted."""
    monkeypatch.setattr(netstep_ops, "_interpret", lambda: False)
    specs = []
    for name in ("mesh", "folded_hexa_torus"):
        r = build_routing(T.build(name, 64))
        specs.append(sim.make_spec(r, TR.uniform(r.topo)))
    batch, shape = stack_specs(specs)
    cfg = sim.SimConfig(cycles=2000, warmup=700)
    runner = sim._make_batch_runner(shape.n, shape.p, shape.c, shape.d,
                                    cfg, "pallas")
    as_sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                            sharding=one_chip)
    compiled = runner.lower(
        jax.tree.map(as_sds, batch),
        jax.ShapeDtypeStruct((len(specs), 8), jnp.float32,
                             sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flight_runner_phase_map_for_v5e(one_chip, monkeypatch):
    """Compiled for v5e, the flight recorder's scatters become fusions
    that carry no `op_name` of their own; the phase map still puts them
    in `step_flight`, by the instructions inside them, and finds every
    other phase and the kernel."""
    from repro.obs.phases import PHASES, _parse, hlo_phases
    monkeypatch.setattr(netstep_ops, "_interpret", lambda: False)
    r = build_routing(T.build("folded_hexa_torus", 16))
    batch, shape = stack_specs([sim.make_spec(r, TR.uniform(r.topo))])
    cfg = sim.SimConfig(cycles=60, warmup=20, telemetry=True,
                        telemetry_windows=4)
    runner = sim._make_batch_runner(shape.n, shape.p, shape.c, shape.d,
                                    cfg, "pallas")
    as_sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                            sharding=one_chip)
    text = runner.lower(
        jax.tree.map(as_sds, batch),
        jax.ShapeDtypeStruct((1, 8), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    phases, _ = hlo_phases(text)
    comps, _ = _parse(text)
    bare = [ins for comp in comps.values() for ins in comp
            if ins.name in phases and ins.opcode == "fusion"
            and ins.op_name is None and phases[ins.name] == "step_flight"
            and any(j.opcode == "scatter" for a, c in ins.called
                    if a == "calls" for j in comps[c])]
    assert bare
    assert set(PHASES) <= set(phases.values())
    kernels = [n for n, p in phases.items() if n.startswith("netstep")]
    assert kernels and {phases[k] for k in kernels} == {"step_alloc"}


def test_static_runner_route_reads_by_select(one_chip, monkeypatch):
    """Compiled for v5e, the static step's route lookup holds one gather:
    the s16 routing-table read.  The head flit, its credit and the
    winner's flit are one-hot selects, which compile to no gather; every
    phase of the telemetry-off step is still found."""
    import re
    from repro.obs.phases import PHASES, _parse, hlo_phases
    monkeypatch.setattr(netstep_ops, "_interpret", lambda: False)
    r = build_routing(T.build("folded_hexa_torus", 16))
    batch, shape = stack_specs([sim.make_spec(r, TR.uniform(r.topo))])
    cfg = sim.SimConfig(cycles=60, warmup=20)
    runner = sim._make_batch_runner(shape.n, shape.p, shape.c, shape.d,
                                    cfg, "pallas")
    as_sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                            sharding=one_chip)
    text = runner.lower(
        jax.tree.map(as_sds, batch),
        jax.ShapeDtypeStruct((1, 8), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    phases, _ = hlo_phases(text)
    comps, _ = _parse(text)

    def gathers(ins):
        return ins.opcode == "gather" or any(
            j.opcode == "gather" for a, c in ins.called if a == "calls"
            for j in comps[c])

    route = [ins.name for comp in comps.values() for ins in comp
             if phases.get(ins.name) == "step_route" and gathers(ins)]
    assert len(route) == 1, route
    result = re.search(rf"^\s*(?:ROOT\s+)?%?{re.escape(route[0])} = (\S+)",
                       text, re.M)
    assert result.group(1).startswith("s16["), result.group(0)
    assert set(PHASES) - {"step_flight"} <= set(phases.values())
