"""Smoke test of the simulator's main path on one TPU chip.

    python3 chip_smoke.py

Drives `repro.experiments.run` — Scenario -> plan -> execute ->
SweepEngine -> simulator.run_batch, with `alloc="auto"` resolving to the
native Pallas netstep kernel — at the paper's own widths under the
benchmark SimConfig with its horizon cut (see SMOKE_CYCLES), 8 rates per
cell:

  (a) every Table III topology at N=64, organic and glass, uniform
      traffic; rerun with the pure-jnp allocator, and every raw counter
      must be bitwise equal;
  (b) mesh, hexamesh, folded_torus, folded_hexa_torus and
      flattened_butterfly at N=256 on organic, uniform traffic — the
      radix-30 FlattenedButterfly runs the kernel at PI=31.

It also checks that the compiled runners hold a `tpu_custom_call` (the
kernel is compiled, not interpreted) and the sanity anchors on organic
grid (a): simulated saturation orders folded_hexa_torus > hexamesh >
mesh and stays <= 1.1x the analytic bound.  Timings, compile counts and
peak device bytes are printed for information.  The last stdout line is
one JSON object naming the device.  Any failure exits non-zero, and
without a TPU the script stops before doing any work.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")
# BENCH_SIM_CFG simulates 2000 cycles after 700 of warm-up.  On one v5e
# the scan's scatters cost about 85 ms of device time per simulated cycle
# over grid (a) and about 230 ms over grid (b), so at 2000 cycles the
# script cannot end within 20 minutes.  The horizon is cut to 400 cycles
# with the same 35% warm-up; topologies, sizes, substrates and rates are
# the paper's.  The anchors still hold there (worst simulated/analytic
# saturation 1.04 at 400 cycles; at 200 it is 1.24 and they fail).
SMOKE_CYCLES, SMOKE_WARMUP = 400, 140
N_RATES = 8
GRID_B = ("mesh", "hexamesh", "folded_torus", "folded_hexa_torus",
          "flattened_butterfly")


def require_tpu():
    """The first device, which must be a TPU; raises otherwise."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"chip_smoke needs a TPU; JAX found "
                           f"{dev.platform!r} ({dev.device_kind})")
    return dev


class _CompileCounter:
    """Counts XLA backend compiles (JAX's own monitoring event)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _run(exp, counter):
    """Run an Experiment; returns (frame, seconds, runner compiles, XLA
    compiles).  run_batch blocks on the device before returning."""
    import repro.experiments as X
    from repro.obs.metrics import cache_counters
    misses0, xla0 = cache_counters()["cache.runner.misses"], counter.n
    t0 = time.perf_counter()
    frame = X.run(exp)
    dt = time.perf_counter() - t0
    bad = [(r["topology"], r["n"], r["substrate"], r["status"], r["error"])
           for r in frame.rows if r["status"] != "ok"]
    if bad:
        raise RuntimeError(f"{exp.name}: cells not ok: {bad}")
    return (frame, dt, cache_counters()["cache.runner.misses"] - misses0,
            counter.n - xla0)


def _compiled_text(exp, topology: str) -> str:
    """Compiled HLO of the runner the engine used for `topology`'s
    bucket, rebuilt with the same padding (spec axis to the engine's
    s_round, every leaf to the bucket's PadShape)."""
    import jax.numpy as jnp
    import numpy as np
    import repro.experiments as X
    from repro.core import simulator as sim
    from repro.sweep.engine import _round_up
    from repro.sweep.padding import stack_specs
    eng = X.engine_for(exp.cfg)
    plan = X.plan(exp, eng)
    bucket = next(b for b in plan.buckets
                  if any(ps.scenario.topology == topology for ps in b.items))
    items = list(bucket.items)
    while len(items) < _round_up(len(items), eng.s_round):
        items.append(items[-1])
    shape = bucket.key.shape
    batch, _ = stack_specs([ps.spec for ps in items], shape)
    rates = np.stack([ps.rates for ps in items]).astype(np.float32)
    runner = sim.get_batch_runner(shape.n, shape.p, shape.c, shape.d,
                                  exp.cfg, sim.resolve_alloc(exp.cfg.alloc))
    return runner.lower(batch, jnp.asarray(rates)).compile().as_text()


def _sat(frame, topology: str, substrate: str = "organic") -> dict:
    return next(r for r in frame.rows if r["topology"] == topology
                and r["substrate"] == substrate)


def check_anchors(frame) -> list[str]:
    """Sanity anchors on organic, uniform traffic: FHT > hexamesh > mesh
    in simulated saturation, each <= 1.1x its analytic bound."""
    errs = []
    fht, hexa, mesh = (_sat(frame, t)["sim_saturation"] for t in
                       ("folded_hexa_torus", "hexamesh", "mesh"))
    if not fht > hexa > mesh:
        errs.append(f"saturation order FHT {fht} > hexamesh {hexa} > "
                    f"mesh {mesh} does not hold")
    for r in frame.rows:
        if r["substrate"] == "organic" and \
                r["sim_saturation"] > 1.1 * r["analytic_saturation"]:
            errs.append(f"{r['topology']}: sim {r['sim_saturation']} > "
                        f"1.1 x analytic {r['analytic_saturation']}")
    return errs


def check_bitwise(kernel, ref) -> list[str]:
    """Every raw counter of every cell bitwise equal across allocators."""
    import numpy as np
    errs = []
    for row, a, b in zip(kernel.rows, kernel.results, ref.results):
        for k in RAW:
            if not np.array_equal(a[k], b[k]):
                errs.append(f"{row['topology']}/{row['substrate']}: {k} "
                            f"differs between pallas and jnp")
    return errs


def smoke(dev, cfg, n_a: int = 64, n_b: int = 256) -> list[str]:
    """Run grids (a) at N=n_a and (b) at N=n_b under `cfg`, print what
    was measured, and return the failed checks."""
    import repro.experiments as X
    from repro.core import topology as T
    counter = _CompileCounter()
    grid = lambda name, topos, n, subs, c: X.Experiment.grid(
        topos, [n], substrates=subs, rates=X.SaturationGrid(N_RATES),
        cfg=c, name=name)
    exp_a = grid(f"grid_a_n{n_a}", list(T.GENERATORS), n_a,
                 ["organic", "glass"], cfg)
    exp_b = grid(f"grid_b_n{n_b}", list(GRID_B), n_b, ["organic"], cfg)
    exp_ref = grid(f"grid_a_n{n_a}_jnp", list(T.GENERATORS), n_a,
                   ["organic", "glass"], cfg._replace(alloc="jnp"))

    errs, frames = [], {}
    for exp in (exp_a, exp_b):
        frame, cold, runners, xla = _run(exp, counter)
        _, warm, wr, wx = _run(exp, counter)
        frames[exp.name] = frame
        print(f"{exp.name}: {len(frame.rows)} cells, cold {cold:.3f} s "
              f"({runners} runner compiles, {xla} XLA compiles), "
              f"warm {warm:.3f} s ({wr} runner / {wx} XLA compiles)")
    for exp, topology in ((exp_a, "folded_hexa_torus"),
                          (exp_b, "flattened_butterfly")):
        if "tpu_custom_call" in _compiled_text(exp, topology):
            print(f"{exp.name}: {topology} runner holds tpu_custom_call")
        else:
            errs.append(f"{exp.name}: no tpu_custom_call in the compiled "
                        f"{topology} runner")
    ref, cold, runners, xla = _run(exp_ref, counter)
    print(f"{exp_ref.name}: cold {cold:.3f} s ({runners} runner compiles, "
          f"{xla} XLA compiles)")
    errs += check_bitwise(frames[exp_a.name], ref)
    errs += check_anchors(frames[exp_a.name])
    for name in (exp_a.name, exp_b.name):
        for r in frames[name].rows:
            if r["substrate"] == "organic":
                print(f"  {name} {r['topology']:20s} sim "
                      f"{r['sim_saturation']:.4f} analytic "
                      f"{r['analytic_saturation']:.4f}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    print(f"XLA compiles in total: {counter.n}")
    return errs


def main() -> int:
    dev = require_tpu()
    from repro.compile_cache import use_compile_cache
    from repro.core.simulator import resolve_alloc
    from benchmarks.common import BENCH_SIM_CFG
    cache_dir = use_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {cache_dir}")
    if resolve_alloc(BENCH_SIM_CFG.alloc) != "pallas":
        print("FAIL alloc='auto' does not resolve to the kernel",
              file=sys.stderr)
        return 1
    errs = smoke(dev, BENCH_SIM_CFG._replace(cycles=SMOKE_CYCLES,
                                             warmup=SMOKE_WARMUP))
    for e in errs:
        print(f"FAIL {e}", file=sys.stderr)
    if errs:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
