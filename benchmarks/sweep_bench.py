"""Batched-vs-looped sweep wall-clock benchmark (DESIGN.md §6/§7).

    PYTHONPATH=src python -m benchmarks.sweep_bench [--smoke]

Measures the paper's core evaluation loop — K topologies x R injection
rates through the cycle simulator — two ways:

  * looped:  one compiled program per topology (the seed behaviour),
    driven by the primitive `run_batch`;
  * batched: the same grid described as a declarative `Experiment` and
    executed through `repro.experiments` (DESIGN.md §10), which lowers
    it onto a handful of padded `SweepEngine` programs.

The plan (routing, specs, rate grids) is resolved before the clock
starts for both paths, so cold times isolate compile + run cost.  The
two paths are checked bitwise-equal, counter for counter, before any
number is reported.  Results land in results/sweep_speedup.csv
(schema-stamped).  --smoke shrinks the grid so the whole benchmark
finishes well under a minute (the `make bench-smoke` target).
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

import repro.experiments as X
from repro.core import simulator as sim
from repro.core.simulator import SimConfig, run_batch
from repro.obs.metrics import metrics
from repro.compile_cache import use_compile_cache

from .common import RESULTS_DIR, write_csv
from .harness import BenchRun

SMOKE = dict(names=("mesh", "folded_torus", "hexamesh",
                    "folded_hexa_torus"),
             n=16, n_rates=4, cycles=300, warmup=100)
FULL = dict(names=("mesh", "folded_torus", "hexamesh",
                   "folded_hexa_torus", "octamesh", "kite_medium"),
            n=36, n_rates=8, cycles=1500, warmup=500)


def _fresh_cache():
    """Clear the compiled-runner LRU so cold timings include compile."""
    sim._RUNNER_CACHE.clear()


def bench_speedup(smoke: bool = True) -> dict:
    params = SMOKE if smoke else FULL
    cfg = SimConfig(cycles=params["cycles"], warmup=params["warmup"])
    engine = X.engine_for(cfg)
    exp = X.Experiment(
        [X.Scenario(name, params["n"],
                    rates=X.SaturationGrid(params["n_rates"]))
         for name in params["names"]],
        cfg=cfg, name="sweep_bench")
    # resolve routing/specs/rates untimed; single_program mirrors the
    # seed bench's semantics (the whole grid as ONE compiled program)
    pl = X.plan(exp, engine, single_program=True)
    planned = sorted((ps for b in pl.buckets for ps in b.items),
                     key=lambda ps: ps.index)
    raw_keys = ("delivered", "offered_n", "accepted_n", "lat_sum")

    def looped():
        out = []
        for ps in planned:
            res = run_batch([ps.spec], ps.rates[None, :], cfg)[0]
            # same per-scenario tidy-row derivation the executor's
            # ResultFrame performs, so the timings compare like-for-like
            X.scenario_row(exp, ps, res)
            out.append(res)
        return out

    def batched():
        return X.execute(pl, engine=engine)   # few padded programs

    _fresh_cache()
    t0 = time.time()
    loop_res = looped()
    looped_cold = time.time() - t0
    t0 = time.time()
    looped()
    looped_warm = time.time() - t0

    _fresh_cache()
    t0 = time.time()
    frame = batched()
    batched_cold = time.time() - t0
    t0 = time.time()
    batched()
    batched_warm = time.time() - t0

    # warm host/device split (DESIGN.md §16): one extra WARM pass with
    # spans + XLA profiling on, never timed — device time is the
    # `sim.wait` total (block_until_ready), host time is the plan /
    # stack / dispatch orchestration around it.  This is the pass that
    # answers where the 0.82x-warm number's time actually goes.
    run = BenchRun("sweep", mode="smoke" if smoke else "full")
    frame2 = run.observed_pass(batched)
    split = run.device_host_split()
    warm_device = split["device_s"]
    warm_host = round(max(batched_warm - warm_device, 0.0), 4)

    # pad-waste accounting: per-scenario live-work fraction (from the
    # runner's pad_fill) and the engine's bucket fill (live rows /
    # padded rows), both straight off the observed pass
    pf = [r["pad_fill"]["state"] for r in frame2.results
          if r is not None]
    pad_fill = round(float(np.mean(pf)), 4) if pf else None
    bf = metrics.snapshot().get("sweep.bucket_fill")
    bucket_fill = round(bf["sum"] / bf["count"], 4) if bf else None

    equal = all(np.array_equal(a[k], frame.results[ps.index][k])
                for a, ps in zip(loop_res, planned) for k in raw_keys)
    out = dict(n_topologies=len(planned), n_rates=params["n_rates"],
               n=params["n"], cycles=params["cycles"],
               looped_cold_s=round(looped_cold, 3),
               looped_warm_s=round(looped_warm, 3),
               batched_cold_s=round(batched_cold, 3),
               batched_warm_s=round(batched_warm, 3),
               batched_warm_host_s=warm_host,
               batched_warm_device_s=warm_device,
               pad_fill_state=pad_fill, bucket_fill=bucket_fill,
               cold_speedup=round(looped_cold / max(batched_cold, 1e-9), 2),
               warm_speedup=round(looped_warm / max(batched_warm, 1e-9), 2),
               bitwise_equal=equal, mode="smoke" if smoke else "full")
    write_csv(os.path.join(RESULTS_DIR, "sweep_speedup.csv"), [out])

    run.metrics({k: v for k, v in out.items()
                 if isinstance(v, (int, float))
                 and not isinstance(v, bool)
                 and k not in ("cold_speedup", "warm_speedup")})
    run.metric("cold_speedup", out["cold_speedup"], direction="higher")
    run.metric("warm_speedup", out["warm_speedup"], direction="higher")
    run.metric("pad_fill_state", pad_fill, direction="higher")
    run.metric("bucket_fill", bucket_fill, direction="higher")
    run.extra(bitwise_equal=equal, csv_row=out)
    run.finish()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid, finishes in well under 60 s")
    args = ap.parse_args(argv)
    use_compile_cache()
    out = bench_speedup(smoke=args.smoke)
    for k, v in out.items():
        print(f"{k}: {v}")
    if not out["bitwise_equal"]:
        raise SystemExit("batched results diverged from looped results")


if __name__ == "__main__":
    main()
