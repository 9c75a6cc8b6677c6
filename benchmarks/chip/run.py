"""Run one benchmark cell on the accelerator and print its result line.

    python3 benchmarks/chip/run.py --workload paper_n64.table3_uniform \
        --seed 7 --seconds 10 --trace 0

Cells, configurations, traffic mixes and metrics are named in
`BENCHMARK.json` at the root of the checkout.  The run needs a TPU with
as many chips as the cell asks for: without one it exits non-zero and
prints no result.  JAX's persistent compile cache lives in the checkout
(`.jax_cache/`, or `$JAX_COMPILATION_CACHE_DIR`), so only the first run
of a cell compiles.  With `--trace 0` the last stdout line carries the
cell's end-to-end metrics; with `--trace 1` one traced pass gives its
per-layer metrics, the device's busy time and a breakdown.  The numbers
compared against the reference are printed, each with its limit, as the
last lines of stderr and under `checks` in the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_devices(chips: int):
    """The run's TPU devices; exits non-zero when there are too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"this benchmark needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform} device(s) "
                 f"({devs[0].device_kind})")
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.load_cell(args.workload)
    devices = require_devices(cell.chips)
    from repro.compile_cache import use_compile_cache
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}; compile cache {use_compile_cache()}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, devices)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
