"""Benchmark orchestrator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--sim] [--only NAME]

Prints ``name,us_per_call,derived`` CSV per bench; per-figure CSVs land
in results/.  Default mode uses the analytic channel-load model (the
cycle-accurate simulator cross-validates it in tests and via --sim).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import use_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper's full 16..256 size sweep")
    ap.add_argument("--sim", action="store_true",
                    help="cycle-accurate simulator instead of analytic")
    ap.add_argument("--only", default=None)
    ap.add_argument("--verify", action="store_true",
                    help="certify every benchmarked topology deadlock-"
                         "free (repro.analysis) before running figures")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.verify:
        # static preflight: a figure produced from an uncertified
        # routing is not worth the simulation time it costs
        from repro.analysis import analyze, builtin_names
        rep = analyze(names=builtin_names())
        print(f"# preflight: {rep.summary()}", file=sys.stderr)
        if not rep.ok:
            for d in rep.errors():
                print(f"# {d}", file=sys.stderr)
            sys.exit(1)

    from . import paper_benches as P
    sizes = P.SIZES_FULL if args.full else None

    print("name,us_per_call,derived")
    for name, fn in P.BENCHES.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        kw = {}
        if "sizes" in fn.__code__.co_varnames:
            kw["sizes"] = sizes
        if "use_sim" in fn.__code__.co_varnames and args.sim:
            kw["use_sim"] = True
        derived = fn(**kw)
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
