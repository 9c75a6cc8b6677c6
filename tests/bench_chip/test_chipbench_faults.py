"""A whole benchmark run with the timed path broken underneath must come
out not correct.  The run skips the harness's look for a chip (CPU, a
16-chiplet cell) and breaks `simulator.run_batch`, which every engine
call of the window goes through, in each way this cell can fail: the
step leaves the state as it found it, half of the batch is left out
and filled with the mean of the rest, or one counter is altered where
it is produced.  (One chip: there is no exchange between chips.)"""
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402

from repro.core import simulator as sim  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _unchanged(out):
    for res in out:
        for k in H.RAW:
            res[k] = np.zeros_like(res[k])


def _half_left_out(out):
    for res in out:
        for k in H.RAW:
            v = np.asarray(res[k])
            half = len(v) // 2
            res[k] = np.concatenate(
                [v[:half], np.full(len(v) - half,
                                   np.rint(v[:half].mean()), v.dtype)])


def _altered(out):
    out[0]["delivered"] = np.asarray(out[0]["delivered"]).copy()
    out[0]["delivered"][-1] += 1


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _altered],
                         ids=["state_unchanged", "half_batch_mean",
                              "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.core import topology as T
    monkeypatch.setattr(H, "load_layout", lambda t, n: (
        T.build(t, n).pos, T.build(t, n).edges))
    real = sim.run_batch

    def broken(*a, **kw):
        out = real(*a, **kw)
        fault(out)
        return out

    monkeypatch.setattr(sim, "run_batch", broken)
    cfg = json.loads((ROOT / "benchmarks/chip/configs/paper_n64.json")
                     .read_text())
    cfg.update(n=16, cycles=60, warmup=20)
    mix = dict(topologies=["mesh", "folded_hexa_torus", "kite_small"],
               substrates=["glass"], patterns=["uniform"], n_rates=8,
               headroom=2.0, pattern_seed=7, check_scenarios=2)
    cell = H.Cell("faulty", cfg, mix, BENCH["end_to_end"], [])
    res = H.run_cell(cell, 2 ** 31 + 3, 0.2, False, time.perf_counter(),
                     jax.devices(), log=lambda *_: None)
    assert res["correct"] is False
    assert res["checks"]["counter_mismatches"]["value"] > 0
