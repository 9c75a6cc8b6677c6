"""A whole benchmark run with the timed path broken underneath must come
out not correct.  The run skips the harness's look for a chip (CPU, a
16-chiplet cell) and breaks `simulator.run_batch`, which every engine
call of the window goes through, in each way this cell can fail: the
step leaves the state as it found it, half of the batch is left out
and filled with the mean of the rest, or one counter is altered where
it is produced.  With the flight recorder on, one windowed counter is
altered, or a window boundary moves by a cycle.  (One chip: there is
no exchange between chips.)"""
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
    """One counter of every scenario in the call altered, so no sample
    of the check can miss the call."""
    for res in out:
        res["delivered"] = np.asarray(res["delivered"]).copy()
        res["delivered"][-1] += 1


def _run_broken(fault, monkeypatch, **config):
    """A whole run of a 16-chiplet cell with `fault` applied to what each
    `run_batch` call returns (`fault(out, real, args, kwargs)` may also
    run the real one again)."""
    from repro.core import topology as T
    monkeypatch.setattr(H, "load_layout", lambda t, n: (
        T.build(t, n).pos, T.build(t, n).edges))
    real = sim.run_batch

    def broken(*a, **kw):
        return fault(real(*a, **kw), real, a, kw)

    monkeypatch.setattr(sim, "run_batch", broken)
    cfg = json.loads((ROOT / "benchmarks/chip/configs/paper_n64.json")
                     .read_text())
    cfg.update(n=16, cycles=60, warmup=20, **config)
    mix = dict(topologies=["mesh", "folded_hexa_torus", "kite_small"],
               substrates=["glass"], patterns=["uniform"], n_rates=8,
               headroom=2.0, pattern_seed=7, check_scenarios=2)
    cell = H.Cell("faulty", cfg, mix, BENCH["end_to_end"], [])
    return H.run_cell(cell, 2 ** 31 + 3, 0.2, False, time.perf_counter(),
                      jax.devices(), log=lambda *_: None)


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _altered],
                         ids=["state_unchanged", "half_batch_mean",
                              "answer_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    def apply(out, *_):
        fault(out)
        return out

    res = _run_broken(apply, monkeypatch)
    assert res["correct"] is False
    assert res["checks"]["counter_mismatches"]["value"] > 0


def _busy_w_altered(out, *_):
    """One `link_busy_w` element of every scenario altered."""
    for res in out:
        res["link_busy_w"] = np.asarray(res["link_busy_w"]).copy()
        res["link_busy_w"][-1, 1, 0] += 1
    return out


def _window_shifted(out, real, args, kw):
    """The boundary between windows 0 and 1 a cycle late: the real run
    again with one window per measured cycle, binned so that window 1's
    first cycle counts in window 0.  `window_cycles` stays as the
    program reports it."""
    cfg = args[2] if len(args) > 2 else kw.pop("cfg")
    meas, W = cfg.cycles - cfg.warmup, cfg.telemetry_windows
    per_cycle = real(*args[:2], cfg._replace(telemetry_windows=meas),
                     **kw)
    w = np.arange(meas) * W // meas
    w[np.argmax(w == 1)] = 0
    for res, fine in zip(out, per_cycle):
        for k in ("link_busy_w", "link_stall_w", "link_occ_w",
                  "inj_node_w", "eject_node_w"):
            v = np.asarray(fine[k])
            res[k] = np.stack([v[:, w == j].sum(1) for j in range(W)], 1)
    return out


@pytest.mark.parametrize("fault", [_busy_w_altered, _window_shifted],
                         ids=["busy_w_altered", "window_shifted"])
def test_broken_flight_recorder_is_not_correct(fault, monkeypatch):
    res = _run_broken(fault, monkeypatch, telemetry=True,
                      telemetry_windows=4)
    assert res["correct"] is False
    assert res["checks"]["counter_mismatches"]["value"] > 0
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["compared"]["delivered"][1] == 0
    assert res["compared"]["link_busy_w"][1] > 0

