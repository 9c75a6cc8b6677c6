"""The set-up readers (`runner_build_s`, `runner_load_s`,
`routing_build_s`): they read the program's compile-pipeline and
routing-build counters after a small run on the CPU, next to a traced
window that ran a runner on the device, and read nothing from an empty
registry or from a window with no runner program."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import trace_reduce as TRD  # noqa: E402

SETUP = ("runner_build_s", "runner_load_s", "routing_build_s")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _ctx(modules):
    red = TRD.Reduced(window=(0.0, 1e9), ops=[], modules=modules, busy=[],
                      spans=[])
    return H.MetricContext(red=red, config={}, peak={}, window_wall_ns=1e9)


RAN = _ctx([("jit_runner(1)", 0.0, 5e8)])


@pytest.fixture(scope="module")
def small_run():
    """A routing built anew and a runner compiled at a shape of its own."""
    from repro.core import routing as RT, topology as T, traffic as TR
    from repro.core.simulator import SimConfig, make_spec, run_batch
    RT.routing_cache_clear()
    topo = T.build("folded_hexa_torus", 16)
    r = RT.routing_for(topo)
    run_batch([make_spec(r, TR.uniform(topo))],
              np.array([0.1, 0.3], np.float32),
              SimConfig(cycles=53, warmup=13))


@pytest.mark.parametrize("name", SETUP)
def test_setup_reader_reads_the_counters_after_a_run(small_run, name):
    v = H.load_metric(name)(RAN)
    assert v is not None and v > 0


@pytest.mark.parametrize("name", SETUP)
def test_setup_reader_is_none_on_an_empty_registry(monkeypatch, name):
    import repro.obs
    from repro.obs.metrics import MetricsRegistry
    monkeypatch.setattr(repro.obs, "metrics", MetricsRegistry())
    assert H.load_metric(name)(RAN) is None


@pytest.mark.parametrize("name", SETUP)
def test_setup_reader_is_none_without_a_runner_on_the_device(small_run,
                                                             name):
    assert H.load_metric(name)(_ctx([])) is None


def test_setup_metrics_move_setup_in_every_cell():
    cells = [w["name"] for w in BENCH["workloads"]]
    got = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in SETUP}
    assert set(got) == set(SETUP)
    for m in got.values():
        assert m["moves"] == "setup_s" and m["unit"] == "s"
        assert m["source"] == "program_span" and m["workloads"] == cells
