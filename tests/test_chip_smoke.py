"""CPU checks of the chip smoke script's guards and of the compile-cache
helper the entry points call (the script itself needs a TPU)."""
import importlib.util
import pathlib
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import compile_cache

_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", _ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_require_tpu_refuses_cpu(chip_smoke):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.require_tpu()


def _row(topology, sim, analytic, substrate="organic"):
    return dict(topology=topology, substrate=substrate,
                sim_saturation=sim, analytic_saturation=analytic)


def test_check_anchors(chip_smoke):
    good = [_row("mesh", 0.09, 0.10), _row("hexamesh", 0.35, 0.41),
            _row("folded_hexa_torus", 0.42, 0.51),
            _row("torus", 0.30, 0.10, substrate="glass")]
    assert chip_smoke.check_anchors(SimpleNamespace(rows=good)) == []
    swapped = [_row("mesh", 0.09, 0.10), _row("hexamesh", 0.45, 0.50),
               _row("folded_hexa_torus", 0.42, 0.51)]
    assert len(chip_smoke.check_anchors(SimpleNamespace(rows=swapped))) == 1
    over = good + [_row("torus", 0.30, 0.10)]
    errs = chip_smoke.check_anchors(SimpleNamespace(rows=over))
    assert len(errs) == 1 and errs[0].startswith("torus")


@pytest.mark.parametrize("key", ["delivered", "offered_n", "accepted_n",
                                 "lat_sum"])
def test_check_bitwise_flags_each_counter(chip_smoke, key):
    res = {k: np.arange(4) for k in chip_smoke.RAW}
    rows = [dict(topology="mesh", substrate="organic")]
    frame = SimpleNamespace(rows=rows, results=[res])
    assert chip_smoke.check_bitwise(frame, frame) == []
    off = dict(res, **{key: res[key] + 1})
    errs = chip_smoke.check_bitwise(
        frame, SimpleNamespace(rows=rows, results=[off]))
    assert len(errs) == 1 and key in errs[0]


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_compile_cache_defaults_inside_checkout(monkeypatch,
                                               restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.use_compile_cache()
    assert got == str(_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
