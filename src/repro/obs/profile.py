"""Compile-time cost/memory profiling for batched runners (DESIGN.md §16).

`run_batch` asks XLA what each compiled executable costs — analytic
FLOPs / bytes-accessed from `cost_analysis()` and the buffer breakdown
from `memory_analysis()` — and records one profile per runner-cache key
(padded shape + SimConfig + alloc impl + kmax + backend).  The key
mirrors `get_batch_runner`'s cache key on purpose: the executable is a
function of the *padded* shape, not of any individual topology, so the
profile answers "what does this PadShape cost to run", which is exactly
the denominator the pad-waste investigation divides live work by.

While profiling or span tracing (`obs.trace`) is on, `run_batch` also
notes each runner it calls under the same key: the runner (weakly) and
the abstract arguments of its call, from which `obs.phases` builds the
runner's phase map when asked.  A note costs no lowering or compile.

Design constraints, matching `obs.trace`:

  * **off is free**: profiling is disabled by default and the hot-path
    check is one attribute read; nothing is lowered or compiled unless
    a caller opted in;
  * **never in timed regions**: on jax 0.9, `lower().compile()` after
    the runner's first call finds the traced, lowered and compiled
    program in the jit's caches (about a millisecond, no retrace or
    compile; `repro.obs.phases` relies on the same), but a capture
    before the first call compiles the runner, and the cost and memory
    analyses take host time of their own.  Benchmarks therefore profile
    in a separate untimed pass — the registry keeps one profile per
    executable;
  * **robust to backend gaps**: `cost_analysis`/`memory_analysis` are
    best-effort across backends; missing fields record as None rather
    than raising mid-experiment.
"""
from __future__ import annotations

import threading
import time
import weakref

__all__ = [
    "ProfileRegistry", "PROFILER", "profiling_enabled",
    "enable_profiling", "disable_profiling", "clear_profiles",
    "get_profiles", "noted_runners", "record_runner",
]


def _cost_fields(compiled) -> dict:
    """Read `cost_analysis()` (one properties dict, keys like 'flops',
    'bytes accessed') into {flops, bytes_accessed, transcendentals};
    None where a field is absent."""
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:
        ca = {}
    names = dict(flops="flops", bytes_accessed="bytes accessed",
                 transcendentals="transcendentals")
    return {field: None if ca.get(key) is None else float(ca[key])
            for field, key in names.items()}


def _memory_fields(compiled) -> dict:
    """Buffer breakdown from `memory_analysis()` (CompiledMemoryStats)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    fields = dict(temp_bytes="temp_size_in_bytes",
                  argument_bytes="argument_size_in_bytes",
                  output_bytes="output_size_in_bytes",
                  generated_code_bytes="generated_code_size_in_bytes")
    return {name: (int(getattr(ma, attr)) if ma is not None
                   and getattr(ma, attr, None) is not None else None)
            for name, attr in fields.items()}


class ProfileRegistry:
    """Thread-safe once-per-executable profile store."""

    def __init__(self):
        self._lock = threading.Lock()
        self._profiles: dict = {}
        self._noted: dict = {}
        self._enabled = False

    # ---- lifecycle -----------------------------------------------------
    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    @property
    def enabled(self) -> bool:
        return self._enabled

    def clear(self) -> None:
        with self._lock:
            self._profiles = {}
            self._noted = {}

    # ---- runners noted for their phase maps ----------------------------
    def note(self, key: tuple, runner, args, **info) -> None:
        """Keep `runner` (weakly) and the abstract arguments of a call,
        once per key; `info` rides along (`obs.phases` reads it)."""
        with self._lock:
            if key in self._noted:
                return
        import jax
        entry = dict(info, runner=weakref.ref(runner), args=jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        with self._lock:
            self._noted.setdefault(key, entry)

    def noted(self) -> list[dict]:
        """The notes whose runner is still alive; a runner the runner
        cache let go of drops out."""
        with self._lock:
            for key in [k for k, e in self._noted.items()
                        if e["runner"]() is None]:
                del self._noted[key]
            return list(self._noted.values())

    # ---- capture -------------------------------------------------------
    def capture(self, key: tuple, runner, args) -> dict:
        """Profile one jitted runner, once per key (cached thereafter).

        AOT-lowers and compiles `runner(*args)` — a real compile, so
        call this outside any timed region — and records the XLA cost
        and memory analyses plus the compile wall-clock.
        """
        with self._lock:
            prof = self._profiles.get(key)
        if prof is not None:
            return prof
        t0 = time.perf_counter()
        compiled = runner.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        prof = dict(key=[_jsonable(k) for k in key],
                    compile_s=round(compile_s, 4),
                    **_cost_fields(compiled), **_memory_fields(compiled))
        with self._lock:
            self._profiles.setdefault(key, prof)
        return prof

    def profiles(self) -> list[dict]:
        """All captured profiles (insertion order)."""
        with self._lock:
            return list(self._profiles.values())


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


# ---------------------------------------------------------------------
# process-wide default registry + module-level convenience API
# ---------------------------------------------------------------------

PROFILER = ProfileRegistry()


def profiling_enabled() -> bool:
    return PROFILER.enabled


def enable_profiling() -> None:
    PROFILER.enable()


def disable_profiling() -> None:
    PROFILER.disable()


def clear_profiles() -> None:
    PROFILER.clear()


def get_profiles() -> list[dict]:
    return PROFILER.profiles()


def noted_runners() -> list[dict]:
    return PROFILER.noted()


def record_runner(shape, cfg, alloc_impl: str, kmax: int,
                  runner, args) -> dict | None:
    """Note a batched runner under its runner-cache key, and profile it
    when profiling is enabled (returns the profile, else None).

    Called by `run_batch` while profiling or span tracing is on; the key
    mirrors `get_batch_runner` so one note and one profile per compiled
    executable, however many topologies share it.
    """
    import jax
    key = (shape.n, shape.p, shape.c, shape.d, cfg, alloc_impl, kmax,
           jax.default_backend())
    PROFILER.note(key, runner, args, shape=str(shape), cfg=cfg, kmax=kmax)
    return PROFILER.capture(key, runner, args) if PROFILER.enabled else None
