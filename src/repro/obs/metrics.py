"""Unified metrics registry (DESIGN.md §13): counters, events, JSONL.

One process-wide `metrics` instance gathers the host-side numbers that
used to live in ad-hoc dicts: sweep-engine run/compile stats, executor
chunk outcomes, synthesis generation counts.  Three primitives:

  * `inc(name, n)` — monotonic counters (thread-safe);
  * `observe(name, value)` — running count/sum/min/max of a value
    (wall-clock seconds, batch sizes, ...);
  * `event(name, **fields)` — an append-only structured log entry,
    wall-clock stamped, optionally mirrored to a JSONL sink file
    (`set_sink`), so failures and skips are never silent.

`snapshot()` additionally absorbs the two LRU caches that predate this
registry — `simulator.runner_cache_info()` and
`routing.routing_cache_info()` — under `cache.runner.*` /
`cache.routing.*` keys, and `cache_counters()` exposes just those
monotonic hit/miss/eviction counters for before/after deltas (the
sweep engine counts compiles this way: a *miss* delta counts new
compiled programs exactly, where the old sum-of-entries subtraction
could be shrunk by an LRU eviction between the two reads and
misattribute compiles).

Two more sources record into the registry always, whether tracing is on
or off, and only when work is built — never on a warm call:

  * **compile pipeline** (`install_compile_listeners`, installed once
    per process when `repro.obs` is imported): JAX's own
    `jax.monitoring` events, one observation per compile stage of each
    jitted function, keyed by the function's name as JAX gives it —
    `jit.trace_s:<fun>` (jaxpr trace: `runner`), `jit.lower_s:<fun>`
    (MLIR lowering: `jit(runner)`) and `jit.compile_s:<fun>` (the
    backend compile, or on a persistent-cache hit the retrieval and
    load of the executable: `jit(runner)`).  A nested jit's trace lies
    inside its caller's and is keyed by its own name, never added to
    the caller's.  `jit.cache_load_s` totals the persistent-cache
    retrievals (JAX names no function for them; they lie inside
    `jit.compile_s`, so never add the two); `jit.cache_hits` /
    `jit.cache_misses` count persistent-cache hits and writes;
  * **routing builds** (`repro.core.routing.routing_for` on a cache
    miss): `routing.build_s`.
"""
from __future__ import annotations

import json
import os
import threading
import time


class MetricsRegistry:
    """Thread-safe counters + observations + structured event log."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._observations: dict[str, dict] = {}
        self._events: list[dict] = []
        self._sink: str | None = None
        self._buffered = False
        self._pending: list[str] = []

    # ---- counters ------------------------------------------------------
    def inc(self, name: str, value: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def get(self, name: str, default: float = 0) -> float:
        with self._lock:
            return self._counters.get(name, default)

    # ---- observations --------------------------------------------------
    def observe(self, name: str, value: float) -> None:
        with self._lock:
            o = self._observations.get(name)
            if o is None:
                o = self._observations[name] = dict(
                    count=0, sum=0.0, min=value, max=value)
            o["count"] += 1
            o["sum"] += value
            o["min"] = min(o["min"], value)
            o["max"] = max(o["max"], value)

    # ---- events --------------------------------------------------------
    def set_sink(self, path: str | None, *, buffered: bool = False
                 ) -> None:
        """Mirror every subsequent event to `path` as one JSON line.

        buffered=True holds lines in memory until `flush()` /
        `close_sink()` — one write syscall per flush instead of per
        event, and nothing hits disk for a sink that is reset before
        flushing.  Switching sinks flushes the old one first so no
        buffered event is ever silently dropped.
        """
        if path is not None:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
        self.flush()
        with self._lock:
            self._sink = path
            self._buffered = buffered

    def event(self, name: str, **fields) -> dict:
        e = dict(event=name, t=time.time(), **fields)
        line = None
        with self._lock:
            self._events.append(e)
            sink = self._sink
            if sink is not None:
                line = json.dumps(e, default=str)
                if getattr(self, "_buffered", False):
                    self._pending.append(line)
                    line = None
        if line is not None:
            with open(sink, "a") as f:
                f.write(line + "\n")
        return e

    def flush(self) -> int:
        """Write buffered event lines to the sink; returns #flushed."""
        with self._lock:
            sink, pending = self._sink, self._pending
            self._pending = []
        if sink is None or not pending:
            return 0
        with open(sink, "a") as f:
            f.write("\n".join(pending) + "\n")
        return len(pending)

    def close_sink(self) -> None:
        """Flush any buffered lines, then detach the sink."""
        self.flush()
        with self._lock:
            self._sink = None
            self._buffered = False

    def events(self, name: str | None = None) -> list[dict]:
        with self._lock:
            evs = list(self._events)
        return evs if name is None else [e for e in evs
                                         if e["event"] == name]

    def save_jsonl(self, path: str) -> int:
        """Write the full event log (one JSON object per line)."""
        evs = self.events()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            for e in evs:
                f.write(json.dumps(e, default=str) + "\n")
        print(f"[obs] wrote {path} ({len(evs)} events)")
        return len(evs)

    # ---- snapshots -----------------------------------------------------
    def snapshot(self) -> dict:
        """Counters + observations + absorbed cache counters."""
        with self._lock:
            out = dict(self._counters)
            out.update({k: dict(v) for k, v in self._observations.items()})
        out.update(cache_counters())
        return out

    def with_prefix(self, prefix: str) -> dict:
        """Counter/observation snapshot filtered to one namespace
        (e.g. "analysis." for the static-verifier counters) — cheap to
        assert on in tests without wading through cache counters."""
        return {k: v for k, v in self.snapshot().items()
                if k.startswith(prefix)}

    def reset(self) -> None:
        """Return the registry to a pristine state: counters,
        observations and events cleared AND the sink detached (buffered
        lines flushed first).  A test or engine that `reset()`s can no
        longer leak events into a sink file another run attached —
        snapshot isolation between runs in one process."""
        self.close_sink()
        with self._lock:
            self._counters.clear()
            self._observations.clear()
            self._events.clear()


def cache_counters() -> dict:
    """Monotonic hit/miss/eviction counters of the two pre-registry
    LRUs, flattened under stable keys.  Misses count cache *builds*
    (compiled runners / routed structures), so a before/after miss
    delta counts new work exactly — immune to concurrent evictions,
    unlike differencing the caches' entry sums."""
    from repro.core.routing import routing_cache_info
    from repro.core.simulator import runner_cache_info
    r = runner_cache_info()
    t = routing_cache_info()
    return {
        "cache.runner.hits": r["hits"],
        "cache.runner.misses": r["misses"],
        "cache.runner.evictions": r["evictions"],
        "cache.runner.size": r["size"],
        "cache.routing.hits": t["hits"],
        "cache.routing.misses": t["misses"],
        "cache.routing.evictions": t["evictions"],
        "cache.routing.size": t["size"],
    }


#: process-wide registry (import `from repro.obs import metrics`)
metrics = MetricsRegistry()


# ---- compile-pipeline counters -------------------------------------------
#: jax.monitoring duration events of one function's compile -> key prefix
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.compile_s",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hits",
    "/jax/compilation_cache/cache_misses": "jit.cache_misses",
}
_listeners_installed = False


def _on_compile_duration(event: str, secs: float, *, fun_name: str = "?",
                         **_kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is not None:
        metrics.observe(f"{stage}:{fun_name}", secs)
    elif event == _CACHE_LOAD:
        metrics.observe("jit.cache_load_s", secs)


def _on_compile_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        metrics.inc(key)


def install_compile_listeners() -> None:
    """Record JAX's compile-pipeline events into `metrics` (idempotent).
    JAX emits them only while it traces, lowers or compiles, so a warm
    call of a compiled function records nothing."""
    global _listeners_installed
    if _listeners_installed:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_duration)
    jax.monitoring.register_event_listener(_on_compile_event)
    _listeners_installed = True
