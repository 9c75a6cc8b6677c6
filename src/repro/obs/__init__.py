"""Opt-in observability (DESIGN.md §13): tracing, metrics, flight data.

Two halves, both off by default and bitwise-inert when off:

  * **host-side**: `trace(...)` spans (Chrome-trace/Perfetto JSON via
    `save_chrome_trace`) and a process-wide `metrics` registry (counters,
    events, JSONL log) that also absorbs the simulator/routing cache
    hit/miss/eviction counters;
  * **in-sim**: the flight recorder — `SimConfig(telemetry=True)` makes
    the batched simulator carry per-link/per-port counter tensors
    through the scan; `obs.flight` turns them into tidy per-link rows
    and `obs.report` into link-load heatmap/summary CSVs.

PR 10 (DESIGN.md §16) adds the performance half: time-windowed
telemetry (`SimConfig(telemetry_windows=W)` -> `window_rows` /
`write_window_reports` time-heatmaps), opt-in XLA cost/memory
profiling per compiled runner (`obs.profile`), and the structured
benchmark harness + regression gate (`obs.bench`,
`python -m repro.obs.bench compare`).

Always on, unlike the rest: the set-up counters — JAX's compile
pipeline per function (`jit.trace_s:<fun>`, `jit.lower_s:<fun>`,
`jit.compile_s:<fun>`, `jit.cache_load_s`, `jit.cache_hits`,
`jit.cache_misses`; the listener is installed once, by this import) and
routing builds (`routing.build_s`).  They record only when a function
compiles or a routing misses its cache, never on a warm call, and are
read through `metrics.snapshot()`: which runner compiled, and how long
its trace, lowering and compile (or cache load) took.  With the flight
recorder on, `flight.calls` and `flight.d2h_bytes` count its copies to
the host.

`phase_maps()` (`obs.phases`) names, for each compiled instruction of
every runner called while span tracing or profiling was on, the step
phase it belongs to, so a device trace's ops (`fusion.264`) can be read
by phase.
"""
from .trace import (Span, clear_trace, disable_tracing, enable_tracing,  # noqa
                    get_spans, save_chrome_trace, span_summary, trace,
                    tracing_enabled)
from .metrics import (MetricsRegistry, cache_counters,  # noqa
                      install_compile_listeners, metrics)
from .flight import link_rows, window_rows, LINK_COLUMNS, WINDOW_COLUMNS  # noqa
from .report import (gini, link_load_summary, window_summary,  # noqa
                     write_link_reports, write_window_reports)
from .profile import (ProfileRegistry, clear_profiles, disable_profiling,  # noqa
                      enable_profiling, get_profiles, profiling_enabled)
from .bench import (BENCH_SCHEMA_VERSION, bench_doc, compare,  # noqa
                    load_bench, write_bench)
from .phases import (OUTSIDE, PHASES, RunnerPhases, hlo_phases,  # noqa
                     phase_maps)

install_compile_listeners()
