"""Workload engine: time-varying traffic through the batched sweep.

A workload is a `Schedule` of `Phase`s — (traffic matrix, intensity,
duration, burstiness) tuples — replayed cyclically by the cycle
simulator (DESIGN.md §9).  Three generator families:

  * `collective_workload` — the collectives of a sharded LLM training
    step mapped onto chiplet positions, sized from the published
    architectures of `ARCHS` (`arch_sizes("qwen3-1.7b")`);
  * `trace_workload` — loadable region traces (generalizes the old
    hard-coded `traffic.TRACE_PROFILES`), with ON/OFF bursts;
  * `synthetic` — adversarial phase-alternating / hotspot-drift /
    bursty-uniform schedules.

Run them with `SweepEngine.run_workloads` (topologies x workloads in
few batched compiled programs) or directly via
`simulator.run_batch(specs, rates, schedules=...)`.
"""
from .collective import (ARCHS, arch_sizes, collective_workload,
                         collective_workloads, default_mesh_shape)
from .mixed import mixed_tenant, mixed_tenant_workload, superimpose
from .schedule import Phase, Schedule, Workload, static_schedule
from .synthetic import bursty_uniform, hotspot_drift, phase_alternating
from .traces import (Trace, TraceRegion, builtin_traces, load_trace,
                     trace_workload, trace_workloads)

__all__ = [
    "Phase", "Schedule", "Workload", "static_schedule",
    "ARCHS", "arch_sizes",
    "collective_workload", "collective_workloads", "default_mesh_shape",
    "mixed_tenant", "mixed_tenant_workload", "superimpose",
    "trace_workload", "trace_workloads", "Trace", "TraceRegion",
    "builtin_traces", "load_trace",
    "phase_alternating", "hotspot_drift", "bursty_uniform",
]
