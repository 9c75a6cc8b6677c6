"""One benchmark cell, from `BENCHMARK.json` to the result line.

A cell names a configuration (`configs/<name>.json`: the modelled system
and the simulator settings) and a traffic mix (`mixes/<name>.json`:
topologies x substrates x patterns and the rate grid).  Its per-layer
metrics are readers `metrics/<name>.py`.  All are found by name.

A run:

  set-up   start JAX, lay out the cell's scenarios in an order drawn
           from the seed (the traffic itself is the mix's fixed draw,
           so every seed runs the same work), and run
           one whole pass, which loads every runner the window uses;
  window   whole passes of `repro.experiments.run(Experiment)` with
           `alloc="auto"` until `seconds` have passed (one traced pass
           with trace=True);
  check    the counters of every window pass, for a sample of the
           scenarios drawn from the seed, against the plain reference
           (`reference.py`), after the peak device memory is read: the
           four raw counters, and with `telemetry` on the flight
           recorder's integer counters too, channels matched by their
           (source, destination) chiplets.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import itertools
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import patterns as PT
from . import reference as R
from . import trace_reduce as TRD
from .peaks import peaks_for

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
RAW = ("delivered", "offered_n", "accepted_n", "lat_sum")
#: the flight recorder's integer counters (`telemetry`), and those of
#: its time windows (`telemetry_windows` > 0)
FLIGHT = ("link_busy", "link_stall", "link_occ_sum", "inj_node",
          "eject_node", "lat_hist")
FLIGHT_W = ("link_busy_w", "link_stall_w", "link_occ_w", "inj_node_w",
            "eject_node_w", "window_cycles")
#: the channel axis of each per-channel counter
CHANNEL_AXIS = {"link_busy": 1, "link_stall": 1, "link_occ_sum": 1,
                "link_busy_w": 2, "link_stall_w": 2, "link_occ_w": 2}
#: where a result carries each channel's (source, destination) chiplets
CHANNELS = "channels"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list
    chips: int = 1


def _load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no {kind}/{name}.json under {HERE}")
    return json.loads(path.read_text())


def load_metric(name: str):
    """The `read(ctx)` function of `metrics/<name>.py`."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no per-layer metric reader named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Cell `name` of `root/BENCHMARK.json`; unknown names are errors."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload named {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names unknown config "
                       f"{w['config']!r}")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    for m in bench["per_layer"]:
        load_metric(m["name"])
    return Cell(name=name, config=config, mix=_load_json("mixes",
                                                         w["traffic"]),
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
                chips=w["chips"])


# ---------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------

@dataclasses.dataclass
class Planned:
    """One scenario of a cell as the benchmark made it."""
    topology: str
    substrate: str
    pattern: str
    net: R.Network            # the reference's own network of the layout
    traffic: np.ndarray       # [n, n], made here from the mix's draw
    rates: np.ndarray         # [R] float64 offered rates


def load_layout(topology: str, n: int) -> tuple:
    """(pos, edges) of `layouts/<topology>.n<n>.json`, the benchmark's
    pinned copy of a Table III layout (centres in pitch units)."""
    d = _load_json("layouts", f"{topology}.n{n}")
    return (np.asarray(d["pos"], np.float64),
            np.asarray(d["edges"], np.int64))


def plan_cell(cell: Cell, seed: int) -> list:
    """The cell's scenarios in an order drawn from `seed`.  Layouts,
    traffic, routing for the analytic bound and the rate grid are the
    benchmark's own; the program builds its layouts from the topology
    names, so a layout of its that moved reads as counters that differ.
    Random patterns come from the mix's `pattern_seed`, not from `seed`:
    the simulator's step time depends on the traffic it carries, so a
    draw per seed would change the work measured."""
    cfg, mix = cell.config, cell.mix
    rng = np.random.default_rng(seed)
    combos = list(itertools.product(mix["substrates"], mix["patterns"],
                                    mix["topologies"]))
    nets: dict = {}
    out = []
    for k in rng.permutation(len(combos)):
        sub, pat, topo_name = combos[k]
        if (topo_name, sub) not in nets:
            pos, edges = load_layout(topo_name, cfg["n"])
            nets[topo_name, sub] = (pos, R.build_network(
                pos, edges, sub, cfg["chiplet_area_mm2"]))
        pos, net = nets[topo_name, sub]
        traffic = PT.PATTERNS[pat](cfg["n"], pos, mix["pattern_seed"])
        analytic = R.analytic_bound(net, traffic)
        out.append(Planned(topology=topo_name, substrate=sub, pattern=pat,
                           net=net, traffic=traffic,
                           rates=PT.rate_grid(analytic, mix["n_rates"],
                                              mix["headroom"])))
    return out


def sim_config(cfg: dict):
    """The `SimConfig` of a configuration: every key that names one of
    its fields, as written, and `sim_seed` as `seed`."""
    from repro.core.simulator import SimConfig
    kw = {k: v for k, v in cfg.items() if k in SimConfig._fields}
    kw["seed"] = cfg["sim_seed"]
    return SimConfig(**kw)


def counter_keys(cfg: dict) -> tuple:
    """The integer counters a configuration switches on."""
    keys = RAW
    if cfg.get("telemetry"):
        keys += FLIGHT
        if cfg.get("telemetry_windows"):
            keys += FLIGHT_W
    return keys


def experiment(cell: Cell, planned: list):
    import repro.experiments as X
    cfg = cell.config
    scen = [X.Scenario(topology=p.topology, n=cfg["n"], substrate=p.substrate,
                       traffic=X.CustomTraffic(p.pattern,
                                               lambda _t, m=p.traffic: m),
                       area=cfg["chiplet_area_mm2"], roles=cfg["roles"],
                       rates=X.ExplicitRates(tuple(p.rates)))
            for p in planned]
    return X.Experiment(scen, cfg=sim_config(cfg), name=cell.name)


def run_pass(exp) -> list:
    """One pass; returns per scenario its integer counters (None if the
    scenario failed).  With the flight recorder on, each also carries
    under `CHANNELS` its channels' [c, 2] (source, destination), read
    where the program's own link rows (`ResultFrame.link_rows`) read
    them: building the rows would cost the window ~0.1 s a pass."""
    import repro.experiments as X
    frame = X.run(exp, on_error="skip")
    keys = counter_keys(exp.cfg._asdict())
    out = []
    for i, (row, res) in enumerate(zip(frame.rows, frame.results)):
        if row["status"] != "ok" or res is None:
            out.append(None)
            continue
        got = {k: np.asarray(res[k]) for k in keys}
        if exp.cfg.telemetry:
            routing = frame.planned[i].routing
            got[CHANNELS] = np.stack([routing.ch_src, routing.ch_dst], 1)
        out.append(got)
    return out


def padded_scenarios(exp) -> set:
    """Indices of scenarios that share an engine call with padding."""
    import repro.experiments as X
    eng = X.engine_for(exp.cfg)
    return {ps.index for b in X.plan(exp, eng).buckets
            if len(b.items) % eng.s_round for ps in b.items}


class CompileCounter:
    """Counts XLA backend compiles (JAX's own monitoring event)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------

def check_sample(cell: Cell, planned: list, padded: set, seed: int) -> list:
    """Scenario indices to check: one that shares a padded engine call
    (where there is one), then the rest drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    k = min(cell.mix["check_scenarios"], len(planned))
    first = [int(rng.choice(sorted(padded)))] if padded else []
    rest = [int(i) for i in rng.permutation(len(planned)) if i not in first]
    return first + rest[:k - len(first)]


def reference_counters(cell: Cell, p: Planned, rotate: bool = True) -> dict:
    """The reference's counters of one scenario; with the flight
    recorder on, also its channels' (source, destination) under
    `CHANNELS`."""
    cfg = cell.config
    ref = R.simulate(p.net, p.traffic, p.rates, cycles=cfg["cycles"],
                     warmup=cfg["warmup"], n_vcs=cfg["n_vcs"],
                     buf_depth=cfg["buf_depth"], seed=cfg["sim_seed"],
                     rotate=rotate, telemetry=cfg.get("telemetry", False),
                     windows=cfg.get("telemetry_windows", 0))
    if cfg.get("telemetry"):
        ref[CHANNELS] = np.stack([p.net.ch_src, p.net.ch_dst], 1)
    return ref


def _channel_index(ends: np.ndarray) -> dict:
    """{(source, destination): channel}; two channels of one pair are an
    error, never merged."""
    index = {}
    for c, pair in enumerate(map(tuple, ends.tolist())):
        if pair in index:
            raise ValueError(f"two channels from chiplet {pair[0]} to "
                             f"{pair[1]}: channels {index[pair]} and {c}")
        index[pair] = c
    return index


def channel_order(got_ends: np.ndarray, ref_ends: np.ndarray):
    """Indices into the program's channel axis in the reference's channel
    order, matched by (source, destination); None where the two sets of
    channels differ."""
    got, ref = _channel_index(got_ends), _channel_index(ref_ends)
    if got.keys() != ref.keys():
        return None
    return np.array([got[pair] for pair in ref], np.int64)


def _differing(got: dict, ref: dict, key: str, order) -> int:
    """Elements of `ref[key]` that `got` does not reproduce."""
    want = ref[key]
    if key not in got:
        return int(want.size)
    have = np.asarray(got[key], np.int64)
    if key in CHANNEL_AXIS:
        axis = CHANNEL_AXIS[key]
        if order is None or have.ndim <= axis or \
                have.shape[axis] != len(order):
            return int(want.size)
        have = np.take(have, order, axis=axis)
    if have.shape != want.shape:
        return int(want.size)
    return int(np.sum(have != want))


def compare(passes: list, refs: dict) -> tuple:
    """Numbers compared, each {value, limit}, and per counter key
    [elements compared, elements that differ].  `passes` holds each window
    pass's counters per scenario, `refs` the reference's counters of the
    sampled scenarios; every key the reference returned is compared,
    per-channel counters channel by channel (`channel_order`)."""
    mismatches, per_key = 0, {}
    for counters in passes:
        for i, ref in refs.items():
            got = counters[i]
            order = None
            if got is not None and CHANNELS in ref and CHANNELS in got:
                order = channel_order(got[CHANNELS], ref[CHANNELS])
            for k in ref:
                if k == CHANNELS:
                    continue
                bad = (int(ref[k].size) if got is None else
                       _differing(got, ref, k, order))
                tally = per_key.setdefault(k, [0, 0])
                tally[0] += int(ref[k].size)
                tally[1] += bad
                mismatches += bad
    failed = sum(c is None for counters in passes for c in counters)
    return ({"counter_mismatches": {"value": mismatches, "limit": 0},
             "failed_scenarios": {"value": failed, "limit": 0}}, per_key)


# ---------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------

@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader sees."""
    red: TRD.Reduced
    config: dict
    peak: dict
    window_wall_ns: float


def _device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def _traced_pass(exp):
    """One pass under the profiler and the program's span tracer."""
    import jax
    import repro.obs as OT          # its `trace` name is the span function
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        OT.clear_trace()
        OT.enable_tracing()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(TRD.WINDOW):
                t0 = time.perf_counter_ns()
                counters = run_pass(exp)
                wall = time.perf_counter_ns() - t0
        finally:
            jax.profiler.stop_trace()
            OT.disable_tracing()
        red = TRD.reduce(TRD.read_planes(TRD.find_xplane(log_dir)),
                         OT.get_spans(), t0)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return counters, wall, red


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, log=print) -> dict:
    """Set up, measure and check one cell; returns the result object.
    `t_start` is the perf_counter reading at process start; `devices`
    the chips the run uses."""
    cfg = cell.config
    counter = CompileCounter()
    planned = plan_cell(cell, seed)
    exp = experiment(cell, planned)
    padded = padded_scenarios(exp)
    run_pass(exp)                              # loads every runner
    setup_s = time.perf_counter() - t_start
    compiles0 = counter.n
    lanes = sum(len(p.rates) for p in planned)
    if trace:
        counters, wall_ns, red = _traced_pass(exp)
        passes, window_s = [counters], wall_ns / 1e9
    else:
        passes, t0 = [], time.perf_counter()
        while True:
            passes.append(run_pass(exp))
            window_s = time.perf_counter() - t0
            if window_s >= seconds:
                break
    compiles = counter.n - compiles0
    device = _device_info(devices)
    log(f"setup {setup_s:.3f} s; window {len(passes)} pass(es) in "
        f"{window_s:.3f} s; {compiles} compiles in the window; peak device "
        f"memory {device['memory_peak_bytes']} bytes")

    sample = check_sample(cell, planned, padded, seed)
    refs = {i: reference_counters(cell, planned[i]) for i in sample}
    checks, compared = compare(passes, refs)
    checks["window_compiles"] = {"value": compiles, "limit": 0}
    failed = sum(len(planned[i].rates) for c in passes
                 for i, x in enumerate(c) if x is None)
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": lanes * len(passes), "failed": failed}
    if trace:
        ctx = MetricContext(red=red, config=cfg,
                            peak=peaks_for(device["kind"]),
                            window_wall_ns=wall_ns)
        metrics = {}
        for m in cell.per_layer:
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red.busy_ns / 1e9, window_s=red.window_ns / 1e9)
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": TRD.top_ops(red),
                                 "idle_gaps": TRD.idle_gaps(red)})
    else:
        work = lanes * cfg["n"] * cfg["cycles"] * len(passes)
        values = {"router_cycles_per_s": work / window_s, "setup_s": setup_s}
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=device)
    result["compared"] = compared
    result["checks"] = checks
    log(f"checked {len(sample)} scenario(s) x {len(planned[0].rates)} rates "
        f"against the reference over {len(passes)} pass(es); elements "
        f"compared / differing per counter: {compared}")
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return result
