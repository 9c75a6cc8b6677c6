"""Cycle-based ICI network simulator, vectorized in JAX (paper §V-B).

BookSim semantics re-expressed as dense array updates so the whole
simulation `lax.scan`s over cycles, `vmap`s over injection rates, and —
since the sweep-engine rework — `vmap`s over *topologies* as well:

  * input-queued routers, V virtual channels x B-flit buffers per input
    port (paper: 4 x 4),
  * credit-based flow control with wire-delayed credit return,
  * two-phase separable switch allocation (rotating priority; an input
    port forwards at most one flit per cycle, an output port accepts at
    most one),
  * per-channel link pipelines whose depth is the Table-IV hop latency
    (router 3 ns + 2 PHY x 2 ns + wire ceil(L*sqrt(eps_r)/c)), cycle=1 ns,
  * one injection queue and one ejection port per chiplet (1 flit/cycle).

Packets are single-flit; multi-flit data packets are injected as bursts
(§V-E traces), which approximates wormhole serialization without ownership
state.  Saturation throughput is measured as the plateau of delivered
throughput over an offered-rate sweep (vmapped), the same quantity BookSim
reports as relative throughput T_r.

Batched execution (DESIGN.md §6)
--------------------------------
`run_batch` executes many heterogeneous `SimSpec`s — different node
counts, port counts, channel counts — in ONE jitted program.  Specs are
padded to a common shape by `repro.sweep.padding` and the step function is
written to be *padding-invariant*: a spec simulated inside a padded batch
produces counters bitwise-equal to the same spec simulated alone.  The
three ingredients:

  * injection randomness is a counter-based hash of (seed, cycle, node,
    stream) rather than `jax.random` array draws, whose values depend on
    the array length and therefore on padding;
  * every scatter either has provably unique indices, is a pure add of
    zeros for padded lanes, or routes padded lanes to a *sacrificial*
    row/slot (extra buffer slot B, extra channel row C) that is never
    read back — this also fixes a latent seed-code hazard where
    non-traversing ports default-wrote channel 0's link slot and could
    clobber a real flit under last-update-wins scatter semantics;
  * the rotating-priority counter advances modulo the spec's own
    V*(P_spec+1) and allocation receives it split into (rr % V,
    rr % PI_spec), which preserves the spec's priority *ordering* under a
    larger padded port axis.

Latency is accumulated per node in int32 (exact, order-independent) and
reduced to float in numpy, so no float reduction depends on padding.

The pure-jnp allocation (`router_phase` / `_alloc_jnp`) also serves as
the reference oracle for the Pallas `netstep` kernel (see repro/kernels);
`SimConfig.alloc` selects the implementation ("auto" uses the kernel on
TPU and the jnp path elsewhere).
"""
from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.profile import profiling_enabled
from repro.obs.trace import trace as _span
from repro.obs.trace import tracing_enabled as _tracing

from . import linkmodel as lm
from .routing import Routing

INF = jnp.int32(2 ** 30)

_GOLD = np.uint32(0x9E3779B9)
_MIX_T = np.uint32(0x85EBCA6B)
_MIX_N = np.uint32(0xC2B2AE3D)

#: flight-recorder latency-histogram bins: bin h counts ejections with
#: latency in [2^(h-1), 2^h) cycles (bin 0: latency < 1 is impossible,
#: so it stays 0; the last bin is open-ended).  Coarse by design — the
#: histogram shape distinguishes "near zero-load" from "saturating"
#: without carrying a per-packet tensor through the scan.
LAT_HIST_BINS = 16

#: per-spec result keys added by `SimConfig(telemetry=True)`; every one
#: has a leading rate axis R (DESIGN.md §13).  `link_occ_escape` /
#: `link_occ_adaptive` split the per-VC occupancy sums into the escape
#: class (VC 0) and the adaptive class (VCs 1..V-1) of the DESIGN.md §15
#: VC partition — derived host-side from `link_occ_sum`, so they are
#: padding-invariant like every other counter.
TELEMETRY_KEYS = ("link_busy", "link_stall", "link_occ_sum", "link_util",
                  "link_occ_escape", "link_occ_adaptive",
                  "inj_node", "eject_node", "lat_hist")

#: additional per-spec result keys when `SimConfig(telemetry_windows=W)`
#: bins the flight recorder over time (DESIGN.md §16).  Every counter
#: key gains a window axis W right after the rate axis; the per-window
#: tensors sum over W to the aggregate counters EXACTLY (same masks,
#: same int adds, each measured cycle lands in exactly one window) and
#: are padding-invariant by the same sacrificial-slot discipline.
#: `window_cycles` [W] is the host-side normalizer (cycles per window).
TELEMETRY_WINDOW_KEYS = ("link_busy_w", "link_stall_w", "link_occ_w",
                         "link_util_w", "inj_node_w", "eject_node_w",
                         "window_cycles")

#: rate-grid headroom above the static analytic bound (DESIGN.md §15):
#: static sweeps plateau below the analytic estimate, adaptive sweeps
#: can exceed it (routing around congestion), so their grid must extend
#: further or it clips the most interesting region.
STATIC_HEADROOM = 2.0
ADAPTIVE_HEADROOM = 3.0


class SimConfig(NamedTuple):
    n_vcs: int = 4
    buf_depth: int = 4
    cycles: int = 3000
    warmup: int = 1000
    seed: int = 0
    alloc: str = "auto"     # "auto" | "jnp" | "pallas"
    telemetry: bool = False  # flight recorder (DESIGN.md §13); off path
    #                          is bitwise identical to pre-telemetry code
    routing: str = "static"  # "static" | "adaptive" (DESIGN.md §15);
    #                          "static" is bitwise identical to the
    #                          pre-adaptive simulator
    telemetry_windows: int = 0  # W > 0 bins the flight recorder into W
    #                          time windows over the measured cycles
    #                          (DESIGN.md §16); requires telemetry=True;
    #                          0 leaves the compiled program unchanged


class SimState(NamedTuple):
    buf_dst: jnp.ndarray     # [N, PI, V, B+1] destination (-1 empty; slot B
    buf_t: jnp.ndarray      # [N, PI, V, B+1]  is a sacrificial write sink)
    head: jnp.ndarray        # [N, PI, V]
    cnt: jnp.ndarray         # [N, PI, V]
    credits: jnp.ndarray     # [N, P, V]
    link_dst: jnp.ndarray    # [C+1, D] (row C is a sacrificial write sink)
    link_t: jnp.ndarray      # [C+1, D]
    link_vc: jnp.ndarray     # [C+1, D]
    credit_pipe: jnp.ndarray  # [C+1, D, V]
    rr: jnp.ndarray          # [] rotating priority
    delivered: jnp.ndarray   # []
    lat_node: jnp.ndarray    # [N] int32 summed ejection latency per node
    offered: jnp.ndarray     # []
    accepted: jnp.ndarray    # []
    # per-phase counters (workload mode only; None in static mode)
    delivered_ph: jnp.ndarray | None = None   # [K]
    offered_ph: jnp.ndarray | None = None     # [K]
    accepted_ph: jnp.ndarray | None = None    # [K]
    lat_ph: jnp.ndarray | None = None         # [K, N] int32
    # flight-recorder counters (telemetry mode only; DESIGN.md §13).
    # Row C / padded tails are sacrificial, sliced away host-side.
    tel_busy: jnp.ndarray | None = None       # [C+1] measured traversals
    tel_stall: jnp.ndarray | None = None      # [C+1] credit-starved cycles
    tel_occ: jnp.ndarray | None = None        # [C+1, V] occupancy sums
    tel_inj: jnp.ndarray | None = None        # [N] accepted injections
    tel_eject: jnp.ndarray | None = None      # [N] ejections
    tel_hist: jnp.ndarray | None = None       # [LAT_HIST_BINS] latency
    # windowed flight-recorder counters (telemetry_windows=W > 0 only;
    # DESIGN.md §16).  Same sacrificial-row discipline, one extra
    # leading window axis; each sums over W to its aggregate above.
    tel_busy_w: jnp.ndarray | None = None     # [W, C+1]
    tel_stall_w: jnp.ndarray | None = None    # [W, C+1]
    tel_occ_w: jnp.ndarray | None = None      # [W, C+1, V]
    tel_inj_w: jnp.ndarray | None = None      # [W, N]
    tel_eject_w: jnp.ndarray | None = None    # [W, N]


@dataclasses.dataclass
class SimSpec:
    """Static simulator inputs derived from a Routing + traffic matrix."""
    n: int
    p: int                  # max real ports
    c: int                  # directed channels
    d: int                  # link pipeline ring depth
    table: np.ndarray       # [N_dst, N, P+1] -> out port, EJECT=-2
    out_ch: np.ndarray      # [N, P]
    in_ch: np.ndarray       # [N, P]
    ch_dst: np.ndarray      # [C]
    ch_in_port: np.ndarray  # [C]
    ch_src: np.ndarray
    ch_out_port: np.ndarray
    ch_depth: np.ndarray    # [C] pipeline depth (cycles per hop)
    traffic_cum: np.ndarray  # [N, N] cumulative traffic rows
    inj_weight: np.ndarray   # [N] relative injection rate per node
    # productive-ports mask [N_dst, N, P] (DESIGN.md §15); consumed only
    # by the adaptive runner — the static runner never reads it, so the
    # leaf is dead-code-eliminated from the compiled static program
    prod: np.ndarray = None


def _traffic_arrays(traffic: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(cumulative rows, injection weights) for one traffic matrix.

    Shared by the static `make_spec` path and the phase-schedule compiler
    (`make_sched_spec`) so a single-phase schedule reproduces the static
    arrays bitwise — the workload path is a strict generalization.
    """
    rows = traffic.sum(axis=1)
    inj_weight = rows / max(rows.max(), 1e-12)
    cum = np.cumsum(traffic, axis=1)
    cum = cum / np.maximum(cum[:, -1:], 1e-12)
    cum[rows <= 0] = 1.0   # inert sources: any draw maps to dst 0, gated off
    return cum, inj_weight


def make_spec(routing: Routing, traffic: np.ndarray) -> SimSpec:
    from .routing import productive_ports
    depth = lm.hop_latency_cycles(routing.ch_len_mm, routing.topo.substrate)
    depth = np.maximum(np.asarray(depth, np.int32), 1)
    d = int(depth.max()) + 1
    cum, inj_weight = _traffic_arrays(traffic)
    return SimSpec(
        n=routing.topo.n, p=routing.max_ports, c=routing.n_channels, d=d,
        table=routing.table, out_ch=routing.out_ch, in_ch=routing.in_ch,
        ch_dst=routing.ch_dst, ch_in_port=routing.ch_in_port,
        ch_src=routing.ch_src, ch_out_port=routing.ch_out_port,
        ch_depth=depth, traffic_cum=cum, inj_weight=inj_weight,
        prod=productive_ports(routing))


# =====================================================================
# phase schedules (time-varying workloads, DESIGN.md §9)
# =====================================================================

@dataclasses.dataclass
class SchedSpec:
    """Compiled phase schedule for one spec (numpy, [K, ...] leaves).

    A workload is a sequence of K phases; phase k is active for cycles
    [start[k], end[k]) of the schedule, which replays cyclically
    (`t_eff = t % total`).  During a phase, injection draws destinations
    from that phase's cumulative traffic rows and offers
    `rate * gain * inj_w[node]` flits/cycle, where the gain is
    `gain_on[k]` inside the ON window of the phase's ON/OFF burst
    modulation and 0 inside the OFF window (no modulation: always ON,
    `gain_on == intensity`).
    """
    k: int
    n: int
    cum: np.ndarray       # [K, N, N] cumulative traffic rows per phase
    inj_w: np.ndarray     # [K, N] relative injection weight per phase
    gain_on: np.ndarray   # [K] float32 rate gain inside the ON window
    start: np.ndarray     # [K] int32 cumulative phase start (cycles)
    end: np.ndarray       # [K] int32 cumulative phase end (cycles)
    on: np.ndarray        # [K] int32 ON window length
    period: np.ndarray    # [K] int32 ON+OFF period (>= 1)
    total: int            # schedule length in cycles


def make_sched_spec(phases) -> SchedSpec:
    """Compile (traffic, intensity, duration[, burst_on, burst_off])
    tuples into a `SchedSpec`.

    intensity scales the offered rate for the whole phase; burst_on/off
    add ON/OFF modulation *within* the phase: during ON the gain is
    intensity * period/on, during OFF it is 0, which preserves the
    phase's mean offered load exactly when the phase duration is a
    multiple of the period (and to within one partial period's ON
    surplus otherwise).  burst_on or burst_off <= 0 disables modulation
    (gain_on == intensity exactly, so an unmodulated unit-intensity
    phase multiplies the rate by exactly 1.0f).
    """
    if not phases:
        raise ValueError("schedule needs at least one phase")
    cums, injs, gains, ons, periods, durs = [], [], [], [], [], []
    n = np.asarray(phases[0][0]).shape[0]
    for ph in phases:
        traffic, intensity, duration = ph[0], float(ph[1]), int(ph[2])
        burst_on = int(ph[3]) if len(ph) > 3 else 0
        burst_off = int(ph[4]) if len(ph) > 4 else 0
        traffic = np.asarray(traffic, np.float64)
        if traffic.shape != (n, n):
            raise ValueError(f"phase traffic shape {traffic.shape} != "
                             f"({n}, {n})")
        if duration < 1:
            raise ValueError("phase duration must be >= 1 cycle")
        cum, inj = _traffic_arrays(traffic)
        cums.append(cum), injs.append(inj), durs.append(duration)
        if burst_on > 0 and burst_off > 0:
            ons.append(burst_on)
            periods.append(burst_on + burst_off)
            gains.append(intensity * (burst_on + burst_off) / burst_on)
        else:
            ons.append(1), periods.append(1)
            gains.append(intensity)
    end = np.cumsum(np.asarray(durs, np.int64)).astype(np.int32)
    start = np.concatenate([[0], end[:-1]]).astype(np.int32)
    return SchedSpec(
        k=len(phases), n=n, cum=np.stack(cums), inj_w=np.stack(injs),
        gain_on=np.asarray(gains, np.float32), start=start, end=end,
        on=np.asarray(ons, np.int32), period=np.asarray(periods, np.int32),
        total=int(end[-1]))


def telemetry_window_cycles(cfg: SimConfig) -> np.ndarray:
    """[W] measured cycles falling in each telemetry window — the
    normalizer for per-window utilization.  Mirrors the in-scan window
    pointer exactly: cycle t (warmup <= t < cycles) lands in window
    ((t - warmup) * W) // meas, so windows partition the measured
    cycles (sum == cycles - warmup) and differ by at most one cycle."""
    w = cfg.telemetry_windows
    if w <= 0:
        raise ValueError("telemetry_windows must be > 0 for a window "
                         "grid")
    meas = cfg.cycles - cfg.warmup
    return np.bincount((np.arange(meas, dtype=np.int64) * w) // meas,
                       minlength=w).astype(np.int64)


def phase_measured_cycles(sched: SchedSpec, cfg: SimConfig) -> np.ndarray:
    """[K] measured (post-warmup) cycles spent in each phase — the
    normalizer for per-phase throughput.  Mirrors the in-scan phase
    pointer exactly: t_eff = t % total, phase = #{ends <= t_eff}."""
    t_eff = np.arange(cfg.warmup, cfg.cycles) % sched.total
    ph = (sched.end[None, :] <= t_eff[:, None]).sum(axis=1)
    return np.bincount(ph, minlength=sched.k).astype(np.int64)


# =====================================================================
# padding-invariant injection randomness
# =====================================================================

def _mix32(h):
    """splitmix-style avalanche on uint32 (wrapping jnp arithmetic)."""
    h = jnp.asarray(h, jnp.uint32)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def _node_bits(seed: int, t, node_idx, stream: int):
    """Per-node uint32 depending only on (seed, cycle, node, stream) —
    bitwise invariant to the node-axis padding, unlike jax.random draws
    whose threefry counter pairing depends on the array length."""
    h = _mix32(jnp.uint32(np.uint32(seed)) ^ (jnp.uint32(stream) * _GOLD))
    h = _mix32(h ^ (jnp.asarray(t, jnp.uint32) * _MIX_T))
    return _mix32(h ^ (node_idx.astype(jnp.uint32) * _MIX_N))


def _bits_to_unit(bits):
    """uint32 -> float32 in [0, 1) using the top 24 bits (exact)."""
    return (bits >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


# =====================================================================
# route lookup + two-phase separable allocation
# =====================================================================

def _select_last(x, idx):
    """`x[..., idx]` along the last axis, with `idx` (shaped like
    `x.shape[:-1]`, or broadcast against it) holding one in-range slot
    per element.

    The step's indexed axes are short and static (B buffer slots, V VCs,
    P+1 ports), so a one-hot compare-and-select over the axis is a few
    elementwise ops, where an element gather reads one element per
    index.  Exactly one slot matches, so the sum is the picked value,
    bitwise.
    """
    hit = idx[..., None] == jnp.arange(x.shape[-1])
    return jnp.sum(jnp.where(hit, x, jnp.zeros((), x.dtype)), axis=-1,
                   dtype=x.dtype)


def _route_lookup(table, cred_pad, head_dst, cnt, n: int, p: int):
    """Table lookup + credit check for every (node, in-port, VC) head flit.

    Returns op_slot [N, PI, V] int32 (requested output slot, ejection = P,
    negative = no request), eligible [N, PI, V] bool, and starved
    [N, PI, V] bool — a valid head flit whose route names a real output
    port but whose downstream VC has no credit (the flight recorder's
    credit-starvation counter; unused outputs are DCE'd under jit, so
    the telemetry-off path is unchanged).
    """
    PI = p + 1
    node_idx = jnp.arange(n)[:, None, None]
    port_idx = jnp.arange(PI)[None, :, None]

    valid = cnt > 0
    dst = jnp.where(valid, head_dst, 0)
    op = table[dst, node_idx, port_idx].astype(jnp.int32)  # [N, PI, V]
    op = jnp.where(valid, op, -3)
    is_eject = op == Routing.EJECT
    op_slot = jnp.where(is_eject, p, op)           # [N, PI, V]
    # credit of VC v at output slot op_slot: select along the port axis
    cred_vp = jnp.swapaxes(cred_pad, 1, 2)[:, None]        # [N, 1, V, P+1]
    have_credit = _select_last(cred_vp, jnp.clip(op_slot, 0, p)) > 0
    eligible = valid & (op_slot >= 0) & (have_credit | is_eject)
    starved = valid & (op_slot >= 0) & ~is_eject & ~have_credit
    return op_slot, eligible, starved


def _route_lookup_adaptive(table, prod, cred_pad, head_dst, cnt,
                           n: int, p: int, v: int):
    """Minimal-adaptive route selection with escape fallback (§15).

    The Duato-style VC partition: VC 0 is the escape class, following
    the static up*/down* table (indexed by the arrival in-port, whose
    channel-dependency graph is certified acyclic); VCs 1..V-1 are the
    adaptive class, free to take any *productive* port — a minimal,
    escape-safe next hop from `routing.productive_ports` — chosen by
    downstream adaptive-credit count (deterministic first-max
    tie-break).  A head flit prefers an adaptive hop whenever some
    productive port has adaptive credit; otherwise it falls back to the
    escape route, gated on VC-0 credit.  Ejection is always eligible.

    Returns (op_slot, eligible, starved) shaped like `_route_lookup`
    plus dvc [N, PI, V] — the downstream VC class of each choice (>= 1
    adaptive, 0 escape).
    """
    PI = p + 1
    node_idx = jnp.arange(n)[:, None, None]
    port_idx = jnp.arange(PI)[None, :, None]

    valid = cnt > 0
    dst = jnp.where(valid, head_dst, 0)
    # escape route: the static table, arrival-in-port indexed
    op = table[dst, node_idx, port_idx].astype(jnp.int32)  # [N, PI, V]
    op = jnp.where(valid, op, -3)
    is_eject = op == Routing.EJECT
    esc_slot = jnp.where(is_eject, p, op)
    esc_credit = _select_last(cred_pad[:, None, None, :, 0],
                              jnp.clip(esc_slot, 0, p)) > 0

    # adaptive candidates: productive ports weighted by the summed
    # downstream adaptive-class credit (argmax = first-max tie-break)
    cand = prod[dst, node_idx]                     # [N, PI, V, P]
    cred_ad = jnp.sum(cred_pad[:, :p, 1:], axis=2)  # [N, P]
    score = jnp.where(cand & (cred_ad[:, None, None, :] > 0),
                      cred_ad[:, None, None, :], -1)
    ad_port = jnp.argmax(score, axis=3).astype(jnp.int32)  # [N, PI, V]
    ad_ok = jnp.max(score, axis=3) > 0
    # downstream adaptive VC with the most credit at the chosen port
    pcred = _select_last(                          # [N, PI, V, V-1]
        jnp.swapaxes(cred_pad[:, :, 1:], 1, 2)[:, None, None],
        jnp.clip(ad_port, 0, p - 1)[..., None])
    dvc_ad = 1 + jnp.argmax(pcred, axis=3).astype(jnp.int32)

    use_ad = valid & ~is_eject & ad_ok
    op_slot = jnp.where(use_ad, ad_port, esc_slot)
    eligible = valid & (op_slot >= 0) & \
        (use_ad | is_eject | ((esc_slot >= 0) & esc_credit))
    starved = valid & ~is_eject & (esc_slot >= 0) & ~eligible
    dvc = jnp.where(use_ad, dvc_ad, 0)
    return op_slot, eligible, starved, dvc


def _alloc_jnp(op_slot, eligible, rr_vc, rr_port):
    """Two-phase separable allocation (pure jnp; Pallas netstep oracle).

    rr_vc rotates the VC priority (phase a), rr_port the input-port
    priority (phase b).  Returns (win_mask [N,PI,V], vc_choice [N,PI],
    out_req [N,PI] in [0..P] or -1).
    """
    N, PI, V = op_slot.shape
    vcs = jnp.arange(V)[None, None, :]

    # phase a: each input port picks one eligible VC (rotating priority)
    vc_score = jnp.where(eligible, (vcs - rr_vc) % V, INF)
    vc_choice = jnp.argmin(vc_score, axis=2).astype(jnp.int32)
    port_ok = jnp.min(vc_score, axis=2) < INF
    out_req = jnp.where(
        port_ok,
        jnp.take_along_axis(op_slot, vc_choice[..., None], axis=2)[..., 0],
        -1)                                        # [N, PI]

    # phase b: each output slot picks one requesting input port
    p_score = (jnp.arange(PI)[None, :] - rr_port) % PI   # [1, PI]
    req_1h = jax.nn.one_hot(jnp.where(out_req >= 0, out_req, PI),
                            PI + 1, dtype=jnp.bool_)[:, :, :PI]  # [N,PI,PI]
    scores = jnp.where(req_1h, p_score[:, :, None], INF)  # [N, in, out]
    win_p = jnp.argmin(scores, axis=1)             # [N, PI(out)]
    win_ok = jnp.min(scores, axis=1) < INF

    # scatter wins back onto input ports; invalid wins go to a dump column
    win_p_safe = jnp.where(win_ok, win_p, PI)
    won = jnp.zeros((N, PI + 1), jnp.bool_)
    won = won.at[jnp.arange(N)[:, None], win_p_safe].set(win_ok)
    port_wins = won[:, :PI] & port_ok              # [N, PI]
    win_mask = (jax.nn.one_hot(vc_choice, V, dtype=jnp.bool_)
                & eligible & port_wins[:, :, None])
    return win_mask, vc_choice, out_req


def _alloc_pallas(op_slot, eligible, rr_vc, rr_port):
    from repro.kernels.netstep.ops import netstep
    return netstep(op_slot, eligible, (rr_vc, rr_port))


def resolve_alloc(alloc: str) -> str:
    """Map SimConfig.alloc to a concrete implementation for this backend."""
    if alloc == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if alloc not in ("jnp", "pallas"):
        raise ValueError(f"unknown alloc impl {alloc!r}")
    return alloc


def router_phase(table, out_ch_pad_credits, head_dst, cnt, rr,
                 n: int, p: int, v: int):
    """Route + allocate with a single rotating counter (legacy signature).

    Kept as the documented oracle entry point; the batched runner calls
    `_route_lookup` + the selected allocator directly with the counter
    split per DESIGN.md §6.  Returns (win_mask, out_req, vc_choice,
    port_wins) like the seed implementation.
    """
    op_slot, eligible, _ = _route_lookup(table, out_ch_pad_credits,
                                         head_dst, cnt, n, p)
    win_mask, vc_choice, out_req = _alloc_jnp(op_slot, eligible, rr, rr)
    return win_mask, out_req, vc_choice, jnp.any(win_mask, axis=2)


# =====================================================================
# batched runner
# =====================================================================

def _init_state(nm: int, pm: int, cm: int, dm: int, cfg: SimConfig,
                kmax: int = 0) -> SimState:
    V, B = cfg.n_vcs, cfg.buf_depth
    PI = pm + 1
    z = jnp.zeros
    ph = dict(delivered_ph=z((kmax,), jnp.int32),
              offered_ph=z((kmax,), jnp.int32),
              accepted_ph=z((kmax,), jnp.int32),
              lat_ph=z((kmax, nm), jnp.int32)) if kmax else {}
    tel = dict(tel_busy=z((cm + 1,), jnp.int32),
               tel_stall=z((cm + 1,), jnp.int32),
               tel_occ=z((cm + 1, V), jnp.int32),
               tel_inj=z((nm,), jnp.int32),
               tel_eject=z((nm,), jnp.int32),
               tel_hist=z((LAT_HIST_BINS,), jnp.int32)) \
        if cfg.telemetry else {}
    W = cfg.telemetry_windows
    if cfg.telemetry and W > 0:
        tel.update(tel_busy_w=z((W, cm + 1), jnp.int32),
                   tel_stall_w=z((W, cm + 1), jnp.int32),
                   tel_occ_w=z((W, cm + 1, V), jnp.int32),
                   tel_inj_w=z((W, nm), jnp.int32),
                   tel_eject_w=z((W, nm), jnp.int32))
    return SimState(
        **ph, **tel,
        buf_dst=jnp.full((nm, PI, V, B + 1), -1, jnp.int32),
        buf_t=z((nm, PI, V, B + 1), jnp.int32),
        head=z((nm, PI, V), jnp.int32),
        cnt=z((nm, PI, V), jnp.int32),
        credits=jnp.full((nm, pm, V), B, jnp.int32),
        link_dst=jnp.full((cm + 1, dm), -1, jnp.int32),
        link_t=z((cm + 1, dm), jnp.int32),
        link_vc=z((cm + 1, dm), jnp.int32),
        credit_pipe=z((cm + 1, dm, V), jnp.int32),
        rr=jnp.int32(0),
        delivered=z((), jnp.int32), lat_node=z((nm,), jnp.int32),
        offered=z((), jnp.int32), accepted=z((), jnp.int32),
    )


def _make_batch_runner(nm: int, pm: int, cm: int, dm: int,
                       cfg: SimConfig, alloc_impl: str, kmax: int = 0):
    """Jitted (batch_arrays, rates[S, R]) -> raw int counters [S, R, ...].

    batch_arrays is a `repro.sweep.padding.BatchSpec` pytree whose array
    leaves carry a leading spec axis S; rates carries one row of R
    injection rates per spec.  All shape parameters are static, so the
    executable is reused for any batch padded to the same shape.

    kmax > 0 builds the *workload* runner: the jitted function takes a
    third argument, a `repro.sweep.padding.SchedBatch` pytree of phase
    schedules padded to kmax phases, and injection becomes time-varying
    (phase pointer advanced inside the scan).  The phase pointer is
    padding-invariant: it counts phase *ends* <= t_eff, and padded phase
    rows carry end == 2^30, so they never register for any real cycle.
    kmax == 0 is the static path, byte-identical to the pre-workload
    runner.
    """
    N, P, V, B, C, D = nm, pm, cfg.n_vcs, cfg.buf_depth, cm, dm
    PI = P + 1
    if cfg.routing not in ("static", "adaptive"):
        raise ValueError(f"unknown routing mode {cfg.routing!r}; "
                         f"choose 'static' or 'adaptive'")
    adaptive = cfg.routing == "adaptive"
    if adaptive and V < 2:
        raise ValueError(
            f"adaptive routing needs n_vcs >= 2 (VC 0 escape + at least "
            f"one adaptive VC), got n_vcs={V}")
    W = cfg.telemetry_windows
    if W < 0:
        raise ValueError(f"telemetry_windows must be >= 0, got {W}")
    if W and not cfg.telemetry:
        raise ValueError(
            "telemetry_windows requires telemetry=True — the windowed "
            "counters bin the flight recorder, they cannot replace it")
    meas = cfg.cycles - cfg.warmup
    if W > meas:
        raise ValueError(
            f"telemetry_windows={W} exceeds the measured window "
            f"({meas} cycles) — some windows would be empty")
    alloc_fn = _alloc_pallas if alloc_impl == "pallas" else _alloc_jnp
    nn = jnp.arange(N)[:, None]
    pp = jnp.arange(PI)[None, :]
    node_r = jnp.arange(N)

    def step(a, sch, state: SimState, t_rate):
        t, rate = t_rate
        slot = t % D
        measuring = t >= cfg.warmup
        ch_depth_pad = jnp.concatenate(
            [a.ch_depth, jnp.ones((1,), jnp.int32)])        # [C+1]

        # ---- 1. link deliveries -> input buffers ----------------------
        with jax.named_scope("step_arrivals"):
            arr_dst = state.link_dst[:C, slot]           # [C]
            arr_ok = arr_dst >= 0
            arr_vc = state.link_vc[:C, slot]
            pos = (state.head[a.ch_dst, a.ch_in_port, arr_vc] +
                   state.cnt[a.ch_dst, a.ch_in_port, arr_vc]) % B
            pos_w = jnp.where(arr_ok, pos, B)            # B = sacrificial slot
            buf_dst = state.buf_dst.at[a.ch_dst, a.ch_in_port, arr_vc,
                                       pos_w].set(arr_dst)
            buf_t = state.buf_t.at[a.ch_dst, a.ch_in_port, arr_vc,
                                   pos_w].set(state.link_t[:C, slot])
            cnt = state.cnt.at[a.ch_dst, a.ch_in_port, arr_vc].add(
                arr_ok.astype(jnp.int32))
            link_dst = state.link_dst.at[:, slot].set(-1)

        # ---- 2. credit returns ----------------------------------------
        with jax.named_scope("step_credits"):
            credits = state.credits.at[a.ch_src, a.ch_out_port].add(
                state.credit_pipe[:C, slot])
            credit_pipe = state.credit_pipe.at[:, slot].set(0)

        # ---- 3. injection ----------------------------------------------
        with jax.named_scope("step_inject"):
            if kmax:
                # phase pointer: replay the schedule cyclically and count the
                # phase ends already passed (padded rows end at 2^30 — inert)
                t_eff = t % sch.total
                ph = jnp.sum((sch.end <= t_eff).astype(jnp.int32))
                in_on = ((t_eff - sch.start[ph]) % sch.period[ph]) < sch.on[ph]
                rate_eff = rate * jnp.where(in_on, sch.gain_on[ph],
                                            jnp.float32(0.0))
                inj_w, cum = sch.inj_w[ph], sch.cum[ph]
            else:
                rate_eff, inj_w, cum = rate, a.inj_weight, a.traffic_cum
            u_inj = _bits_to_unit(_node_bits(cfg.seed, t, node_r, 0))
            want = u_inj < rate_eff * inj_w
            u_dst = _bits_to_unit(_node_bits(cfg.seed, t, node_r, 1))
            dsts = jnp.sum(cum < u_dst[:, None], axis=1)
            dsts = jnp.clip(dsts, 0, N - 1).astype(jnp.int32)
            vcs_inj = (_node_bits(cfg.seed, t, node_r, 2)
                       % V).astype(jnp.int32)
            want &= dsts != node_r
            space = cnt[node_r, P, vcs_inj] < B
            do_inj = want & space
            posi = (state.head[node_r, P, vcs_inj]
                    + cnt[node_r, P, vcs_inj]) % B
            posi_w = jnp.where(do_inj, posi, B)
            buf_dst = buf_dst.at[node_r, P, vcs_inj, posi_w].set(dsts)
            buf_t = buf_t.at[node_r, P, vcs_inj, posi_w].set(t)
            cnt = cnt.at[node_r, P, vcs_inj].add(do_inj.astype(jnp.int32))
            m32 = measuring.astype(jnp.int32)
            offered = state.offered + m32 * jnp.sum(want.astype(jnp.int32))
            accepted = state.accepted + m32 * jnp.sum(do_inj.astype(jnp.int32))

        # ---- 4. route + allocate ---------------------------------------
        with jax.named_scope("step_route"):
            cnt_obs = cnt            # occupancy snapshot (flight recorder):
            #                          post-arrival, post-injection, pre-pop
            # slot B is the sacrificial write sink: head < B always
            head_dst = _select_last(buf_dst[..., :B], state.head)
            head_t = _select_last(buf_t[..., :B], state.head)
            cred_pad = jnp.concatenate(
                [credits, jnp.full((N, 1, V), INF, jnp.int32)], axis=1)
            if adaptive:
                op_slot, eligible, starved, dvc = _route_lookup_adaptive(
                    a.table, a.prod, cred_pad, head_dst, cnt, N, P, V)
            else:
                op_slot, eligible, starved = _route_lookup(
                    a.table, cred_pad, head_dst, cnt, N, P)
        with jax.named_scope("step_alloc"):
            rr_vc = state.rr % V
            rr_port = state.rr % a.pi
            win_mask, vc_choice, out_req = alloc_fn(op_slot, eligible,
                                                    rr_vc, rr_port)
            port_wins = jnp.any(win_mask, axis=2)      # [N, PI]

        # ---- 5. winners: pop, move, credit ------------------------------
        with jax.named_scope("step_move"):
            # wvc is the *source* VC lane popped at (node, in-port); w_dvc is
            # the *downstream* VC lane the flit occupies after the hop.  The
            # static path keeps them equal (bitwise-identical jaxpr); the
            # adaptive path redirects to the class chosen by the route
            # lookup, so the upstream credit return (freeing the popped
            # lane) stays on wvc while the link VC tag and the downstream
            # credit decrement move to w_dvc.
            wvc = vc_choice
            w_dvc = _select_last(dvc, wvc) if adaptive else wvc
            w_dst = _select_last(head_dst, wvc)
            w_t = _select_last(head_t, wvc)
            head = (state.head.at[nn, pp, wvc]
                    .add(port_wins.astype(jnp.int32))) % B
            cnt = cnt.at[nn, pp, wvc].add(-port_wins.astype(jnp.int32))

            # upstream credit return for real input ports
            up_ch = a.in_ch[nn, jnp.clip(pp, 0, P - 1)]  # [N, PI]
            has_up = (pp < P) & (up_ch >= 0) & port_wins
            up_ch_s = jnp.maximum(up_ch, 0)
            ret_slot = (t + ch_depth_pad[up_ch_s]) % D
            credit_pipe = credit_pipe.at[up_ch_s, ret_slot, wvc].add(
                has_up.astype(jnp.int32))

            # ejection vs traversal
            eject = port_wins & (out_req == P)
            traverse = port_wins & (out_req >= 0) & (out_req < P)
            ej32 = jnp.sum(eject.astype(jnp.int32))
            lat_row = jnp.sum(jnp.where(eject, t - w_t, 0), axis=1)
            delivered = state.delivered + m32 * ej32
            lat_node = state.lat_node + m32 * lat_row
            ph_upd = {}
            if kmax:
                ph_upd = dict(
                    delivered_ph=state.delivered_ph.at[ph].add(m32 * ej32),
                    offered_ph=state.offered_ph.at[ph].add(
                        m32 * jnp.sum(want.astype(jnp.int32))),
                    accepted_ph=state.accepted_ph.at[ph].add(
                        m32 * jnp.sum(do_inj.astype(jnp.int32))),
                    lat_ph=state.lat_ph.at[ph].add(m32 * lat_row))

            out_c = a.out_ch[nn, jnp.clip(out_req, 0, P - 1)]
            oc_w = jnp.where(traverse, out_c, C)       # C = sacrificial row
            wslot = (t + ch_depth_pad[oc_w]) % D
            link_dst = link_dst.at[oc_w, wslot].set(w_dst)
            link_t = state.link_t.at[oc_w, wslot].set(w_t)
            link_vc = state.link_vc.at[oc_w, wslot].set(w_dvc)
            credits = credits.at[nn, jnp.clip(out_req, 0, P - 1), w_dvc].add(
                -traverse.astype(jnp.int32))

        # ---- 6. flight recorder (telemetry mode only; DESIGN.md §13) ---
        with jax.named_scope("step_flight"):
            # Pure observers: every update is an int scatter-add onto a
            # dedicated counter tensor, weighted by masks the step already
            # computed, with non-contributing lanes routed to the sacrificial
            # row C (or weighted 0) — so real counters are untouched and the
            # per-spec slices stay padding-invariant.
            tel_upd = {}
            if cfg.telemetry:
                # channel utilization: one traversal per (channel, cycle)
                tel_busy = state.tel_busy.at[oc_w].add(
                    m32 * traverse.astype(jnp.int32))
                # credit starvation, attributed to the requested out channel
                st_ch = a.out_ch[jnp.arange(N)[:, None, None],
                                 jnp.clip(op_slot, 0, P - 1)]  # [N, PI, V]
                st_ch_w = jnp.where(starved, st_ch, C)
                tel_stall = state.tel_stall.at[st_ch_w].add(
                    m32 * starved.astype(jnp.int32))
                # per-VC occupancy of each channel's downstream input buffer
                occ = cnt_obs[a.ch_dst, a.ch_in_port]          # [C, V]
                tel_occ = state.tel_occ.at[jnp.arange(C)].add(m32 * occ)
                # injection/ejection conservation counters (sum == accepted /
                # delivered exactly — the reconciliation tests rely on this)
                tel_inj = state.tel_inj + m32 * do_inj.astype(jnp.int32)
                tel_eject = state.tel_eject + m32 * jnp.sum(
                    eject.astype(jnp.int32), axis=1)
                # coarse latency histogram: bin h counts lat in [2^(h-1), 2^h)
                edges = jnp.int32(2) ** jnp.arange(LAT_HIST_BINS - 1)
                lat = t - w_t                                  # [N, PI]
                hbin = jnp.sum((lat[..., None] >= edges).astype(jnp.int32),
                               axis=-1)
                tel_hist = state.tel_hist.at[hbin].add(
                    m32 * eject.astype(jnp.int32))
                tel_upd = dict(tel_busy=tel_busy, tel_stall=tel_stall,
                               tel_occ=tel_occ, tel_inj=tel_inj,
                               tel_eject=tel_eject, tel_hist=tel_hist)
                if W:
                    # time-windowed bins (DESIGN.md §16): the SAME masks and
                    # weights as the aggregates above, scattered once more
                    # with a leading window index — so summing the window
                    # axis reconciles to the aggregates bitwise (int adds,
                    # every measured cycle lands in exactly one window;
                    # pre-warmup cycles clip to window 0 with weight 0).
                    w = jnp.clip(((t - cfg.warmup) * W) // meas, 0, W - 1)
                    tel_upd.update(
                        tel_busy_w=state.tel_busy_w.at[w, oc_w].add(
                            m32 * traverse.astype(jnp.int32)),
                        tel_stall_w=state.tel_stall_w.at[w, st_ch_w].add(
                            m32 * starved.astype(jnp.int32)),
                        tel_occ_w=state.tel_occ_w.at[w, jnp.arange(C)].add(
                            m32 * occ),
                        tel_inj_w=state.tel_inj_w.at[w].add(
                            m32 * do_inj.astype(jnp.int32)),
                        tel_eject_w=state.tel_eject_w.at[w].add(
                            m32 * jnp.sum(eject.astype(jnp.int32), axis=1)))

        return SimState(
            buf_dst=buf_dst, buf_t=buf_t, head=head, cnt=cnt,
            credits=credits, link_dst=link_dst, link_t=link_t,
            link_vc=link_vc, credit_pipe=credit_pipe,
            rr=(state.rr + 1) % (V * a.pi),
            delivered=delivered, lat_node=lat_node, offered=offered,
            accepted=accepted, **ph_upd, **tel_upd)

    def run_one(a, sch, rate):
        state = _init_state(N, P, C, D, cfg, kmax)
        ts = jnp.arange(cfg.cycles)
        rates = jnp.full((cfg.cycles,), rate)
        state, _ = jax.lax.scan(lambda s, tr: (step(a, sch, s, tr), None),
                                state, (ts, rates))
        out = (state.delivered, state.offered, state.accepted,
               state.lat_node)
        if kmax:
            out += (state.delivered_ph, state.offered_ph,
                    state.accepted_ph, state.lat_ph)
        if cfg.telemetry:
            out += (state.tel_busy, state.tel_stall, state.tel_occ,
                    state.tel_inj, state.tel_eject, state.tel_hist)
            if W:
                out += (state.tel_busy_w, state.tel_stall_w,
                        state.tel_occ_w, state.tel_inj_w,
                        state.tel_eject_w)
        return out

    if kmax:
        def runner(batch, rates, sched):
            per_spec = lambda a, sch, rr_: jax.vmap(
                lambda r: run_one(a, sch, r))(rr_)
            return jax.vmap(per_spec)(batch, sched, rates)
    else:
        def runner(batch, rates):
            per_spec = lambda a, rr_: jax.vmap(
                lambda r: run_one(a, None, r))(rr_)
            return jax.vmap(per_spec)(batch, rates)

    return jax.jit(runner)


_RUNNER_CACHE: OrderedDict = OrderedDict()
_RUNNER_CACHE_MAX = max(
    int(os.environ.get("REPRO_RUNNER_CACHE_MAX", "64")), 1)
_RUNNER_CACHE_STATS = dict(hits=0, misses=0, evictions=0)


def set_runner_cache_limit(max_entries: int) -> None:
    """Bound the compiled-runner LRU (env: REPRO_RUNNER_CACHE_MAX).

    Long-lived sweep services accumulate one jitted runner per padded
    shape x SimConfig; each pins its compiled executables.  The LRU
    evicts the least-recently-used runner beyond `max_entries` —
    eviction only costs recompilation, never changes results
    (tests/test_sweep.py::test_runner_cache_lru_eviction)."""
    global _RUNNER_CACHE_MAX
    if max_entries < 1:
        raise ValueError("runner cache needs at least 1 entry")
    _RUNNER_CACHE_MAX = max_entries
    while len(_RUNNER_CACHE) > _RUNNER_CACHE_MAX:
        _RUNNER_CACHE.popitem(last=False)
        _RUNNER_CACHE_STATS["evictions"] += 1


def get_batch_runner(nm: int, pm: int, cm: int, dm: int, cfg: SimConfig,
                     alloc_impl: str, kmax: int = 0):
    """Compiled-runner LRU keyed on the padded shape + SimConfig; a new
    topology padded to a known shape reuses the existing executable.
    kmax > 0 selects the workload (phase-schedule) runner variant."""
    key = (nm, pm, cm, dm, cfg, alloc_impl, kmax, jax.default_backend())
    fn = _RUNNER_CACHE.get(key)
    if fn is None:
        _RUNNER_CACHE_STATS["misses"] += 1
        fn = _RUNNER_CACHE[key] = _make_batch_runner(
            nm, pm, cm, dm, cfg, alloc_impl, kmax)
        while len(_RUNNER_CACHE) > _RUNNER_CACHE_MAX:
            _RUNNER_CACHE.popitem(last=False)
            _RUNNER_CACHE_STATS["evictions"] += 1
    else:
        _RUNNER_CACHE_STATS["hits"] += 1
        _RUNNER_CACHE.move_to_end(key)
    return fn


def runner_cache_info() -> dict:
    """Executable-cache introspection (sweep-engine stats + ops):
    `entries` maps each full cache key (shape + config + impl) to its
    compiled-variant count; `hits`/`misses`/`evictions` count LRU
    traffic since process start (monotonic, survive cache clears)."""
    return dict(
        entries={key: fn._cache_size()
                 for key, fn in _RUNNER_CACHE.items()},
        size=len(_RUNNER_CACHE), max_size=_RUNNER_CACHE_MAX,
        **_RUNNER_CACHE_STATS)


def _pad_fill(specs, shape, schedules, kmax) -> list[dict]:
    """Live-work fraction of a padded batch, one dict per spec.

    `state` is the live fraction of the router-state grid the compiled
    program iterates (n*(p+1) of N*(P+1) cells — +1 for the ejection
    lane); `chan`/`depth` are the live channel-row and ring-depth
    fractions; `phase` is live schedule phases over k_pad (1.0 on the
    static path).  1 - fill is pad waste: device work spent keeping
    heterogeneous specs in one executable (DESIGN.md §16).
    """
    fills = []
    for i, spec in enumerate(specs):
        fills.append(dict(
            state=(spec.n * (spec.p + 1)) / (shape.n * (shape.p + 1)),
            chan=spec.c / shape.c,
            depth=spec.d / shape.d,
            phase=(schedules[i].k / kmax) if schedules is not None else 1.0))
    return fills


def run_batch(specs, rates, cfg: SimConfig = SimConfig(), *,
              pad_shape=None, schedules=None, k_pad=None) -> list[dict]:
    """Run many SimSpecs x injection rates in one batched jitted program.

    rates: [R] shared across specs, or [S, R] one row per spec.  Returns
    one dict per spec with raw integer counters (`delivered`, `offered`,
    `accepted`, `lat_sum`) plus derived float metrics (`throughput`,
    `latency`, ...) computed in numpy — so derived values are bitwise
    reproducible for any padding of the same spec.

    schedules: optional list of `SchedSpec` (one per spec) switching the
    batch to time-varying workload injection (DESIGN.md §9).  Each spec's
    `traffic_cum`/`inj_weight` are then ignored in favour of its
    schedule's per-phase arrays, and result dicts gain per-phase counters
    (`delivered_ph` [R, K], `lat_sum_ph`, `throughput_ph`, `latency_ph`,
    `phase_cycles` [K]).  k_pad pads the phase axis (executable reuse
    across workloads with different phase counts).

    cfg.telemetry=True switches on the flight recorder (DESIGN.md §13):
    result dicts gain `TELEMETRY_KEYS` — per-directed-channel busy /
    stall / occupancy-sum counters (`link_busy`/`link_stall` [R, c],
    `link_occ_sum` [R, c, V]), derived `link_util` (busy / measured
    cycles), per-node `inj_node`/`eject_node` [R, n] (summing exactly
    to `accepted_n`/`delivered`), and a coarse `lat_hist` [R,
    LAT_HIST_BINS].  Sacrificial and padded lanes are sliced away, so
    telemetry is padding-invariant like every other counter; with
    telemetry off the compiled program is unchanged.

    cfg.telemetry_windows=W (> 0, with telemetry on) additionally bins
    the busy/stall/occupancy/inject/eject counters into W time windows
    over the measured cycles (`TELEMETRY_WINDOW_KEYS`, DESIGN.md §16):
    `link_busy_w`/`link_stall_w` [R, W, c], `link_occ_w` [R, W, c, V],
    `inj_node_w`/`eject_node_w` [R, W, n], derived `link_util_w`
    (busy_w / that window's cycle count) and the `window_cycles` [W]
    normalizer.  Each windowed tensor sums over W to its aggregate
    counter EXACTLY, and the same sacrificial-slot discipline keeps the
    windows padding-invariant.

    Every result dict also carries `pad_fill` — the live-work fraction
    of this padded batch (DESIGN.md §16): `state` = live router-state
    cells / padded cells (n*(p+1) / N*(P+1)), `chan` = c/C, `depth` =
    d/D, `phase` = k/k_pad (1.0 static) — the pad-waste numbers the
    warm-path investigation reads off `ResultFrame` rows.
    """
    from repro.sweep.padding import stack_schedules, stack_specs
    with _span("sim.stack", cat="sim", specs=len(specs)):
        batch, shape = stack_specs(specs, pad_shape)
    s = len(specs)
    rates = np.asarray(rates, np.float32)
    if rates.ndim == 1:
        rates = np.broadcast_to(rates, (s, rates.shape[0]))
    if rates.shape[0] != s:
        raise ValueError(f"rates rows {rates.shape[0]} != specs {s}")
    if schedules is None:
        kmax = 0
        runner = get_batch_runner(shape.n, shape.p, shape.c, shape.d, cfg,
                                  resolve_alloc(cfg.alloc))
        args = (batch, jnp.asarray(rates))
    else:
        if len(schedules) != s:
            raise ValueError(f"schedules {len(schedules)} != specs {s}")
        for spec, sched in zip(specs, schedules):
            if sched.n != spec.n:
                raise ValueError(f"schedule for {sched.n} nodes paired "
                                 f"with a {spec.n}-node spec")
        sbatch, kmax = stack_schedules(schedules, shape.n, k_pad)
        runner = get_batch_runner(shape.n, shape.p, shape.c, shape.d, cfg,
                                  resolve_alloc(cfg.alloc), kmax)
        args = (batch, jnp.asarray(rates), sbatch)
    fills = _pad_fill(specs, shape, schedules, kmax)
    if profiling_enabled() or _tracing():
        # note the runner for its phase map, and profile it if asked
        # (repro.obs.profile); off, this is two attribute reads
        from repro.obs.profile import record_runner
        record_runner(shape, cfg, resolve_alloc(cfg.alloc), kmax,
                      runner, args)
    # dispatch vs wait split (DESIGN.md §13): the dispatch span covers
    # trace+compile on a cold executable (jit compiles synchronously at
    # dispatch) plus argument transfer; the wait span is the device
    # execution tail (`block_until_ready`).  A span with cold=True is a
    # compile; warm dispatches are microseconds.
    variants = runner._cache_size() if _tracing() else 0
    with _span("sim.dispatch", cat="sim", specs=s, shape=str(shape),
               kind="static" if schedules is None else "workload") as sp:
        raw = runner(*args)
        if _tracing():
            d = runner._cache_size() - variants
            sp.set(cold=d > 0, compiled_variants=d,
                   **{f"fill_{k}": round(float(np.mean(
                       [f[k] for f in fills])), 4) for k in fills[0]})
    with _span("sim.wait", cat="sim", specs=s):
        raw = jax.block_until_ready(raw)
    delivered = np.asarray(raw[0])             # [S, R]
    offered = np.asarray(raw[1])
    accepted = np.asarray(raw[2])
    lat_sum = np.asarray(raw[3]).astype(np.int64).sum(axis=2)  # [S, R]
    meas = cfg.cycles - cfg.warmup
    tel = None
    telw = None
    win_cycles = None
    if cfg.telemetry:
        from repro.obs.metrics import metrics
        off = 8 if schedules is not None else 4
        with _span("sim.flight", cat="sim", specs=s):
            tel = tuple(np.asarray(raw[off + j]) for j in range(6))
            if cfg.telemetry_windows:
                telw = tuple(np.asarray(raw[off + 6 + j])
                             for j in range(5))
                win_cycles = telemetry_window_cycles(cfg)
        # the flight recorder's copies to the host (repro.obs.metrics)
        metrics.inc("flight.calls")
        metrics.inc("flight.d2h_bytes",
                    sum(x.nbytes for x in tel + (telw or ())))
    out = []
    for i, spec in enumerate(specs):
        norm = spec.n * meas
        res = dict(
            rate=rates[i].astype(np.float64),
            delivered=delivered[i], offered_n=offered[i],
            accepted_n=accepted[i], lat_sum=lat_sum[i],
            throughput=delivered[i] / norm,
            latency=lat_sum[i] / np.maximum(delivered[i], 1),
            offered=offered[i] / norm,
            accepted=accepted[i] / norm,
            pad_fill=fills[i])
        if schedules is not None:
            sched = schedules[i]
            k = sched.k
            dp = np.asarray(raw[4])[i, :, :k]              # [R, K]
            op = np.asarray(raw[5])[i, :, :k]
            ap = np.asarray(raw[6])[i, :, :k]
            lp = np.asarray(raw[7])[i, :, :k].astype(np.int64).sum(axis=2)
            ph_cy = phase_measured_cycles(sched, cfg)      # [K]
            ph_norm = np.maximum(spec.n * ph_cy, 1)[None, :]
            res.update(
                delivered_ph=dp, offered_ph=op, accepted_ph=ap,
                lat_sum_ph=lp, phase_cycles=ph_cy,
                throughput_ph=dp / ph_norm,
                latency_ph=lp / np.maximum(dp, 1),
                offered_rate_ph=op / ph_norm)
        if tel is not None:
            # flight-recorder slices: drop the sacrificial channel row
            # and every padded channel/node lane (rows beyond the spec's
            # own c/n) so telemetry never reports pad slots
            t_busy, t_stall, t_occ, t_inj, t_ej, t_hist = tel
            c, n = spec.c, spec.n
            busy = t_busy[i, :, :c]                        # [R, c]
            occ = t_occ[i, :, :c, :]                       # [R, c, V]
            res.update(
                link_busy=busy, link_stall=t_stall[i, :, :c],
                link_occ_sum=occ,
                link_occ_escape=occ[:, :, 0],
                link_occ_adaptive=occ[:, :, 1:].sum(axis=-1),
                link_util=busy / float(meas),
                inj_node=t_inj[i, :, :n], eject_node=t_ej[i, :, :n],
                lat_hist=t_hist[i])
            if telw is not None:
                # windowed flight recorder (DESIGN.md §16): same
                # sacrificial/pad-lane slicing as the aggregates, plus
                # the per-window cycle-count normalizer for utilisation
                w_busy, w_stall, w_occ, w_inj, w_ej = telw
                busy_w = w_busy[i, :, :, :c]               # [R, W, c]
                occ_w = w_occ[i, :, :, :c, :]              # [R, W, c, V]
                res.update(
                    link_busy_w=busy_w,
                    link_stall_w=w_stall[i, :, :, :c],
                    link_occ_w=occ_w,
                    link_util_w=busy_w / np.maximum(
                        win_cycles, 1).astype(np.float64)[None, :, None],
                    inj_node_w=w_inj[i, :, :, :n],
                    eject_node_w=w_ej[i, :, :, :n],
                    window_cycles=win_cycles)
        out.append(res)
    return out


def trace_batch(specs, rates, cfg: SimConfig = SimConfig(), *,
                pad_shape=None, schedules=None, k_pad=None):
    """Abstractly trace the batched runner without compiling or running.

    Builds exactly the arguments `run_batch` would dispatch (same
    padding, same runner construction) but hands them to
    `jax.make_jaxpr` instead of the jitted callable — tracing evaluates
    the step symbolically on avals, so it is cheap even for cycle
    counts that would take minutes to simulate.  Returns
    `(closed_jaxpr, pad_shape, batch)`; the static analyzer
    (`repro.analysis.jaxpr_hazards`) walks the jaxpr for host
    callbacks and dtype promotions and inspects `batch` against the
    sacrificial-slot padding contract.
    """
    from repro.sweep.padding import stack_schedules, stack_specs
    batch, shape = stack_specs(specs, pad_shape)
    s = len(specs)
    rates = np.asarray(rates, np.float32)
    if rates.ndim == 1:
        rates = np.broadcast_to(rates, (s, rates.shape[0]))
    if schedules is None:
        fn = _make_batch_runner(shape.n, shape.p, shape.c, shape.d, cfg,
                                resolve_alloc(cfg.alloc))
        args = (batch, jnp.asarray(rates))
    else:
        sbatch, kmax = stack_schedules(schedules, shape.n, k_pad)
        fn = _make_batch_runner(shape.n, shape.p, shape.c, shape.d, cfg,
                                resolve_alloc(cfg.alloc), kmax)
        args = (batch, jnp.asarray(rates), sbatch)
    return jax.make_jaxpr(fn)(*args), shape, batch


# =====================================================================
# single-spec conveniences (thin wrappers over the batched path)
# =====================================================================

def simulate(routing: Routing, traffic: np.ndarray, rates,
             cfg: SimConfig = SimConfig()):
    """Run the simulator for a sweep of injection rates (vmapped).

    Returns dict of numpy arrays: delivered throughput (flits/node/cycle),
    avg packet latency (cycles), offered and accepted rates.  This is a
    batch of one through `run_batch` at the spec's exact shape.
    """
    spec = make_spec(routing, traffic)
    res = run_batch([spec], np.asarray(rates, np.float32)[None, :], cfg)[0]
    return dict(rate=np.asarray(rates), throughput=res["throughput"],
                latency=res["latency"], offered=res["offered"],
                accepted=res["accepted"])


def saturation_throughput(routing: Routing, traffic: np.ndarray,
                          cfg: SimConfig = SimConfig(),
                          n_rates: int = 8) -> dict:
    """Saturation = plateau of delivered throughput over an offered sweep.

    The sweep is seeded by the analytic channel-load bound and refined
    around it.
    """
    analytic = routing.saturation_rate(traffic)
    rates = saturation_rate_grid(analytic, n_rates,
                                 headroom=routing_headroom(cfg.routing))
    res = simulate(routing, traffic, rates, cfg)
    i = int(np.argmax(res["throughput"]))
    return dict(sim_saturation=float(res["throughput"][i]),
                analytic_saturation=float(analytic),
                latency_at_sat=float(res["latency"][i]), sweep=res)


def routing_headroom(routing: str) -> float:
    """Default rate-grid ceiling multiplier for a routing mode: adaptive
    sweeps must extend past the *static* analytic bound (they can beat
    it), static sweeps keep the historical 2x bracket."""
    return ADAPTIVE_HEADROOM if routing == "adaptive" else STATIC_HEADROOM


def saturation_rate_grid(analytic: float, n_rates: int = 8,
                         headroom: float = STATIC_HEADROOM) -> np.ndarray:
    """Offered-rate grid bracketing the analytic saturation estimate.

    `headroom` parameterizes the ceiling above the (static) analytic
    bound; the default reproduces the historical static grid exactly.
    """
    hi = min(1.0, headroom * analytic)
    return np.linspace(max(analytic * 0.25, 1e-3), hi, n_rates)


def zero_load_latency(routing: Routing, traffic: np.ndarray) -> float:
    """Analytic average packet latency at zero load (cycles)."""
    _, hops, lat = routing.paths_channel_loads(traffic)
    w = traffic / max(traffic.sum(), 1e-12)
    return float((lat * w).sum())
