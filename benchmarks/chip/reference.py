"""Plain reference of the modelled network, in numpy, imported by nothing
of the program.

Given a chiplet layout (centres in pitch units, undirected links, the
substrate and the chiplet area) it rebuilds, on its own:

  * the directed channels and their port numbers (ports ordered by the
    neighbour's id), and each hop's latency in cycles from Table IV
    (router 3 ns + two PHYs of 2 ns + the wire, rounded up to a cycle);
  * the paper's deadlock-free routing (§V-B): up*/down* labels from a
    BFS rooted at the most central chiplet, the channel dual graph
    without down->up turns, and for each destination the output port
    that is fewest turns away (lowest port index among ties);
  * the analytic channel-load saturation bound that seeds the rate grid;
  * a cycle-by-cycle simulation of one spec at several offered rates:
    link pipelines, credit-based flow control with 4-flit buffers on
    each VC, injection drawn from a counter hash of (seed, cycle, node),
    table routing, and two-phase separable switch allocation with a
    rotating priority over VCs and then over input ports;
  * on request, the flight recorder's integer counters (per channel,
    per node, a latency histogram), whole or in time windows.

It runs one (spec, rates) lane set at its own unpadded shape, so it
knows nothing of buckets, padding, vmaps, the scan or the kernel.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: Table IV: relative permittivity and chiplet spacing per substrate
SUBSTRATES = {"organic": dict(eps_r=3.1, spacing_mm=0.150),
              "glass": dict(eps_r=3.3, spacing_mm=0.100)}
ROUTER_NS, PHY_NS = 3.0, 2.0
C_MM_PER_NS = 299.792458
EJECT = -2
INF = 2 ** 30
LAT_HIST_BINS = 16
#: each flight-recorder aggregate and its key in time windows
WINDOWED = {"link_busy": "link_busy_w", "link_stall": "link_stall_w",
            "link_occ_sum": "link_occ_w", "inj_node": "inj_node_w",
            "eject_node": "eject_node_w"}

_GOLD = np.uint32(0x9E3779B9)
_MIX_T = np.uint32(0x85EBCA6B)
_MIX_N = np.uint32(0xC2B2AE3D)


@dataclasses.dataclass
class Network:
    """Channels, ports, hop depths and routing table of one layout."""
    n: int
    p: int                   # ports per router (max degree)
    ch_src: np.ndarray       # [C]
    ch_dst: np.ndarray       # [C]
    ch_out_port: np.ndarray  # [C]
    ch_in_port: np.ndarray   # [C]
    out_ch: np.ndarray       # [n, p] channel per output port, -1 none
    in_ch: np.ndarray        # [n, p] channel per input port, -1 none
    depth: np.ndarray        # [C] cycles per hop
    table: np.ndarray        # [dst, node, in port (p = injected)] -> port

    @property
    def c(self) -> int:
        return len(self.ch_src)

    @property
    def d(self) -> int:
        return int(self.depth.max()) + 1


def _bfs_depth(n: int, nbrs: list, root: int) -> np.ndarray:
    depth = np.full(n, np.inf)
    depth[root] = 0.0
    frontier = [root]
    while frontier:
        nxt = []
        for u in frontier:
            for w in nbrs[u]:
                if depth[w] == np.inf:
                    depth[w] = depth[u] + 1
                    nxt.append(w)
        frontier = nxt
    return depth


def build_network(pos: np.ndarray, edges: np.ndarray, substrate: str,
                  area_mm2: float) -> Network:
    """Channels, ports, hop latencies and up*/down* routing of a layout."""
    pos = np.asarray(pos, np.float64)
    edges = np.asarray(edges, np.int64)
    n = len(pos)
    ch_src = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int32)
    ch_dst = np.concatenate([edges[:, 1], edges[:, 0]]).astype(np.int32)
    n_ch = len(ch_src)

    # port k of a router is its k-th link in order of the neighbour's id
    ch_out_port = np.zeros(n_ch, np.int32)
    ch_in_port = np.zeros(n_ch, np.int32)
    for c in range(n_ch):
        ch_out_port[c] = np.sum((ch_src == ch_src[c]) & (ch_dst < ch_dst[c]))
        ch_in_port[c] = np.sum((ch_dst == ch_dst[c]) & (ch_src < ch_src[c]))
    p = int(max(ch_out_port.max(), ch_in_port.max())) + 1
    out_ch = np.full((n, p), -1, np.int32)
    in_ch = np.full((n, p), -1, np.int32)
    out_ch[ch_src, ch_out_port] = np.arange(n_ch)
    in_ch[ch_dst, ch_in_port] = np.arange(n_ch)

    # hop latency: router + tx PHY + wire (whole cycles) + rx PHY
    sub = SUBSTRATES[substrate]
    pitch = float(np.sqrt(area_mm2)) + sub["spacing_mm"]
    pmm = pos * pitch
    length = np.sqrt(((pmm[ch_src] - pmm[ch_dst]) ** 2).sum(-1))
    wire = np.ceil(length * np.sqrt(sub["eps_r"]) / C_MM_PER_NS)
    depth = np.maximum((wire + ROUTER_NS + 2.0 * PHY_NS).astype(np.int64),
                       1).astype(np.int32)

    # up*/down* labels from the chiplet nearest the layout's centre
    nbrs = [[] for _ in range(n)]
    for a, b in edges:
        nbrs[a].append(b)
        nbrs[b].append(a)
    d2 = ((pos - pos.mean(0)) ** 2).sum(-1)
    deg = np.array([len(x) for x in nbrs])
    d2 = np.where(deg > 0, d2, np.inf)
    root = int(np.argmin(d2))
    label = _bfs_depth(n, nbrs, root) * n + np.arange(n)
    up = label[ch_dst] < label[ch_src]

    # allowed turns c1 -> c2 (no u-turn, no down -> up)
    nxt = out_ch[ch_dst]                               # [C, p]
    nxt_safe = np.maximum(nxt, 0)
    allowed = (nxt >= 0) & (ch_dst[nxt_safe] != ch_src[:, None]) & \
        ~((~up)[:, None] & up[nxt_safe])

    # turns from each channel to each destination's ejection (BFS by
    # relaxation: 0 for channels that end at the destination)
    dist = np.where(ch_dst[None, :] == np.arange(n)[:, None], 0.0, np.inf)
    while True:
        via = np.where(allowed[None], dist[:, nxt_safe] + 1.0, np.inf)
        new = np.minimum(dist, via.min(axis=2))
        if np.array_equal(new, dist):
            break
        dist = new

    table = np.full((n, n, p + 1), -1, np.int16)
    # freshly injected: every output port may be taken
    inj = np.where(out_ch[None] >= 0, 1.0 + dist[:, np.maximum(out_ch, 0)],
                   np.inf)                             # [dst, n, p]
    best = np.argmin(inj, axis=2)
    ok = np.take_along_axis(inj, best[..., None], 2)[..., 0] < np.inf
    table[:, :, p] = np.where(ok, best, -1)
    # arrived on channel c1: only allowed turns
    cost = np.where(allowed[None], 1.0 + dist[:, nxt_safe], np.inf)  # [dst, C, p]
    best = np.argmin(cost, axis=2)
    ok = np.take_along_axis(cost, best[..., None], 2)[..., 0] < np.inf
    table[:, ch_dst, ch_in_port] = np.where(ok, best, -1)
    table[np.arange(n), np.arange(n), :] = EJECT
    return Network(n=n, p=p, ch_src=ch_src, ch_dst=ch_dst,
                   ch_out_port=ch_out_port, ch_in_port=ch_in_port,
                   out_ch=out_ch, in_ch=in_ch, depth=depth, table=table)


def analytic_bound(net: Network, traffic: np.ndarray) -> float:
    """Saturation rate of the channel-load model: 1 / the most loaded
    channel at unit injection (or the most loaded ejection port), <= 1.
    Loads are summed hop by hop in (source, destination) order."""
    n = net.n
    s_idx, d_idx = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    s_idx, d_idx = s_idx.ravel(), d_idx.ravel()
    w = traffic[s_idx, d_idx]
    alive = (s_idx != d_idx) & (w > 0)
    cur = s_idx.copy()
    in_port = np.full(n * n, net.p, np.int32)
    loads = np.zeros(net.c)
    for _ in range(4 * n):
        if not alive.any():
            break
        port = net.table[d_idx[alive], cur[alive], in_port[alive]]
        if (port < 0).any():
            raise RuntimeError("routing dead end")
        ch = net.out_ch[cur[alive], port]
        np.add.at(loads, ch, w[alive])
        nxt_node = net.ch_dst[ch]
        cur[alive] = nxt_node
        in_port[alive] = net.ch_in_port[ch]
        alive[alive] = nxt_node != d_idx[alive]
    ej = traffic.sum(axis=0).max()
    return float(min(1.0 / max(loads.max(), 1e-12), 1.0 / max(ej, 1e-12),
                     1.0))


def traffic_arrays(traffic: np.ndarray):
    """(cumulative destination rows, injection weight) as float32."""
    rows = traffic.sum(axis=1)
    inj_w = rows / max(rows.max(), 1e-12)
    cum = np.cumsum(traffic, axis=1)
    cum = cum / np.maximum(cum[:, -1:], 1e-12)
    cum[rows <= 0] = 1.0
    return cum.astype(np.float32), inj_w.astype(np.float32)


def _mix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x7FEB352D)
    h = h ^ (h >> np.uint32(15))
    h = h * np.uint32(0x846CA68B)
    return h ^ (h >> np.uint32(16))


def node_bits(seed: int, t: int, n: int, stream: int) -> np.ndarray:
    """uint32 per node from (seed, cycle, node, stream)."""
    with np.errstate(over="ignore"):
        h = _mix32(np.array([np.uint32(seed) ^ (np.uint32(stream) * _GOLD)],
                            np.uint32))
        h = _mix32(h ^ (np.uint32(t) * _MIX_T))
        return _mix32(h ^ (np.arange(n, dtype=np.uint32) * _MIX_N))


def unit(bits: np.ndarray) -> np.ndarray:
    return (bits >> np.uint32(8)).astype(np.float32) * np.float32(1.0 / (1 << 24))


def simulate(net: Network, traffic: np.ndarray, rates, *, cycles: int,
             warmup: int, n_vcs: int, buf_depth: int, seed: int,
             rotate: bool = True, telemetry: bool = False,
             windows: int = 0) -> dict:
    """Raw counters of one spec at each offered rate (lanes L = rates).

    Returns int64 arrays [L]: `delivered`, `offered_n`, `accepted_n`
    and `lat_sum`, counted over cycles warmup..cycles-1.  rotate=False
    freezes the allocator's rotating priority (the control).

    telemetry=True adds the flight recorder's counters over the same
    cycles, channels in this network's order:

      link_busy [L, C]       flits that left on each channel;
      link_stall [L, C]      head flits that are valid, not ejecting and
                             without a credit for the channel their route
                             names, charged to that channel;
      link_occ_sum [L, C, V] flits held in each channel's downstream
                             input buffer, per VC, read after arrivals and
                             injection and before the winners leave;
      inj_node, eject_node [L, n]  flits injected / ejected per node;
      lat_hist [L, 16]       ejected flits by latency: bin h holds
                             latencies in [2^(h-1), 2^h), the last bin
                             everything from 2^14 up.

    windows=W > 0 (with telemetry) adds `link_busy_w`, `link_stall_w`,
    `link_occ_w`, `inj_node_w`, `eject_node_w`, each with a window axis
    after the lane axis (measured cycle t falls in window
    ((t - warmup) * W) // (cycles - warmup)), and `window_cycles` [W].
    """
    n, P, C, D = net.n, net.p, net.c, net.d
    V, B, PI = n_vcs, buf_depth, net.p + 1
    rates = np.asarray(rates, np.float32)
    L = len(rates)
    cum, inj_w = traffic_arrays(np.asarray(traffic, np.float64))
    thresh = rates[:, None] * inj_w[None, :]              # float32 [L, n]

    buf_dst = np.full((L, n, PI, V, B), -1, np.int32)
    buf_t = np.zeros((L, n, PI, V, B), np.int32)
    head = np.zeros((L, n, PI, V), np.int32)
    cnt = np.zeros((L, n, PI, V), np.int32)
    credits = np.full((L, n, P, V), B, np.int32)
    link_dst = np.full((L, C, D), -1, np.int32)
    link_t = np.zeros((L, C, D), np.int32)
    link_vc = np.zeros((L, C, D), np.int32)
    credit_pipe = np.zeros((L, C, D, V), np.int32)
    delivered = np.zeros(L, np.int64)
    offered = np.zeros(L, np.int64)
    accepted = np.zeros(L, np.int64)
    lat_sum = np.zeros(L, np.int64)
    rr = 0
    meas = cycles - warmup
    if windows and not telemetry:
        raise ValueError("windows bin the flight recorder: telemetry=True")
    if not 0 <= windows <= meas:
        raise ValueError(f"windows={windows} outside 0..{meas}")
    flight = {}
    if telemetry:
        flight = {"link_busy": np.zeros((L, C), np.int64),
                  "link_stall": np.zeros((L, C), np.int64),
                  "link_occ_sum": np.zeros((L, C, V), np.int64),
                  "inj_node": np.zeros((L, n), np.int64),
                  "eject_node": np.zeros((L, n), np.int64)}
        lat_hist = np.zeros((L, LAT_HIST_BINS), np.int64)
        hist_edges = 2 ** np.arange(LAT_HIST_BINS - 1)
    binned = {WINDOWED[k]: np.zeros((L, windows) + v.shape[1:], np.int64)
              for k, v in flight.items()} if windows else {}
    window_cycles = np.zeros(windows, np.int64)

    li = np.arange(L)
    nodes = np.arange(n)
    lN = li[:, None, None, None]
    nN = nodes[None, :, None, None]
    pN = np.arange(PI)[None, None, :, None]
    vN = np.arange(V)[None, None, None, :]
    l3, n3, p3 = li[:, None, None], nodes[None, :, None], \
        np.arange(PI)[None, None, :]
    table, out_ch, in_ch, depth = net.table, net.out_ch, net.in_ch, net.depth
    # flat offsets: a lane's buffer slots, its table row, its credit row
    lane_base = (((lN * n + nN) * PI + pN) * V + vN) * B
    table_flat = table.reshape(-1)
    route_base = nN * PI + pN
    cred_base = (lN * n + nN) * (PI * V) + vN

    for t in range(cycles):
        slot = t % D
        m = 1 if t >= warmup else 0

        # 1. link arrivals into the downstream input buffers
        la, lc = np.nonzero(link_dst[:, :, slot] >= 0)
        if len(la):
            nd, ip = net.ch_dst[lc], net.ch_in_port[lc]
            vc = link_vc[la, lc, slot]
            pos = (head[la, nd, ip, vc] + cnt[la, nd, ip, vc]) % B
            buf_dst[la, nd, ip, vc, pos] = link_dst[la, lc, slot]
            buf_t[la, nd, ip, vc, pos] = link_t[la, lc, slot]
            cnt[la, nd, ip, vc] += 1
        link_dst[:, :, slot] = -1

        # 2. credits returning upstream
        credits[:, net.ch_src, net.ch_out_port, :] += credit_pipe[:, :, slot, :]
        credit_pipe[:, :, slot, :] = 0

        # 3. injection
        want = unit(node_bits(seed, t, n, 0))[None, :] < thresh
        u_dst = unit(node_bits(seed, t, n, 1))
        dst = np.clip(np.sum(cum < u_dst[:, None], axis=1), 0, n - 1)
        vci = (node_bits(seed, t, n, 2) % np.uint32(V)).astype(np.int64)
        want &= (dst != nodes)[None, :]
        do = want & (cnt[:, nodes, P, vci] < B)
        ia, inode = np.nonzero(do)
        ivc = vci[inode]
        posi = (head[ia, inode, P, ivc] + cnt[ia, inode, P, ivc]) % B
        buf_dst[ia, inode, P, ivc, posi] = dst[inode]
        buf_t[ia, inode, P, ivc, posi] = t
        cnt[ia, inode, P, ivc] += 1
        offered += m * want.sum(axis=1)
        accepted += m * do.sum(axis=1)

        # 4. route lookup and credit check of every head flit
        at_head = lane_base + head
        head_dst = buf_dst.reshape(-1)[at_head]
        head_t = buf_t.reshape(-1)[at_head]
        valid = cnt > 0
        op = table_flat[np.where(valid, head_dst, 0) * (n * PI) + route_base]
        op = np.where(valid, op.astype(np.int32), -3)
        is_eject = op == EJECT
        op_slot = np.where(is_eject, P, op)
        cred = np.concatenate([credits, np.full((L, n, 1, V), INF, np.int32)],
                              axis=2)
        have = cred.reshape(-1)[cred_base + np.clip(op_slot, 0, P) * V] > 0
        eligible = valid & (op_slot >= 0) & (have | is_eject)
        if telemetry and m:
            cyc = {k: np.zeros((L,) + v.shape[1:], np.int64)
                   for k, v in flight.items()}
            cyc["link_occ_sum"][:] = cnt[:, net.ch_dst, net.ch_in_port, :]
            cyc["inj_node"][:] = do
            sl, sn, sp, sv = np.nonzero(valid & (op_slot >= 0) & ~is_eject
                                        & ~have)
            s_ch = out_ch[sn, op_slot[sl, sn, sp, sv]]
            if (s_ch < 0).any():
                raise RuntimeError("a route names a port with no channel")
            np.add.at(cyc["link_stall"], (sl, s_ch), 1)

        # switch allocation: a) one VC per input port, b) one input port
        # per output slot, each by rotating priority (lowest index on ties)
        rr_vc, rr_port = (rr % V, rr % PI) if rotate else (0, 0)
        vc_score = np.where(eligible, (vN - rr_vc) % V, INF)
        vc_choice = np.argmin(vc_score, axis=3)                 # [L, n, PI]
        port_ok = vc_score.min(axis=3) < INF
        out_req = np.where(port_ok, np.take_along_axis(
            op_slot, vc_choice[..., None], 3)[..., 0], -1)
        p_score = (np.arange(PI) - rr_port) % PI
        req = out_req[..., :, None] == np.arange(PI)[None, None, None, :]
        score = np.where(req, p_score[None, None, :, None], INF)  # [L,n,in,out]
        win_in = np.argmin(score, axis=2)                       # [L, n, out]
        win_ok = score.min(axis=2) < INF
        wins = np.zeros((L, n, PI), bool)
        wl, wn, wo = np.nonzero(win_ok)
        wins[wl, wn, win_in[wl, wn, wo]] = True
        wins &= port_ok

        # 5. winners leave their buffer
        wl, wn, wp = np.nonzero(wins)
        wv = vc_choice[wl, wn, wp]
        w_dst = head_dst[wl, wn, wp, wv]
        w_t = head_t[wl, wn, wp, wv]
        w_out = out_req[wl, wn, wp]
        head[wl, wn, wp, wv] = (head[wl, wn, wp, wv] + 1) % B
        cnt[wl, wn, wp, wv] -= 1
        # a credit goes back up the channel the flit arrived on
        up = wp < P
        uc = in_ch[wn[up], wp[up]]
        ok = uc >= 0
        ul, uc, uv = wl[up][ok], uc[ok], wv[up][ok]
        credit_pipe[ul, uc, (t + depth[uc]) % D, uv] += 1
        # ejection
        ej = w_out == P
        np.add.at(delivered, wl[ej], m)
        np.add.at(lat_sum, wl[ej], m * (t - w_t[ej]).astype(np.int64))
        # traversal onto the output channel's pipeline
        tr = (w_out >= 0) & (w_out < P)
        tl, tn, to, tv = wl[tr], wn[tr], w_out[tr], wv[tr]
        oc = out_ch[tn, to]
        ws = (t + depth[oc]) % D
        link_dst[tl, oc, ws] = w_dst[tr]
        link_t[tl, oc, ws] = w_t[tr]
        link_vc[tl, oc, ws] = tv
        credits[tl, tn, to, tv] -= 1
        rr = (rr + 1) % (V * PI)

        if telemetry and m:
            np.add.at(cyc["link_busy"], (tl, oc), 1)
            np.add.at(cyc["eject_node"], (wl[ej], wn[ej]), 1)
            lat = t - w_t[ej]
            np.add.at(lat_hist, (wl[ej], np.searchsorted(hist_edges, lat,
                                                         side="right")), 1)
            for k, v in cyc.items():
                flight[k] += v
            if windows:
                w = (t - warmup) * windows // meas
                window_cycles[w] += 1
                for k, v in cyc.items():
                    binned[WINDOWED[k]][:, w] += v

    out = dict(delivered=delivered, offered_n=offered, accepted_n=accepted,
               lat_sum=lat_sum)
    if telemetry:
        out.update(flight, lat_hist=lat_hist)
        _check_flight(out, binned, window_cycles, meas)
        if windows:
            out.update(binned, window_cycles=window_cycles)
    return out


def _check_flight(out: dict, binned: dict, window_cycles, meas: int):
    """The recorder's own conservation laws; a breach is a fault of this
    reference, so it raises."""
    laws = [("inj_node", out["inj_node"].sum(1), out["accepted_n"]),
            ("eject_node", out["eject_node"].sum(1), out["delivered"]),
            ("lat_hist", out["lat_hist"].sum(1), out["delivered"])]
    for k, wk in WINDOWED.items():
        if wk in binned:
            laws.append((wk, binned[wk].sum(1), out[k]))
    if len(window_cycles):
        laws.append(("window_cycles", window_cycles.sum(), meas))
    for name, got, want in laws:
        if not np.array_equal(got, want):
            raise RuntimeError(f"reference flight recorder: {name} does "
                               f"not add up")
