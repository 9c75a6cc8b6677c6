"""The ICI simulator's switch-allocation step as a Pallas TPU kernel —
the paper-specific hot loop (repro.core.simulator executes this every
simulated cycle for every router).

Two-phase separable allocation over a tile of routers:
  phase a — each input port picks its best eligible VC (rotating
            priority argmin over the V lane),
  phase b — each output slot picks one requesting input port.

Inputs per router tile [BN, PI, V]: op_slot (requested output slot per
head flit, -1 if none) and eligible (credit/validity mask, carried as
int32 0/1); plus the two rotating-priority counters, each broadcast over
an (8, 128) int32 tile so that the operand stays tile-aligned under the
simulator's spec x rate vmaps.  Outputs: win_mask [BN, PI, V] (int32
0/1, cast back to bool by the wrapper) and the chosen vc / out-slot per
port.  Pure int32 vector ops (masked mins over iotas, compares) — VPU
work, no MXU.  The kernel's VMEM grows with the router block BN times
PI squared, so BN shrinks as PI grows (`router_block`): at PI=31
(FlattenedButterfly at N=256) a block of 8 routers fits v5e's 16 MiB of
scoped VMEM, where 16 needs 17.6 MiB and 64 far more.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = 2 ** 30   # python literal: jnp constants would be captured consts
_RR_TILE = (8, 128)   # one int32 vreg: the counters' tile-aligned carrier


def router_block(pi: int) -> int:
    """Routers per grid step.  The unrolled output-slot loop keeps about
    PI live [BN, PI] temporaries, each padding PI to whole 8-sublane
    tiles, so the kernel's VMEM grows as BN * PI * ceil8(PI) (about 2.5
    KiB per unit when compiled for v5e).  Take the largest power of two
    <= 64 that keeps that product <= 10240 (~12 MiB of the 16 MiB scoped
    limit): PI <= 9 -> 64, PI=15 -> 32, PI=24 -> 16, PI=31 -> 8."""
    cost = pi * (-(-pi // 8) * 8)
    block = 64
    while block > 8 and block * cost > 10240:
        block //= 2
    return block


def _netstep_kernel(op_slot_ref, eligible_ref, rr_ref, win_ref, vc_ref,
                    req_ref, *, n_out: int):
    op_slot = op_slot_ref[...]                 # [BN, PI, V] int32
    eligible = eligible_ref[...] != 0          # [BN, PI, V]
    rr_vc = jnp.max(rr_ref[0])                 # VC-phase rotating counter
    rr_port = jnp.max(rr_ref[1])               # port-phase rotating counter
    bn, pi, v = op_slot.shape

    # phase a: rotating-priority VC choice per input port; the argmin is
    # the lowest VC index attaining the min score (argmin's tie rule)
    vcs = jax.lax.broadcasted_iota(jnp.int32, (bn, pi, v), 2)
    vc_score = jnp.where(eligible, (vcs - rr_vc) % v, INF)
    best = jnp.min(vc_score, axis=2, keepdims=True)       # [BN, PI, 1]
    vc_choice = jnp.min(jnp.where(vc_score == best, vcs, v), axis=2)
    port_ok = best[:, :, 0] < INF
    sel = vcs == vc_choice[:, :, None]
    out_req = jnp.where(
        port_ok,
        jnp.sum(jnp.where(sel, op_slot, 0), axis=2), -1)  # [BN, PI]

    # phase b: each output slot takes the lowest-priority-score requester
    ports = jax.lax.broadcasted_iota(jnp.int32, (bn, pi), 1)
    p_score = (ports - rr_port) % pi                      # [BN, PI]
    win = jnp.zeros((bn, pi), jnp.bool_)
    for o in range(n_out):                                # static radix
        req_o = out_req == o
        score_o = jnp.where(req_o, p_score, INF)
        m = jnp.min(score_o, axis=1, keepdims=True)
        win_o = req_o & (score_o == m) & (m < INF)
        # strict one-winner: lowest port index among score ties
        first = jnp.min(jnp.where(win_o, ports, pi), axis=1, keepdims=True)
        win |= win_o & (ports == first)
    win_mask = sel & eligible & win[:, :, None]
    win_ref[...] = win_mask.astype(jnp.int32)
    vc_ref[...] = vc_choice
    req_ref[...] = out_req


@functools.partial(jax.jit, static_argnames=("interpret",))
def netstep_pallas(op_slot, eligible, rr, *, interpret: bool = False):
    """op_slot: [N, PI, V] int32 (requested out slot, -1 none);
    eligible: [N, PI, V] bool; rr: scalar int32 — or an (rr_vc, rr_port)
    pair to rotate the VC and port phases with different periods, as the
    batched simulator requires (DESIGN.md §6).
    Returns (win_mask [N,PI,V] bool, vc_choice [N,PI], out_req [N,PI])."""
    if isinstance(rr, tuple):
        rr_vc, rr_port = rr
    else:
        rr_vc = rr_port = rr
    rr2 = jnp.stack([jnp.asarray(rr_vc, jnp.int32),
                     jnp.asarray(rr_port, jnp.int32)])
    rr_tile = jnp.broadcast_to(rr2[:, None, None], (2,) + _RR_TILE)
    eligible = eligible.astype(jnp.int32)
    n, pi, v = op_slot.shape
    block = router_block(pi)
    pad = (-n) % block
    if pad:
        op_slot = jnp.pad(op_slot, ((0, pad), (0, 0), (0, 0)),
                          constant_values=-1)
        eligible = jnp.pad(eligible, ((0, pad), (0, 0), (0, 0)))
    np_ = op_slot.shape[0]
    kern = functools.partial(_netstep_kernel, n_out=pi)
    win, vc, req = pl.pallas_call(
        kern,
        grid=(np_ // block,),
        in_specs=[
            pl.BlockSpec((block, pi, v), lambda i: (i, 0, 0)),
            pl.BlockSpec((block, pi, v), lambda i: (i, 0, 0)),
            pl.BlockSpec((2,) + _RR_TILE, lambda i: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, pi, v), lambda i: (i, 0, 0)),
            pl.BlockSpec((block, pi), lambda i: (i, 0)),
            pl.BlockSpec((block, pi), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, pi, v), jnp.int32),
            jax.ShapeDtypeStruct((np_, pi), jnp.int32),
            jax.ShapeDtypeStruct((np_, pi), jnp.int32),
        ],
        interpret=interpret,
    )(op_slot, eligible, rr_tile)
    return win[:n] != 0, vc[:n], req[:n]
