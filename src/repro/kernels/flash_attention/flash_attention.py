"""Blockwise flash attention (forward) as a Pallas TPU kernel.

Grid: (batch*heads, q_blocks).  Each program holds one q tile
[BQ, hd] in VMEM plus the full k/v stripes for its (batch, head) —
[T_k, hd] each, bf16, which fits VMEM for T_k <= 32k at hd=128 — and
iterates over k tiles with the online-softmax running (m, l, acc)
recurrence.  Causal and sliding-window masks are applied per tile, and
fully-masked tiles are skipped via the loop bounds (causal ⇒ only tiles
with k_start <= q_end; window ⇒ only tiles with k_end > q_start-window).

MXU alignment: BQ = BK = 128, hd padded to a multiple of 128 by the
wrapper when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
               seq_k: int, causal: bool, window: int | None,
               sm_scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale        # [bq, hd]
    hd = q.shape[-1]
    q_start = qi * bq
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    n_k = seq_k // bk
    if causal:
        # highest k tile that any of our queries can see
        hi = jnp.minimum((q_start + bq + bk - 1) // bk, n_k)
    else:
        hi = n_k
    if window is not None:
        lo = jnp.maximum((q_start - window) // bk, 0)
    else:
        lo = 0

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)  # [bk, hd]
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.maximum(m_prev, s.max(axis=1))        # [bq]
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + p.sum(axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_cur, l_cur, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq",
                                             "bk", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=None,
                         bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=False):
    """q: [BH, Tq, hd], k/v: [BH, Tk, hd] (kv already head-broadcast)."""
    bh, tq, hd = q.shape
    tk = k.shape[1]
    assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
    sm_scale = 1.0 / np.sqrt(hd)
    kern = functools.partial(_fa_kernel, bq=bq, bk=bk, seq_k=tk,
                             causal=causal, window=window,
                             sm_scale=sm_scale)
    grid = (bh, tq // bq)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, tk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, tk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
