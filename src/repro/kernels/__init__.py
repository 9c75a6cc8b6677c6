"""Pallas TPU kernel for the simulator's hot loop.

The kernel directory has:
    <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling
    ops.py    — jit'd public wrapper (interpret=True on CPU)
    ref.py    — pure-jnp oracle used by the bitwise test sweeps

Kernel:
    netstep — the ICI simulator's two-phase separable switch
        allocation, tiled over router blocks.
"""
