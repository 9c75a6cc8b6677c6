"""Public wrapper for the netstep Pallas kernel.

`netstep` is the allocation hot loop the batched simulator dispatches to
when `SimConfig.alloc` resolves to "pallas" (auto on TPU).  On CPU the
kernel runs in interpret mode — correct but slow, so the simulator
defaults to the pure-jnp oracle there.

Telemetry neutrality (DESIGN.md §13): the flight recorder observes the
allocation *outputs* (win_mask and the masks the step derives from it)
— it never reaches into the kernel, so `SimConfig(telemetry=...)` can
not change which impl runs or what it computes, and the kernel needs no
recompile when telemetry toggles."""
import jax

from .netstep import netstep_pallas


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def is_available() -> bool:
    """True when the kernel compiles natively (non-interpreted)."""
    return jax.default_backend() == "tpu"


def netstep(op_slot, eligible, rr):
    return netstep_pallas(op_slot, eligible, rr, interpret=_interpret())
