"""Pure-functional statevector operations (jit-internal backend layer).

TPU implementation of the reduction / data-movement / collapse slice of the
reference's ``statevec_*`` backend contract (``QuEST_internal.h:108-246``).
Every function takes a flat complex amplitude array plus static qubit
metadata and returns a new array; under jit XLA fuses these into single
memory passes, and under a sharded mesh the same code lowers to ICI
collectives.

Unitary/diagonal gate application does NOT live here: gates route through
the axis-contraction engine (``core/apply.py``) via the API layer and the
circuit compiler — one engine subsumes the reference's entire per-gate
kernel family (``QuEST_cpu.c:1662-3114``). State initialisation is host-side
in the API layer (``api.py:initZeroState`` etc.): inits are one-time
host→device transfers, not compiled kernels.

All ops are dtype-preserving and jit-compatible (static ints/tuples only).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core.apply import (LANE_BITS, _SWAP, apply_diagonal, apply_unitary,
                          split_shape)

__all__ = [
    "multi_rotate_z_diag",
    "swap_amps",
    "calc_total_prob",
    "calc_inner_product",
    "calc_prob_of_outcome",
    "zero_outcome_part",
    "collapse_to_known_prob_outcome",
    "set_weighted",
]


def multi_rotate_z_diag(k: int, angle: float) -> np.ndarray:
    """(2,)*k parity-phase tensor: even-parity bit patterns get
    exp(-i angle/2), odd get exp(+i angle/2) (``QuEST_cpu.c:3075-3114``)."""
    idx = np.arange(1 << k)
    parity = np.zeros(1 << k, dtype=np.int64)
    for b in range(k):
        parity ^= (idx >> b) & 1
    fac = np.where(parity == 0, np.exp(-0.5j * angle), np.exp(0.5j * angle))
    return fac.reshape((2,) * k)


def swap_amps(state, num_qubits, q1, q2):
    """SWAP as pure data movement — its unit entries become slice moves,
    no arithmetic (vs ``statevec_swapQubitAmps`` ``QuEST_cpu.c:3502``)."""
    return apply_unitary(state, num_qubits, _SWAP, (q1, q2))


# ---------------------------------------------------------------------------
# reductions & collapse (QuEST_cpu.c:3117-3494, QuEST_cpu_distributed.c:34-116)
# ---------------------------------------------------------------------------

def calc_total_prob(state) -> jnp.ndarray:
    """Sum of |amp|^2. XLA owns the reduction tree; the error-compensated
    route (the reference's Kahan analogue,
    ``QuEST_cpu_distributed.c:96-109``) lives in ``ops.reductions`` and is
    selected by the API layer via ``env.compensated``."""
    return jnp.sum(jnp.real(state) ** 2 + jnp.imag(state) ** 2)


def calc_inner_product(bra, ket) -> jnp.ndarray:
    """<bra|ket> (conjugates bra, as ``calcInnerProductLocal``
    ``QuEST_cpu.c:1076``)."""
    return jnp.vdot(bra, ket)


def zero_outcome_part(x, num_qubits: int, qubit: int):
    """The amplitudes of ``x`` (last axis) whose ``qubit`` bit is 0, for a
    reduction: on the ``(rows, 128)`` view (core/apply.py, "Lane qubits"),
    with the other amplitudes zeroed by an ``iota`` mask over the lanes or
    the rows. A slice of the split axis would leave a minor dimension of
    ``2^qubit`` for a lane qubit, and for the top qubit of 28 its
    compensated sum compiled for 12 minutes on a TPU."""
    lead = x.shape[:-1]
    if num_qubits < LANE_BITS:
        pre, _, post = split_shape(num_qubits, (qubit,))
        return x.reshape(lead + (pre, 2, post))[..., 0, :]
    v = x.reshape(lead + (-1, 1 << LANE_BITS))
    if qubit < LANE_BITS:
        i = lax.broadcasted_iota(jnp.int32, (1, 1 << LANE_BITS), 1)
        keep = ((i >> qubit) & 1) == 0
    else:
        i = lax.broadcasted_iota(jnp.int32, (v.shape[-2], 1), 0)
        keep = ((i >> (qubit - LANE_BITS)) & 1) == 0
    return jnp.where(keep, v, jnp.zeros((), v.dtype))


def calc_prob_of_outcome(state, num_qubits: int, qubit: int, outcome: int) -> jnp.ndarray:
    """P(outcome 0) summed directly; P(outcome 1) as its complement 1-P0 —
    the reference's exact semantics (``statevec_calcProbOfOutcome``
    ``QuEST_cpu_local.c:279-285``), observable on unnormalised registers
    (debug state): summing the outcome-1 amplitudes would differ."""
    sub = zero_outcome_part(state, num_qubits, qubit)
    zero_prob = jnp.sum(jnp.real(sub) ** 2 + jnp.imag(sub) ** 2)
    return zero_prob if outcome == 0 else 1.0 - zero_prob


def collapse_to_known_prob_outcome(state, num_qubits, qubit, outcome, prob):
    """Zero the non-outcome half, renormalise the outcome half by 1/sqrt(prob)
    (``QuEST_cpu.c:3346-3494``). ``prob`` may be traced."""
    renorm = (1.0 / jnp.sqrt(prob)).astype(state.dtype)
    fac = jnp.zeros((2,), dtype=state.dtype).at[outcome].set(renorm)
    return apply_diagonal(state, num_qubits, (qubit,), fac)


def set_weighted(fac1, state1, fac2, state2, fac_out, out):
    """out = fac1*state1 + fac2*state2 + facOut*out (``QuEST_cpu.c:3585``)."""
    f1 = jnp.asarray(fac1, dtype=out.dtype)
    f2 = jnp.asarray(fac2, dtype=out.dtype)
    fo = jnp.asarray(fac_out, dtype=out.dtype)
    return f1 * state1 + f2 * state2 + fo * out
