"""Split real/imag state representation.

The register state is stored as a float array of shape ``(2, 2^N)`` — a
real plane and an imaginary plane — mirroring the reference's split
``stateVec.real`` / ``stateVec.imag`` storage (``QuEST_cpu.c:1284-1320``),
and required on TPU: the PJRT backend rejects complex-typed device buffers
at executable boundaries, while complex arithmetic *inside* a compiled
program lowers fine. Every kernel therefore unpacks floats -> complex at
trace time, computes, and packs back; XLA fuses the (de)interleaving into
the surrounding ops for free.

:func:`pack` selects between the two planes instead of stacking them: the
TPU compiler aborts (``Check failed: IsFusibleUnalignedDUS``) on a stack
whose operands were computed on the ``(rows, 128)`` lane view of the
state (``core/apply.py``), while the select fuses into one pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["pack", "unpack", "pack_host", "unpack_host"]


def unpack(state_f: jnp.ndarray) -> jnp.ndarray:
    """(2, ...) float planes -> complex array (jit-internal only)."""
    return jax.lax.complex(state_f[0], state_f[1])


def pack(z: jnp.ndarray) -> jnp.ndarray:
    """complex array -> (2, ...) float planes (jit-internal only)."""
    first = jax.lax.broadcasted_iota(jnp.int32, (2,) + (1,) * z.ndim, 0) == 0
    return jnp.where(first, jnp.real(z)[None], jnp.imag(z)[None])


def pack_host(z: np.ndarray, real_dtype) -> np.ndarray:
    z = np.asarray(z)
    return np.stack([np.real(z), np.imag(z)]).astype(real_dtype)


def unpack_host(f: np.ndarray) -> np.ndarray:
    f = np.asarray(f)
    cdtype = np.complex64 if f.dtype == np.float32 else np.complex128
    return (f[0] + 1j * f[1]).astype(cdtype)
