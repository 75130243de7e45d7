"""Plain reference for the QAOA energies of ``families/qaoa.py``.

It shares no code with the program. The state is a complex64 tensor
whose axes hold the qubits' bits in groups of seven from the lowest
(``2^18`` amplitudes: ``16 x 128 x 128``). The cost layer of a round is
diagonal: ``exp(-i gamma/2 s(z))`` with ``s(z) = sum over edges of
(1 - 2 (z_u xor z_v))``; the mixer ``rx(beta)`` on every qubit is, per
axis, one matrix ``rx(beta)^(x k)`` applied along that axis. The energy
is ``-1/2 sum_z |psi_z|^2 s(z)``. Contractions run at the precision
given (``matmul.py``): ``highest`` for the reference, ``bf16_3x``
(three bfloat16 passes) for the control. Requests are taken in blocks.
"""

from __future__ import annotations

import numpy as np

from ..families import qaoa
from . import matmul

BLOCK = 32


def _groups(n: int) -> list:
    """Bits per tensor axis, most significant axis first."""
    sizes = []
    while n > 0:
        sizes.append(min(7, n))
        n -= sizes[-1]
    return sizes[::-1]


def parity_sum(cfg: dict) -> np.ndarray:
    """``s(z)`` for every basis state ``z``."""
    n = cfg["qubits"]
    z = np.arange(1 << n, dtype=np.int64)
    s = np.zeros(1 << n, dtype=np.int32)
    for u, v in qaoa.graph(cfg):
        s += 1 - 2 * (((z >> u) ^ (z >> v)) & 1).astype(np.int32)
    return s


def make_energies(cfg: dict, precision):
    """A function: ``(B, 2 * rounds)`` parameter rows in, ``(B,)``
    float64 energies out."""
    import jax
    import jax.numpy as jnp

    n, rounds = cfg["qubits"], cfg["rounds"]
    sizes = _groups(n)
    shape = tuple(1 << k for k in sizes)
    s = jnp.asarray(parity_sum(cfg).astype(np.float32).reshape(shape))
    ids = "acdefgh"[:len(sizes)]

    def rx_power(beta, k):
        c, sn = jnp.cos(beta / 2), jnp.sin(beta / 2)
        rx = jnp.array([[c, -1j * sn], [-1j * sn, c]], dtype=jnp.complex64)
        m = jnp.ones((1, 1), jnp.complex64)
        for _ in range(k):
            m = jnp.kron(m, rx)
        return m

    def one(params):
        psi = jnp.full(shape, 2.0 ** (-n / 2), jnp.complex64)
        for l in range(rounds):
            gamma, beta = params[2 * l], params[2 * l + 1]
            psi = psi * jnp.exp(-0.5j * gamma * s)
            for a, k in enumerate(sizes):
                spec = ids[:a] + "Z" + ids[a + 1:]
                psi = matmul.einsum(
                    f"YZ,{spec}->{spec.replace('Z', 'Y')}",
                    rx_power(beta, k), psi, precision)
        prob = jnp.real(psi) ** 2 + jnp.imag(psi) ** 2
        return -0.5 * jnp.sum(prob * s)

    fn = jax.jit(jax.vmap(one))

    def energies(param_rows):
        rows = np.asarray(param_rows, dtype=np.float32)
        out = []
        for i in range(0, len(rows), BLOCK):
            block = rows[i:i + BLOCK]
            pad = BLOCK - len(block)
            if pad:
                block = np.concatenate([block, np.zeros((pad,) + block.shape[1:],
                                                        np.float32)])
            out.append(np.asarray(fn(jnp.asarray(block)))[:BLOCK - pad])
        return np.concatenate(out).astype(np.float64) if out \
            else np.zeros(0)

    return energies
