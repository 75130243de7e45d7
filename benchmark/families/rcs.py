"""Sycamore-style random circuits on a rectangular patch.

The circuit is fixed by the configuration: a grid of ``rows x cols``
qubits (qubit ``(r, c)`` is bit ``r * cols + c``), ``cycles`` cycles of
a 1-qubit layer drawn from {sqrt(X), sqrt(Y), sqrt(W)} (never the same
gate twice in a row on one qubit) followed by fSim(theta, phi) on one
coupler pattern, the patterns in the order of ``pattern_sequence``, and a
final 1-qubit layer. The draw comes from ``structure_seed``, so every run
seed shares one compiled program; a run's seed draws its initial state.

This module holds what the program and the reference share: the gate
list, the gate matrices and the seeded initial state. It imports nothing
of the program; :func:`build_program` is handed the package.
"""

from __future__ import annotations

import numpy as np

_I2 = np.eye(2, dtype=np.complex128)
_X = np.array([[0, 1], [1, 0]], dtype=np.complex128)
_Y = np.array([[0, -1j], [1j, 0]], dtype=np.complex128)
_W = (_X + _Y) / np.sqrt(2.0)


def _sqrt_pauli(p: np.ndarray) -> np.ndarray:
    """P^(1/2) for a Pauli-like P (P^2 = I): (1+i)/2 (I - iP)."""
    return (1 + 1j) / 2 * (_I2 - 1j * p)


SINGLE_QUBIT = {"sqrt_x": _sqrt_pauli(_X), "sqrt_y": _sqrt_pauli(_Y),
                "sqrt_w": _sqrt_pauli(_W)}


def fsim(theta: float, phi: float) -> np.ndarray:
    c, s = np.cos(theta), np.sin(theta)
    return np.array([[1, 0, 0, 0],
                     [0, c, -1j * s, 0],
                     [0, -1j * s, c, 0],
                     [0, 0, 0, np.exp(-1j * phi)]], dtype=np.complex128)


def couplers(rows: int, cols: int, pattern: str) -> list:
    """Qubit pairs of one pattern: A/B horizontal at even/odd columns,
    C/D vertical at even/odd rows."""
    q = lambda r, c: r * cols + c  # noqa: E731
    if pattern in "AB":
        start = 0 if pattern == "A" else 1
        return [(q(r, c), q(r, c + 1)) for r in range(rows)
                for c in range(start, cols - 1, 2)]
    if pattern in "CD":
        start = 0 if pattern == "C" else 1
        return [(q(r, c), q(r + 1, c)) for r in range(start, rows - 1, 2)
                for c in range(cols)]
    raise ValueError(f"unknown coupler pattern {pattern!r}")


def layers(cfg: dict) -> list:
    """The circuit as a list of layers: ``("1q", [gate name per qubit])``
    and ``("2q", pattern, [(q1, q2), ...])``."""
    rows, cols = cfg["grid"]
    n = rows * cols
    if n != cfg["qubits"]:
        raise ValueError(f"grid {rows}x{cols} is not {cfg['qubits']} qubits")
    names = list(cfg["single_qubit_gates"])
    rng = np.random.default_rng(cfg["structure_seed"])
    prev = [None] * n
    out = []

    def one_qubit_layer():
        layer = []
        for q in range(n):
            choices = [g for g in names if g != prev[q]]
            prev[q] = choices[int(rng.integers(len(choices)))]
            layer.append(prev[q])
        out.append(("1q", layer))

    seq = cfg["pattern_sequence"]
    for cycle in range(cfg["cycles"]):
        one_qubit_layer()
        pattern = seq[cycle % len(seq)]
        out.append(("2q", pattern, couplers(rows, cols, pattern)))
    one_qubit_layer()
    return out


def build_program(qt, cfg: dict):
    """The circuit as the program's ``Circuit``: every gate static."""
    circ = qt.Circuit(cfg["qubits"])
    two = fsim(cfg["fsim_theta"], cfg["fsim_phi"])
    for layer in layers(cfg):
        if layer[0] == "1q":
            for q, name in enumerate(layer[1]):
                circ.gate(SINGLE_QUBIT[name], (q,))
        else:
            for q1, q2 in layer[2]:
                circ.gate(two, (q1, q2))
    return circ


def initial_planes(num_qubits: int, seed: int, sharding=None):
    """A normalised complex Gaussian state as packed float32 planes
    ``(2, 2^n)`` (real, imaginary), drawn on the device from ``seed`` in
    one jitted call."""
    import jax
    import jax.numpy as jnp
    from ..seeds import device_key

    def draw(key):
        x = jax.random.normal(key, (2, 1 << num_qubits), jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x))

    fn = jax.jit(draw, out_shardings=sharding)
    return fn(device_key(seed))
