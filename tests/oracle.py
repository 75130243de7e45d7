"""Dense numpy oracle for cross-checking the framework.

An independent brute-force simulator: gates become explicit 2^n x 2^n
operators; density matrices evolve as U rho U^dag; channels as
sum_k K rho K^dag. This plays the role the reference's golden .test files
play (SURVEY.md §4): an implementation-independent source of expected
amplitudes, probabilities and reductions.
"""

from __future__ import annotations

import numpy as np


def spread_bits(m: int, targets) -> int:
    """Scatter the bits of ``m`` into positions ``targets`` (bit j -> targets[j])."""
    out = 0
    for j, t in enumerate(targets):
        if (m >> j) & 1:
            out |= 1 << t
    return out


def full_operator(n: int, u, targets, controls=(), control_states=None) -> np.ndarray:
    """Embed a 2^k x 2^k gate into the full 2^n space (with controls)."""
    u = np.asarray(u, dtype=np.complex128)
    d = 1 << n
    k = len(targets)
    if control_states is None:
        control_states = [1] * len(controls)
    full = np.zeros((d, d), dtype=np.complex128)
    t_mask = spread_bits((1 << k) - 1, targets)
    for i in range(d):
        if any(((i >> c) & 1) != s for c, s in zip(controls, control_states)):
            full[i, i] = 1.0
            continue
        m = sum((((i >> t) & 1) << j) for j, t in enumerate(targets))
        base = i & ~t_mask
        for m2 in range(1 << k):
            full[base | spread_bits(m2, targets), i] += u[m2, m]
    return full


def apply_sv(psi, n, u, targets, controls=(), control_states=None):
    return full_operator(n, u, targets, controls, control_states) @ psi


def apply_gate(psi, n, u, targets, controls=(), control_states=None):
    """:func:`apply_sv` without the dense ``2^n x 2^n`` operator: the same
    map as a contraction on the ``(2,)*n`` tensor (qubit ``q`` is axis
    ``n-1-q``), for registers too wide for :func:`full_operator`."""
    u = np.asarray(u, dtype=np.complex128)
    k = len(targets)
    if control_states is None:
        control_states = [1] * len(controls)
    t = np.asarray(psi, dtype=np.complex128).reshape((2,) * n)
    idx = [slice(None)] * n
    for c, s in zip(controls, control_states):
        idx[n - 1 - c] = s
    idx = tuple(idx)
    sub = t[idx]
    remaining = [q for q in reversed(range(n)) if q not in controls]
    # u's index bit j addresses targets[j]: as a (2,)*2k tensor its axes
    # run from targets[k-1] down to targets[0], outputs then inputs
    in_axes = [remaining.index(targets[j]) for j in reversed(range(k))]
    res = np.tensordot(u.reshape((2,) * (2 * k)), sub,
                       axes=(list(range(k, 2 * k)), in_axes))
    res = np.moveaxis(res, list(range(k)), in_axes)
    out = t.copy()
    out[idx] = res
    return out.reshape(-1)


def apply_dm(rho, n, u, targets, controls=(), control_states=None):
    full = full_operator(n, u, targets, controls, control_states)
    return full @ rho @ full.conj().T


def apply_channel(rho, n, kraus_ops, targets):
    out = np.zeros_like(rho)
    for k in kraus_ops:
        full = full_operator(n, k, targets)
        out += full @ rho @ full.conj().T
    return out


def prob_of_outcome_sv(psi, qubit, outcome):
    idx = np.arange(psi.size)
    mask = ((idx >> qubit) & 1) == outcome
    return float(np.sum(np.abs(psi[mask]) ** 2))


def prob_of_outcome_dm(rho, qubit, outcome):
    diag = np.real(np.diag(rho))
    idx = np.arange(diag.size)
    mask = ((idx >> qubit) & 1) == outcome
    return float(np.sum(diag[mask]))


def random_state(n: int, rng) -> np.ndarray:
    v = rng.standard_normal(1 << n) + 1j * rng.standard_normal(1 << n)
    return v / np.linalg.norm(v)


def random_density(n: int, rng, rank: int = 3) -> np.ndarray:
    """Random mixed state as a convex mix of random pure states."""
    d = 1 << n
    rho = np.zeros((d, d), dtype=np.complex128)
    w = rng.random(rank)
    w /= w.sum()
    for i in range(rank):
        v = random_state(n, rng)
        rho += w[i] * np.outer(v, v.conj())
    return rho


def random_unitary(k: int, rng) -> np.ndarray:
    """Haar-ish random unitary from QR of a Ginibre matrix."""
    d = 1 << k
    z = rng.standard_normal((d, d)) + 1j * rng.standard_normal((d, d))
    q, r = np.linalg.qr(z)
    return q * (np.diag(r) / np.abs(np.diag(r)))


def random_kraus(k: int, num_ops: int, rng) -> list[np.ndarray]:
    """Random CPTP Kraus set: slices of a random isometry."""
    d = 1 << k
    z = rng.standard_normal((num_ops * d, d)) + 1j * rng.standard_normal((num_ops * d, d))
    q, _ = np.linalg.qr(z)  # q: (num_ops*d, d), q^dag q = I
    return [q[i * d:(i + 1) * d, :] for i in range(num_ops)]


def debug_state(num_amps_or_qubits_in_vec: int) -> np.ndarray:
    """The reference's initDebugState fixture (``QuEST_cpu.c:1565``):
    amp[i] = (2i + i(2i+1))/10, given the number of vector qubits."""
    dim = 1 << num_amps_or_qubits_in_vec
    idx = np.arange(dim, dtype=np.float64)
    return (2.0 * idx + 1j * (2.0 * idx + 1.0)) / 10.0


# state setters -------------------------------------------------------------

def set_sv(qureg, psi):
    """Load an arbitrary numpy statevector into a framework register."""
    import quest_tpu as qt
    qt.initStateFromAmps(qureg, np.real(psi), np.imag(psi))


def set_dm(qureg, rho):
    """Load an arbitrary numpy density matrix into a framework register."""
    import quest_tpu as qt
    flat = rho.T.reshape(-1)  # flat[r + c*2^n] = rho[r, c]
    qt.setDensityAmps(qureg, np.real(flat), np.imag(flat))


def get_sv(qureg) -> np.ndarray:
    return qureg.to_numpy()


def get_dm(qureg) -> np.ndarray:
    return qureg.density_matrix_numpy()
