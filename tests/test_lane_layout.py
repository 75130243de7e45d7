"""The TPU-shaped gate forms of ``core/apply.py`` against the float64
oracle: the ``(rows, 128)`` lane view (registers wide enough that the lane
operator is smaller than the state), the in-place split path (unrolled and
contracted, with row and lane controls), and the select-based plane
packing. Small registers take the split path; these widths make the lane
view engage, which the rest of the suite's small registers never do."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from quest_tpu.core.apply import apply_diagonal, apply_unitary
from quest_tpu.core.packing import pack, unpack

import oracle

# (width, targets, controls, control states)
CASES = [
    (16, (0,), (), ()),
    (16, (6,), (2,), (1,)),
    (16, (3, 1), (12,), (0,)),
    (18, (0, 9), (), ()),
    (18, (15, 4), (2, 16), (1, 0)),
    (16, (15,), (0,), (1,)),
    (16, (8,), (0, 14), (0, 1)),
    (10, (8, 3), (1,), (1,)),
    (10, (9, 0, 5), (), ()),
    (10, (1, 3, 5, 8), (9,), (0,)),
    (10, (0, 2, 4, 6, 9), (7,), (1,)),
]


def _ids(case):
    n, t, c, s = case
    return f"n{n}-t{'_'.join(map(str, t))}-c{''.join(map(str, c))}"


@pytest.mark.parametrize("case", CASES, ids=[_ids(c) for c in CASES])
@pytest.mark.parametrize("traced", [False, True], ids=["host", "traced"])
def test_unitary_matches_oracle(case, traced, rng):
    n, targets, controls, states = case
    u = oracle.random_unitary(len(targets), rng)
    psi = oracle.random_state(n, rng)
    cmask = sum(1 << c for c in controls)
    fmask = sum(1 << c for c, s in zip(controls, states) if s == 0)
    if traced:
        out = jax.jit(lambda s, m: apply_unitary(s, n, m, targets, cmask,
                                                 fmask))(psi, u)
    else:
        out = jax.jit(lambda s: apply_unitary(s, n, u, targets, cmask,
                                              fmask))(psi)
    want = oracle.apply_gate(psi, n, u, targets, controls, list(states))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-12)


@pytest.mark.parametrize("qubits", [(0,), (15, 0), (6, 3), (12, 5, 1)])
def test_diagonal_matches_oracle(qubits, rng):
    n = 16
    d = np.exp(1j * rng.uniform(0, 2 * np.pi, size=(2,) * len(qubits)))
    psi = oracle.random_state(n, rng)
    desc = sorted(qubits, reverse=True)
    idx = np.arange(1 << n)
    factor = d[tuple((idx >> q) & 1 for q in desc)]
    out = jax.jit(lambda s, dd: apply_diagonal(s, n, qubits, dd))(psi, d)
    np.testing.assert_allclose(np.asarray(out), factor * psi, atol=1e-12)


def test_pack_round_trips_planes(rng):
    z = oracle.random_state(10, rng)
    planes = jax.jit(lambda s: pack(unpack(s)))(
        jnp.stack([z.real, z.imag]))
    np.testing.assert_array_equal(np.asarray(planes),
                                  np.stack([z.real, z.imag]))


def test_oracle_gate_matches_dense_operator(rng):
    n = 5
    for targets, controls, states in [((0,), (), None),
                                      ((2, 0), (3,), None),
                                      ((1, 4, 2), (0,), [0])]:
        psi = oracle.random_state(n, rng)
        u = oracle.random_unitary(len(targets), rng)
        np.testing.assert_allclose(
            oracle.apply_gate(psi, n, u, targets, controls, states),
            oracle.apply_sv(psi, n, u, targets, controls, states),
            atol=1e-14)
