"""The Pallas row-gate pass (``ops/pallas_kernels.apply_rowgate_planes``)
and the single-chip item loop that routes to it, in interpret mode.

A row gate is a dense uncontrolled operator on 1 to 4 targets, all at
qubit 10 or above; every such item of a single-chip plan runs as one, and
the state stays as re/im planes from one Pallas item to the next. Each
result is held to ``apply_unitary``'s XLA path, or to the same program
compiled with Pallas off.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu.core.apply import apply_unitary
from quest_tpu.ops import pallas_kernels as pk

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
TOL = 1e-5


def _rand_unitary(rng, k):
    a = rng.normal(size=(1 << k, 1 << k)) + 1j * rng.normal(size=(1 << k,
                                                                  1 << k))
    q, _ = np.linalg.qr(a)
    return q


def _rand_state(rng, n):
    z = rng.normal(size=1 << n) + 1j * rng.normal(size=1 << n)
    return jnp.asarray(z / np.linalg.norm(z))


def _rowgate(state, n, u, targets):
    return pk.from_planes(*pk.apply_rowgate_planes(
        *pk.to_planes(state), n, u, targets, interpret=True))


# (num_qubits, targets): contiguous, split and top-bit target sets, sorted
# and unsorted
GEOMETRIES = [
    (13, (10,)), (14, (13,)),
    (14, (10, 11)), (14, (12, 10)), (14, (10, 13)),
    (15, (11, 12, 13)), (15, (14, 10, 12)), (15, (10, 11, 14)),
    (16, (12, 13, 14, 15)), (16, (15, 10, 12, 13)), (16, (10, 11, 12, 13)),
]


@pytest.mark.parametrize("n,targets", GEOMETRIES)
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
def test_kernel_matches_xla(n, targets, traced):
    rng = np.random.default_rng(n * 100 + len(targets))
    u = _rand_unitary(rng, len(targets))
    state = _rand_state(rng, n)
    ref = apply_unitary(state, n, u, targets)
    if traced:
        got = jax.jit(lambda s, m: _rowgate(s, n, m, targets))(
            state, jnp.asarray(u))
    else:
        got = jax.jit(lambda s: _rowgate(s, n, u, targets))(state)
    assert float(jnp.max(jnp.abs(got - ref))) < TOL


def test_zero_pattern_of_a_host_matrix():
    """fSim(pi/2, phi) (x) fSim(pi/2, phi): 16 non-zero entries of 256; the
    kernel sums only those, and a row with no entry writes zeros."""
    from benchmark.families.rcs import fsim
    rng = np.random.default_rng(7)
    f = fsim(np.pi / 2, np.pi / 6)
    n, targets = 16, (11, 12, 14, 15)
    state = _rand_state(rng, n)
    for u in (np.kron(f, f), np.diag([1.0, 0.0]).astype(np.complex128)):
        tg = targets[:int(np.log2(u.shape[0]))]
        ref = apply_unitary(state, n, u, tg)
        got = jax.jit(lambda s: _rowgate(s, n, u, tg))(state)
        assert float(jnp.max(jnp.abs(got - ref))) < TOL


@pytest.mark.parametrize("kind,cmask,targets,eligible", [
    ("u", 0, (10,), True),
    ("u", 0, (27, 20, 13, 10), True),
    ("u", 1 << 12, (10, 11), False),          # controlled
    ("u", 0, (9, 17), False),                 # a target below qubit 10
    ("u", 0, (10, 11, 12, 13, 17), False),    # five targets
    ("diag", 0, (17,), False),                # a diagonal
])
def test_eligibility(kind, cmask, targets, eligible):
    assert pk.rowgate_eligible(kind, cmask, targets) is eligible


def test_ineligible_targets_are_refused():
    re = im = jnp.zeros((1 << 9, 128))
    with pytest.raises(ValueError):
        pk.apply_rowgate_planes(re, im, 16, np.eye(2), (9,))


def _rcs18():
    from benchmark.families import rcs
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sycamore-rcs-28.json")) as f:
        cfg = json.load(f)
    cfg.update(qubits=18, grid=[3, 6], cycles=3)
    return rcs.build_program(qt, cfg)


def _routed(cc):
    return sum(1 for it in cc.plan.items if it[0] == "op"
               and pk.rowgate_eligible(cc._ops[it[1]].kind, it[3], it[2]))


def test_rcs_circuit_matches_pallas_off(monkeypatch):
    """An 18-qubit random circuit of the benchmark's family, with a
    parameterised rotation on qubit 17: the fSim items above qubit 16 run
    as row gates, the rest as layers, and the state agrees with the
    program compiled with Pallas off."""
    env = qt.createQuESTEnv(num_devices=1)
    circ = _rcs18()
    circ.rx(17, circ.parameter("theta"))
    on = circ.compile(env, pallas="interpret")
    off = circ.compile(env, pallas=False)
    stats = on.dispatch_stats()
    assert stats.rowgate_passes == _routed(on) > 0
    assert off.dispatch_stats().rowgate_passes == 0
    assert any(getattr(op, "kind", None) == "layer" for op in on._ops)
    assert any(cc_op.mat_fn is not None for cc_op in on._ops
               if getattr(cc_op, "kind", None) == "u")
    # the routing does not change the plan: the same compile without
    # row gates emits the same items
    with monkeypatch.context() as m:
        m.setattr(pk, "rowgate_eligible", lambda *a: False)
        layers_only = circ.compile(env, pallas="interpret")
    assert layers_only.dispatch_stats().rowgate_passes == 0
    assert layers_only.dispatch_stats().kernels_out == stats.kernels_out
    rng = np.random.default_rng(3)
    z = rng.normal(size=(2, 1 << 18))
    z /= np.linalg.norm(z)
    params = {"theta": 0.37}
    a = on.apply(jnp.asarray(z), params)
    b = off.apply(jnp.asarray(z), params)
    assert float(jnp.max(jnp.abs(a - b))) < TOL


def test_ineligible_items_keep_the_xla_path():
    """A controlled gate, a gate with a target below qubit 10, a
    five-target gate and a diagonal, all above the layer kernel's reach:
    no row gate runs, and the state is the XLA program's."""
    n = 18
    rng = np.random.default_rng(5)
    circ = qt.Circuit(n)
    circ.gate(_rand_unitary(rng, 1), (17,), controls=(12,))
    circ.gate(_rand_unitary(rng, 2), (9, 17))
    circ.gate(_rand_unitary(rng, 5), (10, 11, 12, 13, 17))
    circ.diagonal(np.exp(1j * rng.normal(size=2)), (17,))
    env = qt.createQuESTEnv(num_devices=1)
    on = circ.compile(env, pallas="interpret", fusion=0, supergate_k=0)
    off = circ.compile(env, pallas=False, fusion=0, supergate_k=0)
    assert on.dispatch_stats().rowgate_passes == _routed(on) == 0
    z = rng.normal(size=(2, 1 << n))
    z /= np.linalg.norm(z)
    a = on.apply(jnp.asarray(z))
    b = off.apply(jnp.asarray(z))
    assert float(jnp.max(jnp.abs(a - b))) < TOL


def test_density_program_matches_pallas_off():
    """An 11-qubit density program (22 state qubits): its superoperator
    items on qubit 10's ket and bra bits (10, 21), a damping channel and
    a parameterised rotation among them, run as row gates."""
    n = 11
    rng = np.random.default_rng(11)
    circ = qt.Circuit(n)
    circ.gate(_rand_unitary(rng, 1), (10,))
    circ.damp(10, 0.2)
    circ.ry(10, circ.parameter("phi"))
    circ.gate(_rand_unitary(rng, 2), (9, 10))
    env = qt.createQuESTEnv(num_devices=1)
    on = circ.compile(env, pallas="interpret", density=True)
    off = circ.compile(env, pallas=False, density=True)
    assert on.dispatch_stats().rowgate_passes == _routed(on) > 0
    d = rng.normal(size=(1 << n, 1 << n)) + 1j * rng.normal(size=(1 << n,
                                                                  1 << n))
    rho = d @ d.conj().T
    rho /= np.trace(rho)
    flat = rho.reshape(-1)
    z = np.stack([flat.real, flat.imag])
    params = {"phi": -0.8}
    a = on.apply(jnp.asarray(z), params)
    b = off.apply(jnp.asarray(z), params)
    assert float(jnp.max(jnp.abs(a - b))) < TOL


def test_expectation_traces_the_xla_twin():
    """``jax.grad`` has no rule for a compiled ``pallas_call``: a program
    whose only Pallas items are row gates still differentiates through
    its layer-free twin."""
    n = 12
    circ = qt.Circuit(n)
    circ.ry(11, circ.parameter("a"))
    circ.gate(np.kron(np.eye(2), np.eye(2)), (10, 11))
    env = qt.createQuESTEnv(num_devices=1)
    cc = circ.compile(env, pallas="interpret")
    assert cc.dispatch_stats().rowgate_passes > 0
    assert cc._xla_only() is not cc
    energy = cc.expectation_fn([[(11, 3)]], [1.0])
    g = jax.grad(energy)(jnp.asarray([0.3]))
    assert float(g[0]) == pytest.approx(-np.sin(0.3), abs=1e-6)
