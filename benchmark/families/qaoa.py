"""QAOA MaxCut on a random regular graph, served as energy requests.

The graph is fixed by the configuration's ``structure_seed``; a request
is one vector of ``(gamma_l, beta_l)`` for the ``rounds`` rounds, drawn
from the run's seed. The circuit is H on every qubit, then per round
``multi_rotate_z(gamma_l)`` on every edge and ``rx(beta_l)`` on every
qubit. The observable is the cut cost less its constant: the terms
``-Z_u Z_v / 2``, one per edge.

This module imports nothing of the program; :func:`build_program` is
handed the package.
"""

from __future__ import annotations

import numpy as np


def graph(cfg: dict) -> list:
    """Edges of a random ``degree``-regular graph on ``qubits`` vertices:
    stubs paired at random until the pairing has no loop and no double
    edge."""
    n, d = cfg["qubits"], cfg["degree"]
    rng = np.random.default_rng(cfg["structure_seed"])
    for _ in range(10000):
        stubs = rng.permutation(np.repeat(np.arange(n), d))
        pairs = {tuple(sorted(p)) for p in stubs.reshape(-1, 2).tolist()}
        if len(pairs) == n * d // 2 and all(a != b for a, b in pairs):
            return sorted(pairs)
    raise RuntimeError("no simple regular graph drawn")


def param_names(cfg: dict) -> list:
    return [f"{k}{l}" for l in range(cfg["rounds"]) for k in ("gamma",
                                                             "beta")]


def build_program(qt, cfg: dict):
    n = cfg["qubits"]
    circ = qt.Circuit(n)
    for q in range(n):
        circ.h(q)
    for l in range(cfg["rounds"]):
        gamma = circ.parameter(f"gamma{l}")
        beta = circ.parameter(f"beta{l}")
        for u, v in graph(cfg):
            circ.multi_rotate_z((u, v), gamma)
        for q in range(n):
            circ.rx(q, beta)
    return circ


def observable(cfg: dict):
    """``(pauli_terms, coeffs)``: ``-Z_u Z_v / 2`` per edge (code 3 = Z)."""
    edges = graph(cfg)
    return [[(u, 3), (v, 3)] for u, v in edges], [-0.5] * len(edges)


def draw_params(cfg: dict, rng: np.random.Generator, count: int):
    """``count`` request vectors ordered like :func:`param_names`."""
    g0, g1 = cfg["gamma_range"]
    b0, b1 = cfg["beta_range"]
    out = np.empty((count, 2 * cfg["rounds"]))
    out[:, 0::2] = rng.uniform(g0, g1, size=(count, cfg["rounds"]))
    out[:, 1::2] = rng.uniform(b0, b1, size=(count, cfg["rounds"]))
    return out
