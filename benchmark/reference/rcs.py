"""Plain reference for the random circuits of ``families/rcs.py``.

It shares no code with the program. The state is a complex64 tensor with
one axis per grid row (``2^cols`` entries, bit ``c`` of an axis index is
column ``c``), so every gate is a contraction along an axis or a phase
over two axes:

- a row's 1-qubit gates, and the fSim gates of a horizontal pattern
  inside that row, are one ``2^cols``-square matrix applied along the
  row's axis;
- fSim(pi/2, phi) on a vertical pattern couples every column of two
  rows: it is ``SWAP . D`` with ``D = diag(1, -i, -i, e^{-i phi})``. The
  single-qubit part of ``D``, ``(-i)^x``, folds into each row's matrix,
  the rest is the phase ``e^{i (pi - phi) popcount(x & y)}`` over the two
  axes, and the swaps of all columns together exchange the two rows'
  axes, which the reference tracks as a relabelling.

The reference covers fSim at theta = pi/2 only (the configuration's
value) and refuses other angles. Contractions run at the precision it
is given (``matmul.py``): ``highest`` for the reference, ``bf16_3x``
(three bfloat16 passes) for the control.
"""

from __future__ import annotations

import numpy as np

from ..families import rcs
from . import matmul


def _embed(u: np.ndarray, targets, width: int) -> np.ndarray:
    """The ``2^width``-square matrix of gate ``u`` on bits ``targets``
    (``targets[0]`` is the gate's most significant bit)."""
    k = len(targets)
    dim = 1 << width
    eye = np.eye(dim, dtype=np.complex128).reshape((2,) * width + (dim,))
    # tensor axes are bits width-1 .. 0 (axis 0 is the top bit)
    axes = [width - 1 - t for t in targets]
    moved = np.moveaxis(eye, axes, range(k))
    shape = moved.shape
    out = u @ moved.reshape(1 << k, -1)
    return np.moveaxis(out.reshape(shape), range(k), axes).reshape(dim, dim)


def _popcount(x: np.ndarray) -> np.ndarray:
    return np.array([bin(int(v)).count("1") for v in x.ravel()]
                    ).reshape(x.shape)


def plan(cfg: dict) -> list:
    """The circuit as reference steps: ``("mat", row, M)``,
    ``("phase", row_a, row_b, T)`` and ``("swap", row_a, row_b)``."""
    theta, phi = cfg["fsim_theta"], cfg["fsim_phi"]
    if abs(np.cos(theta)) > 1e-12 or abs(np.sin(theta) - 1) > 1e-12:
        raise ValueError("the reference covers fSim at theta = pi/2 only")
    rows, cols = cfg["grid"]
    dim = 1 << cols
    x = np.arange(dim)
    half = np.diag((-1j) ** _popcount(x))
    table = np.exp(1j * (np.pi - phi) * _popcount(x[:, None] & x[None, :]))
    two = rcs.fsim(theta, phi)
    steps = []
    pending = None
    for layer in rcs.layers(cfg):
        if layer[0] == "1q":
            pending = []
            for r in range(rows):
                m = np.eye(dim, dtype=np.complex128)
                for c in range(cols):
                    g = rcs.SINGLE_QUBIT[layer[1][r * cols + c]]
                    m = _embed(g, (c,), cols) @ m
                pending.append(m)
            continue
        pattern, pairs = layer[1], layer[2]
        mats = pending if pending is not None else \
            [np.eye(dim, dtype=np.complex128)] * rows
        pending = None
        if pattern in "AB":
            for r in range(rows):
                m = mats[r]
                for q1, q2 in pairs:
                    if q1 // cols == r:
                        m = _embed(two, (q2 % cols, q1 % cols), cols) @ m
                steps.append(("mat", r, m))
        else:
            coupled = sorted({q1 // cols for q1, _ in pairs})
            for r in range(rows):
                m = mats[r]
                if r in coupled or r - 1 in coupled:
                    m = half @ m
                steps.append(("mat", r, m))
            for r in coupled:
                steps.append(("phase", r, r + 1, table))
                steps.append(("swap", r, r + 1))
    if pending is not None:
        for r in range(rows):
            steps.append(("mat", r, pending[r]))
    return steps


def _letters(n: int) -> str:
    return "abcdefghijklmnopqrstuvwxyz"[:n]


def make_apply(cfg: dict, precision):
    """A jitted function: packed float32 planes ``(2, 2^n)`` in, the
    final state as a complex64 vector ``(2^n,)`` out."""
    import jax
    import jax.numpy as jnp

    rows, cols = cfg["grid"]
    dim = 1 << cols
    steps = plan(cfg)
    consts = [jnp.asarray(s[-1], jnp.complex64) if s[0] != "swap" else None
              for s in steps]
    ids = _letters(rows)

    def apply(planes, consts):
        psi = jax.lax.complex(planes[0], planes[1]).reshape((dim,) * rows)
        axis_of = {r: rows - 1 - r for r in range(rows)}   # row -> axis
        for step, const in zip(steps, consts):
            if step[0] == "mat":
                a = axis_of[step[1]]
                spec = ids[:a] + "Z" + ids[a + 1:]
                psi = matmul.einsum(f"YZ,{spec}->{spec.replace('Z', 'Y')}",
                                    const, psi, precision)
            elif step[0] == "phase":
                a, b = axis_of[step[1]], axis_of[step[2]]
                t = const if a < b else const.T
                shape = [1] * rows
                shape[min(a, b)] = shape[max(a, b)] = dim
                psi = psi * t.reshape(shape)
            else:
                ra, rb = step[1], step[2]
                axis_of[ra], axis_of[rb] = axis_of[rb], axis_of[ra]
            psi = jax.lax.optimization_barrier(psi)
        order = [axis_of[r] for r in reversed(range(rows))]
        return jnp.transpose(psi, order).reshape(-1)

    fn = jax.jit(apply)
    return lambda planes: fn(planes, consts)


def to_planes(psi):
    """A complex vector as packed float32 planes ``(2, 2^n)``."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda z: jnp.stack([jnp.real(z), jnp.imag(z)]))(psi)


def relative_error(planes, psi):
    """``||state - reference|| / ||reference||`` for packed planes
    against a complex vector, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def err(planes, psi):
        dr = planes[0] - jnp.real(psi)
        di = planes[1] - jnp.imag(psi)
        num = jnp.sum(dr * dr) + jnp.sum(di * di)
        den = jnp.sum(jnp.real(psi) ** 2) + jnp.sum(jnp.imag(psi) ** 2)
        return jnp.sqrt(num / den)

    return float(err(planes, psi))
