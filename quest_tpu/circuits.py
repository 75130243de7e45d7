"""Whole-circuit compilation: a gate program -> ONE XLA executable.

The reference pays per-gate dispatch: every API call crosses the user/library
boundary, validates, and launches a kernel (CUDA: one ``__global__`` launch per
gate, ``QuEST_gpu.cu:722-728``; MPI: one exchange round per cross-chunk gate,
``QuEST_cpu_distributed.c:843-878``). On TPU, launch latency dwarfs per-gate
math, so the idiomatic design is to trace the *entire circuit* into a single
jitted program: XLA fuses adjacent gates into shared memory passes, schedules
cross-shard ``ppermute`` exchanges itself, and the donated state buffer is
updated in place. This module is that fast path (SURVEY.md §7, build stage 5's
"circuit-level jit").

Beyond the reference's capabilities, compiled circuits are:

- **parameterized** — angles may be :class:`Param` placeholders bound at call
  time, so one executable serves every rotation angle (no recompiles);
- **differentiable** — :meth:`CompiledCircuit.expectation` is a pure function
  of the parameter vector, so ``jax.grad`` gives exact gradients for
  variational algorithms (impossible in the reference);
- **pre-fused** — runs of static gates on the same target set are multiplied
  host-side into one matrix, and consecutive static diagonal gates merge into
  one elementwise pass, before XLA ever sees the program.

Usage::

    c = Circuit(20)
    theta = c.parameter("theta")
    for q in range(20):
        c.h(q)
    c.rz(0, theta)
    c.cnot(0, 1)
    f = c.compile(env)
    f.run(qureg, params={"theta": 0.3})      # one executable, donated buffer
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
import warnings
from typing import Callable, Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp

from .core.apply import (apply_unitary, apply_diagonal, bitmask,
                         pass_boundary)
from .core import matrices as mats
from .core.packing import pack, unpack
from .env import QuESTEnv
from .qureg import Qureg
from .resilience import faults as _faults
from .resilience import health as _health
from .telemetry.tracing import dispatch_annotation
from .telemetry import profile as _profile
from .types import PauliOpType

__all__ = ["Circuit", "CompiledCircuit", "Param"]


@dataclasses.dataclass(frozen=True)
class Param:
    """A named angle placeholder, bound at run time."""
    name: str


Angle = Union[float, Param]


@dataclasses.dataclass
class _Op:
    """One recorded gate. ``mat`` is a static numpy matrix (fusable) or a
    traceable ``params -> jnp matrix`` builder; likewise ``diag`` for
    elementwise (phase-family) factors of shape ``(2,)*k``."""
    kind: str                      # "u" | "diag"
    targets: tuple[int, ...]       # user bit order ("u") / sorted desc ("diag")
    ctrl_mask: int = 0
    flip_mask: int = 0
    mat: Optional[np.ndarray] = None
    mat_fn: Optional[Callable] = None
    diag: Optional[np.ndarray] = None
    diag_fn: Optional[Callable] = None
    kraus: Optional[list] = None   # kind "kraus": channel operators

    @property
    def is_static(self) -> bool:
        return (self.mat_fn is None and self.diag_fn is None
                and not callable(self.kraus))


def _angle(params: dict, a: Angle):
    return params[a.name] if isinstance(a, Param) else a


def _rot_matrix(angle, axis) -> jnp.ndarray:
    """Traceable exp(-i angle/2 n.sigma) (getComplexPairFromRotation,
    ``QuEST_common.c:113-120``) — jnp so ``angle`` may be a tracer."""
    n = mats.unit_vector(axis)
    c = jnp.cos(angle / 2.0)
    s = jnp.sin(angle / 2.0)
    alpha = jax.lax.complex(c, -s * n[2])
    beta = jax.lax.complex(s * n[1], -s * n[0])
    return jnp.array([[1.0, 0.0], [0.0, 0.0]]) * alpha \
        + jnp.array([[0.0, -1.0], [0.0, 0.0]]) * jnp.conj(beta) \
        + jnp.array([[0.0, 0.0], [1.0, 0.0]]) * beta \
        + jnp.array([[0.0, 0.0], [0.0, 1.0]]) * jnp.conj(alpha)


def _wire_angle(a: Angle):
    """JSON-able wire form of one builder angle/rate argument: a Param
    placeholder travels by name, a static value by exact float."""
    if isinstance(a, Param):
        return {"param": a.name}
    # quest: allow-host-sync(builder-time journal entry — `a` is the
    # caller's static Python angle, recorded before any device work)
    return float(a)


def _wire_cmat(arr) -> dict:
    """JSON-able wire form of one complex tensor. ``json.dumps`` emits
    ``repr(float)`` so the round trip is bit-exact — the decoded matrix
    hashes to the same ``warmcache.circuit_digest`` bytes."""
    # quest: allow-host-sync(builder-time journal entry — `arr` is the
    # caller's host matrix, recorded before any device work)
    a = np.asarray(arr, dtype=np.complex128)
    return {"re": a.real.tolist(), "im": a.imag.tolist()}


@functools.partial(jax.jit, static_argnums=(0, 1))
def zero_state(num_qubits: int, dtype) -> jnp.ndarray:
    """Packed ``(2, 2^n)`` planes of |0..0>: the shared start state of
    the sweep forms, built on the device as one named executable
    (``jit_zero_state`` in a device trace)."""
    return jnp.zeros((2, 1 << num_qubits), dtype=dtype).at[0, 0].set(1.0)


def _phase_diag(angle) -> jnp.ndarray:
    return jnp.stack([jnp.ones_like(angle) + 0j, jnp.exp(1j * angle)])


class Circuit:
    """A recorded gate program over ``num_qubits`` qubits.

    Builder methods append gates; nothing touches a device until
    :meth:`compile`. Qubit/control indices follow the reference's conventions
    (bit ``j`` of a multi-qubit matrix row indexes ``targets[j]``).
    """

    def __init__(self, num_qubits: int):
        if num_qubits < 1:
            raise ValueError("circuit needs at least one qubit")
        self.num_qubits = num_qubits
        self.ops: list[_Op] = []
        self._params: list[str] = []
        # wire journal: one JSON-able row per recorded op describing the
        # builder call that produced it (None = not wire-serializable).
        # quest_tpu.netserve.wire replays rows through these same
        # builders, so a decoded circuit reproduces the exact op stream
        # — closures included — and with it warmcache.circuit_digest.
        self._wire: list = []
        self._wire_depth = 0

    # -- parameters --------------------------------------------------------

    def parameter(self, name: str) -> Param:
        if name not in self._params:
            self._params.append(name)
        return Param(name)

    @property
    def param_names(self) -> tuple[str, ...]:
        return tuple(self._params)

    # -- recording helpers -------------------------------------------------

    def _check(self, qubits: Sequence[int]) -> None:
        for q in qubits:
            if not 0 <= q < self.num_qubits:
                raise ValueError(f"qubit {q} out of range [0, {self.num_qubits})")
        if len(set(qubits)) != len(tuple(qubits)):
            raise ValueError(f"repeated qubit in {tuple(qubits)}")

    def _register_angle(self, a: Angle) -> Angle:
        """Auto-register Param placeholders used in builder calls so directly
        constructed ``Param("x")`` objects work like ``circuit.parameter``."""
        if isinstance(a, Param):
            return self.parameter(a.name)
        return a

    def _journal(self, entry, fn):
        """Run a builder body with ``entry`` as its wire-journal row:
        the HIGH-LEVEL call (not the primitive it delegates to) is what
        the wire form replays, so parameterized closures decode to the
        same code objects they were recorded from."""
        base = len(self.ops)
        self._wire_depth += 1
        try:
            out = fn()
        finally:
            self._wire_depth -= 1
        if self._wire_depth == 0:
            added = len(self.ops) - base
            # guarded builders append exactly one op; anything else has
            # no 1:1 row and journals opaque rather than guessing
            self._wire.extend([entry] if added == 1 else [None] * added)
        return out

    def _wire_rows(self) -> list:
        """The journal, validated against the op stream (consumed by
        ``quest_tpu.netserve.wire``). A mutation path that bypassed the
        journal (``inverse``, direct ``ops`` edits) misaligns it — every
        row then reads opaque, never a wrong replay."""
        if len(self._wire) != len(self.ops):
            return [None] * len(self.ops)
        return list(self._wire)

    def gate(self, u, targets: Sequence[int], controls: Sequence[int] = (),
             control_states: Optional[Sequence[int]] = None) -> "Circuit":
        """Record an arbitrary k-qubit (controlled) unitary.

        ``u``: a ``(2^k, 2^k)`` matrix, or a callable ``params_dict -> matrix``
        for parameterized gates. ``control_states`` (default all-1) gives the
        conditioning bit per control (multiStateControlledUnitary semantics).
        """
        targets = tuple(int(t) for t in targets)
        controls = tuple(int(c) for c in controls)
        self._check(targets + controls)
        flip = 0
        if control_states is not None:
            if len(control_states) != len(controls):
                raise ValueError(
                    f"{len(controls)} controls but "
                    f"{len(control_states)} control states")
            for c, s in zip(controls, control_states):
                if not s:
                    flip |= 1 << c
        if callable(u):
            op = _Op("u", targets, bitmask(controls), flip, mat_fn=u)
            row = None      # a bare callable payload has no wire form
        else:
            u = np.asarray(u, dtype=np.complex128)
            dim = 1 << len(targets)
            if u.shape != (dim, dim):
                raise ValueError(f"matrix shape {u.shape} != {(dim, dim)}")
            op = _Op("u", targets, bitmask(controls), flip, mat=u)
            row = ["gate", _wire_cmat(u), list(targets), list(controls),
                   [int(s) for s in control_states]
                   if control_states is not None else None]
        self.ops.append(op)
        if self._wire_depth == 0:
            self._wire.append(row)
        return self

    def diagonal(self, factors, qubits: Sequence[int]) -> "Circuit":
        """Record an elementwise phase factor: ``factors`` has shape
        ``(2,)*k`` with axis ``i`` indexed by the bit of ``qubits[i]``, or is
        a callable ``params -> tensor`` (same axis order). Axes are
        re-ordered internally to the engine's sorted-descending layout."""
        qubits = tuple(int(q) for q in qubits)
        self._check(qubits)
        desc = tuple(sorted(qubits, reverse=True))
        axes = tuple(qubits.index(q) for q in desc)
        identity = axes == tuple(range(len(qubits)))
        if callable(factors):
            fn = factors if identity else \
                (lambda p, f=factors, a=axes: jnp.transpose(f(p), a))
            op = _Op("diag", desc, diag_fn=fn)
            row = None
        else:
            t = np.asarray(factors, dtype=np.complex128)
            if t.shape != (2,) * len(qubits):
                raise ValueError(f"diagonal tensor shape {t.shape} != "
                                 f"{(2,) * len(qubits)}")
            op = _Op("diag", desc, diag=t if identity else t.transpose(axes))
            # journal the CALLER's axis order: replay re-derives the
            # engine layout through this same method
            row = ["diagonal", _wire_cmat(t), list(qubits)]
        self.ops.append(op)
        if self._wire_depth == 0:
            self._wire.append(row)
        return self

    # -- named gates (reference API surface) -------------------------------

    def h(self, q: int) -> "Circuit":
        return self.gate(mats.hadamard(), (q,))

    def x(self, q: int) -> "Circuit":
        return self.gate(mats.pauli_x(), (q,))

    def y(self, q: int) -> "Circuit":
        return self.gate(mats.pauli_y(), (q,))

    def z(self, q: int) -> "Circuit":
        return self.diagonal(np.array([1.0, -1.0]), (q,))

    def s(self, q: int) -> "Circuit":
        return self.diagonal(np.array([1.0, 1j]), (q,))

    def t(self, q: int) -> "Circuit":
        return self.diagonal(np.array([1.0, np.exp(1j * np.pi / 4)]), (q,))

    def phase(self, q: int, angle: Angle) -> "Circuit":
        angle = self._register_angle(angle)
        if isinstance(angle, Param):
            return self._journal(
                ["phase", int(q), _wire_angle(angle)],
                lambda: self.diagonal(
                    lambda p, a=angle: _phase_diag(_angle(p, a)), (q,)))
        return self.diagonal(np.array([1.0, np.exp(1j * angle)]), (q,))

    def _rot(self, q: int, angle: Angle, axis, controls=()) -> "Circuit":
        angle = self._register_angle(angle)
        if isinstance(angle, Param):
            return self._journal(
                ["rot", int(q), _wire_angle(angle),
                 # quest: allow-host-sync(builder-time journal entry —
                 # `axis` is the caller's static host tuple)
                 [float(x) for x in axis], [int(c) for c in controls]],
                lambda: self.gate(
                    lambda p, a=angle: _rot_matrix(_angle(p, a), axis),
                    (q,), controls))
        return self.gate(mats.rotation(float(angle), axis), (q,), controls)

    def rx(self, q: int, angle: Angle) -> "Circuit":
        return self._rot(q, angle, (1, 0, 0))

    def ry(self, q: int, angle: Angle) -> "Circuit":
        return self._rot(q, angle, (0, 1, 0))

    def rz(self, q: int, angle: Angle) -> "Circuit":
        angle = self._register_angle(angle)
        # diagonal fast path: exp(∓i angle/2)
        if isinstance(angle, Param):
            def f(p, a=angle):
                half = _angle(p, a) / 2.0
                return jnp.stack([jnp.exp(-1j * half), jnp.exp(1j * half)])
            return self._journal(["rz", int(q), _wire_angle(angle)],
                                 lambda: self.diagonal(f, (q,)))
        half = float(angle) / 2.0
        return self.diagonal(np.array([np.exp(-1j * half), np.exp(1j * half)]),
                             (q,))

    def rotate(self, q: int, angle: Angle, axis) -> "Circuit":
        return self._rot(q, angle, axis)

    def cnot(self, control: int, target: int) -> "Circuit":
        return self.gate(mats.pauli_x(), (target,), (control,))

    def cy(self, control: int, target: int) -> "Circuit":
        return self.gate(mats.pauli_y(), (target,), (control,))

    def cz(self, q1: int, q2: int) -> "Circuit":
        return self.diagonal(np.array([[1.0, 1.0], [1.0, -1.0]]), (q1, q2))

    def cphase(self, control: int, target: int, angle: Angle) -> "Circuit":
        angle = self._register_angle(angle)
        """Controlled phase shift (diag(1,1,1,e^{i angle}))."""
        if isinstance(angle, Param):
            def f(p, a=angle):
                ph = jnp.exp(1j * _angle(p, a))
                return jnp.stack([jnp.ones((2,), ph.dtype),
                                  jnp.stack([jnp.ones((), ph.dtype), ph])])
            return self._journal(
                ["cphase", int(control), int(target), _wire_angle(angle)],
                lambda: self.diagonal(f, (control, target)))
        d = np.ones((2, 2), dtype=np.complex128)
        d[1, 1] = np.exp(1j * angle)
        return self.diagonal(d, (control, target))

    def crz(self, control: int, target: int, angle: Angle) -> "Circuit":
        angle = self._register_angle(angle)
        if isinstance(angle, Param):
            def f(p, a=angle):
                half = _angle(p, a) / 2.0
                lo, hi = jnp.exp(-1j * half), jnp.exp(1j * half)
                return jnp.stack([jnp.ones((2,), lo.dtype), jnp.stack([lo, hi])])
            return self._journal(
                ["crz", int(control), int(target), _wire_angle(angle)],
                lambda: self.diagonal(f, (control, target)))
        half = float(angle) / 2.0
        d = np.ones((2, 2), dtype=np.complex128)
        d[1, 0], d[1, 1] = np.exp(-1j * half), np.exp(1j * half)
        return self.diagonal(d, (control, target))

    def swap(self, q1: int, q2: int) -> "Circuit":
        return self.gate(mats.swap(), (q1, q2))

    def sqrt_swap(self, q1: int, q2: int) -> "Circuit":
        return self.gate(mats.sqrt_swap(), (q1, q2))

    def multi_rotate_z(self, qubits: Sequence[int], angle: Angle) -> "Circuit":
        angle = self._register_angle(angle)
        """exp(-i angle/2 Z⊗…⊗Z): phase by mask-parity
        (``QuEST_cpu.c:3075-3114``)."""
        qubits = tuple(qubits)
        k = len(qubits)
        idx = np.indices((2,) * k).sum(axis=0) % 2  # parity tensor
        if isinstance(angle, Param):
            def f(p, a=angle, parity=idx):
                half = _angle(p, a) / 2.0
                return jnp.exp(1j * half * (2.0 * parity - 1.0))
            return self._journal(
                ["multi_rotate_z", [int(q) for q in qubits],
                 _wire_angle(angle)],
                lambda: self.diagonal(f, qubits))
        half = float(angle) / 2.0
        return self.diagonal(np.exp(-1j * half * (1.0 - 2.0 * idx)), qubits)

    def pauli_string(self, paulis: Sequence[tuple[int, int]]) -> "Circuit":
        """Apply a product of Pauli operators [(qubit, code)] (code: 1=X,2=Y,3=Z)."""
        for q, code in paulis:
            code = int(code)
            if code == int(PauliOpType.PAULI_X):
                self.x(q)
            elif code == int(PauliOpType.PAULI_Y):
                self.y(q)
            elif code == int(PauliOpType.PAULI_Z):
                self.z(q)
        return self

    # -- channels (density-register circuits) ------------------------------

    def kraus(self, ops: Sequence, targets: Sequence[int]) -> "Circuit":
        """Record a Kraus channel ``rho -> sum_k K_k rho K_k^dag``.

        Consumed by ``compile(density=True)`` (one superoperator pass on
        the flattened density vector, ``QuEST_common.c:540-604``) and by
        ``compile_trajectories`` (stochastic statevector unraveling).
        CPTP validation happens at compile time, at the environment's
        precision tolerance.

        ``ops`` may be a callable ``params_dict -> [K_k]`` (traceable, jnp)
        for a PARAMETERIZED channel — the density path differentiates
        straight through the channel strength (noise-model fitting by
        gradient) and the trajectory path draws its jump probabilities
        from the bound stack at call time (noisy-VQE sweeps over channel
        strengths); no CPTP validation is possible for a function, and
        the native path rejects it."""
        targets = tuple(int(t) for t in targets)
        self._check(targets)
        if callable(ops):
            self.ops.append(_Op("kraus", targets, kraus=ops))
            if self._wire_depth == 0:
                self._wire.append(None)
            return self
        mats_l = [np.asarray(m, dtype=np.complex128) for m in ops]
        self.ops.append(_Op("kraus", targets, kraus=mats_l))
        if self._wire_depth == 0:
            self._wire.append(
                ["kraus", [_wire_cmat(m) for m in mats_l], list(targets)])
        return self

    def dephase(self, q: int, prob: Angle) -> "Circuit":
        """rho -> (1-p) rho + p Z rho Z (mixDephasing semantics; max prob
        1/2, ``QuEST_validation.c:108``). ``prob`` may be a Param: the
        channel strength then binds (and differentiates) at run time on
        the density path.

        .. note:: a Param-bound rate BYPASSES the reference's cap
           entirely — a bound value in (1/2, 1] still yields a valid
           CPTP channel here (the Kraus square roots stay real), where
           the reference rejects it; values outside [0, 1] surface as
           NaN planes at run time. Validate bound rates yourself when
           reference parity matters."""
        if isinstance(prob, Param):
            from .ops import channels as chan
            nm = self._register_angle(prob).name
            return self._journal(
                ["dephase", int(q), {"param": nm}],
                lambda: self.kraus(
                    lambda p, nm=nm: chan.dephasing_kraus_traceable(p[nm]),
                    (q,)))
        from . import validation as val
        val.validate_prob(prob, "Circuit.dephase", 0.5,
                          code=val.ErrorCode.E_INVALID_ONE_QUBIT_DEPHASE_PROB)
        return self.kraus([np.sqrt(1 - prob) * np.eye(2),
                           np.sqrt(prob) * mats.pauli_z()], (q,))

    def depolarise(self, q: int, prob: Angle) -> "Circuit":
        """Homogeneous depolarising (mixDepolarising semantics; max 3/4).
        ``prob`` may be a Param (see :meth:`dephase`) — bound values skip
        the reference's 3/4 cap entirely: in (3/4, 1] the channel is
        still CPTP (over-depolarisation past the maximally mixed point),
        outside [0, 1] it NaNs at run time (no record-time check is
        possible for a run-time value)."""
        if isinstance(prob, Param):
            from .ops import channels as chan
            nm = self._register_angle(prob).name
            return self._journal(
                ["depolarise", int(q), {"param": nm}],
                lambda: self.kraus(
                    lambda p, nm=nm: chan.depolarising_kraus_traceable(
                        p[nm]), (q,)))
        from . import validation as val
        from .ops import channels as chan
        val.validate_prob(prob, "Circuit.depolarise", 0.75,
                          code=val.ErrorCode.E_INVALID_ONE_QUBIT_DEPOL_PROB)
        return self.kraus(chan.depolarising_kraus(prob), (q,))

    def damp(self, q: int, prob: Angle) -> "Circuit":
        """Amplitude damping at rate ``prob`` (mixDamping semantics).
        ``prob`` may be a Param (see :meth:`dephase`) — bound rates are
        uncapped at record time: any value in [0, 1] is valid (as in the
        reference), but out-of-range bound values only surface as NaN
        planes when the program runs."""
        if isinstance(prob, Param):
            from .ops import channels as chan
            nm = self._register_angle(prob).name
            return self._journal(
                ["damp", int(q), {"param": nm}],
                lambda: self.kraus(
                    lambda p, nm=nm: chan.damping_kraus_traceable(p[nm]),
                    (q,)))
        from . import validation as val
        from .ops import channels as chan
        val.validate_prob(prob, "Circuit.damp", 1.0)
        return self.kraus(chan.damping_kraus(prob), (q,))

    def pauli_channel(self, q: int, prob_x: Angle, prob_y: Angle,
                      prob_z: Angle) -> "Circuit":
        """rho -> (1-px-py-pz) rho + px X rho X + py Y rho Y + pz Z rho Z
        (mixPauli semantics). Any probability may be a Param (see
        :meth:`dephase`); Param components bind at run time, so only the
        static components (and their sum) validate at record time —
        out-of-range bound values surface as NaN planes."""
        from . import validation as val
        from .ops import channels as chan
        probs = (prob_x, prob_y, prob_z)
        if any(isinstance(p, Param) for p in probs):
            # validate every static piece BEFORE registering any Param:
            # a rejected call must not leave orphan parameter names on
            # the circuit
            statics = [float(p) for p in probs if not isinstance(p, Param)]
            for v in statics:
                val.validate_prob(v, "Circuit.pauli_channel", 1.0)
            val.validate_prob_sum(sum(statics), "Circuit.pauli_channel")
            # the reference's pairwise bound (QuEST_validation.c:447),
            # restricted to what record time can decide: e.g.
            # pauli_channel(q, 0.6, Param, 0.3) can never be CPTP-valid
            # for any bound value and must reject here, not NaN later
            val.validate_partial_pauli_probs(statics,
                                             "Circuit.pauli_channel")
            vals = []
            for p in probs:
                if isinstance(p, Param):
                    nm = self._register_angle(p).name
                    vals.append(lambda pd, nm=nm: pd[nm])
                else:
                    vals.append(lambda pd, v=float(p): v)
            return self._journal(
                ["pauli_channel", int(q), _wire_angle(prob_x),
                 _wire_angle(prob_y), _wire_angle(prob_z)],
                lambda: self.kraus(
                    lambda pd, vs=tuple(vals): chan.pauli_kraus_traceable(
                        vs[0](pd), vs[1](pd), vs[2](pd)), (q,)))
        val.validate_one_qubit_pauli_probs(prob_x, prob_y, prob_z,
                                           "Circuit.pauli_channel")
        return self.kraus(chan.pauli_kraus(prob_x, prob_y, prob_z), (q,))

    def two_qubit_dephase(self, q1: int, q2: int, prob: float) -> "Circuit":
        """rho -> (1-p) rho + p/3 (Z1 rho Z1 + Z2 rho Z2 + Z1 Z2 rho Z1 Z2)
        (mixTwoQubitDephasing semantics; max 3/4)."""
        from . import validation as val
        from .ops import channels as chan
        val.validate_prob(prob, "Circuit.two_qubit_dephase", 0.75,
                          code=val.ErrorCode.E_INVALID_TWO_QUBIT_DEPHASE_PROB)
        return self.kraus(chan.two_qubit_dephasing_kraus(prob), (q1, q2))

    def two_qubit_depolarise(self, q1: int, q2: int, prob: float) -> "Circuit":
        """Homogeneous two-qubit depolarising (mixTwoQubitDepolarising
        semantics; max 15/16)."""
        from . import validation as val
        from .ops import channels as chan
        val.validate_prob(prob, "Circuit.two_qubit_depolarise", 15.0 / 16.0,
                          code=val.ErrorCode.E_INVALID_TWO_QUBIT_DEPOL_PROB)
        return self.kraus(chan.two_qubit_depolarising_kraus(prob), (q1, q2))

    def mid_measure(self, q: int) -> "Circuit":
        """Record a mid-circuit measurement of qubit ``q`` as the
        projector channel ``{|0><0|, |1><1|}`` — a valid Kraus set, so it
        rides the existing channel machinery:

        - on the density path (``compile(density=True)``) it is the exact
          NON-selective measurement (coherences to/from ``q`` die, the
          diagonal is untouched);
        - through ``compile_trajectories`` each trajectory draws a
          definite outcome with the physical probability and collapses —
          genuine mid-circuit measurement statistics, per trajectory.

        The reference has no mid-circuit measurement inside any recorded
        form; its ``measure`` is imperative-only (``QuEST_common.c:360``).
        For selective (outcome-known) collapse, use the imperative
        ``collapseToOutcome`` between circuit runs instead."""
        p0 = np.zeros((2, 2), dtype=np.complex128)
        p1m = np.zeros((2, 2), dtype=np.complex128)
        p0[0, 0] = 1.0
        p1m[1, 1] = 1.0
        return self.kraus([p0, p1m], (q,))

    def with_noise(self, p1: Angle = 0.0, p2: Angle = 0.0,
                   damping: Angle = 0.0) -> "Circuit":
        """Return a copy with a uniform noise model applied: after every
        gate, each touched qubit (targets and controls) gets depolarising
        noise — ``p1`` for single-qubit gates, ``p2`` for multi-qubit —
        followed by amplitude damping at rate ``damping``. The standard
        way to make any clean algorithm noisy without hand-inserting
        channels; run the result on a density register or through
        ``compile_trajectories``. Existing channels are preserved and not
        re-noised. Rates may be Params: every inserted channel shares the
        named strength, so a THREE-parameter uniform device model can be
        fit by gradient on the density path (`examples/noise_fitting.py`
        shows the per-channel version) and swept through
        ``compile_trajectories`` (the trajectory engine binds channel
        strengths per call, like the deterministic sweep path)."""
        from . import validation as val
        for name, p, cap in (("p1", p1, 0.75), ("p2", p2, 0.75),
                             ("damping", damping, 1.0)):
            if not isinstance(p, Param):
                val.validate_prob(p, f"Circuit.with_noise({name})", cap)
        out = Circuit(self.num_qubits)
        out._params = list(self._params)
        for p in (p1, p2, damping):
            if isinstance(p, Param):
                # register up front: a rate whose trigger never fires
                # (e.g. p1 on a circuit with no 1q gates) must still be a
                # declared parameter, not silently absent from the model
                out.parameter(p.name)

        def on(p):
            return isinstance(p, Param) or p > 0.0

        base_rows = self._wire_rows()
        for i, op in enumerate(self.ops):
            out.ops.append(op)
            out._wire.append(base_rows[i])
            if op.kind == "kraus":
                continue
            touched = sorted(
                set(op.targets)
                | {q for q in range(self.num_qubits)
                   if (op.ctrl_mask >> q) & 1})
            p = p1 if len(touched) == 1 else p2
            for q in touched:
                if on(p):
                    out.depolarise(q, p)
                if on(damping):
                    out.damp(q, damping)
        return out

    def _lifted_density(self) -> "Circuit":
        """Rewrite this n-qubit program as a 2n-qubit program on the
        flattened density vector: U becomes conj(U) (x) U on
        (targets, targets+n) in ONE pass (the reference needs two backend
        calls per gate, ``QuEST.c:175-658``); controlled gates keep the
        two-pass form (row and column controls condition independently,
        ``QuEST.c:352-357``); channels become superoperators."""
        n = self.num_qubits
        out = Circuit(2 * n)
        out._params = list(self._params)
        for op in self.ops:
            if op.kind == "kraus":
                from .ops.densmatr import (kraus_superoperator,
                                           kraus_superoperator_traceable)
                t2 = op.targets + tuple(t + n for t in op.targets)
                if callable(op.kraus):
                    out.ops.append(_Op(
                        "u", t2,
                        mat_fn=lambda p, f=op.kraus:
                        kraus_superoperator_traceable(f(p))))
                else:
                    out.ops.append(_Op("u", t2,
                                       mat=kraus_superoperator(op.kraus)))
            elif op.kind == "u":
                shifted = tuple(t + n for t in op.targets)
                if op.ctrl_mask == 0 and op.mat_fn is None:
                    out.ops.append(_Op("u", op.targets + shifted,
                                       mat=np.kron(np.conj(op.mat), op.mat)))
                elif op.mat_fn is None:
                    out.ops.append(dataclasses.replace(op))
                    out.ops.append(_Op("u", shifted, op.ctrl_mask << n,
                                       op.flip_mask << n,
                                       mat=np.conj(op.mat)))
                else:
                    out.ops.append(dataclasses.replace(op))
                    out.ops.append(_Op(
                        "u", shifted, op.ctrl_mask << n, op.flip_mask << n,
                        mat_fn=lambda p, f=op.mat_fn: jnp.conj(f(p))))
            else:
                shifted = tuple(t + n for t in op.targets)
                t2 = shifted + op.targets   # sorted desc overall
                if op.diag_fn is None:
                    out.ops.append(_Op("diag", t2,
                                       diag=np.multiply.outer(
                                           np.conj(op.diag), op.diag)))
                else:
                    out.ops.append(_Op(
                        "diag", t2,
                        diag_fn=lambda p, f=op.diag_fn: jnp.tensordot(
                            jnp.conj(f(p)), f(p), axes=0)))
        return out

    # -- composition -------------------------------------------------------

    def to_qasm(self, params: Optional[dict] = None) -> str:
        """Serialise the recorded program as OpenQASM 2.0 text, using the
        same logger (and therefore the same dialect) as the imperative
        API's recorder — so ``parse_qasm`` reads it back. Parameterized
        gates are bound with ``params`` first. Ops with no QASM form
        (k>=2 dense unitaries, general diagonals, channels) are logged as
        comments, exactly as the reference's logger handles its own
        non-expressible ops (``QuEST.c:634-637``)."""
        from .qasm import QASMLogger, _pair_and_phase_from_unitary
        log = QASMLogger(self.num_qubits)
        log.is_logging = True
        params = params or {}
        missing = [p for p in self.param_names if p not in params]
        if missing:
            raise ValueError(f"missing circuit parameters: {missing}")
        named_u = (("sigma_x", mats.pauli_x()),
                   ("sigma_y", mats.pauli_y()),
                   ("sigma_z", mats.pauli_z()),
                   ("hadamard", mats.hadamard()),
                   ("s", mats.s_gate()),
                   ("t", mats.t_gate()))
        for op in self.ops:
            if op.kind == "kraus":
                log.record_comment(
                    f"Kraus channel on qubits {list(op.targets)} "
                    "(no QASM form)")
                continue
            if op.kind == "diag":
                d = np.asarray(op.diag_fn(params)) \
                    if op.diag_fn is not None else op.diag
                if self._emit_diag_qasm(log, op.targets, d):
                    continue
                log.record_comment(
                    f"{len(op.targets)}-qubit general diagonal on qubits "
                    f"{list(op.targets)} (no QASM form)")
                continue
            controls = tuple(q for q in range(self.num_qubits)
                             if (op.ctrl_mask >> q) & 1)
            if len(op.targets) != 1:
                log.record_comment(
                    f"{len(op.targets)}-qubit unitary on qubits "
                    f"{list(op.targets)}"
                    + (f" controls {list(controls)}" if controls else "")
                    + " (no single-qubit QASM form)")
                continue
            mat = np.asarray(op.mat_fn(params)) \
                if op.mat_fn is not None else op.mat
            named = next((label for label, ref in named_u
                          if np.allclose(mat, ref, atol=1e-12)), None)
            flips = tuple(c for c in controls if (op.flip_mask >> c) & 1)
            for c in flips:              # controlled-on-0: NOT sandwich
                log.record_gate("sigma_x", c)
            if named is not None:
                # exact label (cx/ccz/...), never the lossy ZYZ split
                log.record_gate(named, op.targets[0], controls)
            else:
                alpha, beta, g = _pair_and_phase_from_unitary(mat)
                log.record_compact_unitary(alpha, beta, op.targets[0],
                                           controls)
                if controls and abs(g) > 1e-12:
                    # the dropped phase is PHYSICAL under controls; the
                    # reference's Rz-on-target restore is unfaithful —
                    # c^{n-1}u1(g) on the controls restores it exactly
                    log.record_u1(g, controls[0], controls[1:])
            for c in flips:
                log.record_gate("sigma_x", c)
        return log.text()

    @staticmethod
    def _emit_diag_qasm(log, targets, d) -> bool:
        """Emit a recorded diagonal exactly when the dialect can express
        it: multi-controlled Z / phase (all-ones except the last entry),
        1q relative phases (u1), and the 2q multiRotateZ parity form
        (rzz). Entries must be unit-modulus. Returns False otherwise."""
        flat = np.asarray(d).reshape(-1)
        if not np.allclose(np.abs(flat), 1.0, atol=1e-12):
            return False
        lo = min(targets)
        rest = tuple(q for q in targets if q != lo)
        if np.allclose(flat[:-1], 1.0, atol=1e-12):
            # targets are sorted descending, so flat[-1] is the all-ones
            # bit pattern: a (multi-controlled) phase on the joint 1-state
            if abs(flat[-1] + 1.0) < 1e-12:
                log.record_gate("sigma_z", lo, rest)
            else:
                log.record_u1(float(np.angle(flat[-1])), lo, rest)
            return True
        if len(targets) == 1:
            # diag(a, b) = a * diag(1, b/a): relative phase is exact,
            # the global factor a is dropped (as every ZYZ record does)
            log.record_u1(float(np.angle(flat[1] / flat[0])), targets[0])
            return True
        if len(targets) == 2 and abs(flat[0] - flat[3]) < 1e-12 \
                and abs(flat[1] - flat[2]) < 1e-12 \
                and abs(flat[1] - np.conj(flat[0])) < 1e-12:
            log.record_rzz(float(-2.0 * np.angle(flat[0])),
                           targets[1], targets[0])
            return True
        if len(targets) <= 4:
            # ANY unit-modulus diagonal factors exactly (up to the
            # dropped global flat[0]) into one phase term per nonempty
            # qubit subset: theta_S = angle of the Mobius-alternating
            # product of entries over sub-patterns of S — each term is a
            # c^{|S|-1}u1. Bit j of the flat index is qubit asc[j]
            # (targets are recorded descending, axis 0 most significant).
            k = len(targets)
            asc = sorted(targets)
            for s in range(1, 1 << k):
                prod = 1.0 + 0.0j
                for m in range(1 << k):
                    if m & ~s:
                        continue
                    term = complex(flat[m])
                    if (bin(s ^ m).count("1")) % 2:
                        prod /= term
                    else:
                        prod *= term
                theta = float(np.angle(prod))
                if abs(theta) > 1e-12:
                    qs = [asc[j] for j in range(k) if (s >> j) & 1]
                    log.record_u1(theta, qs[0], tuple(qs[1:]))
            return True
        return False

    def extend(self, other: "Circuit") -> "Circuit":
        if other.num_qubits != self.num_qubits:
            raise ValueError("qubit count mismatch")
        self._wire = self._wire_rows() + other._wire_rows()
        self.ops.extend(other.ops)
        for n in other._params:
            if n not in self._params:
                self._params.append(n)
        return self

    def inverse(self) -> "Circuit":
        """Dagger of a *static* circuit (parameterized ops unsupported)."""
        inv = Circuit(self.num_qubits)
        for op in reversed(self.ops):
            if not op.is_static:
                raise ValueError("cannot invert a parameterized circuit")
            if op.kind == "kraus":
                raise ValueError(
                    "cannot invert a circuit containing channels "
                    "(CPTP maps are not generally invertible)")
            if op.kind == "u":
                inv.ops.append(dataclasses.replace(op, mat=op.mat.conj().T))
            else:
                inv.ops.append(dataclasses.replace(op, diag=op.diag.conj()))
        return inv

    @property
    def depth(self) -> int:
        return len(self.ops)

    # -- compilation -------------------------------------------------------

    def _fused_ops(self, diag_row_cap: int = -1) -> list[_Op]:
        """Host-side peephole fusion over this circuit's static gates
        (delegates to :func:`_peephole_fused`)."""
        return _peephole_fused(self.ops, diag_row_cap)

    def compile(self, env: QuESTEnv, donate: bool = True, fuse: bool = True,
                lookahead: int = 32, pallas: Optional[object] = None,
                supergate_k: int = 4, fusion: Optional[object] = None,
                density: bool = False, comm_planner: Optional[bool] = None,
                overlap: bool = False,
                reorder: Optional[bool] = None,
                error_budget: Optional[float] = None,
                tier=None) -> "CompiledCircuit":
        """Compile to one XLA program; ``lookahead`` is the layout planner's
        relayout-batching window (quest_tpu.parallel.layout); ``pallas``
        controls the fused-layer kernel pass (None=auto on TPU,
        "interpret"=interpreted kernels, False=off); ``fusion`` is the
        gate-fusion support cap k (None=default 3, 0/False=off, int=that
        k — see :mod:`quest_tpu.core.fusion`): runs of adjacent gates
        whose combined support fits in k qubits contract into single
        dense kernels BEFORE layout planning, so relayouts are planned
        per fused group; ``density=True`` compiles the program for
        density registers (gates lift to superoperator form; Kraus
        channels allowed).

        ``comm_planner`` (default on; only meaningful on a mesh env)
        switches the layout planner to the communication-aware cost model
        (:mod:`quest_tpu.parallel.layout` module docs: SWAP absorption,
        cross-shard 1q pair exchanges, collective composition — priced by
        :func:`quest_tpu.profiling.comm_model`); ``False`` restores the
        count-based planner. ``overlap=True`` additionally double-buffers
        each relayout with the dense kernel it serves (slab-pipelined
        ``all_to_all``, :func:`quest_tpu.parallel.exchange.
        run_exchange_overlapped`) so collective and gate math can overlap
        on backends with async collectives.

        ``reorder`` (default on; only meaningful when the mesh spans
        controller processes — :mod:`quest_tpu.parallel.multihost`)
        gates the hot-qubit-local reordering pass: collectives price at
        the interconnect tier they cross and each relayout evicts its
        coldest qubits to the inter-host device positions, keeping
        upcoming work on the fast tier; ``False`` plans tier-priced but
        tier-blind (the bench's reordering-off rows).

        ``error_budget`` is the precision-tier dial (ROADMAP item 4):
        instead of choosing a dtype, state the max amplitude error this
        program's results may carry and the engine picks the CHEAPEST
        :class:`~quest_tpu.config.PrecisionTier` whose modeled error
        (drift-per-gate x depth, :func:`quest_tpu.profiling.
        modeled_tier_error`) fits — FAST (bf16-input MXU matmuls with
        compensated f32 accumulation) when the budget allows, up the
        ladder otherwise; an unmeetable budget raises ``ValueError``
        here, never a silently-wrong answer later. ``tier`` pins a rung
        explicitly (a :class:`~quest_tpu.config.PrecisionTier` or its
        name); both default to the legacy per-environment precision."""
        if density:
            from . import validation as val
            for op in self.ops:
                if op.kind == "kraus" and not callable(op.kraus):
                    val.validate_kraus_ops(op.kraus, len(op.targets),
                                           "Circuit.kraus",
                                           env.precision.eps)
            circ = self._lifted_density()
        else:
            if any(op.kind == "kraus" for op in self.ops):
                raise ValueError(
                    "circuit contains Kraus channels; compile with "
                    "density=True and run on a density register")
            circ = self
        if tier is None and error_budget is not None:
            from .profiling import choose_tier, engine_tiers
            # compile-time tiers pin run()/apply() too, which have no
            # dd form — quad stays a per-DISPATCH rung (sweep/submit
            # budgets may still select it; see engine_tiers)
            ladder = [t for t in engine_tiers(env) if t.name != "quad"]
            tier = choose_tier(float(error_budget), max(len(circ.ops), 1),
                               env, tiers=ladder)
        cc = CompiledCircuit(circ, env, donate=donate, fuse=fuse,
                             lookahead=lookahead, pallas=pallas,
                             supergate_k=supergate_k, fusion=fusion,
                             comm_planner=comm_planner, overlap=overlap,
                             reorder=reorder, tier=tier)
        cc.is_density = density
        cc.error_budget = error_budget
        return cc

    def compile_native(self, threads: Optional[int] = None,
                       density: bool = False):
        """Lower to the native C++ CPU executor (one ctypes call runs the
        whole program over split f64 planes; ``quest_tpu/native/statevec.py``).
        CPU/single-device only — the framework's analogue of the reference's
        native CPU backend, and an XLA-independent cross-checking oracle.

        ``density=True`` lowers the 2n-qubit flattened-density form
        (channels become superoperator ops, `_lifted_density`); the planes
        then hold the flat density vector. Raises ``RuntimeError`` if the
        library can't build, ``ValueError`` for Kraus channels without
        ``density=True``."""
        if density:
            from . import validation as val
            from .config import default_precision
            for op in self.ops:
                if op.kind == "kraus":
                    if callable(op.kraus):
                        raise ValueError(
                            "parameterized channels are density-XLA-path "
                            "only; the native executor needs static ops")
                    val.validate_kraus_ops(op.kraus, len(op.targets),
                                           "Circuit.kraus",
                                           default_precision().eps)
            circ = self._lifted_density()
        else:
            if any(op.kind == "kraus" for op in self.ops):
                raise ValueError(
                    "circuit contains Kraus channels; pass density=True "
                    "(the flattened-density form) or use the XLA path")
            circ = self
        from .native.statevec import NativeProgram
        return NativeProgram(circ, threads=threads)

    def compile_trajectories(self, env: QuESTEnv, pallas=None):
        """Lower to a quantum-trajectory program: channels applied
        stochastically to a STATEVECTOR (Monte-Carlo wavefunction), so a
        noisy n-qubit circuit costs 2^n amplitudes per trajectory
        instead of the density path's 2^(2n) (``ops/trajectories.py``).

        ``pallas`` controls the wave loop's fused-kernel path (same
        semantics as :meth:`compile`: None = auto on TPU backends,
        "interpret" = interpreted kernels for tests, False = off):
        static gate runs apply through the batch-gridded Pallas layer
        kernel and eligible static channels through the fused
        Kraus-draw kernel — active in the unsharded dispatch mode
        (docs/tpu.md "MXU saturation"). The fused-kernel draw stream
        differs bitwise (not statistically) from the XLA path's.

        The trajectory axis is the batched engine's batch axis:
        ``trajectory_sweep(T)`` runs T draws through one keyed
        executable with the mesh sharding priced by
        :func:`quest_tpu.parallel.layout.choose_batch_sharding`;
        ``expectation(..., sampling_budget=)`` aggregates Pauli-sum
        observables on device in waves with convergence-based early
        stopping; Param gates AND Param/callable-Kraus channels bind
        per call, so noisy parameter sweeps run as (B, T) programs —
        served via ``SimulationService.submit(..., trajectories=,
        sampling_budget=)``. docs/tpu.md "Trajectory execution"."""
        from .ops.trajectories import TrajectoryProgram
        return TrajectoryProgram(self, env, pallas=pallas)

    def compile_dd(self, env: QuESTEnv, dtype=None):
        """Compile to the double-double amplitude path: each amplitude
        component is an unevaluated hi+lo pair of ``dtype`` floats
        (``ops/doubledouble.py``). ``dtype`` defaults to the env's real
        dtype: float32 planes give a ~48-bit significand (f64-class
        results on f32-only TPU hardware); float64 planes give ~106 bits
        — the reference quad-build analogue (CPU/x64). On a mesh env the
        planes shard on the amplitude axis like every other register
        form. Raises ``ValueError`` for ops outside the dd subset
        (parameterised or multi-target dense gates)."""
        from .ops.doubledouble import DDProgram
        sharding = env.sharding() if (
            env.mesh is not None
            and (1 << self.num_qubits) >= env.num_devices) else None
        return DDProgram(list(self.ops), self.num_qubits,
                         sharding=sharding,
                         dtype=np.dtype(dtype or env.precision.real_dtype))


def _peephole_fused(ops: Sequence[_Op], diag_row_cap: int = -1) -> list[_Op]:
    """Host-side peephole fusion over static gates.

    1. consecutive static diagonal ops on any qubits merge (union of qubit
       sets, outer-broadcast product) while the union stays small;
    2. consecutive static unitaries with identical (targets, controls)
       merge by matrix product.
    XLA would fuse the arithmetic anyway, but merging *before* tracing
    shrinks the program and halves memory passes.

    ``diag_row_cap`` (>= 0) additionally caps merged diagonals at that
    many row qubits (>= 7): the Pallas layer kernel only fuses
    diagonals with <= 3 row bits, so unbounded merging here would
    weld layer-eligible cphase ladders (QFT's bulk) into 5-6-row-bit
    diagonals that fall off the fused path — measured on the r5
    silicon as 22 standalone full passes in QFT-22.
    """
    fused: list[_Op] = []
    for op in ops:
        if fused and op.is_static and fused[-1].is_static:
            prev = fused[-1]
            if (op.kind == "u" and prev.kind == "u"
                    and op.targets == prev.targets
                    and op.ctrl_mask == prev.ctrl_mask
                    and op.flip_mask == prev.flip_mask):
                fused[-1] = dataclasses.replace(prev, mat=op.mat @ prev.mat)
                continue
            if op.kind == "diag" and prev.kind == "diag":
                union = tuple(sorted(set(op.targets) | set(prev.targets),
                                     reverse=True))
                if len(union) <= 6 and (
                        diag_row_cap < 0
                        or sum(q >= 7 for q in union) <= diag_row_cap):
                    def expand(o):
                        shape = tuple(2 if q in o.targets else 1
                                      for q in union)
                        return o.diag.reshape(shape)
                    fused[-1] = _Op("diag", union,
                                    diag=expand(prev) * expand(op))
                    continue
        fused.append(op)
    return fused


def _group_supergates(ops: list, max_k: int = 4,
                      fold_diags: bool = True,
                      barrier=None) -> list:
    """Merge consecutive static gates into k-qubit super-gates.

    Every gate costs one full pass over the 2^n amplitudes, so L consecutive
    gates whose combined qubit support (targets + controls) fits in ``max_k``
    qubits collapse into one 2^k x 2^k operator — one pass instead of L, and
    a fatter matmul (better MXU shape). Order is preserved: each member is
    kron-embedded into the group support and composed left-to-right.
    Parameterized ops and LayerOps break groups, as does any op matching
    ``barrier`` (used to keep Pallas-layer-eligible gates ungrouped so the
    later layer peephole can claim them).
    """
    if max_k < 2:
        return ops

    out: list = []
    group: list = []
    support: set = set()

    def op_qubits(op) -> set:
        qs = set(op.targets)
        m, q = op.ctrl_mask, 0
        while m:
            if m & 1:
                qs.add(q)
            m >>= 1
            q += 1
        return qs

    def flush():
        nonlocal support
        if len(group) <= 1:
            out.extend(group)
        else:
            from .core.fusion import compose_in_support
            sup = tuple(sorted(support))
            out.append(_Op("u", sup, 0, 0,
                           mat=compose_in_support(group, sup)))
        group.clear()
        support = set()

    for op in ops:
        kinds = ("u", "diag") if fold_diags else ("u",)
        if (getattr(op, "kind", None) not in kinds or not op.is_static
                or (barrier is not None and barrier(op))):
            flush()
            out.append(op)
            continue
        qs = op_qubits(op)
        if len(qs) > max_k:
            flush()
            out.append(op)
            continue
        if len(support | qs) > max_k:
            flush()
        group.append(op)
        support |= qs
    flush()
    return out


def _mxu_policy(enabled: bool, fast: bool):
    """The layer collector's MXU-shaping policy: None (off) or a dict
    with the memoized per-gate crossover ``decide(row_bits,
    gate_qubits)`` and the row-bit ``cap`` — one decision table shared
    by ``_layer_eligible`` (the supergate fence) and
    ``_LayerAccum.try_add`` (the stage emitter), so the fence and the
    collector can never disagree about which gates the MXU tile
    claims."""
    if not enabled:
        return None
    from .parallel.layout import MXU_ROW_CAP, choose_mxu_contraction
    memo: dict = {}

    def decide(row_bits: int, gate_qubits: int) -> bool:
        k = (row_bits, gate_qubits)
        if k not in memo:
            memo[k] = choose_mxu_contraction(row_bits, gate_qubits,
                                             fast)["use_mxu"]
        return memo[k]

    return {"decide": decide, "cap": MXU_ROW_CAP}


class _LayerAccum:
    """Stage accumulator for one Pallas layer run (ops at PHYSICAL
    coordinates of a ``num_local``-qubit state view).

    ``try_add`` either absorbs an op into the stage list (merging with
    compatible adjacent stages) and returns True, or rejects it untouched.
    Masks handed to the kernel use its coordinate split: lane masks over
    the 128-lane index, row masks over the row index (bit p = qubit p+7).

    ``mxu`` (a :func:`_mxu_policy` dict) turns on MXU-shaped
    contractions: a dense uncontrolled gate whose row-bit targets fit
    the tile cap becomes (or folds into) a ``rowmxu`` stage — one
    ``(2^j * 128)``-dim systolic-array contraction — when the modeled
    flops-vs-bytes crossover says the MXU wins; otherwise the existing
    lane/row stages keep it (never-worse by construction).
    """

    LANE_MASK = (1 << 7) - 1   # == (1 << pk.LANE_QUBITS) - 1

    def __init__(self, num_local: int, hi: int, mxu=None):
        self.num_local = num_local
        self.hi = hi
        self.mxu = mxu
        self.stages: list = []
        self.members = 0
        self.src_items: list = []

    def _append_lane(self, m: np.ndarray) -> None:
        # merge backward across row stages that do not read lane bits
        # (disjoint axes commute); stop at anything lane-coupled
        i = len(self.stages) - 1
        while i >= 0:
            st = self.stages[i]
            if st[0] == "lane":
                self.stages[i] = ("lane", m @ st[1])
                return
            if st[0] in ("row", "rowk") and st[3] == 0:
                i -= 1               # lane-blind row stage: commutes
                continue
            if st[0] == "rowmxu" and self.mxu is not None:
                # fold the lane matrix into the open MXU tile (free:
                # kron-embed over the tile's row bits, matrix product).
                # Valid past the skipped lane-blind row stages — a pure
                # lane operator commutes with them.
                big = np.kron(np.eye(1 << len(st[1])), m)
                self.stages[i] = ("rowmxu", st[1], big @ st[2])
                return
            break
        self.stages.append(("lane", m))

    def _append_rowmxu(self, bits: tuple, phys_targets, mat) -> None:
        from .ops import pallas_kernels as pk
        prev = self.stages[-1] if self.stages else None
        if prev is not None and prev[0] == "rowmxu":
            union = tuple(sorted(set(bits) | set(prev[1])))
            if len(union) <= self.mxu["cap"]:
                # merge by union: same flops at the cap (2^(j1+j2) =
                # 2^j1 * 2^j2 column work either way), one stage fewer
                pm = prev[2] if union == prev[1] \
                    else pk.mxu_expand(prev[2], prev[1], union)
                m = pk.mxu_group_matrix(mat, phys_targets, union)
                self.stages[-1] = ("rowmxu", union, m @ pm)
                return
        self.stages.append(
            ("rowmxu", bits, pk.mxu_group_matrix(mat, phys_targets,
                                                 bits)))

    def _append_row(self, q: int, u: np.ndarray, lane_mask: int,
                    lane_want: int, row_mask: int, row_want: int) -> None:
        if self.stages:
            st = self.stages[-1]
            if (st[0] == "row" and st[1] == q and st[3:] ==
                    (lane_mask, lane_want, row_mask, row_want)):
                self.stages[-1] = ("row", q, np.asarray(u) @ st[2],
                                   lane_mask, lane_want, row_mask, row_want)
                return
        self.stages.append(("row", q, np.asarray(u), lane_mask, lane_want,
                            row_mask, row_want))

    def _append_rowdiag(self, table: np.ndarray, bits: tuple) -> None:
        if self.stages:
            st = self.stages[-1]
            if st[0] == "rowdiag" and st[2] == bits:
                self.stages[-1] = ("rowdiag", st[1] * table, bits)
                return
        self.stages.append(("rowdiag", table, bits))

    def try_add(self, op, phys_targets, cmask, fmask, axis_order) -> bool:
        from .ops import pallas_kernels as pk
        if getattr(op, "kind", None) not in ("u", "diag") or not op.is_static:
            return False
        if op.kind == "u":
            if cmask >> self.num_local:      # device-bit control
                return False
            want = cmask & ~fmask
            lane_cm, lane_want = cmask & self.LANE_MASK, want & self.LANE_MASK
            row_cm, row_want = cmask >> 7, want >> 7
            row_t = [t for t in phys_targets if t >= pk.LANE_QUBITS]
            if (self.mxu is not None and cmask == 0 and row_t
                    and len(row_t) <= self.mxu["cap"]
                    and all(t <= self.hi for t in row_t)):
                # MXU-shaped contraction: fold into an open tile for
                # free, else open one when the modeled crossover says
                # the systolic array beats the VPU row path
                bits = tuple(sorted(t - pk.LANE_QUBITS for t in row_t))
                prev = self.stages[-1] if self.stages else None
                fold = (prev is not None and prev[0] == "rowmxu"
                        and set(bits) <= set(prev[1]))
                if fold or self.mxu["decide"](len(bits),
                                              len(phys_targets)):
                    self._append_rowmxu(bits, phys_targets, op.mat)
                    self.members += 1
                    return True
            if all(t < pk.LANE_QUBITS for t in phys_targets):
                m = pk.embed_lane_matrix(op.mat, phys_targets, lane_cm,
                                         fmask & self.LANE_MASK)
                if row_cm:
                    self.stages.append(("clane", m, row_cm, row_want))
                else:
                    self._append_lane(m)
            elif (len(phys_targets) == 1
                    and pk.LANE_QUBITS <= phys_targets[0] <= self.hi):
                self._append_row(phys_targets[0], op.mat, lane_cm,
                                 lane_want, row_cm, row_want)
            elif (2 <= len(phys_targets) <= 3
                    and all(pk.LANE_QUBITS <= t <= self.hi
                            for t in phys_targets)):
                # k-qubit dense gate entirely on row bits: "rowk" stage
                # (the multiControlledMultiQubitUnitaryLocal analogue).
                # Normalise to ascending bit order, permuting the matrix
                # (gate-index bit j addresses targets[j])
                k = len(phys_targets)
                order = sorted(range(k), key=lambda j: phys_targets[j])
                bits_asc = tuple(phys_targets[j] - pk.LANE_QUBITS
                                 for j in order)
                u = np.asarray(op.mat)
                omap = [sum(((a >> m) & 1) << order[m] for m in range(k))
                        for a in range(1 << k)]
                u_asc = u[np.ix_(omap, omap)]
                self.stages.append(("rowk", bits_asc, u_asc, lane_cm,
                                    lane_want, row_cm, row_want))
            else:
                return False
            self.members += 1
            return True
        # diagonal: phys_targets is sorted-desc; position-indifferent ops,
        # so ANY row bit below the local view works (no hi bound) — but at
        # most three row bits (the kernel enumerates 2^k factor rows)
        if any(p >= self.num_local for p in phys_targets):
            return False
        row_desc = [p for p in phys_targets if p >= pk.LANE_QUBITS]
        if len(row_desc) > 3:
            return False
        d = np.asarray(op.diag)
        if axis_order is not None:
            d = np.transpose(d, axis_order)
        if not row_desc:
            self._append_lane(pk.lane_diag_matrix(d, phys_targets))
            self.members += 1
            return True
        lane_desc = [p for p in phys_targets if p < pk.LANE_QUBITS]
        bits_asc = tuple(sorted(p - pk.LANE_QUBITS for p in row_desc))
        table = np.empty((1 << len(bits_asc), 1 << pk.LANE_QUBITS),
                         dtype=np.complex128)
        for cfg in range(1 << len(bits_asc)):
            idx = tuple((cfg >> bits_asc.index(p - pk.LANE_QUBITS)) & 1
                        for p in row_desc)
            table[cfg] = pk.lane_diag_vector(d[idx], lane_desc)
        self._append_rowdiag(table, bits_asc)
        self.members += 1
        return True


def _collect_layers_plan(items: list, ops: list, num_local: int,
                         block_rows: Optional[int] = None,
                         min_members: int = 2, mxu=None):
    """Post-plan peephole: fuse runs of consecutive op items whose PHYSICAL
    footprint fits the Pallas layer kernel into LayerOps.

    Works on LayoutPlan items, so it serves both the single-device path
    (identity placement) and the shard_map local body — phys coordinates
    are per-chip local there, and runs never cross a relayout. Fused
    LayerOps are appended to (a copy of) the ops table; returns
    ``(new_items, new_ops)``.
    """
    from .ops import pallas_kernels as pk
    if num_local < pk.LANE_QUBITS:
        return items, ops
    block_rows = block_rows or pk.DEFAULT_BLOCK_ROWS
    total_rows = (1 << num_local) // 128
    hi = pk.max_mid_qubit(min(block_rows, max(total_rows, 1)))
    ops = list(ops)
    out: list = []
    acc = _LayerAccum(num_local, hi, mxu)

    def flush():
        nonlocal acc
        if acc.members >= min_members:
            ops.append(pk.LayerOp(num_local, acc.members, acc.stages))
            out.append(("op", len(ops) - 1, (), 0, 0, None))
        else:
            out.extend(acc.src_items)
        acc = _LayerAccum(num_local, hi, mxu)

    for item in items:
        if item[0] != "op":
            flush()
            out.append(item)
            continue
        _, i, pt, cm, fm, ao = item
        if acc.try_add(ops[i], pt, cm, fm, ao):
            acc.src_items.append(item)
            continue
        # try_add's rejections are all op-intrinsic (kind, masks, target
        # range) — no retry against a fresh accumulator can succeed
        flush()
        out.append(item)
    flush()
    return out, ops


def _layer_eligible(op, num_local: int, hi: int, mxu=None) -> bool:
    """Mask/target-only mirror of ``_LayerAccum.try_add``'s accept set —
    no operand construction, so it is cheap enough to run per op during
    supergate grouping. ``mxu`` (the :func:`_mxu_policy` dict) extends
    the accept set with the MXU-tile gates the accumulator would claim."""
    from .ops import pallas_kernels as pk
    if getattr(op, "kind", None) not in ("u", "diag") or not op.is_static:
        return False
    if op.kind == "u":
        if op.ctrl_mask >> num_local:
            return False
        if (all(t < pk.LANE_QUBITS for t in op.targets)
                or (len(op.targets) == 1
                    and pk.LANE_QUBITS <= op.targets[0] <= hi)
                or (2 <= len(op.targets) <= 3
                    and all(pk.LANE_QUBITS <= t <= hi
                            for t in op.targets))):
            return True
        if mxu is None or op.ctrl_mask:
            return False
        row_t = [t for t in op.targets if t >= pk.LANE_QUBITS]
        return (bool(row_t) and len(row_t) <= mxu["cap"]
                and all(t <= hi for t in row_t)
                and mxu["decide"](len(row_t), len(op.targets)))
    if any(p >= num_local for p in op.targets):
        return False
    return sum(p >= pk.LANE_QUBITS for p in op.targets) <= 3


def _layer_barrier(ops: Sequence, num_qubits: int, shard_bits: int,
                   mxu=None):
    """Fence set (by op identity) for the supergate pass: ops the layer
    peephole can fuse more cheaply. Only RUNS of >=2 adjacent eligible
    ops are fenced — an isolated eligible gate can never form a layer
    (min_members=2) and is worth more inside a super-gate than as its
    own full-state pass."""
    from .ops import pallas_kernels as pk
    num_local = num_qubits - shard_bits
    total_rows = (1 << num_local) // 128
    hi = pk.max_mid_qubit(min(pk.DEFAULT_BLOCK_ROWS, max(total_rows, 1)))
    elig = [_layer_eligible(op, num_local, hi, mxu) for op in ops]
    fence = set()
    for i, op in enumerate(ops):
        if elig[i] and ((i > 0 and elig[i - 1])
                        or (i + 1 < len(ops) and elig[i + 1])):
            fence.add(id(op))
    return lambda op: id(op) in fence


def _collect_layers(ops: list, num_qubits: int,
                    block_rows: Optional[int] = None,
                    min_members: int = 2, mxu=None) -> list:
    """Ops-level view of the layer peephole (identity placement): merge
    runs of eligible static gates into Pallas LayerOps."""
    from .parallel import plan_layout
    plan = plan_layout(ops, num_qubits, 0)
    items, new_ops = _collect_layers_plan(plan.items, ops, num_qubits,
                                          block_rows, min_members,
                                          mxu=mxu)
    return [new_ops[item[1]] for item in items]


def _schedule(recorded: Sequence[_Op], num_qubits: int, shard_bits: int,
              lookahead: int, fuse_flag: bool,
              diag_row_cap: int = -1, cost_model=None,
              chunk_bytes: float = 0.0, host_bits: int = 0,
              reorder: bool = True):
    """Peephole-fuse + layout-plan the op stream (which the gate-fusion
    pass of :mod:`quest_tpu.core.fusion` has usually already contracted).

    Prefers the native C++ scheduler (quest_tpu.native / native/src/
    scheduler.cc); falls back to the pure-Python passes (_peephole_fused +
    quest_tpu.parallel.plan_layout). Both produce identical schedules.
    ``cost_model``/``chunk_bytes`` switch both planners to the
    communication-aware mode (quest_tpu/parallel/layout.py module docs);
    ``host_bits``/``reorder`` the two-tier multi-host mode (top
    ``host_bits`` device positions priced at the inter-host tier, evicted
    qubits re-paired hot-intra/cold-inter).

    The reordering pass is a greedy eviction re-pairing that usually —
    not always — lowers the inter-host traffic (composition interactions
    can flip its sign on adversarial op streams), so ``reorder=True`` on
    a multi-host mesh plans BOTH variants and keeps the one with the
    lower modeled comm seconds (ties: fewer inter-host bytes, then fewer
    launches). Selection sits ABOVE the native/Python planner pair, so
    either backend yields the same chosen plan and bit-for-bit parity is
    preserved per variant. Single-host (``host_bits == 0``) plans are
    untouched: one pass, no selection.

    Returns (ops_table, LayoutPlan).
    """
    if cost_model is not None and host_bits > 0 and reorder:
        from .parallel.layout import reorder_plan_score

        def score(plan):
            return reorder_plan_score(plan, chunk_bytes, cost_model,
                                      host_bits)

        ops_on, plan_on = _schedule_once(
            recorded, num_qubits, shard_bits, lookahead, fuse_flag,
            diag_row_cap, cost_model, chunk_bytes, host_bits, True)
        ops_off, plan_off = _schedule_once(
            recorded, num_qubits, shard_bits, lookahead, fuse_flag,
            diag_row_cap, cost_model, chunk_bytes, host_bits, False)
        if score(plan_off) < score(plan_on):
            return ops_off, plan_off
        return ops_on, plan_on
    return _schedule_once(recorded, num_qubits, shard_bits, lookahead,
                          fuse_flag, diag_row_cap, cost_model,
                          chunk_bytes, host_bits, reorder)


def _schedule_once(recorded: Sequence[_Op], num_qubits: int,
                   shard_bits: int, lookahead: int, fuse_flag: bool,
                   diag_row_cap: int = -1, cost_model=None,
                   chunk_bytes: float = 0.0, host_bits: int = 0,
                   reorder: bool = True):
    """One planner pass at a fixed ``reorder`` flag (no best-of-both
    selection; :func:`_schedule` is the public entry)."""
    from .parallel.layout import LayoutPlan

    # only host_bits > 0 needs the two-tier native ABI: at host count 1
    # the inter fields (now always present on DEFAULT_COMM_MODEL) are
    # never consulted, so a pre-pod-scale scheduler library still plans
    # bit-identically and must not be bypassed
    two_tier = cost_model is not None and host_bits > 0
    try:
        from . import native as nat
        use_native = nat.available() and (
            cost_model is None or nat.supports_cost_model()) and (
            not two_tier or nat.supports_two_tier())
    # quest: allow-broad-except(native-availability probe: a missing
    # compiler/toolchain or broken .so falls back to the bit-identical
    # Python planner)
    except Exception:
        use_native = False

    if use_native:
        sch = nat.NativeScheduler()
        for i, op in enumerate(recorded):
            if op.kind == "u":
                kind = nat.KIND_U if op.mat_fn is None else nat.KIND_U_PARAM
                data = op.mat
            else:
                kind = nat.KIND_DIAG if op.diag_fn is None \
                    else nat.KIND_DIAG_PARAM
                data = op.diag
            sch.add_op(kind, op.targets, op.ctrl_mask, op.flip_mask,
                       data, i)
        if cost_model is not None:
            sch.set_cost_model(
                cost_model.alpha_s, cost_model.beta_s_per_byte,
                chunk_bytes,
                inter_alpha_s=getattr(cost_model, "inter_alpha_s", None),
                inter_beta_s_per_byte=getattr(
                    cost_model, "inter_beta_s_per_byte", None),
                host_bits=host_bits, reorder=reorder)
        sch.compile(num_qubits, shard_bits, lookahead, fuse_flag,
                    diag_row_cap)
        ops_table: list[_Op] = []
        for kind, targets, cm, fm, data, si in sch.fused_ops():
            if kind == nat.KIND_U:
                ops_table.append(_Op("u", targets, cm, fm, mat=data))
            elif kind == nat.KIND_DIAG:
                ops_table.append(_Op("diag", targets, diag=data))
            else:
                ops_table.append(recorded[si])   # param ops pass through
        plan = LayoutPlan(sch.items(num_qubits), num_qubits, shard_bits,
                          sch.num_relayouts(),
                          num_xshard=sch.num_xshard(),
                          swaps_absorbed=sch.num_swaps_absorbed(),
                          collectives_fused=sch.num_fused_collectives())
        return ops_table, plan

    from .parallel import plan_layout
    ops_table = _peephole_fused(recorded, diag_row_cap) if fuse_flag \
        else list(recorded)
    return ops_table, plan_layout(ops_table, num_qubits, shard_bits,
                                  lookahead=lookahead,
                                  cost_model=cost_model,
                                  chunk_bytes=chunk_bytes,
                                  host_bits=host_bits, reorder=reorder)


class _BoundedExecutableCache:
    """LRU bound for the batched-engine executable cache.

    Keys are (form, donation, mode, dtype) tuples — a serving workload
    that cycles precisions, batch buckets, or mesh policies would
    otherwise pin one jitted executable per distinct key FOREVER (the
    same leak class as the unbounded sampler cache, ADVICE r5).
    Evictions are counted for ``dispatch_stats()``; dropping the jit
    wrapper releases the executable (XLA's own compilation cache may
    still serve a re-compile warm). Iteration/containment mirror a
    plain dict so existing introspection keeps working."""

    def __init__(self, maxsize: int):
        if maxsize < 1:
            raise ValueError("cache bound must be >= 1")
        self.maxsize = maxsize
        self.evictions = 0
        self._d: "collections.OrderedDict" = collections.OrderedDict()

    def get(self, key, default=None):
        fn = self._d.get(key, default)
        if key in self._d:
            self._d.move_to_end(key)
        return fn

    def peek(self, key, default=None):
        """Read without touching LRU order (safe for cross-thread
        health probes: no structural mutation)."""
        return self._d.get(key, default)

    def __setitem__(self, key, value) -> None:
        if key in self._d:
            self._d.move_to_end(key)
        self._d[key] = value
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
            self.evictions += 1

    def __contains__(self, key) -> bool:
        return key in self._d

    def __iter__(self):
        return iter(self._d)

    def __len__(self) -> int:
        return len(self._d)


class CompiledCircuit:
    """One jitted XLA program for a whole :class:`Circuit`.

    The program maps packed float planes ``(2, 2^N)`` -> same (donated), with
    the amplitude sharding pinned so cross-shard gates lower to ppermute
    rather than re-replication.
    """

    def __init__(self, circuit: Circuit, env: QuESTEnv,
                 donate: bool = True, fuse: bool = True,
                 lookahead: int = 32, pallas: Optional[object] = None,
                 supergate_k: int = 4, fusion: Optional[object] = None,
                 comm_planner: Optional[bool] = None,
                 overlap: bool = False,
                 reorder: Optional[bool] = None,
                 tier=None):
        self.circuit = circuit
        self.env = env
        self.num_qubits = circuit.num_qubits
        self.param_names = circuit.param_names
        # precision tier (config.PrecisionTier; None = the legacy
        # per-environment precision): decides the matmul precision every
        # gate contraction runs at, whether observable reductions take
        # the compensated pair path, and the plane dtype the EXECUTION
        # computes in (a FAST/SINGLE-tier program on an f64 env runs
        # f32 inside the executable; callers still see env-dtype planes)
        self.tier = self._resolve_tier(tier)
        self._gate_prec, self._pallas_fast = self._tier_exec_mode(self.tier)
        # recorded for the layer-free twin (_xla_only): it must differ
        # from this program ONLY in the Pallas pass
        self._compile_opts = {"fuse": fuse, "lookahead": lookahead,
                              "supergate_k": supergate_k, "fusion": fusion,
                              "comm_planner": comm_planner,
                              "overlap": overlap, "reorder": reorder,
                              "tier": self.tier}
        n = circuit.num_qubits
        if (1 << n) < env.num_devices:   # register smaller than the mesh
            sharding = None
            shard_bits = 0
        else:
            sharding = env.sharding()
            shard_bits = env.num_devices.bit_length() - 1

        # Pallas fused-layer pass. pallas=None -> auto (TPU backend only);
        # "interpret" -> run kernels interpreted (tests); False -> off.
        # Runs as a POST-PLAN peephole over the item stream (physical
        # coordinates), so it fuses on the shard_map local body too —
        # VERDICT r4 item 2: per-chip local gates ride the fused kernel
        # instead of paying one XLA pass each. Resolved BEFORE scheduling:
        # the fusion pass needs to know whether merged diagonals must stay
        # within the layer kernel's 3-row-bit budget.
        if pallas is None:
            pallas = os.environ.get("QUEST_TPU_PALLAS", "auto")
        interpret = pallas == "interpret"
        enabled = pallas not in (False, "0", "off") and (
            interpret or jax.default_backend() == "tpu")
        self._pallas_interpret = interpret
        use_layers = enabled and (n - shard_bits) >= 7
        # MXU-shaping policy (ROADMAP item 4): dense fused groups with
        # row-bit targets become (2^j * 128)-tile systolic-array
        # contractions when the modeled flops-vs-bytes crossover says
        # the MXU beats the VPU row path (parallel/layout.
        # choose_mxu_contraction; QUEST_TPU_MXU_SHAPE forces either
        # way). Decided with the COMPILE-TIME tier's matmul mode — a
        # per-dispatch tier override reuses these stages at its own
        # precision, which is numerically identical, just priced off
        # this tier's model.
        mxu_policy = _mxu_policy(use_layers, self._pallas_fast)

        # communication-aware planner: on by default wherever there is a
        # mesh to communicate over; ``comm_planner=False`` pins the
        # count-based legacy planner (the bench's planner-off rows).
        comm_on = (comm_planner if comm_planner is not None else True) \
            and shard_bits > 0
        from .profiling import comm_model as _get_comm_model
        cost_model = _get_comm_model(env) if comm_on else None
        chunk_bytes = 2.0 * np.dtype(env.precision.real_dtype).itemsize \
            * (1 << (n - shard_bits))
        self._chunk_bytes = chunk_bytes
        self._cost_model = cost_model

        # multi-host geometry (parallel/multihost.py): the top host_bits
        # of the shard positions cross the process (DCN) boundary, so
        # the planner prices those collectives at the cost model's inter
        # tier and the reordering pass keeps hot qubits off them.
        # host_bits == 0 (single process, the common case) makes both
        # mechanisms inert — plans stay bit-for-bit the single-host
        # plans.
        from .parallel.multihost import host_topology
        topo = host_topology(getattr(env, "mesh", None)) if shard_bits \
            else None
        host_bits = min(topo.host_bits, shard_bits) if topo else 0
        if not comm_on:
            host_bits = 0
        self._host_bits = host_bits
        self._num_hosts = topo.num_hosts if topo else 1
        self._reorder = True if reorder is None else bool(reorder)

        # gate-fusion pass (core/fusion.py): record -> FUSE -> plan ->
        # lower. Runs of adjacent gates contract into single dense
        # kernels / folded diagonal factors BEFORE layout planning, so
        # the planner's relayout decisions are made per fused group and
        # XLA dispatches one kernel where it used to dispatch a ladder.
        # Clamped local-fit-aware (a fused gate must stay gatherable on
        # one chunk); layer-eligible runs are fenced when the Pallas
        # pass will claim them more cheaply, and SWAP gates are fenced
        # when the communication planner will absorb them for free.
        from .core.fusion import fuse_ops, resolve_fusion_k
        from .parallel.layout import is_swap_op

        def _fence(base, comm):
            """Compose the layer barrier with the comm planner's SWAP
            fence (an absorbed SWAP costs zero; welded into a group it
            costs a full kernel pass and may force relayouts)."""
            if not comm:
                return base
            if base is None:
                return is_swap_op
            return lambda op: is_swap_op(op) or base(op)

        def build_pipeline(comm: bool, reorder_on: Optional[bool] = None):
            """fuse -> schedule -> supergate -> replan, under one planner
            mode (``reorder_on`` overrides the compile's reordering flag
            — the reorder-off baseline of the inter-host accounting).
            Returns (ops_table, plan, fusion_stats)."""
            cm = cost_model if comm else None
            hb = host_bits if comm else 0
            ro = self._reorder if reorder_on is None else reorder_on
            recorded = list(circuit.ops)
            fstats = None
            k_fuse = resolve_fusion_k(fusion, n - shard_bits)
            if k_fuse >= 2:
                barrier = _fence(_layer_barrier(recorded, n, shard_bits,
                                                mxu_policy)
                                 if use_layers else None, comm)
                recorded, fstats = fuse_ops(
                    recorded, max_k=k_fuse,
                    diag_row_cap=3 if use_layers else -1,
                    barrier=barrier)
            ops, plan = _schedule(recorded, n, shard_bits,
                                  lookahead, fuse,
                                  diag_row_cap=3 if use_layers else -1,
                                  cost_model=cm, chunk_bytes=chunk_bytes,
                                  host_bits=hb, reorder=ro)

            # super-gate grouping: consecutive static gates collapse into
            # one k-qubit pass. Layer-eligible gates are fenced off
            # (barrier) when the Pallas pass is on — the layer kernel
            # fuses them into a single state pass, strictly cheaper than
            # any super-gate. On a mesh, diagonal ops stay separate —
            # they are communication-free at any position, and folding
            # one into a dense super-gate would force relocalisation it
            # never needed.
            replan = False
            if supergate_k >= 2:
                k_eff = min(supergate_k, n - shard_bits) if shard_bits \
                    else supergate_k
                if k_eff >= 2:
                    before = len(ops)
                    ops = _group_supergates(
                        ops, k_eff, fold_diags=(shard_bits == 0),
                        barrier=_fence(_layer_barrier(ops, n, shard_bits,
                                                      mxu_policy)
                                       if use_layers else None, comm))
                    replan = len(ops) != before
            if replan:
                from .parallel import plan_layout
                plan = plan_layout(ops, n, shard_bits, lookahead=lookahead,
                                   cost_model=cm, chunk_bytes=chunk_bytes,
                                   host_bits=hb, reorder=ro)
                if cm is not None and hb > 0 and ro:
                    # the replan must uphold _schedule's best-of-both
                    # selection: the greedy re-pairing can lose on the
                    # supergate-contracted stream too
                    from .parallel.layout import reorder_plan_score
                    alt = plan_layout(ops, n, shard_bits,
                                      lookahead=lookahead, cost_model=cm,
                                      chunk_bytes=chunk_bytes,
                                      host_bits=hb, reorder=False)
                    if reorder_plan_score(alt, chunk_bytes, cm, hb) < \
                            reorder_plan_score(plan, chunk_bytes, cm, hb):
                        plan = alt
            return ops, plan, fstats

        from .parallel import apply_relayout
        ops, self.plan, self.fusion_stats = build_pipeline(comm_on)

        # comm accounting is LAZY (first dispatch_stats() call): the
        # baseline count-based replan that comm_bytes_saved compares
        # against would otherwise double every mesh compile's host-side
        # planning work even when nobody reads the stats. The pipeline
        # closure is retained for that deferred replan.
        self._comm_bytes_planned = None
        self._comm_bytes_saved = 0.0
        self._comm_inter_planned = 0.0
        self._comm_inter_saved = 0.0
        self._inter_launches = 0
        self._baseline_pipeline = build_pipeline if comm_on else None

        if use_layers:
            from .parallel.layout import LayoutPlan
            items, ops = _collect_layers_plan(self.plan.items, ops,
                                              n - shard_bits,
                                              mxu=mxu_policy)
            # prune the table to executed ops (fused members are
            # superseded by their LayerOp) so _ops remains the program
            ref = sorted({it[1] for it in items
                          if it[0] in ("op", "xshard")})
            remap = {old: new for new, old in enumerate(ref)}
            ops = [ops[i] for i in ref]
            items = [(it[0], remap[it[1]], *it[2:])
                     if it[0] in ("op", "xshard") else it
                     for it in items]
            self.plan = LayoutPlan(items, n, shard_bits,
                                   self.plan.num_relayouts,
                                   num_xshard=self.plan.num_xshard,
                                   swaps_absorbed=self.plan.swaps_absorbed,
                                   collectives_fused=self.plan
                                   .collectives_fused)

        self._ops = ops
        self._overlapped_pairs = 0
        plan_items = self.plan.items
        flat_sharding = env.sharding_flat() if shard_bits else None
        gate_prec = self._gate_prec
        pallas_fast = self._pallas_fast

        # single-chip Pallas routing: besides the layers, every dense
        # uncontrolled item of at most 4 targets at qubit 10+ runs as a
        # row-gate pass (ops/pallas_kernels.apply_rowgate_planes)
        from .ops import pallas_kernels as pk
        rowgate = [use_layers and not shard_bits and item[0] == "op"
                   and pk.rowgate_eligible(ops[item[1]].kind, item[3],
                                           item[2])
                   for item in plan_items]
        self._rowgate_passes = sum(rowgate)

        def run_plan_seq(state, params):
            """Sequential (single-trace) form: relayouts as plain
            transposes, no collectives (a cross-shard pair-exchange item
            is just the unitary at its physical position here — the
            full-state form reaches any bit). The compiled path on a mesh
            uses the shard_map program instead.

            Pallas items (layers and row gates) read and write the
            state's re/im planes; the planes are kept from one Pallas
            item to the next and the state turns complex again only
            before an XLA item."""
            planes = None
            for item, as_rowgate in zip(plan_items, rowgate):
                pallas_item = as_rowgate or (
                    item[0] == "op" and ops[item[1]].kind == "layer")
                if pallas_item:
                    if planes is None:
                        planes = pk.to_planes(state)
                    _, i, phys_targets = item[:3]
                    op = ops[i]
                    if as_rowgate:
                        u = op.mat_fn(params) if op.mat_fn is not None \
                            else op.mat
                        planes = pk.apply_rowgate_planes(
                            *planes, n, u, phys_targets,
                            interpret=self._pallas_interpret)
                    else:
                        planes = pk.apply_layer_planes(
                            *planes, n, op,
                            interpret=self._pallas_interpret,
                            fast=pallas_fast)
                    planes = pass_boundary(planes)
                    continue
                if planes is not None:
                    state = pk.from_planes(*planes).astype(state.dtype)
                    planes = None
                if item[0] == "relayout":
                    _, before, after = item
                    state = pass_boundary(
                        apply_relayout(state, n, before, after, None))
                    continue
                _, i, phys_targets, cmask, fmask, axis_order = item
                op = ops[i]
                if op.kind == "u":
                    u = op.mat_fn(params) if op.mat_fn is not None \
                        else op.mat
                    state = apply_unitary(state, n, u, phys_targets,
                                          cmask, fmask,
                                          precision=gate_prec)
                else:
                    d = op.diag_fn(params) if op.diag_fn is not None \
                        else op.diag
                    d = jnp.transpose(jnp.asarray(d), axis_order)
                    state = apply_diagonal(state, n, phys_targets, d)
                state = pass_boundary(state)
            if planes is not None:
                state = pk.from_planes(*planes).astype(state.dtype)
            return state

        self._run_plan_seq = run_plan_seq

        if shard_bits:
            # the distributed fast path: ONE shard_map program — local
            # kernels on per-device chunks, relayouts as explicit
            # all_to_all/ppermute pair exchanges (parallel/exchange.py),
            # cross-shard 1q items as role-split ppermute combines.
            # GSPMD never sees a transpose it could rematerialize.
            from .parallel.exchange import (plan_exchange, run_exchange,
                                            apply_op_local,
                                            apply_1q_cross_shard,
                                            overlap_eligible,
                                            run_exchange_overlapped)
            from .env import AMP_AXIS
            from jax.sharding import PartitionSpec as P
            lt = n - shard_bits
            ex_plans = [plan_exchange(n, shard_bits, item[1], item[2])
                        if item[0] == "relayout" else None
                        for item in plan_items]

            # comm/compute overlap (opt-in): a relayout immediately
            # followed by the dense kernel it localises runs as the slab
            # double-buffered pipeline — the collective for slab i+1 is
            # independent of the gate math on slab i, so async-collective
            # backends overlap them. Pairs are chosen at trace-setup time
            # (static plan), with strict eligibility (no post-transpose,
            # gate must not touch the slab bit).
            overlapped = set()
            if overlap:
                for j, item in enumerate(plan_items):
                    if item[0] != "relayout" or j + 1 >= len(plan_items):
                        continue
                    nxt = plan_items[j + 1]
                    if nxt[0] != "op" or \
                            getattr(ops[nxt[1]], "kind", None) != "u":
                        continue
                    if overlap_eligible(ex_plans[j], nxt[2], nxt[3]):
                        overlapped.add(j)
            self._overlapped_pairs = len(overlapped)

            def local_body(local, params):
                consumed = False
                for j, (item, expl) in enumerate(zip(plan_items, ex_plans)):
                    if consumed:
                        consumed = False
                        continue
                    if item[0] == "relayout":
                        if j in overlapped:
                            _, i, pt, cmask, fmask, _ = plan_items[j + 1]
                            op = ops[i]
                            u = op.mat_fn(params) if op.mat_fn is not None \
                                else op.mat
                            local = run_exchange_overlapped(
                                local, expl, AMP_AXIS, u, pt, cmask, fmask,
                                precision=gate_prec)
                            consumed = True
                            continue
                        local = pass_boundary(
                            run_exchange(local, expl, AMP_AXIS))
                        continue
                    _, i, phys_targets, cmask, fmask, axis_order = item
                    op = ops[i]
                    if item[0] == "xshard":
                        u = op.mat_fn(params) if op.mat_fn is not None \
                            else op.mat
                        local = apply_1q_cross_shard(
                            local, u, phys_targets[0], lt, shard_bits,
                            AMP_AXIS, cmask, fmask)
                    elif op.kind == "layer":
                        from .ops import pallas_kernels as pk
                        local = pk.apply_layer(
                            local, lt, op,
                            interpret=self._pallas_interpret,
                            fast=pallas_fast)
                    elif op.kind == "u":
                        u = op.mat_fn(params) if op.mat_fn is not None \
                            else op.mat
                        local = apply_op_local(local, "u", u, phys_targets,
                                               cmask, fmask, lt, AMP_AXIS,
                                               precision=gate_prec)
                    else:
                        d = op.diag_fn(params) if op.diag_fn is not None \
                            else op.diag
                        d = jnp.transpose(jnp.asarray(d), axis_order)
                        local = apply_op_local(local, "diag", d, phys_targets,
                                               0, 0, lt, AMP_AXIS)
                    local = pass_boundary(local)
                return local

            from jax import shard_map
            sharded_body = shard_map(
                local_body, mesh=env.mesh,
                in_specs=(P(AMP_AXIS), P()), out_specs=P(AMP_AXIS),
                check_vma=False)

            def run_plan(state, params):
                return sharded_body(state, params)
        else:
            run_plan = run_plan_seq

        self._run_plan = run_plan
        self._flat_sharding = flat_sharding

        env_rdt = np.dtype(env.precision.real_dtype)
        tier_rdt, tier_cdt = self._tier_dtypes(self.tier, env)
        self._run_rdtype = tier_rdt

        def apply_fn(state_f, param_vec):
            params = {name: param_vec[i]
                      for i, name in enumerate(self.param_names)}
            z = unpack(state_f)
            # tier execution dtype: a FAST/SINGLE-tier program on an f64
            # env computes in f32 (half the memory traffic — part of
            # what the budget bought); callers keep env-dtype planes
            if z.dtype != tier_cdt:
                z = z.astype(tier_cdt)
            z = run_plan(z, params)
            out = pack(z)
            if out.dtype != env_rdt:
                out = out.astype(env_rdt)
            if sharding is not None:
                out = jax.lax.with_sharding_constraint(out, sharding)
            return out

        self._apply_fn = apply_fn
        self._jitted = jax.jit(apply_fn, donate_argnums=(0,) if donate else ())
        self._donate = donate
        self._in_sharding = sharding   # the run()/precompile() input layout

        # batched ensemble engine (sweep / expectation_sweep /
        # sample_sweep): executables keyed on (form, dtype,
        # batch-sharding mode, donation) — a precision or mesh-policy
        # change compiles its own program instead of reusing a stale
        # one. LRU-bounded (QUEST_TPU_BATCH_CACHE, default 16 entries)
        # with evictions surfaced in dispatch_stats().
        self._batched_cache = _BoundedExecutableCache(
            int(os.environ.get("QUEST_TPU_BATCH_CACHE", "16")))
        # warm-start AOT side cache (serve/warmcache.py): persisted
        # executables deserialized at warm() time, keyed (form key,
        # exact arg shapes). Shape-specialized — the dispatch sites
        # consult it FIRST and fall back to the retracing jit wrappers
        # above for any other shape. Installed via install_batched_aot.
        self._batched_aot: dict = {}
        self._batch_stats: Optional[dict] = None
        self._warned_nondivisible = False
        # the serving runtime mutates batch stats / the executable
        # cache from its background dispatcher thread while callers may
        # read dispatch_stats() (or run their own sweeps) concurrently;
        # RLock so the lazy comm accounting can nest
        self._stats_lock = threading.RLock()
        # numerical health guard cadence counter (resilience/health.py):
        # ticks once per guarded dispatch; the active config decides
        # which ticks actually pay a check
        self._health_counter = 0

    def _resolve_tier(self, tier, dispatch: bool = False):
        """Validate a tier request (None passes through); ``dispatch``
        marks a per-dispatch request (sweep/expectation_sweep/serving)
        as opposed to the compile-time tier. QUAD executes on
        double-double planes THROUGH the batched engine
        (``_dd_batched_runner``) as a per-dispatch tier; it needs x64
        AND an f64-storage env because results leave the engine as
        env-dtype planes — on an f32 env the ~2^-49-significand dd
        values would round straight back to f32 on exit and the tier
        would quietly deliver SINGLE accuracy. The DOUBLE tier's f64
        planes need the same pair of guards (without x64 JAX silently
        downcasts — the QUAD64 env guard, one ladder down)."""
        if tier is None:
            return None
        from .config import tier_by_name
        tier = tier_by_name(tier)
        if tier.name == "quad":
            if not dispatch:
                raise ValueError(
                    "the QUAD tier is a per-DISPATCH rung: pass "
                    "tier='quad' to sweep/expectation_sweep/"
                    "sample_sweep (or submit()) — a compile-time quad "
                    "tier would pin run()/apply() to the XLA "
                    "executable, which has no dd form; for static "
                    "circuits Circuit.compile_dd is the whole-program "
                    "dd path")
            if not jax.config.jax_enable_x64 or \
                    np.dtype(self.env.precision.real_dtype) != \
                    np.dtype(np.float64):
                raise ValueError(
                    "the QUAD tier's double-double planes recombine to "
                    "env-dtype planes at the engine boundary: it needs "
                    "jax_enable_x64 AND an f64-storage environment "
                    "(precision=DOUBLE) so the ~48-bit significand "
                    "survives the exit; on this env use "
                    "Circuit.compile_dd (static circuits) instead")
            return tier
        if tier.real_dtype == jnp.dtype("float64"):
            if not jax.config.jax_enable_x64:
                raise ValueError(
                    "the DOUBLE tier needs jax_enable_x64; without it "
                    "JAX silently downcasts the f64 planes and the tier "
                    "quietly degrades to SINGLE")
            if np.dtype(self.env.precision.real_dtype) != \
                    np.dtype(np.float64):
                raise ValueError(
                    "the DOUBLE tier needs an f64-storage environment: "
                    "results are returned as env-dtype planes, so on "
                    "this f32 env the f64 execution would round back "
                    "to f32 on exit — create the env with "
                    "precision=DOUBLE (or use compile_dd)")
        return tier

    def _effective_tier(self, tier):
        """The tier one engine dispatch runs at: the per-call override
        (serving submits per-request tiers against one compiled
        program), else the compile-time tier, else None (legacy env
        precision)."""
        if tier is None:
            return self.tier
        return self._resolve_tier(tier, dispatch=True)

    @staticmethod
    def _tier_exec_mode(tier) -> tuple:
        """(matmul precision override, pallas fast flag) for one tier —
        the ONE definition of the tier -> execution-mode rule, shared by
        the compile-time program (``__init__``) and the per-dispatch
        batched runners."""
        fast = tier is not None and tier.matmul_precision == "default"
        return (jax.lax.Precision.DEFAULT if fast else None), fast

    @staticmethod
    def _tier_token(tier) -> str:
        """The executable-cache key component for a tier: tier name, or
        ``"env"`` for the legacy per-environment precision. Shared by
        the batched cache, the warm-form keys, and (through those) the
        persistent WarmCache — a tier mismatch is always a MISS, never
        a wrong program."""
        return tier.name if tier is not None else "env"

    @staticmethod
    def _tier_dtypes(tier, env) -> tuple:
        """(real, complex) EXECUTION dtypes for one dispatch. QUAD is
        special: its PLANES are f32 dd pairs but its engine boundary is
        complex128 — casting the entry states to complex64 would
        destroy the precision the dd split is about to preserve."""
        if tier is not None and tier.name == "quad":
            return np.dtype(np.float64), jnp.complex128
        rdt = np.dtype(tier.real_dtype) if tier is not None \
            else np.dtype(env.precision.real_dtype)
        cdt = jnp.complex64 if rdt == np.dtype(np.float32) \
            else jnp.complex128
        return rdt, cdt

    def _param_vec(self, params: Optional[dict]) -> jnp.ndarray:
        if params is None:
            params = {}
        missing = [p for p in self.param_names if p not in params]
        if missing:
            raise ValueError(f"missing circuit parameters: {missing}")
        vals = [params[nm] for nm in self.param_names]
        if not vals:
            # cache the empty vector: building it per run() is a fresh
            # host-to-device transfer per call, for a constant
            if getattr(self, "_empty_vec", None) is None:
                self._empty_vec = jnp.zeros(
                    (0,), dtype=self.env.precision.real_dtype)
            return self._empty_vec
        return jnp.asarray(vals, dtype=self.env.precision.real_dtype)

    def _modeled_tier_error(self) -> float:
        """The budget model's per-run error bound for this program's
        compile-time tier (0.0 when no tier is selected)."""
        if self.tier is None:
            return 0.0
        from .profiling import modeled_tier_error
        return float(modeled_tier_error(self.tier,
                                        max(self.circuit.depth, 1)))

    # -- execution ---------------------------------------------------------

    is_density = False   # set by Circuit.compile(density=True)
    error_budget = None  # set by Circuit.compile(error_budget=...)
    _aot = None          # set by precompile()
    _digest_cached = None   # lazy program_digest (content-addressed)
    _plan_comm_s = None     # lazy modeled plan comm seconds (profiler)

    @property
    def program_digest(self) -> str:
        """Stable content digest of the recorded program (the
        :func:`~quest_tpu.serve.warmcache.circuit_digest` address) —
        what the dispatch profiler and the persistent perf ledger key
        on, so measurements survive process restarts and object
        identity churn. Falls back to a process-local id token when an
        op resists content addressing."""
        if self._digest_cached is None:
            from .serve.warmcache import circuit_digest
            d = circuit_digest(self.circuit, self.is_density)
            self._digest_cached = d or f"id-{id(self):x}"
        return self._digest_cached

    def _bytes_per_pass(self, batch: int = 1, terms: int = 0) -> float:
        """The planner-known device traffic of ONE dispatch of this
        program: every planned kernel/relayout streams the split re/im
        planes once (read + write, the memory-bound model bench.py's
        offline rooflines use), times the batch rows, plus one gather
        pass per Pauli term for energy dispatches. The dispatch
        profiler divides this by measured wall-to-ready seconds for a
        live achieved-bytes/s per key."""
        itemsize = np.dtype(self.env.precision.real_dtype).itemsize
        state_bytes = 4.0 * itemsize * (1 << self.num_qubits)
        passes = max(self.plan.num_dispatches, 1) + max(int(terms), 0)
        return passes * max(int(batch), 1) * state_bytes

    def _plan_comm_seconds(self) -> float:
        """Modeled collective seconds of one execution of this plan
        (0.0 unsharded) — the ``comm_plan`` drift model's modeled side,
        cached after the first call."""
        if not self.plan.shard_bits:
            return 0.0
        if self._plan_comm_s is None:
            from .parallel.layout import plan_comm_stats
            from .profiling import DEFAULT_COMM_MODEL
            model = self._cost_model or DEFAULT_COMM_MODEL
            self._plan_comm_s = plan_comm_stats(
                self.plan, self._chunk_bytes, model,
                host_bits=self._host_bits)["seconds"] + 0.0
        return self._plan_comm_s

    def _drift_models(self, mode: str, rows: int, pol: dict) -> dict:
        """The drift-monitor model dict for one batched dispatch — ONE
        definition for the library sweep paths and the serving
        dispatcher. Models exist only where the dispatch actually pays
        collectives: amp mode runs every planned relayout per batch row
        (``comm_plan``) at the crossover price the sharding policy
        modeled (``batch_amp_comm``)."""
        models: dict = {}
        if mode == "amp":
            cps = self._plan_comm_seconds()
            if cps > 0.0:
                models["comm_plan"] = cps * rows
            if pol.get("amp_comm_seconds", 0.0) > 0.0:
                models["batch_amp_comm"] = pol["amp_comm_seconds"]
        return models

    def precompile(self) -> "CompiledCircuit":
        """Ahead-of-time compile (lower + compile), no execution.

        ``jit`` otherwise compiles on the first :meth:`run` dispatch,
        which buries a cold compile (seconds to minutes at full width on
        a TPU, docs/tpu.md) inside the first timed call. After ``precompile()``, :meth:`run` dispatches the
        compiled executable directly. Returns ``self`` for chaining:
        ``cc = circ.compile(env).precompile()``."""
        dt = self.env.precision.real_dtype
        state = jax.ShapeDtypeStruct((2, 1 << self.num_qubits), dt,
                                     sharding=self._in_sharding)
        vec = jax.ShapeDtypeStruct((len(self.param_names),), dt)
        self._aot = self._jitted.lower(state, vec).compile()
        return self

    def run(self, qureg: Qureg, params: Optional[dict] = None) -> None:
        """Apply in place (the donated buffer is reused by XLA)."""
        if qureg.is_density_matrix != self.is_density:
            if self.is_density:
                raise ValueError("this circuit was compiled with "
                                 "density=True; run it on a density register")
            raise ValueError(
                "running a statevector-compiled circuit on a density "
                "register; compile with density=True")
        if qureg.num_qubits_in_state_vec != self.num_qubits:
            raise ValueError(
                f"circuit has {self.num_qubits} qubits; register state vector "
                f"has {qureg.num_qubits_in_state_vec}")
        if getattr(qureg, "is_quad", False):
            raise ValueError(
                "QUAD registers hold double-double planes; compile with "
                "Circuit.compile_dd and run on its packed planes, or use "
                "the imperative API (which routes to dd kernels)")
        qureg.ensure_canonical()   # compiled programs address canonical bits
        state = qureg.state
        fn = self._aot if (self._aot is not None
                           and self._aot_accepts(state)) else self._jitted
        # QL004 trio: the profile span opens BEFORE the fault hook so
        # injected stalls land inside the measured wall-to-ready time
        sp = _profile.profile_dispatch("circuits.run")
        poison = _faults.fire("circuits.run")
        # QL004: every dispatch boundary carries a fault hook AND a
        # profiler annotation (device profiles align with host spans)
        with dispatch_annotation(
                f"quest_tpu.circuits.run:{self.num_qubits}q"):
            qureg.state = fn(state, self._param_vec(params))
        if sp is not None:
            models = {}
            cps = self._plan_comm_seconds()
            if cps > 0.0:
                models["comm_plan"] = cps
            sp.done(qureg.state, program=self.program_digest,
                    kind="run", bucket=1,
                    tier=self._tier_token(self.tier),
                    dtype=str(np.dtype(self.env.precision.real_dtype)),
                    sharding="amp" if self.plan.shard_bits else "none",
                    bytes_per_pass=self._bytes_per_pass(),
                    models=models)
        qureg.state = _faults.poison_output(poison, qureg.state)
        qureg.state = self._health_tick(
            qureg.state, is_density=qureg.is_density_matrix,
            num_qubits=qureg.num_qubits_represented, where="run")

    def apply(self, state_f: jnp.ndarray, params=None):
        """Pure form: packed planes in -> packed planes out.

        ``params`` may be a name->angle dict (as in :meth:`run`) or an
        already-built parameter vector ordered like ``param_names`` —
        including a traced one, so ``apply`` composes with ``jax.vmap`` /
        ``lax.scan`` for batched simulation (no reference counterpart)."""
        if params is None or isinstance(params, dict):
            vec = self._param_vec(params)
        else:
            vec = jnp.asarray(params, dtype=self.env.precision.real_dtype)
            if vec.shape != (len(self.param_names),):
                # shapes are static even under vmap/scan (each mapped call
                # sees the unbatched shape), so this check is free — and
                # JAX's clamped gather would otherwise turn a wrong-length
                # vector into silently wrong angles; a still-batched
                # (batch, n_params) array must go through vmap, not raw
                raise ValueError(
                    f"parameter vector has shape {vec.shape}; expected "
                    f"({len(self.param_names)},) ordered like "
                    f"{list(self.param_names)} (use jax.vmap for batches)")
        if (self._aot is not None
                and not isinstance(state_f, jax.core.Tracer)
                and not isinstance(vec, jax.core.Tracer)
                and getattr(state_f, "shape", None)
                == (2, 1 << self.num_qubits)
                and getattr(state_f, "dtype", None)
                == self.env.precision.real_dtype
                and self._aot_accepts(state_f)):
            # concrete inputs ride the precompiled executable — the jit
            # cache is NOT populated by precompile(), so _jitted here
            # would silently recompile. Traced inputs (vmap/scan/grad)
            # must still trace through the jit path.
            return self._aot(state_f, vec)
        return self._jitted(state_f, vec)

    def _health_tick(self, planes, *, is_density: bool, num_qubits: int,
                     where: str, tier=None):
        """Numerical health guard at the dispatch boundary: every
        ``cadence``-th guarded dispatch (global config,
        :func:`quest_tpu.resilience.health.configure` /
        ``QUEST_TPU_HEALTH_EVERY``) checks the output invariants —
        NaN/Inf, statevector norm, density trace — as one tiny jitted
        reduction, raising a typed ``NumericalFault`` or renormalizing
        in the degraded mode. Free when the guard is off (one int
        compare).

        With a precision tier active the check is the tier's FIDELITY
        MONITOR: the drift threshold widens to the tier's runtime
        tolerance (:func:`quest_tpu.profiling.tier_runtime_tol` — the
        modeled per-run error with headroom, so an in-budget FAST run
        never trips) and a violation carries the ``"precision"`` fault
        kind, which the serving recovery policy answers by re-executing
        one tier up instead of retrying the same rung."""
        cfg = _health.get_config()
        if cfg.cadence <= 0:
            return planes
        with self._stats_lock:
            self._health_counter += 1
            due = (self._health_counter % cfg.cadence) == 0
        if not due:
            return planes
        drift_kind = None
        if tier is None:
            tier = self.tier
        if tier is not None:
            from .profiling import tier_runtime_tol
            tol = tier_runtime_tol(tier, max(self.circuit.depth, 1))
            if tol > cfg.norm_tol:
                cfg = dataclasses.replace(cfg, norm_tol=tol)
            drift_kind = "precision"
        return _health.check_planes(
            planes, is_density=is_density, num_qubits=num_qubits,
            config=cfg, where=f"{where} ({self.num_qubits}q program)",
            drift_kind=drift_kind)

    def _aot_accepts(self, state_f) -> bool:
        """True when the precompiled executable can take this input as
        is. AOT executables hard-error on inputs ``jit`` would silently
        reshard — a host numpy array, or an array laid out differently
        from the sharding the program was lowered for (ADVICE r5) — so
        those fall back to the jit path instead of raising."""
        if not isinstance(state_f, jax.Array):
            return False
        if self._in_sharding is None:
            return True
        sh = getattr(state_f, "sharding", None)
        if sh is None:
            return False
        try:
            return sh.is_equivalent_to(self._in_sharding, state_f.ndim)
        except (AttributeError, TypeError):
            return sh == self._in_sharding

    # -- analysis / autodiff ----------------------------------------------

    def dispatch_stats(self):
        """Compile-time dispatch accounting (:class:`quest_tpu.profiling.
        DispatchStats`): recorded gates in, kernels out, planned
        relayouts, the gate-fusion pass's per-group counters, and the
        communication planner's accounting (cross-shard pair exchanges,
        absorbed SWAPs, fused collectives, modeled collective bytes
        planned/saved). The observables the fusion engine and the comm
        planner optimise — ``bench.py`` emits these fields next to
        gates/sec."""
        from .profiling import DispatchStats
        fs = self.fusion_stats
        with self._stats_lock:
            if self._comm_bytes_planned is None:
                # deferred comm accounting: modeled bytes of the active
                # plan, and — when the comm planner chose it — a
                # count-based replan of the same circuit as the
                # comm_bytes_saved baseline (host-side only; cached
                # after the first call)
                planned = 0.0
                saved = 0.0
                inter_planned = 0.0
                inter_saved = 0.0
                inter_launches = 0
                launches = 0
                if self.plan.shard_bits:
                    from .parallel.layout import plan_comm_stats
                    from .profiling import DEFAULT_COMM_MODEL
                    model = self._cost_model or DEFAULT_COMM_MODEL
                    hb = self._host_bits
                    tot = plan_comm_stats(
                        self.plan, self._chunk_bytes, model,
                        self.env.num_devices, host_bits=hb)
                    planned = tot["bytes"]
                    inter_planned = tot["inter_bytes"]
                    inter_launches = tot["inter_launches"]
                    # an overlapped relayout issues one all_to_all per
                    # slab, two where the plan counts one
                    launches = tot["launches"] + self._overlapped_pairs
                    if self._baseline_pipeline is not None:
                        _, base_plan, _ = self._baseline_pipeline(False)
                        base = plan_comm_stats(base_plan,
                                               self._chunk_bytes, model,
                                               self.env.num_devices,
                                               host_bits=hb)
                        saved = max(0.0, base["bytes"] - planned)
                    if (hb > 0 and self._reorder
                            and self._baseline_pipeline is not None):
                        # the reordering pass's primary observable:
                        # inter-host bytes vs the same comm-planned
                        # pipeline with reordering off
                        _, roff_plan, _ = self._baseline_pipeline(
                            True, reorder_on=False)
                        roff = plan_comm_stats(roff_plan,
                                               self._chunk_bytes, model,
                                               self.env.num_devices,
                                               host_bits=hb)
                        inter_saved = max(
                            0.0, roff["inter_bytes"] - inter_planned)
                self._comm_bytes_planned = planned
                self._comm_bytes_saved = saved
                self._comm_inter_planned = inter_planned
                self._comm_inter_saved = inter_saved
                self._inter_launches = inter_launches
                self._comm_launches = launches
            bs = dict(self._batch_stats or {})
            cache_evictions = self._batched_cache.evictions
            cache_size = len(self._batched_cache)
        return DispatchStats(
            gates_in=self.circuit.depth,
            kernels_out=self.plan.num_kernels,
            relayouts=self.plan.num_relayouts,
            fused_groups=fs.fused_groups if fs else 0,
            diag_folds=fs.diag_folds if fs else 0,
            commuted_diagonals=fs.commuted_diagonals if fs else 0,
            max_group_gates=fs.max_group_gates if fs else 0,
            cross_shard_exchanges=self.plan.num_xshard,
            collective_launches=self._comm_launches,
            swaps_absorbed=self.plan.swaps_absorbed,
            collectives_fused=self.plan.collectives_fused,
            comm_bytes_planned=self._comm_bytes_planned,
            comm_bytes_saved=self._comm_bytes_saved,
            num_hosts=self._num_hosts,
            inter_host_collectives=self._inter_launches,
            comm_bytes_inter_planned=self._comm_inter_planned,
            comm_bytes_inter_saved=self._comm_inter_saved,
            batch_size=bs.get("batch_size", 0),
            host_syncs_avoided=bs.get("host_syncs_avoided", 0),
            batch_sharding_mode=bs.get("batch_sharding_mode", "none"),
            evolve_steps_fused=bs.get("evolve_steps_fused", 0),
            batched_cache_size=cache_size,
            batched_cache_evictions=cache_evictions,
            rowgate_passes=self._rowgate_passes,
            precision_tier=self._tier_token(self.tier),
            modeled_tier_error=self._modeled_tier_error())

    def _xla_only(self) -> "CompiledCircuit":
        """This program with the Pallas pass off (cached twin).

        ``jax.grad`` and ``jax.vmap`` have no rules for a compiled
        ``pallas_call``, so the transform-composable consumers
        (:meth:`expectation_fn`, :meth:`sweep`) trace the twin's
        layer-free plan — identical math, XLA ops only. Execution paths
        (:meth:`run`, :meth:`apply`) keep the fused kernels and the row
        gates."""
        if not self._rowgate_passes and not any(
                getattr(op, "kind", None) == "layer" for op in self._ops):
            return self
        if getattr(self, "_xla_twin", None) is None:
            self._xla_twin = CompiledCircuit(
                self.circuit, self.env, donate=False, pallas=False,
                **self._compile_opts)
        return self._xla_twin

    def _validated_pauli_terms(self, pauli_terms, coeffs):
        """Shared Hamiltonian validation for :meth:`expectation_fn` and
        :meth:`expectation_sweep`: returns ``(nq, terms, coeffs)`` with
        identity factors dropped AFTER validation (a malformed
        ``(qubit, 0)`` pair still errors instead of vanishing)."""
        nq = self.num_qubits // 2 if self.is_density else self.num_qubits
        for t in pauli_terms:
            for q, code in t:
                if not 0 <= int(q) < nq:
                    raise ValueError(
                        f"pauli qubit {q} out of range [0, {nq})")
                if int(code) not in (0, 1, 2, 3):
                    raise ValueError(f"invalid pauli code {code}")
        terms = [tuple((int(q), int(c)) for q, c in t if int(c) != 0)
                 for t in pauli_terms]
        coeffs = np.asarray(coeffs, dtype=np.float64)
        if len(coeffs) != len(terms):
            raise ValueError(f"{len(terms)} pauli terms but "
                             f"{len(coeffs)} coefficients")
        return nq, terms, coeffs

    def expectation_fn(self, pauli_terms: Sequence[Sequence[tuple[int, int]]],
                       coeffs: Sequence[float]) -> Callable:
        """Return jitted ``param_vec -> <H>`` for ``H = sum_j coeffs[j] *
        prod Pauli``, starting from |0…0>.

        A pure real-valued function of the parameter vector — feed it to
        ``jax.grad`` / ``jax.value_and_grad`` for variational optimisation.

        On a density-compiled circuit (``compile(density=True)``) the
        value is ``Tr(H rho(params))`` with rho evolved through the
        lifted program INCLUDING its noise channels — exact gradients
        THROUGH decoherence, which neither the statevector form (noise
        is not a unitary) nor the reference (no autodiff at all) can
        provide. Channel probabilities are static; the differentiable
        inputs are the gate parameters.
        """
        n = self.num_qubits
        cdtype = self.env.precision.complex_dtype
        nq, terms, coeffs = self._validated_pauli_terms(pauli_terms, coeffs)

        if self.is_density:
            # Tr(P rho): P applied on the KET half (low positions — the
            # bra half carries conj(U), verified by the Y-term sign),
            # then the real diagonal sum (the densmatr trace helper)
            from .ops.densmatr import calc_total_prob

            def reduce_term(state, phi):
                return calc_total_prob(phi, nq)
        else:
            def reduce_term(state, phi):
                return jnp.real(jnp.vdot(state, phi))

        run_plan = self._xla_only()._run_plan

        def energy(param_vec):
            params = {nm: param_vec[i] for i, nm in enumerate(self.param_names)}
            state = jnp.zeros(1 << n, dtype=cdtype).at[0].set(1.0)
            if self._flat_sharding is not None:
                state = jax.lax.with_sharding_constraint(
                    state, self._flat_sharding)
            state = run_plan(state, params)
            total = jnp.zeros((), dtype=jnp.float64)
            for term, c in zip(terms, coeffs):
                phi = state
                for q, code in term:
                    phi = apply_unitary(phi, n, mats.PAULI_MATS[code], (q,))
                total = total + c * reduce_term(state, phi)
            return total

        return jax.jit(energy)

    # -- batched ensemble engine ------------------------------------------
    #
    # The serving workload is not one circuit — it is thousands of
    # parameter bindings of the SAME circuit (VQE energy surfaces,
    # phase-diagram sweeps, shot batches; arXiv:2203.16044,
    # arXiv:2111.10466 optimise exactly this ensemble shape). The engine
    # maps (batch, 2, 2^n) planes through ONE executable: sequential plan
    # segments are vmapped, Pallas layer runs ride a batch-grown kernel
    # grid (ops/pallas_kernels.apply_layer_batched) instead of falling
    # back to the layer-free XLA twin, and on a mesh the batch axis
    # shards per the CommCostModel-priced policy
    # (parallel/layout.choose_batch_sharding) with non-divisible batches
    # padded-and-masked rather than silently replicated.

    def _batched_segments(self):
        """The plan's item stream split into vmappable sequential
        segments and batched Pallas layer steps: a list of
        ``("seq", items)`` / ``("layer", op_index)`` entries."""
        segs: list = []
        cur: list = []
        for item in self.plan.items:
            if (item[0] == "op"
                    and getattr(self._ops[item[1]], "kind", None)
                    == "layer"):
                if cur:
                    segs.append(("seq", tuple(cur)))
                    cur = []
                segs.append(("layer", item[1]))
            else:
                cur.append(item)
        if cur:
            segs.append(("seq", tuple(cur)))
        return segs

    def _run_plan_batched(self, states, pm, gate_prec=None,
                          pallas_fast: bool = False):
        """(batch, 2^n) complex states + (batch, P) params -> same shape.
        Mirrors ``run_plan_seq`` (relayouts as plain transposes; a
        cross-shard pair-exchange item is just the unitary at its
        physical position — the full-state form reaches any bit), with
        the batch axis vmapped per segment and fused layers applied by
        the batch-gridded Pallas kernel. ``gate_prec``/``pallas_fast``
        carry one dispatch's precision-tier matmul mode."""
        from .parallel import apply_relayout
        n = self.num_qubits
        ops = self._ops
        names = self.param_names
        for kind, payload in self._batched_segments():
            if kind == "layer":
                from .ops import pallas_kernels as pk
                states = pk.apply_layer_batched(
                    states, n, ops[payload],
                    interpret=self._pallas_interpret,
                    fast=pallas_fast)
                continue

            def seg_fn(state, vec, _items=payload):
                params = {nm: vec[i] for i, nm in enumerate(names)}
                for item in _items:
                    if item[0] == "relayout":
                        _, before, after = item
                        state = pass_boundary(apply_relayout(
                            state, n, before, after, None))
                        continue
                    _, i, phys_targets, cmask, fmask, axis_order = item
                    op = ops[i]
                    if op.kind == "u":
                        u = op.mat_fn(params) if op.mat_fn is not None \
                            else op.mat
                        state = apply_unitary(state, n, u, phys_targets,
                                              cmask, fmask,
                                              precision=gate_prec)
                    else:
                        d = op.diag_fn(params) if op.diag_fn is not None \
                            else op.diag
                        d = jnp.transpose(jnp.asarray(d), axis_order)
                        state = apply_diagonal(state, n, phys_targets, d)
                    state = pass_boundary(state)
                return state

            states = jax.vmap(seg_fn, in_axes=(0, 0))(states, pm)
        return states

    def _batch_policy(self, batch: int, mem_factor: float = 1.0) -> dict:
        """The mesh batch-sharding decision for a ``batch``-point
        ensemble (:func:`quest_tpu.parallel.layout.choose_batch_sharding`,
        priced by the compile-time comm model). ``mem_factor=2.0`` is
        the gradient executables' pricing: reverse mode keeps primal
        and cotangent planes live together, so the batch-parallel
        memory wall arrives one doubling earlier."""
        from .parallel.layout import choose_batch_sharding
        return choose_batch_sharding(
            self.num_qubits, batch, self.env.num_devices,
            np.dtype(self.env.precision.real_dtype).itemsize,
            self.plan.num_relayouts, cost_model=self._cost_model,
            host_bits=self._host_bits, mem_factor=mem_factor)

    def _batch_constraint(self, mode: str):
        """Amplitude-axis sharding constraint for the in-engine
        (batch, 2^n) complex ensemble (``amp`` mode only — batch mode
        runs under shard_map and needs no constraints)."""
        if mode != "amp" or self.env.mesh is None:
            return lambda z: z
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .env import AMP_AXIS
        sh = NamedSharding(self.env.mesh, P(None, AMP_AXIS))
        return lambda z: jax.lax.with_sharding_constraint(z, sh)

    def _batched_runner(self, mode: str, tier=None):
        """The plan executor for a policy mode. In ``amp`` mode the
        ensemble is amplitude-sharded under GSPMD, which has no
        partitioning rule for a ``pallas_call`` (it would replicate the
        whole batch on every device — an OOM exactly where amp mode was
        chosen for memory), so the layer-free XLA twin's plan runs
        there; every other mode keeps the fused layers (batch mode wraps
        the call in shard_map, where the kernel sees only the per-device
        sub-batch). ``tier`` (already effective) sets the dispatch's
        matmul precision and Pallas fast mode."""
        if tier is not None and tier.name == "quad":
            return self._dd_batched_runner()
        src = self._xla_only() if (mode == "amp"
                                   and self.env.mesh is not None) else self
        prec, fast = self._tier_exec_mode(tier)

        def run(states, pm):
            return src._run_plan_batched(states, pm, gate_prec=prec,
                                         pallas_fast=fast)

        return run

    def _dd_batched_runner(self):
        """The QUAD rung's plan executor: each batch row walks the
        (layer-free) plan on DOUBLE-DOUBLE planes — every dense group
        through :func:`~quest_tpu.ops.doubledouble.dd_apply_kq_traced`
        (bound Param matrices dd-split traceably, so parameterised
        sweeps ride the dd path the standalone ``DDProgram`` rejects),
        diagonals through the dd factor kernel, relayouts as per-plane
        transposes — then recombines to complex128 at the boundary.
        Closes ROADMAP item 4's "dd sweeps fall off the fast path": one
        keyed executable per (form, mode, dtype, tier='quad') through
        the same ``_BoundedExecutableCache``, so the coalescer and the
        serving tier ladder admit the highest-precision rung like any
        other."""
        from .ops import doubledouble as dd
        # the dd walk needs the layer-free twin (Pallas stages have no
        # dd form), same rule as the amp-mode runner
        src = self._xla_only() if any(
            getattr(op, "kind", None) == "layer" for op in self._ops) \
            else self
        ops = src._ops
        plan_items = src.plan.items
        n = self.num_qubits
        names = self.param_names

        def make_step(item):
            if item[0] == "relayout":
                _, before, after = item
                return lambda planes, vec: dd.dd_relayout(
                    planes, n, before, after)
            _, i, phys_targets, cmask, fmask, axis_order = item
            op = ops[i]
            if op.kind == "u":
                def step_u(planes, vec, _op=op, _pt=phys_targets,
                           _cm=cmask, _fm=fmask):
                    params = {nm: vec[j] for j, nm in enumerate(names)}
                    u = _op.mat_fn(params) if _op.mat_fn is not None \
                        else _op.mat
                    return dd.dd_apply_kq_traced(planes, n, u, _pt,
                                                 _cm, _fm)
                return step_u

            def step_d(planes, vec, _op=op, _pt=phys_targets,
                       _ao=axis_order):
                params = {nm: vec[j] for j, nm in enumerate(names)}
                d = _op.diag_fn(params) if _op.diag_fn is not None \
                    else _op.diag
                d = jnp.transpose(jnp.asarray(d), _ao)
                return dd.dd_apply_diag_traced(planes, n, d, _pt)
            return step_d

        steps = [make_step(item) for item in plan_items]

        def run(states, pm):
            planes_b = jax.vmap(dd.dd_split_traceable)(states)
            for step in steps:
                planes_b = jax.vmap(step)(planes_b, pm)
                # stop XLA's simplifier from folding the error-free
                # transformations ACROSS op boundaries (the DDProgram
                # barrier rule — measured 1.4e-6 instead of 4e-13 on
                # QFT-6 without it); outside the vmap: the primitive
                # has no batching rule
                planes_b = jax.lax.optimization_barrier(planes_b)
            return jax.vmap(dd.dd_join_traceable)(planes_b)

        return run

    def _validated_param_matrix(self, param_matrix):
        """Shared (B, P) coercion/validation for the engine entries."""
        pm = jnp.asarray(param_matrix, dtype=self.env.precision.real_dtype)
        if pm.ndim != 2 or pm.shape[1] != len(self.param_names):
            raise ValueError(
                f"param_matrix must be (batch, {len(self.param_names)}); "
                f"got {pm.shape}")
        return pm

    def _wrap_batch_spmd(self, fn, mode: str, in_specs, out_specs):
        """Batch-parallel SPMD wrapper, shared by every batched
        executable: in ``batch`` mode on a mesh the whole body runs as a
        shard_map over the batch axis — each device computes WHOLE
        states on its local sub-batch with zero collectives, and the
        Pallas layer call stays inside the per-device body (the same
        pattern as the amplitude-sharded executor's local_body) so it
        never meets the GSPMD partitioner, which has no rule for a
        ``pallas_call`` and would replicate the ensemble. Identity in
        every other mode."""
        if mode != "batch" or self.env.mesh is None:
            return fn
        from jax import shard_map
        return shard_map(fn, mesh=self.env.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _batched_fn(self, broadcast: bool, donate: bool, mode: str,
                    tier=None):
        """The batched executable for one (form, mode, tier) combination.
        Keyed cache — dtype, batch-sharding mode, AND precision tier are
        part of the key, so a precision, tier, or mesh-policy change
        compiles fresh instead of reusing a stale program (a FAST-tier
        executable must never serve a SINGLE-tier dispatch)."""
        key = (broadcast, donate, mode,
               str(np.dtype(self.env.precision.real_dtype)),
               self._tier_token(tier))
        with self._stats_lock:
            fn = self._batched_cache.get(key)
        if fn is not None:
            return fn
        constrain = self._batch_constraint(mode)
        run_batched = self._batched_runner(mode, tier)
        env_rdt, tier_cdt = np.dtype(self.env.precision.real_dtype), \
            self._tier_dtypes(tier, self.env)[1]

        def body(states, pm):
            if states.dtype != tier_cdt:
                # tier execution dtype (FAST/SINGLE on an f64 env runs
                # f32 inside the executable; callers keep env planes)
                states = states.astype(tier_cdt)
            states = constrain(states)
            states = run_batched(states, pm)
            out = constrain(states)
            planes = jnp.stack([jnp.real(out), jnp.imag(out)], axis=1)
            return planes.astype(env_rdt) if planes.dtype != env_rdt \
                else planes

        if broadcast:
            def apply_fn(state_f, pm):
                z = unpack(state_f)
                states = jnp.broadcast_to(z, (pm.shape[0],) + z.shape)
                return body(states, pm)
        else:
            def apply_fn(planes, pm):
                return body(jax.lax.complex(planes[:, 0], planes[:, 1]),
                            pm)

        from jax.sharding import PartitionSpec as P
        from .env import AMP_AXIS
        apply_fn = self._wrap_batch_spmd(
            apply_fn, mode,
            in_specs=(P() if broadcast else P(AMP_AXIS, None, None),
                      P(AMP_AXIS, None)),
            out_specs=P(AMP_AXIS, None, None))
        # a shared (broadcast) input cannot be donated
        fn = jax.jit(apply_fn,
                     donate_argnums=(0,) if donate and not broadcast
                     else ())
        with self._stats_lock:
            self._batched_cache[key] = fn
        return fn

    def _padded_params(self, pm, mode: str):
        """Pad-and-mask for non-divisible batches: the parameter matrix
        is zero-padded to the next device multiple (the padded rows
        compute throwaway states that the caller-facing slice masks off)
        instead of silently running the whole sweep replicated. Warns
        once per compiled circuit."""
        B = pm.shape[0]
        D = self.env.num_devices
        # only the batch-parallel mode splits the batch axis; amp mode
        # shards amplitudes, so any batch size runs unpadded there
        if mode != "batch" or B % D == 0:
            return pm, B
        pad = (-B) % D
        with self._stats_lock:
            warn_now = not self._warned_nondivisible
            self._warned_nondivisible = True
        if warn_now:
            warnings.warn(
                f"sweep batch of {B} is not divisible by the {D}-device "
                f"mesh; padding to {B + pad} and masking the {pad} extra "
                "rows (earlier releases silently ran the batch "
                "replicated on every device)", UserWarning, stacklevel=3)
        pm = jnp.concatenate(
            [pm, jnp.zeros((pad,) + pm.shape[1:], pm.dtype)])
        return pm, B

    def _record_batch_stats(self, batch: int, mode: str,
                            host_syncs_avoided: int,
                            evolve_steps_fused: int = 0) -> None:
        # one atomic dict swap under the stats lock: the serving
        # dispatcher records from its background thread while callers
        # read dispatch_stats() (satellite: no torn batch accounting).
        # evolve_steps_fused: Trotter/imaginary-time steps the last
        # dynamics dispatch iterated INSIDE the executable (batch x
        # steps) — 0 for every non-dynamics dispatch
        with self._stats_lock:
            self._batch_stats = {"batch_size": batch,
                                 "batch_sharding_mode": mode,
                                 "host_syncs_avoided": host_syncs_avoided,
                                 "evolve_steps_fused": evolve_steps_fused}

    def _place_batch(self, arr, mode: str, amp_shardable: bool = False):
        """Commit a batch-leading array to the policy's input layout so
        the executable starts from the right placement instead of
        resharding on entry. In ``amp`` mode only state-plane arrays
        (``amp_shardable``) split — small operands (the parameter
        matrix) stay replicated."""
        if mode == "none" or self.env.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .env import AMP_AXIS
        if mode == "batch":
            spec = P(AMP_AXIS, *([None] * (arr.ndim - 1)))
        elif amp_shardable:
            spec = P(*([None] * (arr.ndim - 1)), AMP_AXIS)
        else:
            return arr
        return jax.device_put(arr, NamedSharding(self.env.mesh, spec))

    def _pauli_operands(self, hamiltonian):
        """The ONE shared Hamiltonian encoder for the energy executables:
        validate ``(pauli_terms, coeffs)``, flatten to the
        calcExpecPauliSum codes layout, and build the device mask
        operands (two mask builders would desynchronise silently).
        Returns ``(nq, T, xm, ym, zm, coeffs)``."""
        from .ops import reductions as red
        pauli_terms, coeffs = hamiltonian
        nq, terms, coeffs = self._validated_pauli_terms(pauli_terms,
                                                        coeffs)
        T = len(terms)
        codes = np.zeros((T, nq), np.int64)
        for t, term in enumerate(terms):
            for q, code in term:
                if codes[t, q]:
                    raise ValueError(
                        f"pauli term {t} repeats qubit {q} (a product of "
                        "Paulis on one qubit is not a Pauli string)")
                codes[t, q] = code
        xm, ym, zm, coeffs = red.pauli_sum_operands(
            codes.reshape(-1), nq, coeffs)
        return nq, T, xm, ym, zm, coeffs

    def _energies_trace(self, constrain, run_batched, tier):
        """The ONE batched-energy lowering shared by :meth:`_energy_fn`
        and :meth:`_grad_fn` (its differentiated form): broadcast the
        shared start state over the batch, run the plan, reduce the
        Pauli sum per row. One definition, so a change to the energy
        lowering (compensated reductions, density trace, constraint
        placement) can never leave gradient energies diverging from
        ``expectation_sweep`` energies. Returns a traceable
        ``(z, pm, xm, ym, zm, cf) -> (B,)`` closure."""
        from .ops import reductions as red
        is_density = self.is_density
        nq = self.num_qubits // 2 if is_density else self.num_qubits
        comp = tier is not None and tier.compensated

        def energies(z, pm_, xm_, ym_, zm_, cf_):
            states = jnp.broadcast_to(z, (pm_.shape[0],) + z.shape)
            states = constrain(states)
            states = run_batched(states, pm_)
            states = constrain(states)
            if is_density:
                return jax.vmap(lambda s: red.pauli_sum_total_dm(
                    s, nq, xm_, ym_, zm_, cf_, compensated=comp))(states)
            return jax.vmap(lambda s: red.pauli_sum_total_sv(
                s, xm_, ym_, zm_, cf_, compensated=comp))(states)

        return energies

    def _energy_fn(self, mode: str, tier=None):
        """The batched-energy jit wrapper for one (sharding mode, tier)
        (masks and coefficients are ARGUMENTS, so one executable serves
        every Hamiltonian of the same bucketed term shape). Cached in
        the keyed executable cache; also the lowering source for the
        warm cache's ``energy`` artifacts. A compensated tier
        (SINGLE/QUAD) routes each Pauli-term reduction through the
        TwoSum/Veltkamp pair path (:mod:`quest_tpu.ops.reductions`) —
        ~4x the per-term memory traffic, exact to the state's true sum;
        the FAST tier keeps the naive reduce its budget already covers."""
        key = ("energy", mode,
               str(np.dtype(self.env.precision.real_dtype)),
               self._tier_token(tier))
        with self._stats_lock:
            fn = self._batched_cache.get(key)
        if fn is not None:
            return fn
        constrain = self._batch_constraint(mode)
        energies = self._energies_trace(
            constrain, self._batched_runner(mode, tier), tier)
        tier_cdt = self._tier_dtypes(tier, self.env)[1]

        def energy(state_f_, pm_, xm_, ym_, zm_, cf_):
            z = unpack(state_f_)
            if z.dtype != tier_cdt:
                z = z.astype(tier_cdt)
            return energies(z, pm_, xm_, ym_, zm_, cf_)

        from jax.sharding import PartitionSpec as P
        from .env import AMP_AXIS
        energy = self._wrap_batch_spmd(
            energy, mode,
            in_specs=(P(), P(AMP_AXIS, None), P(), P(), P(), P()),
            out_specs=P(AMP_AXIS))
        fn = jax.jit(energy)
        with self._stats_lock:
            self._batched_cache[key] = fn
        return fn

    def _grad_fn(self, mode: str, tier=None):
        """The batched value-and-grad executable for one (sharding
        mode, tier): ``jax.value_and_grad`` through the SAME
        ``_run_plan_batched`` trace ``expectation_sweep`` runs, so one
        reverse pass replaces the whole parameter-shift loop
        (PennyLane-Lightning's adjoint insight, arXiv:2508.13615,
        recast through the batched engine). Rows are independent, so
        the gradient of the SUMMED energies w.r.t. the ``(B, P)``
        parameter matrix is exactly the per-row gradient block — no
        per-row vjp loop, one backward walk for the whole batch. The
        executable returns ONE ``(B, P + 1)`` array (column 0 the
        energies, columns 1..P the gradients) so the whole gradient
        sweep leaves the device as a single transfer. Always traces
        the layer-free XLA twin (``jax.grad`` has no rule for a
        compiled ``pallas_call``); density-compiled programs
        differentiate through their lifted channels, including
        Param-rate Kraus strengths."""
        key = ("grad", mode,
               str(np.dtype(self.env.precision.real_dtype)),
               self._tier_token(tier))
        with self._stats_lock:
            fn = self._batched_cache.get(key)
        if fn is not None:
            return fn
        constrain = self._batch_constraint(mode)
        src = self._xla_only()
        prec, _fast = self._tier_exec_mode(tier)
        energies = self._energies_trace(
            constrain,
            lambda states, pmat: src._run_plan_batched(
                states, pmat, gate_prec=prec),
            tier)
        tier_cdt = self._tier_dtypes(tier, self.env)[1]

        def value_and_grad(state_f_, pm_, xm_, ym_, zm_, cf_):
            z = unpack(state_f_)
            if z.dtype != tier_cdt:
                z = z.astype(tier_cdt)

            def total(pmat):
                e = energies(z, pmat, xm_, ym_, zm_, cf_)
                return jnp.sum(e), e

            (_, e), g = jax.value_and_grad(total, has_aux=True)(pm_)
            return jnp.concatenate(
                [e[:, None].astype(pm_.dtype), g], axis=1)

        from jax.sharding import PartitionSpec as P
        from .env import AMP_AXIS
        value_and_grad = self._wrap_batch_spmd(
            value_and_grad, mode,
            in_specs=(P(), P(AMP_AXIS, None), P(), P(), P(), P()),
            out_specs=P(AMP_AXIS, None))
        fn = jax.jit(value_and_grad)
        with self._stats_lock:
            self._batched_cache[key] = fn
        return fn

    def _evolve_fn(self, mode: str, tier=None, *, steps: int,
                   order: int):
        """The batched TROTTER-EVOLUTION executable for one (sharding
        mode, tier, steps, order): run the state-prep program per row,
        then iterate ``steps`` Trotter steps of ``exp(-i H dt)``
        INSIDE the executable (``lax.scan`` over
        :func:`quest_tpu.ops.dynamics.trotter_step`), reducing the
        Pauli-sum energy after every step and folding the step energies
        through the device-resident Welford carry. Masks, coefficients,
        and ``dt`` are DATA — one executable serves every Hamiltonian
        of the term bucket at every time step; only the scan length and
        splitting order are trace constants (part of the cache key).
        Returns ONE packed ``(B, steps + 3 + 2^{n+1})`` real block per
        dispatch (:func:`quest_tpu.ops.dynamics.pack_evolve_block`) —
        the whole segment leaves the device as a single transfer, where
        a stepping client pays ``steps`` dispatches and transfers per
        row."""
        key = ("evolve", int(order), int(steps), mode,
               str(np.dtype(self.env.precision.real_dtype)),
               self._tier_token(tier))
        with self._stats_lock:
            fn = self._batched_cache.get(key)
        if fn is not None:
            return fn
        from .ops import dynamics as dyn
        from .ops import reductions as red
        constrain = self._batch_constraint(mode)
        run_batched = self._batched_runner(mode, tier)
        env_rdt = np.dtype(self.env.precision.real_dtype)
        tier_cdt = self._tier_dtypes(tier, self.env)[1]
        comp = tier is not None and tier.compensated
        S = int(steps)

        def evolve(state_f_, pm_, xm_, ym_, zm_, cf_, dt_):
            z = unpack(state_f_)
            if z.dtype != tier_cdt:
                z = z.astype(tier_cdt)
            states = jnp.broadcast_to(z, (pm_.shape[0],) + z.shape)
            states = constrain(states)
            states = run_batched(states, pm_)
            states = constrain(states)

            def row(zrow):
                def step(zc, _):
                    zc = dyn.trotter_step(zc, xm_, ym_, zm_, cf_, dt_,
                                          order=order)
                    e = red.pauli_sum_total_sv(zc, xm_, ym_, zm_, cf_,
                                               compensated=comp)
                    return zc, e
                zf, es = jax.lax.scan(step, zrow, None, length=S)
                es = es.astype(env_rdt)
                wn, wm, ws = red.welford_wave(
                    es, jnp.ones((S,), dtype=env_rdt))
                planes = jnp.stack([jnp.real(zf), jnp.imag(zf)]
                                   ).astype(env_rdt)
                return dyn.pack_evolve_block(
                    es, jnp.stack([wn, wm, ws]), planes)

            return jax.vmap(row)(states)

        from jax.sharding import PartitionSpec as P
        from .env import AMP_AXIS
        evolve = self._wrap_batch_spmd(
            evolve, mode,
            in_specs=(P(), P(AMP_AXIS, None), P(), P(), P(), P(), P()),
            out_specs=P(AMP_AXIS, None))
        fn = jax.jit(evolve)
        with self._stats_lock:
            self._batched_cache[key] = fn
        return fn

    def _ground_fn(self, mode: str, tier=None, *, steps: int,
                   method: str):
        """The batched GROUND-STATE executable for one (sharding mode,
        tier, steps, method). ``method="power"``: ``steps``
        imaginary-time Trotter iterations
        (:func:`quest_tpu.ops.dynamics.imag_time_step` — on-device
        renormalisation every step) with the per-iteration energy
        recorded and the convergence residual ``|e_S - e_{S-1}|``
        computed device-side. ``method="lanczos"``: one fixed-``steps``
        Krylov recursion (:func:`quest_tpu.ops.dynamics.
        lanczos_ground`) whose residual is the Ritz bound
        ``beta_m |y_m|``. Either way the dispatch returns ONE packed
        ``(B, steps + 4 + 2^{n+1})`` real block (energies, residual,
        Welford carry, final planes) — the serving handle reads the
        residual from the SAME single transfer that carries the
        checkpoint planes."""
        key = ("ground", str(method), int(steps), mode,
               str(np.dtype(self.env.precision.real_dtype)),
               self._tier_token(tier))
        with self._stats_lock:
            fn = self._batched_cache.get(key)
        if fn is not None:
            return fn
        from .ops import dynamics as dyn
        from .ops import reductions as red
        constrain = self._batch_constraint(mode)
        run_batched = self._batched_runner(mode, tier)
        env_rdt = np.dtype(self.env.precision.real_dtype)
        tier_cdt = self._tier_dtypes(tier, self.env)[1]
        comp = tier is not None and tier.compensated
        S = int(steps)
        lanczos = method == "lanczos"

        def ground(state_f_, pm_, xm_, ym_, zm_, cf_, tau_):
            z = unpack(state_f_)
            if z.dtype != tier_cdt:
                z = z.astype(tier_cdt)
            states = jnp.broadcast_to(z, (pm_.shape[0],) + z.shape)
            states = constrain(states)
            states = run_batched(states, pm_)
            states = constrain(states)

            def row(zrow):
                if lanczos:
                    ritz, energy, residual = dyn.lanczos_ground(
                        zrow, xm_, ym_, zm_, cf_, num_vectors=S)
                    es = jnp.full((S,), energy).astype(env_rdt)
                    zf = ritz
                else:
                    e0 = red.pauli_sum_total_sv(
                        zrow, xm_, ym_, zm_, cf_, compensated=comp)

                    def step(zc, _):
                        zc = dyn.imag_time_step(zc, xm_, ym_, zm_,
                                                cf_, tau_)
                        e = red.pauli_sum_total_sv(
                            zc, xm_, ym_, zm_, cf_, compensated=comp)
                        return zc, e
                    zf, es = jax.lax.scan(step, zrow, None, length=S)
                    es = es.astype(env_rdt)
                    prev = es[-2] if S >= 2 else e0.astype(env_rdt)
                    residual = jnp.abs(es[-1] - prev)
                wn, wm, ws = red.welford_wave(
                    es, jnp.ones((S,), dtype=env_rdt))
                planes = jnp.stack([jnp.real(zf), jnp.imag(zf)]
                                   ).astype(env_rdt)
                return dyn.pack_ground_block(
                    es, residual.astype(env_rdt),
                    jnp.stack([wn, wm, ws]), planes)

            return jax.vmap(row)(states)

        from jax.sharding import PartitionSpec as P
        from .env import AMP_AXIS
        ground = self._wrap_batch_spmd(
            ground, mode,
            in_specs=(P(), P(AMP_AXIS, None), P(), P(), P(), P(), P()),
            out_specs=P(AMP_AXIS, None))
        fn = jax.jit(ground)
        with self._stats_lock:
            self._batched_cache[key] = fn
        return fn

    def _dynamics_dispatch(self, kind: str, param_matrix, hamiltonian,
                           spec, state_f, tier):
        """The shared evolve/ground dispatch body: validate, choose the
        batch policy, build or fetch the keyed executable, run, record
        the fused-step accounting. Statevector programs only — Trotter
        rotations act on ket amplitudes; density evolution belongs to
        the channel machinery."""
        from .ops import dynamics as dyn
        if self.is_density:
            raise ValueError(
                f"{kind}_sweep runs on statevector-compiled programs "
                "(Trotter rotations act on ket amplitudes); evolve "
                "density registers through their channel circuits")
        with dispatch_annotation("quest_tpu.circuits.prepare"):
            tier = self._effective_tier(tier)
            if tier is not None and tier.name == "quad":
                raise ValueError(
                    f"{kind}_sweep cannot run at the QUAD tier: the "
                    "double-double walk has no scan-resident Trotter "
                    "form; use tier='double' for the highest rung")
            nq, T, xm, ym, zm, coeffs = self._pauli_operands(hamiltonian)
            n = self.num_qubits
            pm = self._validated_param_matrix(param_matrix)
            # fault injection for dynamics dispatches happens at the
            # serving boundary ("serve.evolve" in faults.SITES) — the
            # circuits layer contributes the profiling span and trace
            # annotation only
            sp = _profile.profile_dispatch(f"circuits.{kind}_sweep")
            B = pm.shape[0]
            pol = self._batch_policy(B)
            mode = pol["mode"]
            pm_run, B = self._padded_params(pm, mode)
            pm_run = self._place_batch(pm_run, mode)
            if state_f is None:
                state_f = zero_state(n, self.env.precision.real_dtype)
            elif getattr(state_f, "shape", None) != (2, 1 << n):
                raise ValueError(
                    f"{kind}_sweep state_f must be shared (2, {1 << n}) "
                    f"planes; got {getattr(state_f, 'shape', None)}")
            else:
                state_f = jnp.asarray(
                    state_f, dtype=self.env.precision.real_dtype)
            if kind == "evolve":
                S = int(spec.steps)
                fn = self._evolve_fn(mode, tier, steps=S,
                                     order=int(spec.order))
                knob = jnp.asarray(spec.dt,
                                   dtype=self.env.precision.real_dtype)
            else:
                S = int(spec.steps)
                fn = self._ground_fn(mode, tier, steps=S,
                                     method=str(spec.method))
                knob = jnp.asarray(spec.tau,
                                   dtype=self.env.precision.real_dtype)
            args = (state_f, pm_run, jnp.asarray(xm), jnp.asarray(ym),
                    jnp.asarray(zm),
                    jnp.asarray(coeffs,
                                dtype=self.env.precision.real_dtype), knob)
            ann_name = (f"quest_tpu.circuits.{kind}_sweep:"
                        f"b{pm_run.shape[0]}:t{T}:s{S}:"
                        f"{tier.name if tier is not None else 'env'}")
        with dispatch_annotation(ann_name):
            out = fn(*args)
        # the stepping client pays one dispatch + one transfer per
        # step per row; the fused loop returns the segment as ONE
        # block — S*B transfers collapse to 1
        self._record_batch_stats(B, mode, B * S - 1,
                                 evolve_steps_fused=B * S)
        if sp is not None:
            sp.done(out, program=self.program_digest, kind=kind,
                    bucket=pm_run.shape[0],
                    tier=self._tier_token(tier),
                    dtype=str(np.dtype(self.env.precision.real_dtype)),
                    sharding=mode,
                    # every Trotter step re-streams the planes once per
                    # term sweep (order 2 sweeps twice), plus the prep
                    # program's own passes
                    bytes_per_pass=self._bytes_per_pass(
                        pm_run.shape[0], terms=T * S),
                    models=self._drift_models(mode, pm_run.shape[0],
                                              pol))
        return out[:B] if out.shape[0] != B else out

    def evolve_sweep(self, param_matrix, hamiltonian, spec,
                     state_f=None, tier=None):
        """Trotterised ``exp(-i H t)`` for a whole parameter batch from
        ONE executable and ONE device->host transfer.

        Each row runs the compiled program from ``state_f`` (default
        |0..0>; the state-prep circuit), then ``spec.steps`` Trotter
        steps of order ``spec.order`` iterate INSIDE the executable
        (``lax.scan`` — no per-step dispatch), with the Pauli-sum
        energy reduced after every step. ``hamiltonian``:
        ``(pauli_terms, coeffs)`` exactly as :meth:`expectation_sweep`;
        ``spec``: an :class:`~quest_tpu.ops.dynamics.EvolveSpec`.

        Returns the packed ``(B, steps + 3 + 2^{n+1})`` real block —
        per-step energies, the folded Welford carry, and the final
        state planes; decode with :func:`quest_tpu.ops.dynamics.
        unpack_evolve_block` (the serving layer materialises the block
        with ONE transfer per checkpointed segment)."""
        from .ops.dynamics import EvolveSpec
        if not isinstance(spec, EvolveSpec):
            raise TypeError("spec must be an EvolveSpec")
        return self._dynamics_dispatch("evolve", param_matrix,
                                       hamiltonian, spec, state_f, tier)

    def ground_sweep(self, param_matrix, hamiltonian, spec,
                     state_f=None, tier=None):
        """One imaginary-time (or Lanczos) ground-state SEGMENT for a
        whole parameter batch: ``spec.steps`` on-device iterations with
        per-iteration energies and a device-resident convergence
        residual, as one packed ``(B, steps + 4 + 2^{n+1})`` block
        (:func:`quest_tpu.ops.dynamics.unpack_ground_block`). ``spec``:
        a :class:`~quest_tpu.ops.dynamics.GroundSpec`. The serving
        layer (``SimulationService.ground_state``) chains segments —
        each segment's output planes seed the next via ``state_f`` —
        and stops when the residual crosses ``spec.tol``."""
        from .ops.dynamics import GroundSpec
        if not isinstance(spec, GroundSpec):
            raise TypeError("spec must be a GroundSpec")
        return self._dynamics_dispatch("ground", param_matrix,
                                       hamiltonian, spec, state_f, tier)

    # -- warm-start AOT hooks (serve/warmcache.py) -------------------------

    def _warm_form_key(self, kind: str, mode: str, tier=None) -> tuple:
        """The AOT form key shared by :meth:`lower_batched` (the store/
        install side) and the ``sweep``/``expectation_sweep`` dispatch
        lookups — one definition, so a key-shape edit cannot decouple
        install from lookup and silently turn every warm restart back
        into a full recompile. The ``sweep`` booleans pin the form the
        serving dispatcher uses: shared start state, not donated. The
        precision-tier token is part of the form, so a FAST-tier
        artifact (in-memory AOT slot or persistent WarmCache entry) is
        NEVER served to a request compiled at another tier — a tier
        mismatch is a miss, not a wrong program."""
        dtstr = str(np.dtype(self.env.precision.real_dtype))
        tok = self._tier_token(tier)
        if kind == "sweep":
            return ("sweep", True, False, mode, dtstr, tok)
        if kind == "energy":
            return ("energy", mode, dtstr, tok)
        if kind == "grad":
            return ("grad", mode, dtstr, tok)
        raise ValueError(f"unknown warm form kind {kind!r}")

    @staticmethod
    def _aot_key(form: tuple, args: tuple) -> tuple:
        return (form, tuple(getattr(a, "shape", None) for a in args))

    def _aot_lookup(self, form: tuple, args: tuple):
        """A warm-installed AOT executable for these EXACT concrete arg
        shapes, or None (any other shape rides the retracing jit
        wrapper). Tracers never match — transforms must trace the jit
        path."""
        if not self._batched_aot:
            return None
        if any(isinstance(a, jax.core.Tracer) for a in args):
            return None
        return self._batched_aot.get(self._aot_key(form, args))

    def install_batched_aot(self, form: tuple, args_shapes: tuple,
                            compiled) -> None:
        """Install one compiled batched executable (typically
        deserialized from the persistent warm cache) for an exact
        ``(form, arg shapes)`` slot. Bounded: warm() installs a handful
        of buckets; past 64 slots the oldest goes."""
        with self._stats_lock:
            self._batched_aot[(form, tuple(args_shapes))] = compiled
            while len(self._batched_aot) > 64:
                self._batched_aot.pop(next(iter(self._batched_aot)))

    def lower_batched(self, kind: str, batch: int, hamiltonian=None,
                      lower: bool = True, tier=None):
        """Lower (no compile, no execution) the batched executable one
        warm form would run: ``kind`` is ``"sweep"`` (broadcast start
        state — the serving dispatcher's state/sample form),
        ``"energy"``, or ``"grad"`` (the value-and-grad block — so
        gradient-heavy tenants restart warm too). Returns
        ``(form, args_shapes, lowered)`` ready for
        ``lowered.compile()`` + :meth:`install_batched_aot` — the warm
        cache serializes the compiled artifact so a restarted replica
        LOADS it instead of recompiling. ``lower=False`` computes only
        the ``(form, args_shapes)`` cache coordinates (no tracing) so a
        cache hit never pays the trace. Only the unsharded (``"none"``)
        batch mode lowers here: mesh modes carry input shardings that
        a deserialized executable would have to re-match exactly, and
        they are covered by the XLA disk-cache layer instead."""
        if batch < 1:
            raise ValueError("batch must be >= 1")
        tier = self._effective_tier(tier)
        mode = self._batch_policy(int(batch))["mode"]
        if mode != "none":
            raise ValueError(
                f"warm AOT lowering covers the unsharded batch mode; "
                f"batch {batch} chose {mode!r} on this mesh env")
        dt = self.env.precision.real_dtype
        n = self.num_qubits
        state = jax.ShapeDtypeStruct((2, 1 << n), dt)
        pm = jax.ShapeDtypeStruct((int(batch), len(self.param_names)), dt)
        if kind == "sweep":
            form = self._warm_form_key("sweep", mode, tier)
            args = (state, pm)
            fn_builder = lambda: self._batched_fn(True, False, mode, tier)
        elif kind == "energy":
            if hamiltonian is None:
                raise ValueError("kind='energy' needs hamiltonian=")
            _, _, xm, ym, zm, coeffs = self._pauli_operands(hamiltonian)
            xm, ym, zm = jnp.asarray(xm), jnp.asarray(ym), jnp.asarray(zm)
            cf = jnp.asarray(coeffs, dtype=dt)
            form = self._warm_form_key("energy", mode, tier)
            args = (state, pm,
                    jax.ShapeDtypeStruct(xm.shape, xm.dtype),
                    jax.ShapeDtypeStruct(ym.shape, ym.dtype),
                    jax.ShapeDtypeStruct(zm.shape, zm.dtype),
                    jax.ShapeDtypeStruct(cf.shape, cf.dtype))
            fn_builder = lambda: self._energy_fn(mode, tier)
        elif kind == "grad":
            if hamiltonian is None:
                raise ValueError("kind='grad' needs hamiltonian=")
            if not self.param_names:
                raise ValueError(
                    "kind='grad' needs a parameterised circuit (no "
                    "Param placeholders declared)")
            tier = self._grad_tier(tier)
            _, _, xm, ym, zm, coeffs = self._pauli_operands(hamiltonian)
            xm, ym, zm = jnp.asarray(xm), jnp.asarray(ym), jnp.asarray(zm)
            cf = jnp.asarray(coeffs, dtype=dt)
            form = self._warm_form_key("grad", mode, tier)
            args = (state, pm,
                    jax.ShapeDtypeStruct(xm.shape, xm.dtype),
                    jax.ShapeDtypeStruct(ym.shape, ym.dtype),
                    jax.ShapeDtypeStruct(zm.shape, zm.dtype),
                    jax.ShapeDtypeStruct(cf.shape, cf.dtype))
            fn_builder = lambda: self._grad_fn(mode, tier)
        else:
            raise ValueError(f"unknown warm form kind {kind!r}")
        shapes = tuple(a.shape for a in args)
        if not lower:
            return form, shapes, None
        return form, shapes, fn_builder().lower(*args)

    def sweep(self, param_matrix, state_f=None, tier=None):
        """Run a whole batch of parameter vectors through ONE executable.

        ``param_matrix``: ``(B, len(param_names))``. ``state_f``: either
        packed ``(2, 2^n)`` planes shared by every run (default |0..0>),
        or an OWNED ``(B, 2, 2^n)`` batch of planes — the batch form is
        DONATED to the executable (XLA reuses the buffer in place), so
        chained sweeps stream through one allocation. Returns ``(B, 2,
        2^n)`` packed planes.

        Fused Pallas layer runs stay active under the batch axis (the
        kernel grid grows a batch dimension); on a mesh env the batch
        axis shards per :func:`quest_tpu.parallel.layout.
        choose_batch_sharding` — batch-parallel while the per-device
        working set fits, amplitude-sharded past the memory wall — and
        non-divisible batches are padded and masked.

        ``tier`` runs this dispatch at one precision-tier rung
        (:class:`~quest_tpu.config.PrecisionTier` or name; default: the
        compile-time tier, else the env precision) — the serving layer
        passes per-request tiers against one compiled program, and each
        tier compiles and caches its OWN executable."""
        with dispatch_annotation("quest_tpu.circuits.prepare"):
            tier = self._effective_tier(tier)
            pm = self._validated_param_matrix(param_matrix)
            sp = _profile.profile_dispatch("circuits.sweep")
            poison = _faults.fire("circuits.sweep")
            n = self.num_qubits
            B = pm.shape[0]
            pol = self._batch_policy(B)
            mode = pol["mode"]
            pm_run, B = self._padded_params(pm, mode)
            pm_run = self._place_batch(pm_run, mode)
            # ONE annotation label for both dispatch branches (profiler
            # span names must group); annotations are built fresh per
            # entry — a TraceMe must not be re-entered after exit
            ann_name = (f"quest_tpu.circuits.sweep:b{pm_run.shape[0]}:"
                        f"{tier.name if tier is not None else 'env'}")
            # coerce BEFORE shape-dispatching: a nested list has no .ndim,
            # and a wrong-width or wrong-dtype shared state must fail here
            # with a shaped error, not deep inside the trace
            if state_f is not None:
                state_f = jnp.asarray(state_f,
                                      dtype=self.env.precision.real_dtype)
                if state_f.ndim not in (2, 3):
                    raise ValueError(
                        f"state_f must be shared (2, {1 << n}) planes or an "
                        f"owned (batch, 2, {1 << n}) batch; got shape "
                        f"{state_f.shape}")
                if state_f.ndim == 2 and state_f.shape != (2, 1 << n):
                    raise ValueError(
                        f"shared state_f must be (2, {1 << n}); got "
                        f"{state_f.shape}")
            if state_f is None:
                state_f = zero_state(n, self.env.precision.real_dtype)
        if state_f.ndim == 2:
            form = self._warm_form_key("sweep", mode, tier)
            aot = self._aot_lookup(form, (state_f, pm_run))
            out = None
            if aot is not None:
                try:
                    with dispatch_annotation(ann_name):
                        out = aot(state_f, pm_run)
                except (TypeError, ValueError):
                    out = None   # layout/placement drift: retrace via jit
            if out is None:
                with dispatch_annotation(ann_name):
                    out = self._batched_fn(True, False, mode,
                                           tier)(state_f, pm_run)
        else:
            planes = state_f
            if planes.shape != (B, 2, 1 << n):
                raise ValueError(
                    f"batched state_f must be ({B}, 2, {1 << n}); got "
                    f"{planes.shape}")
            if pm_run.shape[0] != B:
                planes = jnp.concatenate(
                    [planes, jnp.zeros((pm_run.shape[0] - B,) +
                                       planes.shape[1:], planes.dtype)])
            planes = self._place_batch(planes, mode, amp_shardable=True)
            with dispatch_annotation(ann_name):
                out = self._batched_fn(False, True, mode,
                                       tier)(planes, pm_run)
        self._record_batch_stats(B, mode, B - 1)
        if sp is not None:
            sp.done(out, program=self.program_digest, kind="sweep",
                    bucket=pm_run.shape[0],
                    tier=self._tier_token(tier),
                    dtype=str(np.dtype(self.env.precision.real_dtype)),
                    sharding=mode,
                    bytes_per_pass=self._bytes_per_pass(
                        pm_run.shape[0]),
                    models=self._drift_models(mode, pm_run.shape[0],
                                              pol))
        out = out[:B] if out.shape[0] != B else out
        out = _faults.poison_output(poison, out)
        return self._health_tick(
            out, is_density=self.is_density,
            num_qubits=(self.num_qubits // 2 if self.is_density
                        else self.num_qubits), where="sweep", tier=tier)

    def expectation_sweep(self, param_matrix, hamiltonian, state_f=None,
                          tier=None):
        """``(B,)`` energies ``<H>(params_b)`` from ONE executable and
        ONE device->host transfer.

        ``hamiltonian``: ``(pauli_terms, coeffs)`` exactly as
        :meth:`expectation_fn` takes them. Each point runs the compiled
        program from |0..0> (or ``state_f`` planes) and reduces the
        whole Pauli sum device-side (term-batched xor-gather kernels,
        :mod:`quest_tpu.ops.reductions`) — where a loop of ``run`` +
        ``calcExpecPauliSum`` pays at least one transfer per point (the
        reference pays one per TERM per point,
        ``QuEST_common.c:464-491``). Works on density-compiled circuits
        too: the value is ``Tr(H rho(params))`` through the program's
        channels. ``tier`` as in :meth:`sweep`; compensated tiers
        additionally run each Pauli term through the pair-path
        reduction."""
        with dispatch_annotation("quest_tpu.circuits.prepare"):
            tier = self._effective_tier(tier)
            nq, T, xm, ym, zm, coeffs = self._pauli_operands(hamiltonian)
            n = self.num_qubits

            pm = self._validated_param_matrix(param_matrix)
            sp = _profile.profile_dispatch("circuits.expectation_sweep")
            poison = _faults.fire("circuits.expectation_sweep")
            if poison == "precision":
                # energies carry no unit-norm invariant for any monitor to
                # check, so a drifted energy would be UNDETECTABLE silent
                # corruption — degrade the injected fault to the NaN form
                # the screens catch (same rule as the serving boundary)
                poison = "nan"
            B = pm.shape[0]
            pol = self._batch_policy(B)
            mode = pol["mode"]
            pm_run, B = self._padded_params(pm, mode)
            pm_run = self._place_batch(pm_run, mode)

            fn = self._energy_fn(mode, tier)
            if state_f is None:
                state_f = zero_state(n, self.env.precision.real_dtype)
            elif getattr(state_f, "shape", None) != (2, 1 << n):
                # the energy executable broadcasts ONE shared start state; a
                # (B, 2, 2^n) batch would silently mis-unpack deep in the
                # trace — reject it at the boundary
                raise ValueError(
                    f"expectation_sweep state_f must be shared (2, {1 << n}) "
                    f"planes; got {getattr(state_f, 'shape', None)} (run "
                    "batched planes through sweep(), then reduce)")
            args = (state_f, pm_run, jnp.asarray(xm), jnp.asarray(ym),
                    jnp.asarray(zm),
                    jnp.asarray(coeffs, dtype=self.env.precision.real_dtype))
            aot = self._aot_lookup(self._warm_form_key("energy", mode, tier),
                                   args)
        out = None
        ann_name = (f"quest_tpu.circuits.expectation_sweep:"
                    f"b{pm_run.shape[0]}:t{T}:"
                    f"{tier.name if tier is not None else 'env'}")
        if aot is not None:
            try:
                with dispatch_annotation(ann_name):
                    out = aot(*args)
            except (TypeError, ValueError):
                out = None     # layout/placement drift: retrace via jit
        if out is None:
            with dispatch_annotation(ann_name):
                out = fn(*args)
        # the engine-off path is B runs x (>= 1 sync per point; the
        # reference: one per term per point) — the engine's whole sweep
        # is one (B,) transfer
        self._record_batch_stats(B, mode, B * max(T, 1) - 1)
        if sp is not None:
            sp.done(out, program=self.program_digest, kind="energy",
                    bucket=pm_run.shape[0],
                    tier=self._tier_token(tier),
                    dtype=str(np.dtype(self.env.precision.real_dtype)),
                    sharding=mode,
                    bytes_per_pass=self._bytes_per_pass(
                        pm_run.shape[0], terms=T),
                    models=self._drift_models(mode, pm_run.shape[0],
                                              pol))
        out = out[:B] if out.shape[0] != B else out
        return _faults.poison_output(poison, out)

    def _grad_tier(self, tier):
        """Tier resolution for GRADIENT dispatches: the ladder applies
        (FAST/SINGLE/DOUBLE change only dtype and matmul precision, the
        reverse pass differentiates through them unchanged), but the
        QUAD rung's double-double walk is not a supported
        differentiation path — its per-op ``optimization_barrier`` +
        plane-splitting steps would need custom transpose rules; reject
        typed instead of silently falling to a lower rung. (Residual
        headroom: an SPSA fallback could serve quad gradients without
        differentiating the dd walk — ROADMAP open items.)"""
        tier = self._effective_tier(tier)
        if tier is not None and tier.name == "quad":
            raise ValueError(
                "gradient sweeps cannot run at the QUAD tier: the "
                "double-double engine walk is not differentiable "
                "(no transpose rules for the dd split/barrier steps); "
                "use tier='double' for the highest differentiable "
                "rung, or estimate quad gradients by parameter shift "
                "over expectation_sweep(tier='quad')")
        return tier

    def value_and_grad_sweep(self, param_matrix, hamiltonian,
                             state_f=None, tier=None):
        """``(B,)`` energies AND their ``(B, P)`` parameter gradients
        from ONE executable and ONE ``(B, P+1)`` device->host transfer.

        The variational fast path (ROADMAP item 1): where a client-side
        parameter-shift loop pays ``2P + 1`` energy evaluations per
        point — ``B * (2P + 1)`` executables and transfers for the
        sweep — this is ``jax.value_and_grad`` THROUGH the
        ``expectation_sweep`` trace, vmapped over the batch axis: one
        reverse pass per batch, one executable, one transfer.
        ``hamiltonian``/``state_f`` exactly as
        :meth:`expectation_sweep`. Works on density-compiled circuits
        (gradients of ``Tr(H rho)`` THROUGH the noise channels,
        including Param-bound channel rates — noise-model fitting by
        gradient at batch scale). ``tier`` as in :meth:`sweep`, except
        QUAD (rejected typed — the dd walk has no transpose rules).

        Returns ``(values, grads)``: ``(B,)`` and ``(B, P)`` arrays.
        """
        with dispatch_annotation("quest_tpu.circuits.prepare"):
            tier = self._grad_tier(tier)
            nparams = len(self.param_names)
            if nparams == 0:
                raise ValueError(
                    "this circuit declares no parameters; there is nothing "
                    "to differentiate (record angles via "
                    "Circuit.parameter / Param placeholders)")
            nq, T, xm, ym, zm, coeffs = self._pauli_operands(hamiltonian)
            n = self.num_qubits
            pm = self._validated_param_matrix(param_matrix)
            sp = _profile.profile_dispatch("circuits.grad_sweep")
            poison = _faults.fire("circuits.grad_sweep")
            if poison == "precision":
                # gradients carry no unit-norm invariant for a monitor to
                # check — degrade the injected drift to the NaN form the
                # row screens catch (same rule as expectation_sweep)
                poison = "nan"
            B = pm.shape[0]
            # reverse mode holds primal + cotangent planes: the memory wall
            # prices at 2x the forward sweep's working set
            pol = self._batch_policy(B, mem_factor=2.0)
            mode = pol["mode"]
            pm_run, B = self._padded_params(pm, mode)
            pm_run = self._place_batch(pm_run, mode)
            fn = self._grad_fn(mode, tier)
            if state_f is None:
                state_f = zero_state(n, self.env.precision.real_dtype)
            elif getattr(state_f, "shape", None) != (2, 1 << n):
                raise ValueError(
                    f"value_and_grad_sweep state_f must be shared "
                    f"(2, {1 << n}) planes; got "
                    f"{getattr(state_f, 'shape', None)}")
            args = (state_f, pm_run, jnp.asarray(xm), jnp.asarray(ym),
                    jnp.asarray(zm),
                    jnp.asarray(coeffs, dtype=self.env.precision.real_dtype))
            ann_name = (f"quest_tpu.circuits.grad_sweep:"
                        f"b{pm_run.shape[0]}:t{T}:"
                        f"{tier.name if tier is not None else 'env'}")
            aot = self._aot_lookup(self._warm_form_key("grad", mode, tier),
                                   args)
        out = None
        if aot is not None:
            try:
                with dispatch_annotation(ann_name):
                    out = aot(*args)
            except (TypeError, ValueError):
                out = None     # layout/placement drift: retrace via jit
        if out is None:
            with dispatch_annotation(ann_name):
                out = fn(*args)
        # the parameter-shift client pays (2P+1) energy dispatches per
        # row, each >= 1 transfer; the engine's whole (B, P) gradient
        # sweep is one (B, P+1) block
        self._record_batch_stats(B, mode, B * (2 * nparams + 1) - 1)
        if sp is not None:
            sp.done(out, program=self.program_digest, kind="gradient",
                    bucket=pm_run.shape[0],
                    tier=self._tier_token(tier),
                    dtype=str(np.dtype(self.env.precision.real_dtype)),
                    sharding=mode,
                    # forward + reverse each stream every planned pass
                    bytes_per_pass=2.0 * self._bytes_per_pass(
                        pm_run.shape[0], terms=T),
                    models=self._drift_models(mode, pm_run.shape[0],
                                              pol))
        out = out[:B] if out.shape[0] != B else out
        out = _faults.poison_output(poison, out)
        return out[:, 0], out[:, 1:]

    def grad_sweep(self, param_matrix, hamiltonian, state_f=None,
                   tier=None):
        """The ``(B, P)`` gradient block alone (one executable, one
        transfer — :meth:`value_and_grad_sweep` with the energies
        dropped; the values are computed by the same reverse pass
        either way, so there is no cheaper gradient-only form)."""
        return self.value_and_grad_sweep(param_matrix, hamiltonian,
                                         state_f=state_f, tier=tier)[1]

    def sample_sweep(self, param_matrix, num_shots: int, key=None,
                     tier=None):
        """Shot batches over a parameter sweep: run the batched program
        and draw ``num_shots`` basis outcomes per point (one vmapped
        sampling pass, :func:`quest_tpu.parallel.sampling.
        sample_batched`). Returns ``(indices, totals)``: an int64
        ``(B, num_shots)`` outcome array and the ``(B,)`` pre-sampling
        norms. Statevector-compiled circuits only."""
        if self.is_density:
            raise ValueError(
                "sample_sweep draws from |amp|^2 of statevector "
                "programs; sample density registers via sampleOutcomes")
        from .parallel.sampling import sample_batched
        planes = self.sweep(param_matrix, tier=tier)
        if key is None:
            key = self.env.next_key()
        idx, totals = sample_batched(planes, key, int(num_shots))
        with self._stats_lock:
            stats = dict(self._batch_stats or {})
            # the engine pays exactly two transfers (the (B, shots)
            # index block and the (B,) totals) where the per-point loop
            # pays 2B (one run + one sampling sync per point)
            stats["host_syncs_avoided"] = 2 * planes.shape[0] - 2
            self._batch_stats = stats
        return idx, totals

    def __repr__(self) -> str:
        return (f"CompiledCircuit(qubits={self.num_qubits}, "
                f"gates={len(self._ops)} (recorded {self.circuit.depth}), "
                f"params={list(self.param_names)}, "
                f"devices={self.env.num_devices})")
