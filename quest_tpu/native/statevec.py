"""Native CPU statevector executor (``native/src/statevec_kernel.cc``).

The reference's CPU backend is native code driven one gate per library call
(`QuEST_cpu_local.c` dispatching into `QuEST_cpu.c` kernel bodies); this is
the framework's CPU analogue with the dispatch inverted: a recorded
:class:`~quest_tpu.circuits.Circuit` is lowered once to a flat descriptor
program (kind / targets / control masks / matrix table) and a single ctypes
call streams the state through every gate. Python never appears between
gates, so the executor runs at the memory roofline the reference's
hand-written loops set — and multithreads past it with ``threads>1``.

This path is CPU-only and single-device by design: on TPU the compiled XLA
program (`Circuit.compile`) is the fast path; here the same recorded circuit
gets a second, independent executor — which also makes it a cross-checking
oracle for the XLA path (both consume identical ``_Op`` streams).

Shared library is built on demand with g++ (same pattern as the scheduler);
``QUEST_TPU_NO_NATIVE=1`` disables it.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional

import numpy as np

from . import build_and_load, tagged_lib_path

__all__ = ["available", "load", "NativeProgram"]

# -march=native is safe here: the library is JIT-built by g++ on the
# machine it runs on (never shipped, and keyed by the host's ISA), and the
# pair loop's contiguous inner runs are written to auto-vectorize
_FLAGS = ("-O3", "-pthread", "-march=native")
_LIB_PATH = tagged_lib_path("libquest_statevec", "statevec_kernel.cc",
                            _FLAGS)
_lib: Optional[ctypes.CDLL] = None
_load_failed = False

_MAX_DENSE_QUBITS = 8
_MAX_DIAG_QUBITS = 16
_I32P = ctypes.POINTER(ctypes.c_int32)
_I64P = ctypes.POINTER(ctypes.c_int64)
_F64P = ctypes.POINTER(ctypes.c_double)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the executor library, or None."""
    global _lib, _load_failed
    if os.environ.get("QUEST_TPU_NO_NATIVE"):
        return None               # checked per call: unsetting re-enables
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    lib = build_and_load("statevec_kernel.cc", _LIB_PATH, _FLAGS)
    if lib is None:
        _load_failed = True
        return None
    lib.qtk_run_f64.restype = ctypes.c_int
    lib.qtk_run_f64.argtypes = [
        _F64P, _F64P, ctypes.c_int, ctypes.c_int,
        _I32P, _I32P, _I64P, _I64P, _I32P, _I32P, _I64P, _F64P,
        ctypes.c_int]
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def _default_threads() -> int:
    env = os.environ.get("QUEST_TPU_NATIVE_THREADS")
    if env:
        return max(1, int(env))
    return min(os.cpu_count() or 1, 16)


class NativeProgram:
    """A circuit lowered to the native executor's descriptor protocol.

    State is split float64 planes (re, im), bit ``q`` of the flat index =
    qubit ``q`` — numerically the reference's double-precision build.
    Parameterized gates are supported: their matrix slots are re-evaluated
    host-side per :meth:`run` (tiny 2^k matrices; the state pass dominates).
    """

    def __init__(self, circuit, threads: Optional[int] = None):
        lib = load()
        if lib is None:
            raise RuntimeError(
                "native statevector executor unavailable "
                "(g++ build failed or QUEST_TPU_NO_NATIVE set)")
        self._lib = lib
        self.num_qubits = circuit.num_qubits
        self.param_names = circuit.param_names
        self.threads = threads if threads is not None else _default_threads()

        kinds, ks, cmasks, fmasks = [], [], [], []
        t_off, targets_flat, m_off = [], [], []
        mats: list[np.ndarray] = []
        self._param_slots = []     # (mats_list_index, fn, kind, k)
        n_dbl = 0
        for op in circuit.ops:
            if op.kind == "u":
                nat_targets = list(op.targets)
                k = len(nat_targets)
                if k > _MAX_DENSE_QUBITS:
                    raise ValueError(
                        f"native executor caps dense gates at "
                        f"{_MAX_DENSE_QUBITS} qubits (got {k})")
                kinds.append(0)
            elif op.kind == "diag":
                # recorded targets are sorted descending and the tensor's
                # axes follow them; the executor wants bit j of the table
                # index = targets[j], which the C-order flattening gives
                # when targets are listed ascending
                nat_targets = list(reversed(op.targets))
                k = len(nat_targets)
                if k > _MAX_DIAG_QUBITS:
                    raise ValueError(
                        f"native executor caps diagonal ops at "
                        f"{_MAX_DIAG_QUBITS} qubits (got {k})")
                kinds.append(1)
            else:
                raise ValueError(
                    f"native executor supports unitary/diagonal ops only "
                    f"(got kind={op.kind!r}; compile channels with the XLA "
                    f"path)")
            ks.append(k)
            cmasks.append(op.ctrl_mask)
            fmasks.append(op.flip_mask)
            t_off.append(len(targets_flat))
            targets_flat.extend(nat_targets)
            m_off.append(n_dbl)
            count = (1 << k) ** 2 if op.kind == "u" else (1 << k)
            if op.is_static:
                data = op.mat if op.kind == "u" else op.diag
                flat = np.ascontiguousarray(
                    data, dtype=np.complex128).reshape(-1)
                mats.append(flat.view(np.float64))
            else:
                fn = op.mat_fn if op.kind == "u" else op.diag_fn
                mats.append(np.zeros(2 * count, dtype=np.float64))
                self._param_slots.append((len(mats) - 1, fn, count))
            n_dbl += 2 * count

        self.num_ops = len(kinds)
        self._kinds = np.asarray(kinds, dtype=np.int32)
        self._ks = np.asarray(ks, dtype=np.int32)
        self._cmasks = np.asarray(cmasks, dtype=np.int64)
        self._fmasks = np.asarray(fmasks, dtype=np.int64)
        self._t_off = np.asarray(t_off, dtype=np.int32)
        self._targets = np.asarray(targets_flat, dtype=np.int32)
        self._m_off = np.asarray(m_off, dtype=np.int64)
        self._mats = (np.concatenate(mats) if mats
                      else np.zeros(0, dtype=np.float64))

    # -- state helpers -----------------------------------------------------

    def init_zero(self) -> tuple[np.ndarray, np.ndarray]:
        re = np.zeros(1 << self.num_qubits, dtype=np.float64)
        im = np.zeros(1 << self.num_qubits, dtype=np.float64)
        re[0] = 1.0
        return re, im

    def init_plus(self) -> tuple[np.ndarray, np.ndarray]:
        amp = 1.0 / np.sqrt(1 << self.num_qubits)
        re = np.full(1 << self.num_qubits, amp, dtype=np.float64)
        return re, np.zeros(1 << self.num_qubits, dtype=np.float64)

    # -- execution ---------------------------------------------------------

    def _bind_params(self, params: Optional[dict]) -> None:
        if not self._param_slots:
            return
        params = params or {}
        missing = [p for p in self.param_names if p not in params]
        if missing:
            raise ValueError(f"missing circuit parameters: {missing}")
        for op_idx, fn, count in self._param_slots:
            data = np.asarray(fn(params), dtype=np.complex128)
            flat = np.ascontiguousarray(data).reshape(-1).view(np.float64)
            if flat.size != 2 * count:
                raise ValueError(
                    f"parameterized op {op_idx} produced "
                    f"{flat.size // 2} complex entries; its slot holds "
                    f"{count} (wrong matrix/tensor shape from the callable)")
            # m_off indexes doubles in the concatenated buffer; one mats
            # part per op, so op index and part index coincide
            self._mats[int(self._m_off[op_idx]):
                       int(self._m_off[op_idx]) + flat.size] = flat

    def run(self, re: np.ndarray, im: np.ndarray,
            params: Optional[dict] = None) -> None:
        """Apply the program in place to split f64 planes."""
        if re.shape != (1 << self.num_qubits,) or re.shape != im.shape:
            raise ValueError(
                f"state planes must each have shape "
                f"{(1 << self.num_qubits,)}; got {re.shape} / {im.shape}")
        if re.dtype != np.float64 or im.dtype != np.float64 \
                or not re.flags.c_contiguous or not im.flags.c_contiguous:
            raise ValueError("state planes must be contiguous float64")
        self._bind_params(params)
        rc = self._lib.qtk_run_f64(
            re.ctypes.data_as(_F64P), im.ctypes.data_as(_F64P),
            self.num_qubits, self.num_ops,
            self._kinds.ctypes.data_as(_I32P),
            self._ks.ctypes.data_as(_I32P),
            self._cmasks.ctypes.data_as(_I64P),
            self._fmasks.ctypes.data_as(_I64P),
            self._t_off.ctypes.data_as(_I32P),
            self._targets.ctypes.data_as(_I32P),
            self._m_off.ctypes.data_as(_I64P),
            self._mats.ctypes.data_as(_F64P),
            int(self.threads))
        if rc != 0:
            raise RuntimeError(f"native executor failed with code {rc}")

    def run_statevector(self, psi: np.ndarray,
                        params: Optional[dict] = None) -> np.ndarray:
        """Convenience: complex statevector in -> new complex statevector."""
        psi = np.asarray(psi, dtype=np.complex128).reshape(-1)
        re = np.ascontiguousarray(psi.real)
        im = np.ascontiguousarray(psi.imag)
        self.run(re, im, params)
        return re + 1j * im

    # -- observables (numpy reductions over the split planes) --------------

    @staticmethod
    def total_prob(re: np.ndarray, im: np.ndarray) -> float:
        return float(re @ re + im @ im)

    def prob_of_outcome(self, re: np.ndarray, im: np.ndarray,
                        qubit: int, outcome: int) -> float:
        """P(qubit = outcome) of the current planes."""
        if not 0 <= qubit < self.num_qubits:
            raise ValueError(f"qubit {qubit} outside register")
        if outcome not in (0, 1):
            raise ValueError(f"outcome must be 0 or 1, got {outcome}")
        n = self.num_qubits
        view = (re * re + im * im).reshape(
            1 << (n - qubit - 1), 2, 1 << qubit)
        return float(view[:, outcome, :].sum())

    def sample(self, re: np.ndarray, im: np.ndarray, num_samples: int,
               rng: Optional[np.random.Generator] = None) -> np.ndarray:
        """Draw basis indices from |amp|^2 (no collapse; numpy RNG)."""
        if num_samples < 1:
            raise ValueError("num_samples must be >= 1")
        rng = rng or np.random.default_rng()
        probs = re * re + im * im
        total = probs.sum()
        if total <= 0.0:
            raise ValueError("cannot sample a zero-probability state")
        return rng.choice(probs.size, size=num_samples, p=probs / total)
