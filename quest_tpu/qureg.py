"""The quantum register: one (shardable) flat jax.Array of amplitudes.

TPU-native replacement for the ``Qureg`` struct (``QuEST.h:161-192``): the
split real/imag malloc'd chunks plus ``pairStateVec`` collapse into a single
complex ``jax.Array`` that JAX shards over the environment mesh on its
leading (high-qubit) axis — the same chunkId-prefix layout as the reference's
MPI amplitude sharding, with no mirror buffer (XLA stages exchanges itself).

Density matrices reuse the statevector storage as a flat 2n-qubit vector
(``QuEST.c:8-10``); ``flat[r + c*2^n] = rho[r, c]``.

The object is a thin mutable handle (state is swapped, never mutated) so the
user-facing API can stay imperative like the reference while every kernel
underneath is pure.

Storage is a *float* array of shape ``(2, 2^N)`` — split real/imag planes,
like the reference's ``stateVec.real``/``stateVec.imag`` — because the TPU
PJRT backend forbids complex device buffers at executable boundaries (and the
split layout is the faster one regardless); see ``core/packing.py``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .core.packing import pack_host, unpack_host
from .env import QuESTEnv
from .qasm import QASMLogger

__all__ = ["Qureg"]


class Qureg:
    """A state-vector or density-matrix register bound to an environment."""

    def __init__(self, num_qubits: int, env: QuESTEnv, is_density: bool = False):
        self.env = env
        self.is_density_matrix = is_density
        self.num_qubits_represented = num_qubits
        self.num_qubits_in_state_vec = (2 * num_qubits) if is_density else num_qubits
        self.num_amps_total = 1 << self.num_qubits_in_state_vec
        self.qasm_log = QASMLogger(num_qubits)
        self._state: Optional[jax.Array] = None
        # lazy logical->physical qubit permutation over the state-vector
        # positions (None = identity). Maintained only by the sharded
        # per-gate path (parallel/pergate.py): swaps become metadata and
        # swap-to-local relayouts defer their swap-back until a reader
        # needs canonical order (ensure_canonical).
        self.layout: Optional[np.ndarray] = None
        # opt-in imperative gate fusion (api.startGateFusion): while
        # active, gate calls buffer here and flush — contracted through
        # core/fusion.py — at the first state read
        self._fusion_buffer = None

    # -- reference struct-field aliases (QuEST.h:161-192 spellings, used
    #    by the reference's own test drivers, e.g. createQureg.test) ------

    @property
    def isDensityMatrix(self) -> bool:
        return self.is_density_matrix

    @property
    def numQubitsRepresented(self) -> int:
        return self.num_qubits_represented

    @property
    def numQubitsInStateVec(self) -> int:
        return self.num_qubits_in_state_vec

    @property
    def numAmpsTotal(self) -> int:
        return self.num_amps_total

    # -- state plumbing ----------------------------------------------------

    @property
    def state(self) -> jax.Array:
        buf = self._fusion_buffer
        if buf is not None and buf.pending and not buf.flushing:
            buf.flush()     # every reader sees buffered gates applied
        return self._state

    @state.setter
    def state(self, new_state: jax.Array) -> None:
        buf = self._fusion_buffer
        if buf is not None and buf.pending and not buf.flushing:
            # a full overwrite supersedes pending gates (writers that
            # read-modify-write flushed at the read; the flush's own
            # writes are fenced by buf.flushing)
            buf.discard()
        self._state = new_state

    def flush_gates(self) -> None:
        """Apply any gates buffered by the opt-in imperative fusion path
        (``api.startGateFusion``). No-op otherwise."""
        buf = self._fusion_buffer
        if buf is not None:
            buf.flush()

    @property
    def dtype(self):
        """Logical (complex) dtype of the amplitudes."""
        return self.env.precision.complex_dtype

    @property
    def real_dtype(self):
        """Storage dtype of the split re/im planes."""
        return self.env.precision.real_dtype

    @property
    def is_quad(self) -> bool:
        """True for QUAD registers: (4, 2^N) double-double planes
        (``ops/doubledouble.py``), the QuEST_PREC=4 analogue."""
        return self.env.precision.quest_prec == 4

    def sharding(self):
        """Amplitude sharding for this register: the env mesh sharding, or
        None when the register has fewer amplitudes than the mesh has devices
        (a 1-qubit density register on an 8-device env stays replicated —
        the analogue of the reference's numRanks <= 2^n requirement,
        ``QuEST_cpu.c:1287``, relaxed to a fallback instead of an error)."""
        if self.num_amps_total < self.env.num_devices:
            return None
        return self.env.sharding()

    def sharding_flat(self):
        """Same decision for the flat (2^N,) jit-internal complex form."""
        if self.num_amps_total < self.env.num_devices:
            return None
        return self.env.sharding_flat()

    def device_put(self, host_array: np.ndarray) -> None:
        """Place a host complex array as the register state (packed to float
        planes), sharded over the mesh."""
        host_array = np.asarray(host_array)
        if host_array.shape != (self.num_amps_total,):
            raise ValueError(
                f"state array has shape {host_array.shape}; this register "
                f"holds {self.num_amps_total} amplitudes")
        buf = self._fusion_buffer
        if buf is not None and buf.pending and not buf.flushing:
            buf.discard()        # overwrite supersedes buffered gates,
        self.layout = None       # exactly like the state setter
        # full overwrite in canonical order
        if self.is_quad:
            from .ops.doubledouble import _dd_split_host
            arr = _dd_split_host(host_array, self.real_dtype)
        else:
            arr = pack_host(host_array, self.real_dtype)
        sharding = self.sharding()
        if sharding is not None and self.env.is_multihost:
            # multi-host: each process materialises only ITS addressable
            # shards from the (replicated) host array — the analogue of the
            # reference's per-rank chunk fill (QuEST_cpu.c:1284-1320); a
            # plain device_put of a global array is invalid across hosts
            self._state = jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
            return
        arr = jnp.asarray(arr)
        self._state = jax.device_put(arr, sharding) if sharding is not None else arr

    # -- convenience mirrors of the reference struct fields ---------------

    @property
    def num_amps_per_chunk(self) -> int:
        return self.num_amps_total // self.env.num_devices

    @property
    def num_chunks(self) -> int:
        return self.env.num_devices

    def ensure_canonical(self) -> None:
        """Restore the identity qubit layout (one batched exchange) so the
        raw state array can be read positionally. No-op off the sharded
        per-gate path. Drains the imperative fusion buffer first, so a
        compiled run or host read never races buffered gates."""
        self.flush_gates()
        if self.layout is not None:
            from .parallel.pergate import canonicalise
            canonicalise(self)

    def to_numpy(self) -> np.ndarray:
        """Gather the FULL state to host as a complex vector — debug/test
        seam ONLY: this is O(2^n) host memory and transfer. Use
        ``getAmp``/``getProbAmp`` (shard-local single-element reads) or
        ``calc*`` reductions in real programs. Transfers the float planes
        (complex transfers are unsupported on the TPU backend) and
        recombines host-side. Multi-host: shards on other processes are
        not addressable, so the state is allgathered first (every process
        must call this collectively, as with the reference's
        ``copyVecIntoMatrixPairState`` replication)."""
        self.ensure_canonical()
        if self.env.is_multihost and self.sharding() is not None:
            # replicated (unsharded) registers are already host-local;
            # only sharded states need the cross-process gather
            from jax.experimental import multihost_utils
            gathered = multihost_utils.process_allgather(self._state,
                                                         tiled=True)
            host = np.asarray(gathered)
        else:
            host = np.asarray(self._state)
        if self.is_quad:
            from .ops.doubledouble import dd_unpack
            return dd_unpack(host)
        return unpack_host(host)

    def density_matrix_numpy(self) -> np.ndarray:
        """rho[r, c] view of a density register (host-side)."""
        dim = 1 << self.num_qubits_represented
        return self.to_numpy().reshape(dim, dim).T

    def __repr__(self) -> str:
        kind = "density-matrix" if self.is_density_matrix else "state-vector"
        return (f"Qureg({kind}, qubits={self.num_qubits_represented}, "
                f"amps={self.num_amps_total}, dtype={self.dtype}, "
                f"devices={self.env.num_devices})")
