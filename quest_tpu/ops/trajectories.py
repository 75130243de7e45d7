"""Quantum-trajectory (Monte-Carlo wavefunction) unraveling of noisy
circuits: channels applied stochastically to a STATEVECTOR.

The reference can simulate noise only on density matrices — 2^(2n)
amplitudes per register (``mixDamping`` etc. on the flattened vector,
``QuEST_common.c:540-604``). The trajectory method simulates the same
channel as an ensemble of 2^n-amplitude pure states: at each Kraus
channel, one operator ``K_j`` is drawn with the physical probability
``p_j = <psi| K_j^dag K_j |psi>`` and applied with renormalisation.
Averaging ``|psi><psi|`` over trajectories converges to the exact
density evolution at O(1/sqrt(T)) — exponentially cheaper per
trajectory, embarrassingly parallel across them.

TPU-native shape: the whole stochastic program is ONE jitted function of
``(state planes, PRNG key, param vector)`` — channel probabilities come
from a single state pass that builds the targets' 2^t x 2^t reduced
density matrix (every ``p_j`` is then a tiny trace against the
``E_j = K_j^dag K_j`` stack), the draw is a categorical over log
probabilities, and the chosen operator is applied by dynamic indexing
into the Kraus stack (``apply_unitary`` takes a traced matrix).

The TRAJECTORY axis is the batched engine's batch axis (ISSUE 10):

- :meth:`TrajectoryProgram.trajectory_sweep` runs ``T`` draws through
  one keyed, LRU-bounded executable (the engine's
  ``_BoundedExecutableCache``), with the mesh sharding mode priced by
  :func:`quest_tpu.parallel.layout.choose_batch_sharding` —
  trajectory-parallel (state replicated, keys split, zero collectives)
  while the per-device working set fits, amplitude-sharded past the
  memory wall — and non-divisible trajectory counts padded-and-masked
  with the engine's one-time warning instead of a hard error;
- :meth:`TrajectoryProgram.expectation` lowers Pauli-sum observables to
  the on-device xor-gather masks (:mod:`quest_tpu.ops.reductions`) and
  runs the ensemble in WAVES with a device-resident running
  (count, mean, M2) triple — one executable and ONE device->host
  transfer per wave, and convergence-based early stopping against a
  caller-stated ``sampling_budget`` (the target standard error);
- parameterized circuits are first-class: Param gates AND Param /
  callable-Kraus channels bind per call exactly like the deterministic
  sweep path, so noisy-VQE parameter sweeps run as ``(B, T)`` programs
  (:meth:`TrajectoryProgram.expectation_batch` — the serving runtime's
  ``kind="trajectory"`` dispatch).
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.apply import apply_unitary, apply_diagonal
from ..core.packing import pack, unpack
from ..telemetry import profile as _profile
from . import reductions as red

__all__ = ["TrajectoryProgram", "DensityMaterialisationError",
           "plan_waves", "DENSITY_DEBUG_QUBITS_ENV"]

DENSITY_DEBUG_QUBITS_ENV = "QUEST_TPU_DENSITY_DEBUG_QUBITS"
_DENSITY_DEBUG_DEFAULT = 14


class DensityMaterialisationError(ValueError):
    """``average_density`` was asked to materialise a 2^n x 2^n matrix
    past the debug-scale bound (``QUEST_TPU_DENSITY_DEBUG_QUBITS``,
    default 14). The scalable alternatives keep everything at
    statevector cost: :meth:`TrajectoryProgram.expectation` for
    observables, :meth:`TrajectoryProgram.trajectory_sweep` for the raw
    ensemble."""


def plan_waves(max_trajectories: int, wave_size: int,
               device_multiple: int = 1):
    """The wave schedule one convergence loop executes: a list of
    ``(start, live)`` slices of the up-front key array, every wave
    dispatched at the SAME padded bucket (``wave_size`` rounded up to
    ``device_multiple``) so the whole loop reuses one executable and
    padded rows are masked out of the statistics exactly. Host-side and
    pure — ``tools/traj_trace.py`` replays it offline."""
    if max_trajectories < 1:
        raise ValueError("max_trajectories must be >= 1")
    if wave_size < 1:
        raise ValueError("wave_size must be >= 1")
    mult = max(1, int(device_multiple))
    bucket = -(-int(wave_size) // mult) * mult
    waves = []
    start = 0
    while start < max_trajectories:
        live = min(bucket, max_trajectories - start)
        waves.append((start, live))
        start += live
    return waves, bucket


class TrajectoryProgram:
    """A recorded circuit lowered to a stochastic pure-state program.

    ``apply(state_f, key, params=None)`` is pure and jitted: packed
    float planes + PRNG key (+ bound parameters) -> packed planes.
    Unitary/diagonal ops apply as in the deterministic path; each Kraus
    channel consumes one ``fold_in`` of the key. Parameterized gates and
    channels (Param strengths, callable Kraus sets) bind at call time —
    one compiled program serves every binding. Batch with
    :meth:`trajectory_sweep` / :meth:`run_batch`; estimate observables
    with :meth:`expectation` (convergence-based early stopping).
    """

    tier = None          # trajectory dispatches run at the env precision
    is_density = False   # the point: pure states at statevector cost
    _digest_cached = None   # lazy program_digest (content-addressed)

    @property
    def program_digest(self) -> str:
        """Stable content digest of the recorded circuit (the perf
        ledger / dispatch-profiler key, shared with the deterministic
        compile path's :attr:`CompiledCircuit.program_digest`)."""
        if self._digest_cached is None:
            from ..serve.warmcache import circuit_digest
            d = circuit_digest(self.circuit, False)
            self._digest_cached = d or f"id-{id(self):x}"
        return self._digest_cached

    def __init__(self, circuit, env, pallas=None):
        self.env = env
        self.circuit = circuit
        self.num_qubits = circuit.num_qubits
        self.param_names = tuple(circuit.param_names)
        ops = []
        n_channels = 0
        # reuse the host-side peephole fusion every other compile path
        # gets; kraus and parameterized ops match neither fusion branch,
        # so they act as barriers and pass through untouched
        for op in circuit._fused_ops():
            if op.kind == "kraus":
                if callable(op.kraus):
                    # parameterized channel: the Kraus stack is built at
                    # bind time (traceable, jnp) — no CPTP validation is
                    # possible for a function (same contract as the
                    # density path); out-of-range bound strengths
                    # surface as NaN planes at run time
                    ops.append(("kraus_fn", op.targets, op.kraus,
                                n_channels))
                else:
                    from .. import validation as val
                    val.validate_kraus_ops(op.kraus, len(op.targets),
                                           "TrajectoryProgram",
                                           env.precision.eps)
                    stack = np.stack([np.asarray(k, dtype=np.complex128)
                                      for k in op.kraus])
                    # E_j = K_j^dag K_j, precomputed: channel
                    # probabilities then need only the reduced density
                    # of the targets
                    estack = np.einsum("kba,kbc->kac", stack.conj(),
                                       stack)
                    ops.append(("kraus", op.targets, (stack, estack),
                                n_channels))
                n_channels += 1
            elif op.kind == "u":
                data = op.mat_fn if op.mat_fn is not None else op.mat
                kind = "u_fn" if op.mat_fn is not None else "u"
                ops.append((kind, op.targets, data,
                            (op.ctrl_mask, op.flip_mask)))
            else:
                data = op.diag_fn if op.diag_fn is not None else op.diag
                kind = "diag_fn" if op.diag_fn is not None else "diag"
                ops.append((kind, op.targets, data, None))
        self._ops = ops
        self.num_channels = n_channels
        self._apply = jax.jit(self._apply_core)

        # batched-engine state: the keyed executable cache (same
        # LRU-bounded class and env knob as CompiledCircuit._batched
        # _cache), pad-and-mask warning latch, batch stats, and the
        # last convergence-loop accounting — all read/written under one
        # lock because the serving dispatcher drives this program from
        # its background thread while callers read dispatch_stats()
        from ..circuits import _BoundedExecutableCache
        self._cache = _BoundedExecutableCache(
            int(os.environ.get("QUEST_TPU_BATCH_CACHE", "16")))
        self._stats_lock = threading.RLock()
        self._batch_stats: Optional[dict] = None
        self._warned_nondivisible = False
        self._last_traj_stats: dict = {}
        self._empty_vec = None
        self._cost_model_cached = False
        self._cost_model = None
        self._host_bits = 0
        if env.mesh is not None and env.num_devices > 1:
            from ..parallel.multihost import host_topology
            topo = host_topology(env.mesh)
            shard_bits = env.num_devices.bit_length() - 1
            self._host_bits = min(topo.host_bits, shard_bits) if topo \
                else 0

        # Pallas layer path for the WAVE LOOP (ROADMAP item 4: "the
        # trajectory amp-mode wave loop has no Pallas layer path"):
        # static gate runs between channels fuse into LayerOps applied
        # by the batch-gridded layer kernel (one HBM pass per run,
        # whole wave at once), and an eligible static channel (all
        # targets on lane qubits) runs the FUSED per-trajectory Kraus
        # draw + apply + renorm kernel instead of the plain-XLA
        # categorical-draw -> stacked-operator-gather chain. Same knob
        # semantics as Circuit.compile (None = auto on TPU backends,
        # "interpret" for tests, False off); active only in the
        # unsharded ("none") dispatch mode — mesh modes keep the XLA
        # twin (GSPMD has no pallas_call partitioning rule), so the
        # cache key carries the path token. NOTE the fused kernel draws
        # by inverse-CDF from the key stream's uniform rather than the
        # XLA path's Gumbel categorical: statistically identical,
        # bitwise different — the pallas-on path is its own draw
        # stream.
        if pallas is None:
            pallas = os.environ.get("QUEST_TPU_PALLAS", "auto")
        interpret = pallas == "interpret"
        self._pallas_interpret = interpret
        enabled = pallas not in (False, "0", "off") and (
            interpret or jax.default_backend() == "tpu") \
            and self.num_qubits >= 7
        self._pallas_items = self._build_pallas_items() if enabled \
            else None

    def _build_pallas_items(self):
        """The layered item stream for the batched Pallas walker:
        ``("layer", LayerOp)`` for fused static runs, ``("kraus_fused",
        targets, (stack, estack, lane-embedded stack), idx)`` for
        channels the fused draw+apply kernel covers, the plain op
        tuples otherwise. Channel order (and so key fold-in indices)
        matches ``self._ops``."""
        from ..circuits import _collect_layers
        from . import pallas_kernels as pk
        n = self.num_qubits
        layered = _collect_layers(list(self.circuit._fused_ops()), n)
        kraus_tuples = [t for t in self._ops
                        if t[0] in ("kraus", "kraus_fn")]
        items = []
        ki = 0
        for op in layered:
            kind = getattr(op, "kind", None)
            if kind == "layer":
                items.append(("layer", op))
            elif kind == "kraus":
                t = kraus_tuples[ki]
                ki += 1
                if t[0] == "kraus" and all(
                        q < pk.LANE_QUBITS for q in t[1]):
                    stack, estack = t[2]
                    kemb = np.stack([pk.embed_lane_matrix(k, t[1])
                                     for k in stack])
                    items.append(("kraus_fused", t[1],
                                  (stack, estack, kemb), t[3]))
                else:
                    items.append(t)
            elif kind == "u":
                data = op.mat_fn if op.mat_fn is not None else op.mat
                items.append(("u_fn" if op.mat_fn is not None else "u",
                              op.targets, data,
                              (op.ctrl_mask, op.flip_mask)))
            else:
                data = op.diag_fn if op.diag_fn is not None else op.diag
                items.append(
                    ("diag_fn" if op.diag_fn is not None else "diag",
                     op.targets, data, None))
        return items

    # -- the per-trajectory program ----------------------------------------

    def _channel_probs(self, psi, targets, estack):
        """``p_j = <psi| E_j |psi> = tr(E_j rho_T)``: ONE state pass
        builds the 2^t x 2^t reduced density of the targets, then every
        probability is a tiny trace. HIGHEST: these feed the
        renormalisation, so the TPU bf16 matmul default would drift
        every trajectory's norm (same reason as core/apply.py)."""
        n = self.num_qubits
        k = len(targets)
        axes_front = [n - 1 - targets[j] for j in reversed(range(k))]
        rest = [ax for ax in range(n) if ax not in axes_front]
        a = jnp.transpose(psi.reshape((2,) * n),
                          axes_front + rest).reshape(1 << k, -1)
        rho_t = jnp.matmul(a, a.conj().T,
                           precision=jax.lax.Precision.HIGHEST)
        return jnp.real(jnp.einsum(
            "kab,ba->k", estack, rho_t,
            precision=jax.lax.Precision.HIGHEST))

    def _op_step(self, psi, key, params, op):
        """One op of the per-trajectory program on an UNPACKED complex
        state (shared by the single-trajectory jit and the batched XLA
        fallback's vmapped walker)."""
        return self._op_step_lp(psi, None, key, params, op)[0]

    def _op_step_lp(self, psi, logq, key, params, op):
        """:meth:`_op_step` with draw log-probability accounting: when
        ``logq`` is not None, every channel draw adds its NORMALISED
        log-probability ``log(p_j / sum_k p_k)`` to the running total —
        the measure term the gradient wave loop's score-function
        surrogate (:func:`quest_tpu.ops.reductions.score_surrogate`)
        needs for unbiased trajectory gradients. The drawn operator
        index and the state update are BITWISE the value path's (the
        categorical reads the same unnormalised log weights), so
        gradient waves replay the exact draw stream of the value
        waves under the same key."""
        n = self.num_qubits
        cdtype = self.env.precision.complex_dtype
        kind, targets, data, extra = op
        if kind in ("u", "u_fn"):
            cmask, fmask = extra
            u = data(params) if kind == "u_fn" else data
            return apply_unitary(psi, n, jnp.asarray(u, cdtype),
                                 targets, cmask, fmask), logq
        if kind in ("diag", "diag_fn"):
            d = data(params) if kind == "diag_fn" else data
            return apply_diagonal(psi, n, targets,
                                  jnp.asarray(d, cdtype)), logq
        if kind == "kraus_fn":
            kstack = jnp.stack(
                [jnp.asarray(m).astype(cdtype)
                 for m in data(params)])
            estack = jnp.einsum(
                "kba,kbc->kac", jnp.conj(kstack), kstack,
                precision=jax.lax.Precision.HIGHEST)
        else:
            kstack = jnp.asarray(data[0], cdtype)
            estack = jnp.asarray(data[1], cdtype)
        sub = jax.random.fold_in(key, extra)
        probs = self._channel_probs(psi, targets, estack)
        # categorical draw over the physical channel probs
        # (log space; zero-prob branches get ~-inf)
        tiny = jnp.finfo(probs.dtype).tiny
        logp = jnp.log(jnp.maximum(probs, tiny))
        j = jax.random.categorical(sub, logp)
        if logq is not None:
            logq = logq + logp[j] - jnp.log(
                jnp.maximum(jnp.sum(probs), tiny))
        psi = apply_unitary(psi, n, kstack[j], targets)
        return psi * jax.lax.rsqrt(
            jnp.maximum(probs[j], tiny)).astype(psi.dtype), logq

    def _apply_core(self, state_f, key, param_vec=None):
        if param_vec is None:
            params = {}
        else:
            params = {nm: param_vec[i]
                      for i, nm in enumerate(self.param_names)}
        psi = unpack(state_f)
        for op in self._ops:
            if op[0] == "kraus_fused":
                op = ("kraus", op[1], op[2][:2], op[3])
            psi = self._op_step(psi, key, params, op)
        return pack(psi)

    def _apply_core_lp(self, state_f, key, param_vec):
        """The gradient walker's form of :meth:`_apply_core`: returns
        the UNPACKED final state plus the accumulated draw
        log-probability (the score-surrogate's measure term). Same op
        order, same key folds — the draw stream is the value path's."""
        params = {nm: param_vec[i]
                  for i, nm in enumerate(self.param_names)}
        psi = unpack(state_f)
        logq = jnp.zeros((), dtype=self.env.precision.real_dtype)
        for op in self._ops:
            psi, logq = self._op_step_lp(psi, logq, key, params, op)
        return psi, logq

    def _apply_batch(self, state_f, keys, flat_pv):
        """The PALLAS wave-loop walker: the whole trajectory batch
        advances item by item — fused static runs through the
        batch-gridded layer kernel (:func:`quest_tpu.ops.
        pallas_kernels.apply_layer_batched`, one HBM pass per run for
        the WHOLE wave), eligible channels through the fused
        draw+apply+renorm Kraus kernel, everything else through the
        vmapped XLA step. Returns the ``(T, 2^n)`` complex batch."""
        from . import pallas_kernels as pk
        n = self.num_qubits
        T = keys.shape[0]
        psi0 = unpack(state_f)
        states = jnp.broadcast_to(psi0, (T,) + psi0.shape)
        interp = self._pallas_interpret
        for op in self._pallas_items:
            kind = op[0]
            if kind == "layer":
                states = pk.apply_layer_batched(states, n, op[1],
                                                interpret=interp)
                continue
            if kind == "kraus_fused":
                _, targets, (stack, estack, kemb), idx = op
                cdtype = self.env.precision.complex_dtype
                es = jnp.asarray(estack, cdtype)
                probs = jax.vmap(
                    lambda s: self._channel_probs(s, targets, es))(
                    states)
                subs = jax.vmap(
                    lambda k: jax.random.fold_in(k, idx))(keys)
                u01 = jax.vmap(
                    lambda k: jax.random.uniform(
                        k, dtype=probs.dtype))(subs)
                states = pk.fused_kraus_apply_batched(
                    states, n, kemb, probs, u01, interpret=interp)
                continue

            def step(s, k, vec, _op=op):
                params = {nm: vec[i]
                          for i, nm in enumerate(self.param_names)}
                return self._op_step(s, k, params, _op)

            states = jax.vmap(step)(states, keys, flat_pv)
        return states

    # -- parameters / operands ---------------------------------------------

    def _param_vec(self, params):
        """Name->angle dict (or ordered vector) -> the program's
        parameter vector; all declared names must bind (mirrors
        ``CompiledCircuit._param_vec``)."""
        if params is not None and not isinstance(params, dict):
            vec = jnp.asarray(params,
                              dtype=self.env.precision.real_dtype)
            if vec.shape != (len(self.param_names),):
                raise ValueError(
                    f"parameter vector has shape {vec.shape}; expected "
                    f"({len(self.param_names)},) ordered like "
                    f"{list(self.param_names)}")
            return vec
        params = params or {}
        missing = [p for p in self.param_names if p not in params]
        if missing:
            raise ValueError(f"missing circuit parameters: {missing}")
        vals = [params[nm] for nm in self.param_names]
        if not vals:
            if self._empty_vec is None:
                self._empty_vec = jnp.zeros(
                    (0,), dtype=self.env.precision.real_dtype)
            return self._empty_vec
        return jnp.asarray(vals, dtype=self.env.precision.real_dtype)

    def _validated_pauli_terms(self, pauli_terms, coeffs):
        """The serving runtime's Hamiltonian validation hook (same
        shape as ``CompiledCircuit._validated_pauli_terms``)."""
        nq = self.num_qubits
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

    def _pauli_operands(self, terms, coeffs):
        """Validated terms -> bucketed on-device mask operands (the
        PR-3 xor-gather encoding, :func:`quest_tpu.ops.reductions.
        pauli_sum_operands`)."""
        nq = self.num_qubits
        T = len(terms)
        codes = np.zeros((max(T, 1), nq), np.int64)
        for t, term in enumerate(terms):
            for q, code in term:
                if codes[t, q]:
                    raise ValueError(
                        f"pauli term {t} repeats qubit {q} (a product "
                        "of Paulis on one qubit is not a Pauli string)")
                codes[t, q] = code
        coeffs = np.asarray(coeffs, dtype=np.float64)
        if T == 0:
            coeffs = np.zeros((1,), np.float64)
        xm, ym, zm, cf = red.pauli_sum_operands(
            codes.reshape(-1), nq, coeffs)
        return T, xm, ym, zm, cf

    # -- sharding policy ----------------------------------------------------

    def _comm_model(self):
        if not self._cost_model_cached:
            from ..profiling import comm_model
            self._cost_model = comm_model(self.env) \
                if self.env.mesh is not None else None
            self._cost_model_cached = True
        return self._cost_model

    def _policy(self, batch: int, mem_factor: float = 1.0) -> dict:
        """The priced sharding decision for a ``batch``-trajectory wave
        (:func:`quest_tpu.parallel.layout.choose_batch_sharding`):
        trajectory-parallel while the replicated working set fits,
        amplitude-sharded past the wall, with the amp fallback's
        per-trajectory collectives counted by
        :func:`~quest_tpu.parallel.layout.traj_cross_shard_ops`.
        ``mem_factor=2.0`` is the gradient wave loop's pricing
        (primal + cotangent live together through the reverse walk)."""
        if self.env.mesh is None or self.env.num_devices < 2:
            return {"mode": "none"}
        from ..parallel.layout import (choose_batch_sharding,
                                       traj_cross_shard_ops)
        paired = [targets for kind, targets, _, _ in self._ops
                  if not kind.startswith("diag")]
        est = traj_cross_shard_ops(paired, self.num_qubits,
                                   self.env.num_devices)
        return choose_batch_sharding(
            self.num_qubits, batch, self.env.num_devices,
            np.dtype(self.env.precision.real_dtype).itemsize, est,
            cost_model=self._comm_model(), host_bits=self._host_bits,
            mem_factor=mem_factor)

    def _device_multiple(self) -> int:
        return self.env.num_devices if (
            self.env.mesh is not None and self.env.num_devices > 1) else 1

    def _resolve_mode(self, batch: int, shard_trajectories,
                      mem_factor: float = 1.0) -> str:
        """``shard_trajectories``: None -> the priced policy; True ->
        force trajectory-parallel (mesh required); False -> force
        unsharded."""
        if shard_trajectories is True:
            if self.env.mesh is None or self.env.num_devices < 2:
                raise ValueError(
                    "shard_trajectories needs a multi-device mesh env")
            return "batch"
        if shard_trajectories is False:
            return "none"
        return self._policy(batch, mem_factor=mem_factor)["mode"]

    def _padded_keys(self, key, num: int, mode: str):
        """Split ``num`` per-trajectory keys and pad to the device
        multiple in trajectory-parallel mode. The first ``num`` keys are
        ALWAYS ``split(key, num)`` — padding duplicates ``keys[0]`` into
        throwaway rows rather than changing the split width, so results
        are bit-identical across modes and pad amounts. One-time
        warning, matching the engine's sweep behaviour."""
        keys = jax.random.split(key, num)
        pad = 0
        if mode == "batch":
            D = self.env.num_devices
            pad = (-num) % D
            if pad:
                with self._stats_lock:
                    warn_now = not self._warned_nondivisible
                    self._warned_nondivisible = True
                if warn_now:
                    warnings.warn(
                        f"trajectory batch of {num} is not divisible by "
                        f"the {D}-device mesh; padding to {num + pad} "
                        f"and masking the {pad} extra draws (earlier "
                        "releases rejected the batch outright)",
                        UserWarning, stacklevel=4)
                keys = jnp.concatenate([keys] + [keys[:1]] * pad)
        return keys, pad

    def _place(self, state_f, keys, mode: str):
        """Commit the wave inputs to the policy's layout so the
        executable starts from the right placement: trajectory-parallel
        splits the KEYS (state replicated), amp mode splits the
        amplitude axis of the shared state (keys replicated)."""
        if mode == "none" or self.env.mesh is None:
            return state_f, keys
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..env import AMP_AXIS
        mesh = self.env.mesh
        if mode == "batch":
            keys = jax.device_put(keys, NamedSharding(mesh, P(AMP_AXIS)))
            state_f = jax.device_put(state_f, NamedSharding(mesh, P()))
        else:
            state_f = jax.device_put(
                state_f, NamedSharding(mesh, P(None, AMP_AXIS)))
        return state_f, keys

    def _out_constraint(self, mode: str, ndim: int = 3):
        """The sharding constraint pinned on a batched executable's
        (T, 2, 2^n) output (leading-axis split in trajectory-parallel
        mode, amplitude-axis split in amp mode)."""
        if mode == "none" or self.env.mesh is None:
            return lambda z: z
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..env import AMP_AXIS
        spec = [None] * ndim
        spec[0 if mode == "batch" else ndim - 1] = AMP_AXIS
        sh = NamedSharding(self.env.mesh, P(*spec))
        return lambda z: jax.lax.with_sharding_constraint(z, sh)

    def _record_batch_stats(self, batch: int, mode: str,
                            host_syncs_avoided: int) -> None:
        with self._stats_lock:
            self._batch_stats = {"batch_size": batch,
                                 "batch_sharding_mode": mode,
                                 "host_syncs_avoided": host_syncs_avoided}

    # -- batched executables (keyed, LRU-bounded) ---------------------------

    def _dt_token(self) -> str:
        return str(np.dtype(self.env.precision.real_dtype))

    def _cached(self, key, builder):
        with self._stats_lock:
            fn = self._cache.get(key)
        if fn is not None:
            return fn
        fn = builder()
        with self._stats_lock:
            # quest: allow-cache-key(the key is built at the _cached()
            # call sites, which the QL002 rule checks individually --
            # trajectory keys carry form+mode+dtype+kernel-path (the
            # pallas/xla token: the two paths trace different programs);
            # the tier ladder is rejected at the trajectory submit
            # boundary, so no tier)
            self._cache[key] = fn
        return fn

    def _use_pallas(self, mode: str) -> bool:
        """The Pallas layer path runs in the unsharded mode only: mesh
        modes dispatch under GSPMD, which has no ``pallas_call``
        partitioning rule (it would replicate the wave exactly where a
        mesh mode was chosen for memory)."""
        return self._pallas_items is not None and mode == "none"

    def _path_token(self, mode: str) -> str:
        return "pallas" if self._use_pallas(mode) else "xla"

    def _sweep_fn(self, mode: str):
        """The trajectory-sweep executable for one sharding mode:
        vmapped draws over the key axis (or the batched Pallas walker
        in the unsharded mode), output pinned to the policy's layout."""
        constrain = self._out_constraint(mode)
        use_p = self._use_pallas(mode)

        def build():
            if use_p:
                def fn(state_f, keys, pv):
                    flat_pv = jnp.broadcast_to(
                        pv, (keys.shape[0],) + pv.shape)
                    z = self._apply_batch(state_f, keys, flat_pv)
                    out = jnp.stack([jnp.real(z), jnp.imag(z)], axis=1)
                    return constrain(out)
            else:
                def fn(state_f, keys, pv):
                    out = jax.vmap(
                        lambda k: self._apply_core(state_f, k, pv))(keys)
                    return constrain(out)
            return jax.jit(fn)

        return self._cached(("tsweep", mode, self._dt_token(),
                             self._path_token(mode)), build)

    def _wave_fn(self, mode: str):
        """One convergence-loop wave for the ``(B, W)`` request-batch
        form (``B = 1`` is the single-ensemble path): run B*W draws
        (row b binds parameter row b), lower the Pauli sum to the
        on-device masks, fold the wave into the device-resident running
        (count, mean, M2) rows. ONE executable, and the returned
        ``(3, B)`` carry is the only device->host transfer the stop
        decision needs."""
        constrain = self._out_constraint(mode)
        use_p = self._use_pallas(mode)
        rdt = jnp.float64 if np.dtype(
            self.env.precision.real_dtype) == np.float64 else jnp.float32

        def build():
            def fn(state_f, flat_keys, pm, mask, xm, ym, zm, cf, carry):
                B = pm.shape[0]
                W = flat_keys.shape[0] // B
                flat_pv = jnp.repeat(pm, W, axis=0)
                if use_p:
                    z = self._apply_batch(state_f, flat_keys, flat_pv)
                else:
                    planes = jax.vmap(
                        lambda k, pv_: self._apply_core(
                            state_f, k, pv_))(flat_keys, flat_pv)
                    planes = constrain(planes)
                    z = jax.lax.complex(planes[:, 0], planes[:, 1])
                vals = jax.vmap(lambda s: red.pauli_sum_total_sv(
                    s, xm, ym, zm, cf))(z)
                vals = vals.reshape(B, W).astype(rdt)
                n_w, m_w, s_w = red.welford_wave(vals, mask)
                n, m, s = red.welford_merge(
                    (carry[0], carry[1], carry[2]), (n_w, m_w, s_w))
                return jnp.stack([n, m, s])
            return jax.jit(fn, donate_argnums=(8,))
        return self._cached(("twave", mode, self._dt_token(),
                             self._path_token(mode)), build)

    def _grad_wave_fn(self, mode: str):
        """One GRADIENT wave for the ``(B, W)`` form: every trajectory
        is differentiated by ``jax.value_and_grad`` through the
        score-function surrogate (:func:`quest_tpu.ops.reductions.
        score_surrogate` — pathwise + measure term, so the wave mean
        is an unbiased estimate of the density-path gradient), and the
        per-trajectory ``(P + 1)``-component (value, grad...) rows fold
        into a device-resident ``(3, B, P+1)`` Welford carry. ONE
        executable per wave, the carry its only transfer — noisy-VQE
        gradients ride the same early-stopping machinery as values.
        Always the vmapped XLA walker (``jax.grad`` has no rule for a
        compiled ``pallas_call``), so the kernel-path token is pinned
        ``"xla"``."""
        # the (B*W, P+1) value rows split on the trajectory axis in
        # batch mode; in amp mode they are tiny per-trajectory scalars
        # (the STATE carries the sharding) — no constraint
        constrain = self._out_constraint(mode, ndim=2) \
            if mode == "batch" else (lambda z: z)
        rdt = jnp.float64 if np.dtype(
            self.env.precision.real_dtype) == np.float64 else jnp.float32

        def build():
            def fn(state_f, flat_keys, pm, mask, xm, ym, zm, cf, carry):
                B = pm.shape[0]
                W = flat_keys.shape[0] // B
                flat_pv = jnp.repeat(pm, W, axis=0)
                # REINFORCE baseline: the running mean VALUE of each
                # row's earlier waves (carry mean column 0; zero on the
                # first wave, where count is 0 and the mean row is the
                # init zeros). Independent of this wave's draws, so the
                # score term stays unbiased while its v-weights centre
                # — the variance-reduction satellite of ISSUE 18.
                flat_bl = jnp.repeat(
                    jax.lax.stop_gradient(carry[1][:, 0]), W)

                def one(k, vec, bl):
                    def surrogate(v):
                        psi, logq = self._apply_core_lp(state_f, k, v)
                        val = red.pauli_sum_total_sv(psi, xm, ym, zm,
                                                     cf)
                        return red.score_surrogate(
                            val, logq.astype(val.dtype),
                            baseline=bl.astype(val.dtype)), val

                    (_, val), g = jax.value_and_grad(
                        surrogate, has_aux=True)(vec)
                    return jnp.concatenate(
                        [jnp.reshape(val, (1,)).astype(g.dtype), g])

                vals = jax.vmap(one)(flat_keys, flat_pv,
                                     flat_bl)  # (B*W, P+1)
                vals = constrain(vals)
                C = vals.shape[1]
                vals = vals.reshape(B, W, C).transpose(0, 2, 1)
                n_w, m_w, s_w = red.welford_wave(vals.astype(rdt), mask)
                n, m, s = red.welford_merge(
                    (carry[0], carry[1], carry[2]), (n_w, m_w, s_w))
                return jnp.stack([n, m, s])
            return jax.jit(fn, donate_argnums=(8,))
        return self._cached(("tgradwave", mode, self._dt_token(),
                             "xla"), build)

    # -- execution ---------------------------------------------------------

    def apply(self, state_f, key, params=None):
        """Pure form: packed planes + key -> packed planes (one draw).
        ``params`` binds the circuit's Param gates/channels."""
        return self._apply(state_f, key, self._param_vec(params))

    def run(self, qureg, key: Optional[jax.Array] = None,
            params=None) -> None:
        """One trajectory in place on a statevector register; the env RNG
        stream advances when ``key`` is not given."""
        if qureg.is_density_matrix:
            raise ValueError("trajectory programs run on statevector "
                             "registers (that is the point)")
        if qureg.num_qubits_represented != self.num_qubits:
            raise ValueError(
                f"program has {self.num_qubits} qubits; register has "
                f"{qureg.num_qubits_represented}")
        pv = self._param_vec(params)
        if key is None:
            key = self.env.next_key()
        qureg.ensure_canonical()   # the program addresses canonical bits
        qureg.state = self._apply(qureg.state, key, pv)

    def _default_state(self):
        return jnp.zeros((2, 1 << self.num_qubits),
                         dtype=self.env.precision.real_dtype
                         ).at[0, 0].set(1.0)

    def trajectory_sweep(self, num_trajectories: int, params=None,
                         state_f=None, key: Optional[jax.Array] = None,
                         shard_trajectories: Optional[bool] = None):
        """``num_trajectories`` independent draws from one initial packed
        state — a ``(T, 2, 2^n)`` batch through ONE keyed executable
        (the engine's batch axis; ``dispatch_stats()`` carries the
        batch accounting).

        On a mesh env the trajectory axis shards per the priced policy
        (:meth:`_policy`): trajectory-parallel (state replicated, keys
        split — noise unraveling is embarrassingly parallel, throughput
        scales linearly with mesh size) while the per-device working
        set fits, amplitude-sharded past the memory wall so big-n
        ensembles still run. Results are bit-identical across modes —
        the key array, not the placement, decides every draw — and
        non-divisible counts pad-and-mask with a one-time warning.
        ``shard_trajectories`` overrides the policy (True forces
        trajectory-parallel, False forces unsharded).

        One caveat: with the Pallas wave path on (``pallas=`` at
        compile), the UNSHARDED mode's fused Kraus kernel draws by
        inverse-CDF where the XLA twin draws categorically — the two
        KERNEL paths are separate (statistically identical) draw
        streams, so cross-mode bit-identity holds within a kernel path,
        not across the pallas/xla boundary."""
        T = int(num_trajectories)
        if T < 1:
            raise ValueError("num_trajectories must be >= 1")
        mode = self._resolve_mode(T, shard_trajectories)
        pv = self._param_vec(params)
        if key is None:
            key = self.env.next_key()
        if state_f is None:
            state_f = self._default_state()
        keys, pad = self._padded_keys(key, T, mode)
        state_f, keys = self._place(state_f, keys, mode)
        out = self._sweep_fn(mode)(state_f, keys, pv)
        self._record_batch_stats(T, mode, T - 1)
        return out[:T] if pad else out

    def run_batch(self, state_f, num_trajectories: int,
                  key: Optional[jax.Array] = None,
                  shard_trajectories: Optional[bool] = None,
                  params=None):
        """Pre-engine spelling of :meth:`trajectory_sweep` (state first,
        policy-driven sharding by default)."""
        return self.trajectory_sweep(num_trajectories, params=params,
                                     state_f=state_f, key=key,
                                     shard_trajectories=shard_trajectories)

    # -- observables with convergence-based early stopping ------------------

    def _default_wave(self, max_trajectories: int) -> int:
        return min(int(max_trajectories),
                   max(32, self._device_multiple()))

    def expectation(self, pauli_terms, coeffs, state_f=None,
                    num_trajectories: int = None,
                    key: Optional[jax.Array] = None, *, params=None,
                    sampling_budget: Optional[float] = None,
                    wave_size: Optional[int] = None,
                    shard_trajectories: Optional[bool] = None
                    ) -> tuple[float, float]:
        """Monte-Carlo estimate of ``<H>`` under the noisy evolution,
        ``H = sum_j coeffs[j] * prod Pauli`` (terms as ``(qubit, code)``
        pairs, codes 1=X 2=Y 3=Z). Returns ``(mean, stderr)`` over the
        trajectory ensemble — the noisy-VQE objective at statevector
        cost.

        The ensemble runs in WAVES of ``wave_size`` draws (default
        ``max(32, device count)``), each wave ONE executable whose
        Pauli sum lowers to the PR-3 on-device bit masks and whose
        running (count, mean, M2) stays device-resident — one
        device->host transfer per wave, never one per trajectory.
        ``sampling_budget`` (target standard error of the mean) turns
        on convergence-based early stopping: the loop stops at the
        first wave whose standard error fits the budget, so typical
        requests execute a fraction of ``num_trajectories``. The stop
        decision is a pure function of the seeded key stream —
        identical results on every replay. The accounting
        (``trajectories_run``, ``early_stopped``, waves, stderr) lands
        in :attr:`last_traj_stats` and the serving metrics."""
        from .. import validation as val
        if num_trajectories is None or int(num_trajectories) < 2:
            raise ValueError("expectation needs >= 2 trajectories for a "
                             "standard error")
        if sampling_budget is not None and sampling_budget <= 0.0:
            raise ValueError("sampling_budget is a target standard "
                             "error and must be > 0")
        T = int(num_trajectories)
        terms = []
        for t in pauli_terms:
            term = tuple((int(q), int(code)) for q, code in t)
            for q, code in term:
                val.validate_target(self.num_qubits, q,
                                    "TrajectoryProgram.expectation")
            val.validate_pauli_codes([code for _, code in term],
                                     "TrajectoryProgram.expectation")
            terms.append(term)
        coeffs = [float(c) for c in coeffs]
        if state_f is None:
            state_f = self._default_state()
        pm = jnp.reshape(self._param_vec(params),
                         (1, len(self.param_names)))
        mean, err, info = self._converge(
            pm, terms, coeffs, state_f, T, key,
            sampling_budget=sampling_budget, wave_size=wave_size,
            shard_trajectories=shard_trajectories)
        return float(mean[0]), float(err[0])

    def expectation_batch(self, param_matrix, hamiltonian,
                          num_trajectories: int,
                          key: Optional[jax.Array] = None, *,
                          sampling_budget: Optional[float] = None,
                          wave_size: Optional[int] = None,
                          live_rows: Optional[int] = None,
                          state_f=None, progress=None):
        """The ``(B, T)`` form: one noisy-VQE ensemble per parameter
        row, all rows advancing through shared waves of one executable
        (the serving runtime's ``kind="trajectory"`` dispatch). Early
        stopping waits for EVERY live row's standard error to fit the
        budget (``live_rows`` excludes the coalescer's padded rows from
        the decision). Returns ``(means, stderrs, info)`` with ``(B,)``
        arrays."""
        pm = jnp.asarray(param_matrix,
                         dtype=self.env.precision.real_dtype)
        if pm.ndim != 2 or pm.shape[1] != len(self.param_names):
            raise ValueError(
                f"param_matrix must be (batch, {len(self.param_names)}); "
                f"got {pm.shape}")
        if int(num_trajectories) < 2:
            raise ValueError("expectation needs >= 2 trajectories for a "
                             "standard error")
        terms_in, coeffs_in = hamiltonian
        _, terms, coeffs = self._validated_pauli_terms(terms_in,
                                                       coeffs_in)
        if state_f is None:
            state_f = self._default_state()
        means, errs, info = self._converge(
            pm, terms, [float(c) for c in coeffs], state_f,
            int(num_trajectories), key,
            sampling_budget=sampling_budget, wave_size=wave_size,
            live_rows=live_rows, progress=progress)
        return means, errs, info

    def expectation_grad(self, pauli_terms, coeffs, state_f=None,
                         num_trajectories: int = None,
                         key: Optional[jax.Array] = None, *,
                         params=None,
                         sampling_budget: Optional[float] = None,
                         wave_size: Optional[int] = None,
                         shard_trajectories: Optional[bool] = None):
        """Monte-Carlo estimate of ``<H>`` AND its parameter gradient
        under the noisy evolution — the noisy-VQE objective and its
        derivative from ONE wave loop. Returns ``(value, grad,
        stderr)``: the scalar energy, the ``(P,)`` gradient, and the
        ``(P + 1,)`` standard errors (component 0 the value's).

        Each trajectory differentiates through the stochastic trace
        with the score-function correction
        (:func:`quest_tpu.ops.reductions.score_surrogate`), so the
        ensemble mean converges to the DENSITY-path gradient — channel
        draws are parameter-dependent measures, and the pathwise
        derivative alone would be biased. Early stopping
        (``sampling_budget``) waits for EVERY component's standard
        error to fit, and the stop decision is a pure function of the
        seeded key stream — identical on every replay, sharing the
        value loop's per-row streams."""
        if num_trajectories is None or int(num_trajectories) < 2:
            raise ValueError("expectation_grad needs >= 2 trajectories "
                             "for a standard error")
        if not self.param_names:
            raise ValueError(
                "this circuit declares no parameters; there is nothing "
                "to differentiate (record angles via Circuit.parameter "
                "/ Param placeholders)")
        pm = jnp.reshape(self._param_vec(params),
                         (1, len(self.param_names)))
        _, terms, cfs = self._validated_pauli_terms(pauli_terms, coeffs)
        if state_f is None:
            state_f = self._default_state()
        means, errs, _info = self._converge(
            pm, terms, cfs, state_f, int(num_trajectories), key,
            sampling_budget=sampling_budget, wave_size=wave_size,
            shard_trajectories=shard_trajectories, grad=True)
        # quest: allow-host-sync(result boundary: the convergence loop
        # already synced its carry; means is a host array here)
        return float(means[0, 0]), means[0, 1:], errs[0]

    def expectation_grad_batch(self, param_matrix, hamiltonian,
                               num_trajectories: int,
                               key: Optional[jax.Array] = None, *,
                               sampling_budget: Optional[float] = None,
                               wave_size: Optional[int] = None,
                               live_rows: Optional[int] = None,
                               state_f=None, progress=None):
        """The ``(B, T)`` gradient form — one noisy-VQE ensemble per
        parameter row, every row's value AND gradient advancing through
        shared gradient waves of one executable (the serving runtime's
        ``kind="gradient"`` dispatch for trajectory programs). Early
        stopping waits for every live row's every component. Returns
        ``(values, grads, stderrs, info)``: ``(B,)``, ``(B, P)``,
        ``(B, P+1)`` arrays and the convergence accounting."""
        if not self.param_names:
            # BEFORE the shape check: the dedicated typed rejection
            # must not be preempted by a confusing (batch, 0) message
            raise ValueError(
                "this circuit declares no parameters; there is nothing "
                "to differentiate (record angles via Circuit.parameter "
                "/ Param placeholders)")
        pm = jnp.asarray(param_matrix,
                         dtype=self.env.precision.real_dtype)
        if pm.ndim != 2 or pm.shape[1] != len(self.param_names):
            raise ValueError(
                f"param_matrix must be (batch, {len(self.param_names)}); "
                f"got {pm.shape}")
        if int(num_trajectories) < 2:
            raise ValueError("expectation_grad needs >= 2 trajectories "
                             "for a standard error")
        terms_in, coeffs_in = hamiltonian
        _, terms, coeffs = self._validated_pauli_terms(terms_in,
                                                       coeffs_in)
        if state_f is None:
            state_f = self._default_state()
        means, errs, info = self._converge(
            pm, terms, coeffs, state_f, int(num_trajectories), key,
            sampling_budget=sampling_budget, wave_size=wave_size,
            live_rows=live_rows, grad=True, progress=progress)
        return means[:, 0], means[:, 1:], errs, info

    def _converge(self, pm, terms, coeffs, state_f, max_trajectories,
                  key, sampling_budget=None, wave_size=None,
                  live_rows=None, shard_trajectories=None,
                  grad: bool = False, progress=None):
        """The shared convergence loop. ``pm``: ``(B, P)``; per row the
        keys are an up-front ``split`` of one fold of the base key, so
        wave boundaries never change any draw. ``grad=True`` runs the
        GRADIENT wave executable instead: the carry grows a
        ``P + 1``-component axis (value + every parameter gradient),
        the stop decision requires EVERY component's standard error to
        fit the budget, and the returned means/stderrs are
        ``(B, P+1)``."""
        B = pm.shape[0]
        T = max_trajectories
        live = B if live_rows is None else max(1, min(int(live_rows), B))
        num_terms, xm, ym, zm, cf = self._pauli_operands(terms, coeffs)
        if key is None:
            key = self.env.next_key()
        W = int(wave_size) if wave_size else self._default_wave(T)
        waves, bucket = plan_waves(T, W, self._device_multiple())
        mode = self._resolve_mode(B * bucket, shard_trajectories,
                                  mem_factor=2.0 if grad else 1.0)
        # per-row key streams: row b's trajectory t key is
        # split(fold_in(key, b), T)[t] — wave slicing never re-splits
        keys_rows = [jax.random.split(jax.random.fold_in(key, b), T)
                     for b in range(B)]
        rdt = np.float64 if np.dtype(
            self.env.precision.real_dtype) == np.float64 else np.float32
        carry = jnp.zeros(
            (3, B, len(self.param_names) + 1) if grad else (3, B),
            dtype=rdt)
        fn = self._grad_wave_fn(mode) if grad else self._wave_fn(mode)
        args_const = (jnp.asarray(xm), jnp.asarray(ym), jnp.asarray(zm),
                      jnp.asarray(cf, dtype=rdt))
        # the whole wave loop is one profiled dispatch: trajectory
        # waves get the same live roofline number every other mode has
        sp = _profile.profile_dispatch("trajectories.wave")
        run = 0
        waves_run = 0
        early = False
        stderr = np.full(carry.shape[1:], np.inf)
        snap = None
        for start, live_w in waves:
            mask = np.zeros((bucket,), dtype=bool)
            mask[:live_w] = True
            kslices = []
            for b in range(B):
                ks = keys_rows[b][start:start + live_w]
                if live_w < bucket:
                    ks = jnp.concatenate(
                        [ks] + [ks[:1]] * (bucket - live_w))
                kslices.append(ks)
            # row-major flat (B*bucket,) key axis: the trajectory-
            # parallel mode shards it even for a single-row ensemble
            keys = kslices[0] if B == 1 else jnp.concatenate(kslices)
            state_p, keys = self._place(state_f, keys, mode)
            carry = fn(state_p, keys, pm, jnp.asarray(mask),
                       *args_const, carry)
            run += live_w
            waves_run += 1
            snap = np.asarray(carry)           # the wave's ONE transfer
            stderr = red.welford_stderr(snap[0], snap[2])
            if progress is not None:
                # the per-wave signal (netserve streaming, notebooks):
                # reuses the wave's existing host snapshot — no extra
                # transfer, no extra sync
                try:
                    progress({"wave": int(waves_run),
                              "trajectories_run": int(run),
                              "max_trajectories": int(T),
                              # quest: allow-host-sync(stderr is the
                              # wave's existing host snapshot — no new
                              # device transfer)
                              "max_stderr": float(np.max(stderr[:live]))})
                # quest: allow-broad-except(progress listeners are
                # caller code; a sick listener must never kill the
                # wave loop)
                except Exception:
                    pass
            if sampling_budget is not None and \
                    np.all(snap[0][:live] >= 2.0) and \
                    np.all(stderr[:live] <= float(sampling_budget)):
                early = run < T
                break
        means = snap[1]
        info = {
            "max_trajectories": T,
            "trajectories_run": int(run),
            "early_stopped": bool(early),
            "waves": int(waves_run),
            "wave_size": int(bucket),
            "batch_rows": int(B),
            "sampling_budget": (float(sampling_budget)
                                if sampling_budget is not None else None),
            "max_stderr": float(np.max(stderr[:live])),
            "mode": mode,
            "num_terms": int(num_terms),
            "kind": "gradient" if grad else "value",
        }
        with self._stats_lock:
            self._last_traj_stats = dict(info)
        if sp is not None:
            itemsize = np.dtype(self.env.precision.real_dtype).itemsize
            state_bytes = 4.0 * itemsize * (1 << self.num_qubits)
            # the reverse walk streams every pass twice (primal +
            # cotangent), so a gradient wave's traffic doubles
            sp.done(snap, program=self.program_digest,
                    kind="gradient" if grad else "trajectory",
                    bucket=int(bucket), tier="env",
                    dtype=str(np.dtype(self.env.precision.real_dtype)),
                    sharding=mode,
                    bytes_per_pass=(2.0 if grad else 1.0)
                    * max(len(self._ops), 1) * B * run * state_bytes)
        # the engine-off path pays one device->host sync per trajectory
        # per row; the wave loop pays one per wave
        self._record_batch_stats(B * run, mode, B * run - waves_run)
        return np.asarray(means, dtype=np.float64), \
            np.asarray(stderr, dtype=np.float64), info

    @property
    def last_traj_stats(self) -> dict:
        """Accounting of the most recent convergence loop
        (``trajectories_run`` / ``early_stopped`` / waves / stderr) —
        the serving layer copies these onto its telemetry spans."""
        with self._stats_lock:
            return dict(self._last_traj_stats)

    # -- sampling / debug ---------------------------------------------------

    def sample(self, num_shots: int, num_trajectories: int, params=None,
               state_f=None, key: Optional[jax.Array] = None):
        """Basis samples from the noisy output MIXTURE: run the
        ensemble once, then draw ``num_shots`` outcomes stratified
        evenly over the trajectories (:func:`quest_tpu.parallel.
        sampling.sample_mixture`) — the physical shot statistics of the
        noisy circuit at statevector cost. Returns ``(indices int64
        [num_shots], totals (T,))``."""
        if int(num_shots) < 1:
            raise ValueError("num_shots must be >= 1")
        if key is None:
            key = self.env.next_key()
        skey, tkey = jax.random.split(key)
        planes = self.trajectory_sweep(num_trajectories, params=params,
                                       state_f=state_f, key=tkey)
        from ..parallel.sampling import sample_mixture
        return sample_mixture(planes, skey, int(num_shots))

    def average_density(self, state_f, num_trajectories: int,
                        key: Optional[jax.Array] = None,
                        params=None) -> np.ndarray:
        """Monte-Carlo estimate of the channel-evolved density matrix:
        mean of |psi><psi| over trajectories (host-side, debug/analysis
        scale — the 2^n x 2^n matrix is MATERIALISED). Refuses above
        ``QUEST_TPU_DENSITY_DEBUG_QUBITS`` (default 14) qubits with
        :class:`DensityMaterialisationError`; at scale use
        :meth:`expectation` (observables, device-resident) or
        :meth:`trajectory_sweep` (the raw 2^n ensemble) instead."""
        limit = int(os.environ.get(DENSITY_DEBUG_QUBITS_ENV,
                                   str(_DENSITY_DEBUG_DEFAULT)))
        if self.num_qubits > limit:
            raise DensityMaterialisationError(
                f"average_density would materialise a "
                f"2^{2 * self.num_qubits}-amplitude density matrix "
                f"({self.num_qubits} qubits > the "
                f"{DENSITY_DEBUG_QUBITS_ENV}={limit} debug bound); use "
                "expectation() for observables or trajectory_sweep() "
                "for the raw statevector ensemble — both stay at "
                "2^n cost")
        batch = np.asarray(self.run_batch(state_f, num_trajectories,
                                          key, params=params))
        psis = batch[:, 0] + 1j * batch[:, 1]
        return np.einsum("ti,tj->ij", psis, psis.conj()) / len(psis)

    # -- accounting ---------------------------------------------------------

    def dispatch_stats(self):
        """Engine-style dispatch accounting
        (:class:`quest_tpu.profiling.DispatchStats`): the batched
        trajectory engine's batch size / sharding mode /
        ``host_syncs_avoided`` (the one-transfer-per-wave observable)
        and the keyed executable cache's occupancy, next to the
        program's op counts."""
        from ..profiling import DispatchStats
        with self._stats_lock:
            bs = dict(self._batch_stats or {})
            cache_size = len(self._cache)
            cache_evictions = self._cache.evictions
        return DispatchStats(
            gates_in=len(self.circuit.ops),
            kernels_out=len(self._ops),
            relayouts=0,
            batch_size=bs.get("batch_size", 0),
            host_syncs_avoided=bs.get("host_syncs_avoided", 0),
            batch_sharding_mode=bs.get("batch_sharding_mode", "none"),
            batched_cache_size=cache_size,
            batched_cache_evictions=cache_evictions)
