"""Per-gate sharded execution with a lazy qubit layout on the register.

The imperative API (``quest_tpu.api``) pays per-gate dispatch like the
reference; on a mesh the reference additionally pays per-gate routing —
SWAP a sharded target down, run local, SWAP back
(``statevec_multiControlledMultiQubitUnitary``,
``QuEST_cpu_distributed.c:1420-1461``) — i.e. two data moves per offending
gate. Here the register carries a **lazy logical->physical permutation**
(``Qureg.layout``), so:

- ``swapGate`` on a mesh is METADATA ONLY — no data moves at all;
- a dense 1q gate on a sharded position runs as the role-split pair
  exchange (``apply_1q_cross_shard`` — one ppermute, no relayout, layout
  unchanged);
- a k>=2-qubit dense gate with sharded targets triggers ONE relayout that
  swaps its targets onto the all_to_all staging slots (three-way rotation,
  post-transpose-free) and LEAVES them there — the inverse swap the
  reference pays per gate is deferred until some reader actually needs
  canonical order (``Qureg.ensure_canonical``);
- diagonal gates and controls run at ANY position with zero communication.

All kernels are ``shard_map`` programs over the env mesh (explicit
collectives, no GSPMD rematerialisation — see ``parallel/exchange.py``),
cached per static signature like the ``api`` module's jit kernels.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ..core.packing import pack, unpack
from ..env import AMP_AXIS
from ..resilience import faults as _faults
from ..telemetry.tracing import dispatch_annotation
from ..telemetry import profile as _profile
from .exchange import (plan_exchange, run_exchange, apply_op_local,
                       apply_1q_cross_shard, overlap_eligible,
                       run_exchange_overlapped)

__all__ = ["use_lazy", "phys_targets", "localise_targets", "canonicalise",
           "sharded_unitary", "sharded_diag", "metadata_swap", "phys_index",
           "GateFusionBuffer", "overlap_enabled"]

# number of relayout exchanges actually executed (observability/testing:
# the lazy layout exists to keep this far below the count of gates that
# touch sharded qubits)
RELAYOUT_COUNT = 0


def _maybe_inject(qureg, site: str) -> None:
    """Fault-injection boundary for the imperative sharded path
    (:mod:`quest_tpu.resilience.faults`; no-op unless an injector is
    installed). A drawn output-corrupting fault (``nan`` poisons, a
    ``precision`` fault norm-drifts) corrupts the INPUT planes — the
    corruption then propagates through the dispatch exactly like a bad
    kernel output would."""
    poison = _faults.fire(site)
    if poison:
        qureg.state = _faults.poison_output(poison, qureg.state)


def overlap_enabled() -> bool:
    """Opt-in comm/compute overlap for the per-gate path
    (``QUEST_TPU_OVERLAP=1``): a swap-to-local relayout and the gate
    kernel it serves fuse into ONE dispatch whose collective is slab
    double-buffered (``exchange.run_exchange_overlapped``) — the
    imperative analogue of ``compile(overlap=True)``. Read per call so
    tests (and users) can flip it at run time."""
    return os.environ.get("QUEST_TPU_OVERLAP", "0") not in ("0", "", "off")


def use_lazy(qureg) -> bool:
    """True when the register runs the sharded per-gate path. QUAD
    registers are excluded: their (4, 2^n) dd planes run the dedicated
    dd kernels (GSPMD-sharded), not the lazy-layout machinery."""
    return (qureg.env.mesh is not None and qureg.sharding() is not None
            and not qureg.is_quad)


def fits_local(qureg, k: int) -> bool:
    """A k-qubit dense gather needs k chunk-local positions (the
    ``validateMultiQubitMatrixFitsInNode`` predicate,
    ``QuEST_validation.c:116``). 1q gates always fit — a sharded position
    rides the role-split exchange. Callers fall back to the GSPMD path
    instead of erroring where the reference would abort."""
    if k <= 1:
        return True
    return k <= qureg.num_qubits_in_state_vec - _shard_bits(qureg)


def _shard_bits(qureg) -> int:
    return qureg.env.num_devices.bit_length() - 1


def _perm(qureg) -> np.ndarray:
    if qureg.layout is None:
        return np.arange(qureg.num_qubits_in_state_vec)
    return qureg.layout


def phys_index(qureg, index: int) -> int:
    """Physical amplitude index of logical basis index (bit q of the
    logical index lives at physical bit ``layout[q]``)."""
    if qureg.layout is None:
        return int(index)
    out = 0
    for q, p in enumerate(qureg.layout):
        if (index >> q) & 1:
            out |= 1 << int(p)
    return out


# ---------------------------------------------------------------------------
# cached shard_map kernels (packed (2, 2^n) planes in and out)
# ---------------------------------------------------------------------------

def _shard_jit(mesh, body, n_extra_args: int):
    """shard_map + jit boilerplate shared by every per-gate kernel: the
    packed planes shard on the amplitude axis (donated), trailing
    operand arrays are replicated."""
    sm = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, AMP_AXIS),) + (P(),) * n_extra_args,
        out_specs=P(None, AMP_AXIS), check_vma=False)
    return jax.jit(sm, donate_argnums=(0,))


@functools.lru_cache(maxsize=1024)
def _gate_fn(mesh, n, s, targets, cmask, fmask):
    lt = n - s

    def body(local_f, u_f):
        z = apply_op_local(unpack(local_f), "u", unpack(u_f), targets,
                           cmask, fmask, lt, AMP_AXIS)
        return pack(z)

    return _shard_jit(mesh, body, 1)


@functools.lru_cache(maxsize=1024)
def _cross_1q_fn(mesh, n, s, position, cmask, fmask):
    lt = n - s

    def body(local_f, u_f):
        z = apply_1q_cross_shard(unpack(local_f), unpack(u_f), position,
                                 lt, s, AMP_AXIS, cmask, fmask)
        return pack(z)

    return _shard_jit(mesh, body, 1)


@functools.lru_cache(maxsize=1024)
def _diag_fn(mesh, n, s, phys_desc):
    lt = n - s

    def body(local_f, d_f):
        z = apply_op_local(unpack(local_f), "diag", unpack(d_f), phys_desc,
                           0, 0, lt, AMP_AXIS)
        return pack(z)

    return _shard_jit(mesh, body, 1)


@functools.lru_cache(maxsize=1024)
def _relayout_fn(mesh, n, s, before, after):
    plan = plan_exchange(n, s, before, after)

    def body(local_f):
        return pack(run_exchange(unpack(local_f), plan, AMP_AXIS))

    return _shard_jit(mesh, body, 0)


@functools.lru_cache(maxsize=1024)
def _relayout_gate_fn(mesh, n, s, before, after, targets, cmask, fmask):
    """Fused swap-to-local + gate dispatch with the slab double-buffered
    collective (one shard_map program instead of two; opt-in via
    ``QUEST_TPU_OVERLAP``)."""
    plan = plan_exchange(n, s, before, after)

    def body(local_f, u_f):
        z = run_exchange_overlapped(unpack(local_f), plan, AMP_AXIS,
                                    unpack(u_f), targets, cmask, fmask)
        return pack(z)

    return _shard_jit(mesh, body, 1)


# ---------------------------------------------------------------------------
# layout management
# ---------------------------------------------------------------------------

def canonicalise(qureg) -> None:
    """Restore identity layout (one batched exchange), if needed."""
    lay = qureg.layout
    if lay is None:
        return
    if np.array_equal(lay, np.arange(len(lay))):
        qureg.layout = None
        return
    n = qureg.num_qubits_in_state_vec
    s = _shard_bits(qureg)
    fn = _relayout_fn(qureg.env.mesh, n, s,
                      tuple(int(p) for p in lay), tuple(range(n)))
    sp = _profile.profile_dispatch("pergate.relayout")
    _maybe_inject(qureg, "pergate.relayout")
    global RELAYOUT_COUNT
    RELAYOUT_COUNT += 1
    with dispatch_annotation("quest_tpu.pergate.relayout"):
        qureg.state = fn(qureg.state)
    if sp is not None:
        sp.done(qureg.state, program="pergate", kind="relayout",
                bucket=1, dtype=str(qureg.state.dtype), sharding="amp",
                bytes_per_pass=2.0 * qureg.state.nbytes)
    qureg.layout = None


def _localise_perm(qureg, targets):
    """The permutation a swap-to-local relayout would realize: every
    sharded logical target lands on an all_to_all staging slot. Returns
    ``(perm, new_perm)`` where ``new_perm is None`` when nothing is
    sharded (no relayout needed)."""
    n = qureg.num_qubits_in_state_vec
    s = _shard_bits(qureg)
    lt = n - s
    perm = _perm(qureg)
    sharded = [t for t in targets if perm[t] >= lt]
    if not sharded:
        return perm, None
    inv = np.empty(n, dtype=np.int64)
    inv[perm] = np.arange(n)
    # victims: the qubits occupying the staging slots themselves (direct
    # swap, minimal-support pre-transpose) — skipping the gate's own qubits
    stages = []
    for p in range(lt - 1, -1, -1):
        if int(inv[p]) in targets:
            continue
        stages.append(p)
        if len(stages) == len(sharded):
            break
    if len(stages) < len(sharded):
        raise ValueError(
            f"a {len(targets)}-qubit unitary cannot be localised with "
            f"{lt} local qubit positions")
    new_perm = perm.copy()
    for q, stage in zip(sharded, stages):
        victim = int(inv[stage])
        new_perm[victim] = new_perm[q]
        new_perm[q] = stage
        inv[stage] = q
        inv[new_perm[victim]] = victim
    return perm, new_perm


def localise_targets(qureg, targets) -> np.ndarray:
    """Ensure every logical target sits on a local physical position,
    emitting at most ONE relayout (targets land on the all_to_all staging
    slots — the swap-to-local of ``QuEST_cpu_distributed.c:1426-1448``,
    batched, with the swap-back deferred). Returns the active perm."""
    perm, new_perm = _localise_perm(qureg, targets)
    if new_perm is None:
        return perm
    n = qureg.num_qubits_in_state_vec
    s = _shard_bits(qureg)
    fn = _relayout_fn(qureg.env.mesh, n, s,
                      tuple(int(p) for p in perm),
                      tuple(int(p) for p in new_perm))
    sp = _profile.profile_dispatch("pergate.relayout")
    _maybe_inject(qureg, "pergate.relayout")
    global RELAYOUT_COUNT
    RELAYOUT_COUNT += 1
    with dispatch_annotation("quest_tpu.pergate.relayout"):
        qureg.state = fn(qureg.state)
    if sp is not None:
        sp.done(qureg.state, program="pergate", kind="relayout",
                bucket=1, dtype=str(qureg.state.dtype), sharding="amp",
                bytes_per_pass=2.0 * qureg.state.nbytes)
    qureg.layout = new_perm
    return new_perm


def phys_targets(qureg, qubits) -> tuple:
    perm = _perm(qureg)
    return tuple(int(perm[q]) for q in qubits)


# ---------------------------------------------------------------------------
# gate application
# ---------------------------------------------------------------------------

def sharded_unitary(qureg, u_packed, targets, ctrl_mask, flip_mask) -> None:
    """Apply a dense (controlled) unitary on LOGICAL targets, routing per
    gate: local positions -> local kernel; one sharded 1q target ->
    role-split pair exchange; multi-qubit sharded -> batched swap-to-local
    relayout then local kernel. Controls never move."""
    sp = _profile.profile_dispatch("pergate.gate")
    _maybe_inject(qureg, "pergate.gate")
    n = qureg.num_qubits_in_state_vec
    s = _shard_bits(qureg)
    lt = n - s
    mesh = qureg.env.mesh
    perm = _perm(qureg)
    phys_t = tuple(int(perm[t]) for t in targets)

    def _done(form: str) -> None:
        if sp is not None:
            sp.done(qureg.state, program="pergate", kind="gate",
                    bucket=1, dtype=str(qureg.state.dtype),
                    sharding=form,
                    bytes_per_pass=2.0 * qureg.state.nbytes)

    if len(targets) == 1 and phys_t[0] >= lt:
        cmask, fmask = _phys_masks(perm, ctrl_mask, flip_mask)
        fn = _cross_1q_fn(mesh, n, s, phys_t[0], cmask, fmask)
        with dispatch_annotation("quest_tpu.pergate.gate:xshard"):
            qureg.state = fn(qureg.state, u_packed)
        _done("xshard")
        return
    if any(p >= lt for p in phys_t):
        if overlap_enabled():
            # fused relayout+gate with the slab double-buffered
            # collective: one dispatch, and the exchange for slab i+1 is
            # independent of the gate math on slab i
            old_perm, new_perm = _localise_perm(qureg, tuple(targets))
            phys_new = tuple(int(new_perm[t]) for t in targets)
            cmask, fmask = _phys_masks(new_perm, ctrl_mask, flip_mask)
            expl = plan_exchange(n, s, tuple(int(p) for p in old_perm),
                                 tuple(int(p) for p in new_perm))
            if overlap_eligible(expl, phys_new, cmask):
                fn = _relayout_gate_fn(
                    mesh, n, s, tuple(int(p) for p in old_perm),
                    tuple(int(p) for p in new_perm), phys_new, cmask,
                    fmask)
                global RELAYOUT_COUNT
                RELAYOUT_COUNT += 1
                with dispatch_annotation(
                        "quest_tpu.pergate.gate:overlap"):
                    qureg.state = fn(qureg.state, u_packed)
                _done("overlap")
                qureg.layout = new_perm
                return
        perm = localise_targets(qureg, tuple(targets))
        phys_t = tuple(int(perm[t]) for t in targets)
    cmask, fmask = _phys_masks(perm, ctrl_mask, flip_mask)
    fn = _gate_fn(mesh, n, s, phys_t, cmask, fmask)
    with dispatch_annotation("quest_tpu.pergate.gate:local"):
        qureg.state = fn(qureg.state, u_packed)
    _done("local")


def sharded_diag(qureg, tensor_np, qs_desc) -> None:
    """Apply a diagonal factor on LOGICAL qubits (any position, zero
    communication). ``tensor_np`` axes follow ``qs_desc`` (logical sorted
    descending); axes are reordered to physical descending here."""
    n = qureg.num_qubits_in_state_vec
    s = _shard_bits(qureg)
    perm = _perm(qureg)
    phys = tuple(int(perm[q]) for q in qs_desc)
    order = tuple(int(i) for i in np.argsort(phys)[::-1])
    phys_desc = tuple(phys[i] for i in order)
    t = np.transpose(np.asarray(tensor_np), order)
    from ..core.packing import pack_host
    fn = _diag_fn(qureg.env.mesh, n, s, phys_desc)
    qureg.state = fn(qureg.state,
                     jax.numpy.asarray(pack_host(t, qureg.real_dtype)))


def metadata_swap(qureg, q1: int, q2: int) -> None:
    """swapGate as pure bookkeeping: exchange the physical positions of two
    logical qubits. The reference moves amplitudes
    (``statevec_swapQubitAmps``, ``QuEST_cpu_distributed.c:1355-1371``);
    here nothing moves until a reader wants canonical order."""
    perm = _perm(qureg).copy()
    perm[q1], perm[q2] = perm[q2], perm[q1]
    qureg.layout = perm


# ---------------------------------------------------------------------------
# opt-in imperative gate fusion
# ---------------------------------------------------------------------------

class GateFusionBuffer:
    """Opt-in gate fusion for the imperative per-gate path.

    Activated by ``api.startGateFusion`` (or the ``fusedGates`` context
    manager): gate calls append LOGICAL op records here instead of
    dispatching, and :meth:`flush` contracts them through the same fusion
    engine as the compiled pipeline (:mod:`quest_tpu.core.fusion`) before
    dispatching each fused group once — group-granular dispatch, so a run
    of L adjacent small gates costs one kernel (and, on a mesh, at most
    one relayout) instead of L.

    Flushing is automatic at every state read: ``Qureg.state`` and
    ``Qureg.ensure_canonical`` drain the buffer first, so measurements,
    reductions, channels, compiled-circuit runs and host reads always see
    the up-to-date state. A full state overwrite (``init*``) discards
    pending gates — exactly what applying them first would have produced.
    """

    def __init__(self, qureg, max_k: int = 3):
        from ..core.fusion import resolve_fusion_k
        lt = qureg.num_qubits_in_state_vec - (
            _shard_bits(qureg) if use_lazy(qureg) else 0)
        # density registers lift a k-qubit gate to 2k state-vector
        # targets; halving the local budget keeps every fused group on
        # the one-pass lifted path. The same halving bounds folded
        # diagonals: a u-qubit folded factor lifts to a 2^(2u)-entry
        # superfactor at dispatch, so the fold cap must stay well below
        # register size on the density path
        local = lt // 2 if qureg.is_density_matrix else lt
        self.qureg = qureg
        self.max_k = resolve_fusion_k(max_k, max(local, 1))
        self.diag_max = min(12, max(local, 1))
        self.ops: list = []
        self.flushing = False
        self.gates_in = 0
        self.kernels_out = 0

    @property
    def pending(self) -> bool:
        return bool(self.ops)

    def add_gate(self, u, targets: tuple, ctrl_mask: int,
                 flip_mask: int) -> None:
        from ..circuits import _Op
        self.ops.append(_Op("u", tuple(int(t) for t in targets),
                            ctrl_mask, flip_mask,
                            mat=np.asarray(u, dtype=np.complex128)))

    def add_diag(self, tensor, qs_desc: tuple) -> None:
        from ..circuits import _Op
        self.ops.append(_Op("diag", tuple(int(q) for q in qs_desc),
                            diag=np.asarray(tensor, dtype=np.complex128)))

    def flush(self) -> None:
        """Contract and dispatch everything pending (reentrancy-safe:
        the dispatched kernels read and write ``qureg.state`` themselves)."""
        if not self.ops or self.flushing:
            return
        ops, self.ops = self.ops, []
        self.flushing = True
        try:
            from ..core.fusion import fuse_ops
            from .. import api
            fused, stats = fuse_ops(ops, max_k=self.max_k,
                                    diag_max=self.diag_max)
            self.gates_in += stats.gates_in
            self.kernels_out += stats.kernels_out
            for op in fused:
                api._dispatch_fused_op(self.qureg, op)
        finally:
            self.flushing = False

    def discard(self) -> None:
        """Drop pending gates (the register state was fully overwritten)."""
        self.ops.clear()


def _phys_masks(perm, ctrl_mask: int, flip_mask: int) -> tuple[int, int]:
    cm = fm = 0
    m, q = ctrl_mask, 0
    while m:
        if m & 1:
            cm |= 1 << int(perm[q])
            if (flip_mask >> q) & 1:
                fm |= 1 << int(perm[q])
        m >>= 1
        q += 1
    return cm, fm
