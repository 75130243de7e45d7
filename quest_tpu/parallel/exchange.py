"""Explicit shard_map lowering of relayouts and cross-shard gates.

The planner (:mod:`quest_tpu.parallel.layout`) schedules WHAT moves; this
module is HOW it moves. Round-3 evidence showed that expressing a relayout
as a global transpose under GSPMD sometimes triggers "[SPMD] Involuntary
full rematerialization" — XLA replicates the whole 2^n-amplitude tensor
instead of emitting an all-to-all, which is exactly the failure mode a
distributed simulator exists to avoid. Here every data movement is an
explicit collective inside one :func:`jax.shard_map` program, so the
lowering is *provably* a pair exchange:

- a **relayout** (a permutation of physical qubit positions where ``k``
  device-index bits trade places with ``k`` chunk-local bits) decomposes
  into: local pre-transpose -> ``lax.all_to_all`` over groups of ``2^k``
  devices -> optional ``lax.ppermute`` (residual device-bit permutation)
  -> local post-transpose. This is the reference's chunk-pair exchange
  (``exchangeStateVectors``, ``QuEST_cpu_distributed.c:478-506``;
  pair-rank calc ``:300-309``) generalised from one bit to ``k`` bits and
  batched into a single collective;
- a **cross-shard 1q gate** is the reference's role-split combine
  (``statevec_compactUnitaryDistributed``, ``QuEST_cpu.c:1975-2016``,
  driven by ``QuEST_cpu_distributed.c:843-878``): ``ppermute`` the chunk
  to the pair device (``chunkId ^ 2^j``), then each device applies its own
  row of U elementwise — ``out = U[r,r]·mine + U[r,1-r]·theirs`` with the
  role bit ``r`` read off ``lax.axis_index`` (the ``chunkIsUpper`` /
  ``getRotAngle`` math, ``:224-265``);
- gates whose targets are chunk-local apply with plain local kernels;
  controls sitting on device-index bits become a ``lax.cond`` on
  ``lax.axis_index`` (the distributed control-skip,
  ``QuEST_cpu_distributed.c:888-908``), and diagonal factors indexed by
  device bits are sliced per device — zero communication either way.

Amplitude layout matches the reference's chunk model (``QuEST.h:169-177``):
with ``2^s`` devices, device index = amplitude index >> (n-s), i.e. device
bit ``j`` holds physical qubit position ``(n-s)+j``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..core.apply import (apply_diagonal, apply_unitary, pass_boundary,
                          transpose_qubits)

__all__ = ["ExchangePlan", "plan_exchange", "run_exchange",
           "apply_op_local", "apply_1q_cross_shard",
           "overlap_eligible", "run_exchange_overlapped", "slab_remap"]


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Static choreography for one relayout on a ``2^s``-device mesh."""
    local_top: int                      # n - s: positions below are local
    k: int                              # device<->local bits exchanged
    pre_axes: Optional[tuple]           # local transpose before exchange
    groups: Optional[tuple]             # all_to_all axis_index_groups
    device_perm: Optional[tuple]        # ppermute (src, dst) pairs
    post_axes: Optional[tuple]          # local transpose after exchange


def _axes_from_position_map(pos_map: np.ndarray) -> Optional[tuple]:
    """Transpose axes realising ``position p -> pos_map[p]`` on the
    ``(2,)*local_top`` view (position q is axis ``local_top-1-q``)."""
    lt = len(pos_map)
    axes = np.empty(lt, dtype=np.int64)
    for p in range(lt):
        axes[lt - 1 - int(pos_map[p])] = lt - 1 - p
    if np.array_equal(axes, np.arange(lt)):
        return None
    return tuple(int(a) for a in axes)


def plan_exchange(n: int, shard_bits: int,
                  perm_before: Sequence[int],
                  perm_after: Sequence[int]) -> ExchangePlan:
    """Decompose 'qubit at position perm_before[l] moves to perm_after[l]'
    into the local/collective steps of :func:`run_exchange`."""
    s = shard_bits
    lt = n - s
    sigma = np.empty(n, dtype=np.int64)
    for b, a in zip(perm_before, perm_after):
        sigma[int(b)] = int(a)

    A = [p for p in range(lt) if sigma[p] >= lt]          # local -> device
    B = [p for p in range(lt, n) if sigma[p] < lt]        # device -> local
    k = len(A)
    if len(B) != k:
        raise ValueError("malformed relayout permutation")

    # Assign each outgoing local bit a vacated device slot; preferring the
    # slot it is destined for makes the residual ppermute vanish in the
    # common case (straight swap of a device qubit with a local qubit).
    slots = list(B)
    assign: dict[int, int] = {}
    leftovers = []
    for a in A:
        if int(sigma[a]) in slots:
            assign[a] = int(sigma[a])
            slots.remove(int(sigma[a]))
        else:
            leftovers.append(a)
    for a, b in zip(leftovers, slots):
        assign[a] = b
    # Pair order = ascending destination of the INCOMING bit: the exchange
    # delivers device bit b_i to staging slot lt-k+i, so when the planner
    # lands incoming qubits on the top-k positions (layout.py's three-way
    # rotation) the slot IS the destination and the post-transpose is
    # identity. Ties (same destination impossible) need no care.
    pairs = sorted(((b, a) for a, b in assign.items()),
                   key=lambda ba: int(sigma[ba[0]]))
    b_list = [b for b, _ in pairs]
    a_list = [a for _, a in pairs]

    # local pre-permutation: stage the outgoing bit of pair i at position
    # lt-k+i (bit i of the all_to_all split index); staying locals go
    # STRAIGHT to their final position when it's free, so all local
    # movement happens in this one pass
    psi = np.full(lt, -1, dtype=np.int64)
    taken = set()
    for i, a in enumerate(a_list):
        psi[a] = lt - k + i
        taken.add(lt - k + i)
    rest = [p for p in range(lt) if p not in a_list]
    deferred = []
    for p in rest:
        dest = int(sigma[p])
        if dest not in taken:
            psi[p] = dest
            taken.add(dest)
        else:
            deferred.append(p)
    free = [q for q in range(lt) if q not in taken]
    for p, q in zip(deferred, free):
        psi[p] = q
    pre_axes = _axes_from_position_map(psi)

    # after the exchange, staged position lt-k+i holds old device bit b_i;
    # identity whenever direct placement succeeded throughout
    phi = np.empty(lt, dtype=np.int64)
    for i, b in enumerate(b_list):
        phi[lt - k + i] = sigma[b]
    for p in rest:
        phi[psi[p]] = sigma[p]
    post_axes = _axes_from_position_map(phi)

    groups = None
    if k:
        j_list = [b - lt for b in b_list]
        others = [j for j in range(s) if j not in j_list]
        gs = []
        for ov in range(1 << len(others)):
            base = 0
            for t, j in enumerate(others):
                if (ov >> t) & 1:
                    base |= 1 << j
            gs.append(tuple(
                base | sum(((m >> i) & 1) << j for i, j in enumerate(j_list))
                for m in range(1 << k)))
        groups = tuple(gs)

    # residual device-bit permutation (only when a staying device bit moves
    # or an incoming bit could not land directly in its destined slot)
    mu = {b: int(sigma[a]) for b, a in zip(b_list, a_list)}
    for d in range(lt, n):
        if d not in mu:
            mu[d] = int(sigma[d])
    device_perm = None
    if any(p != q for p, q in mu.items()):
        pp = []
        for v in range(1 << s):
            w = 0
            for p, q in mu.items():
                if (v >> (p - lt)) & 1:
                    w |= 1 << (q - lt)
            pp.append((v, w))
        device_perm = tuple(pp)

    return ExchangePlan(lt, k, pre_axes, groups, device_perm, post_axes)


def run_exchange(local: jnp.ndarray, plan: ExchangePlan,
                 axis_name: str) -> jnp.ndarray:
    """Execute one relayout on the per-device chunk (shard_map-internal)."""
    lt = plan.local_top
    if plan.pre_axes is not None:
        local = transpose_qubits(local, lt, plan.pre_axes)
    if plan.k:
        y = local.reshape(1 << plan.k, -1)
        y = lax.all_to_all(y, axis_name, 0, 0,
                           axis_index_groups=plan.groups, tiled=True)
        # each step its own pass: an all_to_all fused with the gate or
        # swap after it compiled for 84 s at 2^28 amplitudes per chip,
        # 3.5 s with the boundary (core/apply.py pass_boundary)
        local = pass_boundary(y.reshape(-1))
    if plan.device_perm is not None:
        local = pass_boundary(lax.ppermute(local, axis_name,
                                           plan.device_perm))
    if plan.post_axes is not None:
        local = transpose_qubits(local, lt, plan.post_axes)
    return local


def apply_op_local(local: jnp.ndarray, kind: str, operand: jnp.ndarray,
                   phys_targets: tuple, ctrl_mask: int, flip_mask: int,
                   local_top: int, axis_name: str,
                   precision=None) -> jnp.ndarray:
    """Apply one planned op to the per-device chunk.

    Targets must be chunk-local (< local_top) for dense ops — the planner
    guarantees it. Controls and diagonal-op qubits may sit on device bits:
    device controls gate the whole chunk update on ``lax.axis_index``
    (``lax.cond``), device diagonal bits slice the factor tensor.
    ``precision`` threads the precision-tier matmul mode into
    :func:`~quest_tpu.core.apply.apply_unitary` (None = HIGHEST).
    """
    lt = local_top
    if kind == "u":
        dev_c = ctrl_mask >> lt
        loc_c = ctrl_mask & ((1 << lt) - 1)
        loc_f = flip_mask & ((1 << lt) - 1)
        if dev_c:
            want = dev_c & ~(flip_mask >> lt)
            idx = lax.axis_index(axis_name)
            pred = (idx & dev_c) == want
            return lax.cond(
                pred,
                lambda st: apply_unitary(st, lt, operand, phys_targets,
                                         loc_c, loc_f,
                                         precision=precision),
                lambda st: st,
                local)
        return apply_unitary(local, lt, operand, phys_targets, loc_c, loc_f,
                             precision=precision)

    # diagonal: phys_targets sorted descending, so device positions are the
    # leading tensor axes — index them with this device's bits
    dev_pos = tuple(p for p in phys_targets if p >= lt)
    loc_pos = tuple(p for p in phys_targets if p < lt)
    d = jnp.asarray(operand)
    if dev_pos:
        idx = lax.axis_index(axis_name)
        sel = tuple((idx >> (p - lt)) & 1 for p in dev_pos)
        d = d[sel]
        if not loc_pos:
            return local * d.astype(local.dtype)
    return apply_diagonal(local, lt, loc_pos, d)


def overlap_eligible(plan: ExchangePlan, phys_targets: tuple,
                     ctrl_mask: int, slab_bits: int = 1) -> bool:
    """True when a relayout + following dense gate can run as the slab
    double-buffered pipeline of :func:`run_exchange_overlapped`.

    The slab axis is carved out of the TOP ``slab_bits`` column bits of
    the ``(2^k, columns)`` exchange view — physical positions
    ``[lt-k-slab_bits, lt-k)`` — so the gate must not target or condition
    on those positions (staging slots and low positions are fine, device
    bits are fine), the exchange must actually move data (``k >= 1``) and
    leave no post-transpose (the planner's three-way staging guarantees
    this on its own relayouts), and at least one column bit must remain
    below the slab."""
    lt = plan.local_top
    k = plan.k
    if k < 1 or plan.post_axes is not None:
        return False
    if lt - k - slab_bits <= 0:
        return False      # >= 1 column bit must remain below the slab
    slab_lo, slab_hi = lt - k - slab_bits, lt - k
    if any(slab_lo <= p < slab_hi for p in phys_targets):
        return False
    if any((ctrl_mask >> p) & 1 for p in range(slab_lo, slab_hi)):
        return False
    return True


def slab_remap(pos: int, lt: int, k: int, slab_bits: int = 1) -> int:
    """Physical position inside one slab's reduced ``lt - slab_bits``-qubit
    coordinate system: low column bits keep their position, staging and
    device bits shift down by the carved-out slab bits."""
    return pos - slab_bits if pos >= lt - k else pos


def _slab_mask(mask: int, lt: int, k: int, slab_bits: int) -> int:
    out = 0
    p = 0
    m = mask
    while m:
        if m & 1:
            out |= 1 << slab_remap(p, lt, k, slab_bits)
        m >>= 1
        p += 1
    return out


def run_exchange_overlapped(local: jnp.ndarray, plan: ExchangePlan,
                            axis_name: str, u: jnp.ndarray,
                            phys_targets: tuple, ctrl_mask: int,
                            flip_mask: int, slab_bits: int = 1,
                            precision=None) -> jnp.ndarray:
    """One relayout fused with the dense gate it serves, double-buffered
    over ``2^slab_bits`` slabs of the chunk.

    The reference's distributed path serializes exchange and compute
    (``exchangeStateVectors`` then the local kernel,
    ``QuEST_cpu_distributed.c:843-878``); here the chunk is split into
    slabs along a column bit untouched by both the exchange and the gate,
    and each slab's ``all_to_all`` is issued independently of every other
    slab's gate kernel — so XLA's async collectives can put slab ``i+1``'s
    exchange on the wire while slab ``i``'s gate math runs. Caller must
    have checked :func:`overlap_eligible`."""
    lt = plan.local_top
    k = plan.k
    if plan.pre_axes is not None:
        local = transpose_qubits(local, lt, plan.pre_axes)
    y = local.reshape(1 << k, -1)
    nslabs = 1 << slab_bits
    m = y.shape[1] // nslabs
    lt_slab = lt - slab_bits
    tgt = tuple(slab_remap(p, lt, k, slab_bits) for p in phys_targets)
    cm = _slab_mask(ctrl_mask, lt, k, slab_bits)
    fm = _slab_mask(flip_mask, lt, k, slab_bits)
    outs = []
    for j in range(nslabs):
        slab = y[:, j * m:(j + 1) * m]
        slab = lax.all_to_all(slab, axis_name, 0, 0,
                              axis_index_groups=plan.groups, tiled=True)
        if plan.device_perm is not None:
            slab = lax.ppermute(slab, axis_name, plan.device_perm)
        z = apply_op_local(slab.reshape(-1), "u", u, tgt, cm, fm,
                           lt_slab, axis_name, precision=precision)
        outs.append(z.reshape(1 << k, m))
    return jnp.concatenate(outs, axis=1).reshape(-1)


def apply_1q_cross_shard(local: jnp.ndarray, u: jnp.ndarray, position: int,
                         local_top: int, shard_bits: int, axis_name: str,
                         ctrl_mask: int = 0, flip_mask: int = 0) -> jnp.ndarray:
    """Role-split pair exchange for a 1q gate on a device-index bit.

    The reference's distributed hot path (``QuEST_cpu_distributed.c:843-878``
    + ``statevec_compactUnitaryDistributed``, ``QuEST_cpu.c:1975-2016``):
    exchange chunks with the pair device (index XOR 2^j), then combine
    elementwise with the row of U selected by this device's role bit. Local
    controls slice the combine; device controls gate it entirely.
    """
    lt = local_top
    j = position - lt
    pairs = tuple((v, v ^ (1 << j)) for v in range(1 << shard_bits))
    other = pass_boundary(lax.ppermute(local, axis_name, pairs))
    idx = lax.axis_index(axis_name)
    r = (idx >> j) & 1
    u = jnp.asarray(u, dtype=local.dtype)
    mine, theirs = u[r, r], u[r, 1 - r]

    dev_c = ctrl_mask >> lt
    loc_c = ctrl_mask & ((1 << lt) - 1)

    def combine(st):
        new = mine * st + theirs * other
        if loc_c:
            # only amplitudes whose local control bits match update
            controls = tuple(q for q in range(lt) if (loc_c >> q) & 1)
            pos_desc = tuple(sorted(controls, reverse=True))
            from ..core.apply import split_shape
            shape = split_shape(lt, pos_desc)
            mask = np.ones((2,) * len(pos_desc), dtype=bool)
            for i, c in enumerate(pos_desc):
                bit_want = 0 if (flip_mask >> c) & 1 else 1
                take = np.arange(2) == bit_want
                mask &= take.reshape((1,) * i + (2,) + (1,) *
                                     (len(pos_desc) - i - 1))
            bshape = [1] * len(shape)
            for i in range(len(pos_desc)):
                bshape[2 * i + 1] = 2
            m = jnp.asarray(mask).reshape(bshape)
            return jnp.where(m, new.reshape(shape), st.reshape(shape)
                             ).reshape(-1)
        return new

    if dev_c:
        want = dev_c & ~(flip_mask >> lt)
        pred = (idx & dev_c) == want
        return lax.cond(pred, combine, lambda st: st, local)
    return combine(local)
