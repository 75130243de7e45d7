"""Core gate-application engine.

This single module replaces all three of the reference's backend kernel
families (the OpenMP block-stride pair loops of ``QuEST_cpu.c:1662-1901``, the
CUDA per-amplitude kernels of ``QuEST_gpu.cu:667-1246``, and the MPI
exchange-and-combine kernels of ``QuEST_cpu_distributed.c``): on TPU a gate is
an axis contraction that XLA vectorises, fuses, and — when the amplitude axis
is sharded over a mesh — lowers to ICI collectives automatically.

State layout
------------
A register of ``N`` qubits is one flat complex ``jax.Array`` of ``2**N``
amplitudes, where bit ``q`` of the amplitude index is the computational-basis
value of qubit ``q`` (identical indexing to the reference, ``QuEST.h:161-192``).
Viewed as a tensor of shape ``(2,)*N`` in C order, qubit ``q`` is axis
``N-1-q``.

Applying a k-qubit operator ``u`` to targets ``(t_0 … t_{k-1})`` (bit ``j`` of
``u``'s index addresses target ``t_j``, the reference's ComplexMatrixN
convention) is:

1. reshape to split out the target (and control) axes — rank ``2(k+c)+1``,
   never rank ``N``, so XLA sees small static shapes;
2. mix the target axes in place: up to 4 targets as unrolled complex
   multiply-adds over the ``2^k`` target slices, more as a contraction;
   nothing is transposed;
3. pass the slices outside the controlled subspace through untouched
   (the reference's ctrlMask skip, ``QuEST_cpu.c:2146-2210``, without
   per-amplitude branching), and flatten.

Targets that form a contiguous block skip the split: one batched matmul
on the ``(pre, 2^k, post)`` view.

Diagonal operators (phase gates, multiRotateZ, dephasing) never pair
amplitudes; they are broadcast elementwise multiplies (`apply_diagonal`),
which XLA fuses into a single memory pass — the analogue of
``statevec_phaseShiftByTerm`` (``QuEST_cpu.c:2946-2985``).

Lane qubits
-----------
The TPU tiles the two minor dimensions of every array by ``(8, 128)``. A
split at qubit ``p`` leaves a minor dimension of ``2^p``, so a gate, control
or diagonal factor on a qubit below 7 would give XLA arrays whose minor
dimension is 1..64: padded up to 128x in device memory, and compiled to
code whose size and compile time grow with the state (a single Hadamard on
qubit 0 of 20 qubits compiled for 95 s into 63 MB of code and 537 MB of
temporaries for an 8 MB state). So whenever such a qubit is involved and
the register is large enough, the state is viewed as ``(rows, 128)``
instead: the low 7 qubits ("lane qubits") are the 128-wide minor axis and
never split. Lane targets fold into one ``(2^j*128)``-square operator
over the lanes and the ``j`` row targets (a matmul on the MXU); lane and
row controls become ``iota`` masks; lane factors of a diagonal become a
128-wide factor row.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "LANE_BITS",
    "pass_boundary",
    "transpose_qubits",
    "apply_unitary",
    "apply_diagonal",
    "bitmask",
    "permutation_to_order",
    "permutation_to_sorted_desc",
    "split_shape",
]


LANE_BITS = 7            # 2^7 = 128: the TPU's lane width
_LANES = 1 << LANE_BITS


def pass_boundary(state: jnp.ndarray) -> jnp.ndarray:
    """Close one operator's memory pass in a traced chain of operators.

    XLA turns a contraction over a 2-wide axis into elementwise
    arithmetic and then fuses a chain of them into one expression in
    which every output recomputes its ``2^k`` inputs: on a described v5e
    chip, eight one-qubit gates on the high qubits of a 28-qubit state
    compiled in 56 s to 233 MB of code, and a whole 28-qubit circuit did
    not finish compiling in ten minutes. The barrier keeps each operator
    one pass over the state (8 gates: 13 s, 23 MB of code) — the memory
    model the planner prices anyway."""
    return jax.lax.optimization_barrier(state)


def bitmask(qubits: Sequence[int]) -> int:
    """OR of ``1 << q`` (the reference's ``getQubitBitMask``,
    ``QuEST_common.c:43-51``)."""
    m = 0
    for q in qubits:
        m |= 1 << int(q)
    return m


def split_shape(num_qubits: int, positions_desc: Sequence[int]) -> tuple[int, ...]:
    """Shape that splits the flat amplitude axis at each qubit position.

    ``positions_desc`` must be strictly descending qubit indices. The returned
    shape interleaves block axes with the 2-sized qubit axes; the axis of the
    i-th position is ``2*i + 1``.
    """
    shape = []
    upper = num_qubits
    for p in positions_desc:
        shape.append(1 << (upper - p - 1))
        shape.append(2)
        upper = p
    shape.append(1 << upper)
    return tuple(shape)


def permutation_to_order(targets: Sequence[int],
                         order: Sequence[int]) -> np.ndarray:
    """Index permutation re-expressing a gate matrix in a new bit order.

    The input matrix indexes bit ``j`` by ``targets[j]``; the output indexes
    bit ``i`` by ``order[i]`` (same qubit set). ``perm[m_new] = m_old``.
    """
    targets = tuple(targets)
    k = len(targets)
    perm = np.zeros(1 << k, dtype=np.int64)
    for mp in range(1 << k):
        m = 0
        for i, q in enumerate(order):
            if (mp >> i) & 1:
                m |= 1 << targets.index(q)
        perm[mp] = m
    return perm


def permutation_to_sorted_desc(targets: Sequence[int]) -> np.ndarray:
    """Index permutation mapping sorted-descending bit order to user order.

    The engine flattens target axes with the highest qubit as the most
    significant bit; the user matrix indexes bit ``j`` by ``targets[j]``.
    Returns ``perm`` with ``perm[m_sorted] = m_user``.
    """
    targets = tuple(targets)
    k = len(targets)
    desc = sorted(targets, reverse=True)
    perm = np.zeros(1 << k, dtype=np.int64)
    for mp in range(1 << k):
        m = 0
        for i, q in enumerate(desc):
            if (mp >> (k - 1 - i)) & 1:
                m |= 1 << targets.index(q)
        perm[mp] = m
    return perm


def apply_unitary(
    state: jnp.ndarray,
    num_qubits: int,
    u: jnp.ndarray,
    targets: Sequence[int],
    ctrl_mask: int = 0,
    flip_mask: int = 0,
    precision=None,
) -> jnp.ndarray:
    """Apply a ``2^k x 2^k`` operator to target qubits of a flat state.

    ``ctrl_mask`` selects control qubits; a control conditions on bit value 1
    unless its bit is also set in ``flip_mask`` (then it conditions on 0) —
    the mask/flip-mask semantics of ``statevec_multiControlledUnitary``
    (``QuEST_cpu.c:2146``) and multiStateControlledUnitary.

    ``precision`` sets the matmul precision of the contraction (default
    ``HIGHEST``, the full-f32 MXU passes; the FAST precision tier passes
    ``Precision.DEFAULT`` — bf16 MXU inputs — through the compiled-
    circuit executors, trading the ~1e-4/gate drift the tier error
    model budgets for one MXU pass instead of six).

    All arguments except ``state`` and ``u`` must be static under jit.
    """
    # HIGHEST keeps the MXU in full-f32 passes: the TPU default (bf16
    # operands) loses ~1e-3 per gate worst case, far outside simulation
    # tolerance unless a caller-stated error budget opted into it
    prec = jax.lax.Precision.HIGHEST if precision is None else precision
    targets = tuple(int(t) for t in targets)
    k = len(targets)
    controls = tuple(q for q in range(num_qubits) if (ctrl_mask >> q) & 1)

    with jax.named_scope(
            f"gate_u{k}q_t{'_'.join(map(str, targets))}"
            + (f"_c{len(controls)}" if controls else "")):
        if _use_lanes(num_qubits, targets):
            return _apply_unitary_lanes(state, num_qubits, u, targets,
                                        ctrl_mask, flip_mask, prec)
        # --- no-transpose fast paths (uncontrolled, contiguous ends) ------
        # A gate on the lowest k qubits is a plain right-matmul on the
        # (rest, 2^k) view; on the highest k, a left-matmul on (2^k, rest).
        # Either costs exactly one read+write pass with one matmul.
        if not controls and set(targets) == set(range(k)):
            u = jnp.asarray(u, dtype=state.dtype)
            if targets != tuple(range(k)):
                perm_asc = permutation_to_order(targets, tuple(range(k)))
                u = u[perm_asc][:, perm_asc]
            s = state.reshape(-1, 1 << k)
            out = jnp.matmul(s, u.T, precision=prec)
            return out.reshape(-1)
        lo = min(targets) if targets else 0
        if not controls and set(targets) == set(range(lo, lo + k)):
            # contiguous block [lo, lo+k): batched matmul on the
            # (pre, 2^k, post) view — bit i of the middle index is qubit
            # lo+i. pre==1 and post==1 degenerate to plain left-matmuls.
            u = jnp.asarray(u, dtype=state.dtype)
            order = tuple(range(lo, lo + k))
            if targets != order:
                perm_o = permutation_to_order(targets, order)
                u = u[perm_o][:, perm_o]
            s = state.reshape(-1, 1 << k, 1 << lo)
            out = jnp.matmul(u, s, precision=prec)
            return out.reshape(-1)

        return _apply_unitary_split(state, num_qubits, u, targets,
                                    ctrl_mask, flip_mask, prec)


# above this many targets the unrolled form's 4^k terms cost more than a
# contraction
_UNROLL_TARGETS = 4


def _apply_unitary_split(state, num_qubits: int, u, targets: tuple,
                         ctrl_mask: int, flip_mask: int, prec):
    """The general path of :func:`apply_unitary`: split the flat axis at
    every target and mix the target axes in place — up to
    ``_UNROLL_TARGETS`` targets as unrolled complex multiply-adds over the
    ``2^k`` target slices, more as a contraction — then select the
    controlled subspace with an ``iota`` mask, one pass later. Nothing is
    transposed and controls never split the state: a transpose of a split
    2^28-amplitude state compiled for minutes into hundreds of MB of TPU
    code, splitting at 19 controls took 319 s, and a select fused into
    the gate compiled a CNOT for ten minutes; this form compiles each in
    seconds."""
    k = len(targets)
    pos_desc = tuple(sorted(targets, reverse=True))
    shape = split_shape(num_qubits, pos_desc)
    axis_of = {p: 2 * i + 1 for i, p in enumerate(pos_desc)}
    t_axes = [axis_of[t] for t in targets]    # gate index bit j <-> t_j
    x = state.reshape(shape)
    if k > _UNROLL_TARGETS:
        # u as a (2,)*2k tensor: output bits k-1..0, then input bits
        in_axes = [t_axes[j] for j in reversed(range(k))]
        ut = jnp.asarray(u, dtype=state.dtype).reshape((2,) * (2 * k))
        new = jnp.tensordot(ut, x, axes=(list(range(k, 2 * k)), in_axes),
                            precision=prec)
        new = jnp.moveaxis(new, list(range(k)), in_axes)
    else:
        new = _mix_unrolled(x, u, t_axes, state.dtype)
    new = new.reshape(-1)
    if not ctrl_mask:
        return new
    return _select_controls(pass_boundary(new), state, num_qubits,
                            ctrl_mask, flip_mask)


def _mix_unrolled(x, u, t_axes: list, dtype):
    """Mix the target slices of the split state ``x``: output slice ``r``
    is ``sum_m u[r, m] * slice_m``. A host matrix drops its zero terms and
    keeps its unit ones as plain slices (X, CNOT and SWAP move data
    without arithmetic)."""
    k = len(t_axes)
    host = u if isinstance(u, np.ndarray) else None
    uj = jnp.asarray(u, dtype=dtype)
    slices = []
    for m in range(1 << k):
        idx = [slice(None)] * x.ndim
        for j, ax in enumerate(t_axes):
            b = (m >> j) & 1
            idx[ax] = slice(b, b + 1)
        slices.append(x[tuple(idx)])

    def term(r, m):
        if host is not None and host[r, m] == 1:
            return slices[m]
        return uj[r, m] * slices[m]
    outs = [sum(term(r, m) for m in range(1 << k)
                if host is None or host[r, m] != 0)
            for r in range(1 << k)]

    def assemble(j, base):
        if j == k:
            return outs[base]
        return jnp.concatenate([assemble(j + 1, base),
                                assemble(j + 1, base | (1 << j))],
                               axis=t_axes[j])
    return assemble(0, 0)


_SWAP = np.eye(4, dtype=np.complex128)[[0, 2, 1, 3]]


def transpose_qubits(state: jnp.ndarray, num_qubits: int,
                     axes: Sequence[int]) -> jnp.ndarray:
    """``state.reshape((2,)*n).transpose(axes).reshape(-1)`` as a sequence
    of qubit swaps, one pass each. A rank-n transpose leaves the two
    minor dimensions of size 2: padded 64x on a TPU and compiled into code
    that grows with the state."""
    n = num_qubits
    # the bit at output position p comes from input position src[p]
    src = [n - 1 - int(axes[n - 1 - p]) for p in range(n)]
    cur = list(range(n))          # cur[p]: input position now held at p
    for p in range(n):
        if cur[p] == src[p]:
            continue
        q = cur.index(src[p])
        state = pass_boundary(apply_unitary(state, n, _SWAP, (p, q)))
        cur[p], cur[q] = cur[q], cur[p]
    return state


def _use_lanes(num_qubits: int, targets: tuple) -> bool:
    """Whether :func:`apply_unitary` takes the lane view: a target below
    qubit 7, and the lane operator (``(2^j*128)^2`` entries for ``j`` row
    targets) smaller than the state — below that size the split view's
    padding costs less than the operator."""
    if num_qubits < LANE_BITS or min(targets) >= LANE_BITS:
        return False
    rows = sum(t >= LANE_BITS for t in targets)
    return 2 * (LANE_BITS + rows) < num_qubits


def _support_operator(u, targets: tuple, support: tuple, ctrl_mask: int,
                      flip_mask: int, dtype):
    """``u`` embedded over the qubits ``support`` (ascending: bit ``i`` of
    the operator's index is qubit ``support[i]``), conditioned on the
    controls of ``ctrl_mask`` that lie in the support and the identity
    elsewhere. Host arithmetic for a host matrix; a gather for a traced
    one (parameterised gates)."""
    dim = 1 << len(support)
    idx = np.arange(dim)
    pos = {q: i for i, q in enumerate(support)}
    m = np.zeros(dim, dtype=np.int64)
    tmask = 0
    for j, t in enumerate(targets):
        m |= ((idx >> pos[t]) & 1) << j
        tmask |= 1 << pos[t]
    cm = cw = 0
    for q in support:
        if (ctrl_mask >> q) & 1:
            cm |= 1 << pos[q]
            if not (flip_mask >> q) & 1:
                cw |= 1 << pos[q]
    ok = (idx & cm) == cw
    base = idx & ~tmask
    sel = (base[:, None] == base[None, :]) & ok[None, :]
    ident = np.diag(~ok)
    if isinstance(u, np.ndarray):
        return np.where(sel, u[m[:, None], m[None, :]], ident).astype(dtype)
    u = jnp.asarray(u, dtype=dtype)
    return jnp.where(sel, u[m[:, None], m[None, :]], ident.astype(dtype))


def _select_controls(new, old, num_qubits: int, ctrl_mask: int,
                     flip_mask: int):
    """``new`` where every control of ``ctrl_mask`` holds, else ``old`` —
    masks from ``iota`` over the ``(rows, 128)`` view (the flat index for
    a register narrower than one row)."""
    if not ctrl_mask:
        return new
    want = ctrl_mask & ~flip_mask
    if num_qubits < LANE_BITS:
        i = jax.lax.broadcasted_iota(jnp.int32, (1 << num_qubits,), 0)
        return jnp.where((i & ctrl_mask) == want, new, old)
    rows = 1 << (num_qubits - LANE_BITS)
    cond = None
    for mask, w, shape, axis in (
            (ctrl_mask & (_LANES - 1), want & (_LANES - 1), (1, _LANES), 1),
            (ctrl_mask >> LANE_BITS, want >> LANE_BITS, (rows, 1), 0)):
        if mask:
            i = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            c = (i & mask) == w
            cond = c if cond is None else cond & c
    return jnp.where(cond, new.reshape(rows, _LANES),
                     old.reshape(rows, _LANES)).reshape(-1)


def _apply_unitary_lanes(state, num_qubits: int, u, targets: tuple,
                         ctrl_mask: int, flip_mask: int, prec):
    """:func:`apply_unitary` for operators touching a lane qubit (module
    docstring, "Lane qubits")."""
    low = _LANES - 1
    row_t = tuple(sorted(t for t in targets if t >= LANE_BITS))
    m = _support_operator(u, targets, tuple(range(LANE_BITS)) + row_t,
                          ctrl_mask & low, flip_mask, state.dtype)
    rows = 1 << (num_qubits - LANE_BITS)
    if not row_t:
        new = jnp.matmul(state.reshape(rows, _LANES), m.T, precision=prec)
    else:
        # row targets move next to the lane axis: the contraction runs
        # over (row targets, lanes), lowest row target just above lane 6
        rdesc = tuple(t - LANE_BITS for t in reversed(row_t))
        shape = split_shape(num_qubits - LANE_BITS, rdesc) + (_LANES,)
        targ_axes = [2 * i + 1 for i in range(len(rdesc))]
        rest = [a for a in range(len(shape) - 1) if a not in targ_axes]
        perm = rest + targ_axes + [len(shape) - 1]
        arr = state.reshape(shape).transpose(perm)
        new = jnp.matmul(arr.reshape(-1, m.shape[0]), m.T, precision=prec)
        new = new.reshape(arr.shape).transpose(np.argsort(perm))
    return _select_controls(new.reshape(-1), state, num_qubits,
                            ctrl_mask & ~low, flip_mask)


def apply_diagonal(
    state: jnp.ndarray,
    num_qubits: int,
    qubits: Sequence[int],
    diag_tensor: jnp.ndarray,
) -> jnp.ndarray:
    """Elementwise-multiply amplitudes by a per-bit-pattern factor.

    ``diag_tensor`` has shape ``(2,)*k``; axis ``i`` is indexed by the bit of
    the i-th qubit of ``qubits`` *sorted descending*. One fused memory pass,
    no amplitude pairing — the fast path for every phase-family gate and for
    dephasing channels.
    """
    pos_desc = tuple(sorted((int(q) for q in qubits), reverse=True))
    with jax.named_scope(f"gate_diag_q{'_'.join(map(str, pos_desc))}"):
        if num_qubits >= LANE_BITS and pos_desc \
                and pos_desc[-1] < LANE_BITS:
            return _apply_diagonal_lanes(state, num_qubits, pos_desc,
                                         diag_tensor)
        shape = split_shape(num_qubits, pos_desc)
        bshape = [1] * len(shape)
        for i in range(len(pos_desc)):
            bshape[2 * i + 1] = 2
        factor = jnp.asarray(diag_tensor, dtype=state.dtype).reshape(bshape)
        return (state.reshape(shape) * factor).reshape(-1)


def _apply_diagonal_lanes(state, num_qubits: int, pos_desc: tuple,
                          diag_tensor):
    """:func:`apply_diagonal` with a lane qubit among ``pos_desc``: the
    lane factors expand to a 128-wide row of the factor table, which
    broadcasts over the ``(..., 2, ..., 128)`` split of the row qubits."""
    row = tuple(p - LANE_BITS for p in pos_desc if p >= LANE_BITS)
    lane = tuple(p for p in pos_desc if p < LANE_BITS)
    lanes = np.arange(_LANES)
    idx = np.zeros(_LANES, dtype=np.int64)
    for i, q in enumerate(lane):
        idx |= ((lanes >> q) & 1) << (len(lane) - 1 - i)
    xp = np if isinstance(diag_tensor, np.ndarray) else jnp
    table = xp.asarray(diag_tensor).reshape(1 << len(row), 1 << len(lane))
    table = table[:, idx]
    shape = split_shape(num_qubits - LANE_BITS, row) + (_LANES,)
    bshape = [1] * len(shape)
    for i in range(len(row)):
        bshape[2 * i + 1] = 2
    bshape[-1] = _LANES
    factor = jnp.asarray(table, dtype=state.dtype).reshape(bshape)
    return (state.reshape(shape) * factor).reshape(-1)
