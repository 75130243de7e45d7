"""Pallas fused gate-layer kernel: one HBM pass for many gates.

The XLA path applies every gate as its own full-state pass (2^n amplitudes
read + written per gate) — the same roofline as the reference's per-gate CUDA
kernels (`QuEST_gpu.cu:667-1246`). But a *layer* of gates on distinct low
qubits is a single linear map acting block-locally, so one kernel can stream
the state through VMEM once and apply the whole layer: an L-gate layer costs
1 memory pass instead of L. XLA cannot do this fusion itself (each gate is a
differently-reshaped matmul), which makes it exactly the Pallas case flagged
in SURVEY.md §7.2.

Qubit classes, with the state viewed as ``(rows, 128)`` float planes:

- **lane qubits** (0..6): bits inside the 128-lane dimension. ANY static
  gate — controlled and multi-qubit included — whose targets all live here
  is a 128x128 matrix on the lane axis (kron-embedded host-side); runs of
  them multiply into ONE matrix applied by MXU matmuls. Diagonal
  (phase-family) ops embed as diagonal matrices.
- **row qubits** (>= 7): bits of the row index. Dense 1q gates whose target
  bit lies inside the kernel block pair rows at stride 2^(q-7) (VPU 2x2
  combine); diagonal factors over up to two row bits become per-row
  multiplicative tables; and gates CONTROLLED on row bits apply under an
  iota-derived row mask — the global row index (grid block base + local
  row) makes any row-bit control addressable, not just in-block ones.

A layer is an ordered list of STAGES (see :class:`LayerOp`); adjacent
compatible stages are merged by the collector (`circuits._collect_layers`),
and the whole list executes inside one ``pallas_call`` — one read + one
write of the state regardless of stage count.

Complex arithmetic runs on split re/im planes (4 real MXU matmuls per lane
matrix; see `core/packing.py` for why planes are the storage format anyway).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

LANE_QUBITS = 7          # 2^7 = 128 lanes
DEFAULT_BLOCK_ROWS = 1024

__all__ = ["LANE_QUBITS", "DEFAULT_BLOCK_ROWS", "LayerOp",
           "embed_lane_matrix", "lane_diag_matrix", "lane_diag_vector",
           "max_mid_qubit", "to_planes", "from_planes", "apply_layer",
           "apply_layer_planes", "apply_layer_batched",
           "ROWGATE_MIN_QUBIT", "ROWGATE_MAX_TARGETS", "rowgate_eligible",
           "apply_rowgate_planes",
           "mxu_group_matrix", "apply_mxu_tile",
           "fused_kraus_apply_batched"]


def embed_lane_matrix(u: np.ndarray, targets: Sequence[int],
                      ctrl_mask: int = 0, flip_mask: int = 0) -> np.ndarray:
    """Embed a gate on lane qubits into the full 128x128 lane operator
    (bit ``j`` of the gate's index addresses ``targets[j]``, the
    ComplexMatrixN convention; controls condition on 1 unless flipped)."""
    k = len(targets)
    dim = 1 << LANE_QUBITS
    full = np.zeros((dim, dim), dtype=np.complex128)
    t_mask = 0
    for t in targets:
        t_mask |= 1 << t
    want = ctrl_mask & ~flip_mask
    for col in range(dim):
        if (col & ctrl_mask) != want:
            full[col, col] = 1.0
            continue
        m = 0
        for j, t in enumerate(targets):
            if (col >> t) & 1:
                m |= 1 << j
        base = col & ~t_mask
        for m2 in range(1 << k):
            row = base
            for j, t in enumerate(targets):
                if (m2 >> j) & 1:
                    row |= 1 << t
            full[row, col] += u[m2, m]
    return full


def lane_diag_vector(tensor: np.ndarray,
                     qubits_desc: Sequence[int]) -> np.ndarray:
    """Evaluate a diagonal factor tensor ((2,)*k, axes = lane qubits sorted
    desc) into a per-lane factor vector of length 128."""
    dim = 1 << LANE_QUBITS
    d = np.ones(dim, dtype=np.complex128)
    k = len(qubits_desc)
    for lane in range(dim):
        idx = tuple((lane >> q) & 1 for q in qubits_desc)
        d[lane] = tensor[idx] if k else tensor[()] if tensor.ndim == 0 else 1.0
    return d


def lane_diag_matrix(tensor: np.ndarray,
                     qubits_desc: Sequence[int]) -> np.ndarray:
    """Embed a diagonal factor tensor ((2,)*k, axes = qubits sorted desc)
    over lane qubits as a diagonal 128x128 operator."""
    return np.diag(lane_diag_vector(tensor, qubits_desc))


def max_mid_qubit(block_rows: int) -> int:
    """Highest qubit index a dense (row-pairing) gate can target for a
    given block size. Controls and diagonal factors address ANY row bit
    (they read the global row index), so this bounds targets only."""
    return LANE_QUBITS + int(np.log2(block_rows)) - 1


def mxu_group_matrix(u: np.ndarray, targets: Sequence[int],
                     row_bits_asc: Sequence[int]) -> np.ndarray:
    """Embed a dense (uncontrolled) gate into the MXU-tile contraction
    operator over ``(lane qubits 0..6) + (row bits + 7)``: a
    ``(2^j * 128, 2^j * 128)`` matrix whose index bit ``l < 7`` is lane
    bit ``l`` and bit ``7 + m`` is row bit ``row_bits_asc[m]`` — exactly
    the flat ``b * 128 + lane`` axis the ``rowmxu`` kernel stage
    contracts after regrouping. ``targets`` are the gate's physical
    qubit positions (lane and row positions mixed freely)."""
    from ..core import matrices as mats
    sup = tuple(range(LANE_QUBITS)) + tuple(
        int(b) + LANE_QUBITS for b in row_bits_asc)
    # quest: allow-host-sync(compile-time operand prep: u is a host
    # matrix, never a device array)
    return mats.embed_in_support(np.asarray(u, np.complex128), targets,
                                 sup)


def mxu_expand(m: np.ndarray, prev_bits: Sequence[int],
               union_bits: Sequence[int]) -> np.ndarray:
    """Expand an MXU-tile operator over ``(lanes + prev_bits)`` to the
    superset support ``(lanes + union_bits)`` (identity on the new row
    bits) — vectorized, so merging adjacent ``rowmxu`` stages with
    different row-bit sets stays cheap at compile time."""
    prev_bits = tuple(int(b) for b in prev_bits)
    union_bits = tuple(int(b) for b in union_bits)
    dim_u = (1 << len(union_bits)) * (1 << LANE_QUBITS)
    idx = np.arange(dim_u)
    a_p = idx & ((1 << LANE_QUBITS) - 1)
    a_e = np.zeros_like(idx)
    e = 0
    for mpos, b in enumerate(union_bits):
        bit = (idx >> (LANE_QUBITS + mpos)) & 1
        if b in prev_bits:
            a_p = a_p | (bit << (LANE_QUBITS + prev_bits.index(b)))
        else:
            a_e = a_e | (bit << e)
            e += 1
    # quest: allow-host-sync(compile-time operand prep: m is the host
    # tile matrix, never a device array)
    return np.asarray(m)[a_p[:, None], a_p[None, :]] \
        * (a_e[:, None] == a_e[None, :])


class LayerOp:
    """A fused layer: an ordered list of stages applied in one HBM pass.

    Stage forms (``q``/mask bit positions are the KERNEL's physical qubit
    positions — the collector has already mapped logical->physical):

    - ``("lane", M)`` — unconditional 128x128 complex matrix on the lane
      axis (a merged run of lane-qubit gates, dense and diagonal).
    - ``("clane", M, row_mask, row_want)`` — lane matrix applied only to
      rows whose global row index matches ``(row & row_mask) == row_want``
      (masks in row-bit coordinates: bit ``p`` = qubit ``p+7``).
    - ``("row", q, u2x2, lane_mask, lane_want, row_mask, row_want)`` —
      dense 2x2 on row-bit target ``q`` (>= 7), conditioned on lane
      controls (mask over the 128-lane index) and/or row controls.
    - ``("rowdiag", table, row_bits)`` — multiplicative per-amplitude
      factor: ``table`` is complex ``(2^k, 128)``; the factor row is
      selected by the bits of the global row index at ``row_bits``
      (ascending positions, in row-bit coordinates).
    - ``("rowmxu", row_bits, M)`` — MXU-shaped fused contraction: the
      ``j`` row bits (ascending, row-bit coordinates) pack with the
      128-lane axis into one ``(2^j * 128)``-dim contraction and ``M``
      is the complex operator over that combined axis (bit ``l < 7`` =
      lane bit ``l``, bit ``7 + m`` = ``row_bits[m]``; see
      :func:`mxu_group_matrix`). One systolic-array matmul serves the
      whole fused dense group — the FAST bf16 tier rides the MXU here
      instead of the VPU row path. Uncontrolled groups only; selection
      is the modeled crossover
      :func:`quest_tpu.parallel.layout.choose_mxu_contraction`.

    Quacks enough like circuits._Op for the executors (kind/targets/
    masks/is_static).
    """

    kind = "layer"
    ctrl_mask = 0
    flip_mask = 0
    is_static = True
    mat_fn = None
    diag_fn = None

    def __init__(self, num_qubits: int, members: int, stages: list,
                 support: Optional[set] = None):
        self.num_qubits = num_qubits
        self.members = members            # how many recorded ops were fused
        self.stages = stages
        if support is None:
            support = set()
            for st in stages:
                if st[0] in ("lane", "clane"):
                    support |= set(range(min(LANE_QUBITS, num_qubits)))
                elif st[0] == "row":
                    support.add(st[1])
                elif st[0] in ("rowk", "rowmxu"):
                    if st[0] == "rowmxu":
                        support |= set(range(min(LANE_QUBITS,
                                                 num_qubits)))
                    support |= {b + LANE_QUBITS for b in st[1]}
                else:
                    support |= {b + LANE_QUBITS for b in st[2]}
        self.targets = tuple(sorted(support))

    # -- legacy views (round-4 shape: one lane matrix + uncontrolled mids) --

    @property
    def lane_matrix(self):
        for st in self.stages:
            if st[0] == "lane":
                return st[1]
        return None

    @property
    def mid_gates(self):
        return [(st[1], st[2]) for st in self.stages
                if st[0] == "row" and st[3] == 0 and st[5] == 0]


def _global_row(base, shape, axis):
    """Global row index, broadcast over ``shape`` along ``axis``."""
    return base + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _mxu_matmuls(re, im, mre_t, mim_t, acc, fast: bool):
    """The shared complex contraction ``(v_re + i v_im) @ (M_re + i
    M_im)^T`` as 4 real MXU matmuls — HIGHEST-precision f32 passes, or
    the FAST tier's bf16-split compensated form (state splits error-free
    into a bf16 hi plane + f32 residual, residual partials combine
    first; same trade as the lane stage, see the comment there)."""
    acc_dt = acc
    if fast:
        lp = jax.lax.Precision.DEFAULT

        def _fdot(v, m):
            hi = v.astype(jnp.bfloat16).astype(acc_dt)
            lo = (v - hi).astype(acc_dt)
            return (jnp.dot(hi, m, preferred_element_type=acc_dt,
                            precision=lp),
                    jnp.dot(lo, m, preferred_element_type=acc_dt,
                            precision=lp))

        rr_h, rr_l = _fdot(re, mre_t)
        ii_h, ii_l = _fdot(im, mim_t)
        ri_h, ri_l = _fdot(re, mim_t)
        ir_h, ir_l = _fdot(im, mre_t)
        return ((rr_h - ii_h) + (rr_l - ii_l),
                (ri_h + ir_h) + (ri_l + ir_l))
    hp = jax.lax.Precision.HIGHEST
    new_re = (jnp.dot(re, mre_t, preferred_element_type=acc_dt,
                      precision=hp)
              - jnp.dot(im, mim_t, preferred_element_type=acc_dt,
                        precision=hp))
    new_im = (jnp.dot(re, mim_t, preferred_element_type=acc_dt,
                      precision=hp)
              + jnp.dot(im, mre_t, preferred_element_type=acc_dt,
                        precision=hp))
    return new_re, new_im


def _row_regroup_plan(rows: int, bits: tuple):
    """Static reshape/transpose plan bringing the row ``bits`` adjacent:
    ``(dims, perm, inv_perm, groups, dim)`` such that reshaping to
    ``dims + (128,)``, transposing by ``perm + (last,)`` and flattening
    yields ``(groups, dim, 128)`` with combined-axis bit ``m`` = row bit
    ``bits[m]`` (the ``rowk`` choreography, factored for reuse)."""
    k = len(bits)
    dim = 1 << k
    rlog = int(np.log2(rows))
    dims = []
    prev = rlog
    for b in reversed(bits):
        dims += [1 << (prev - b - 1), 2]
        prev = b
    dims.append(1 << prev)
    two_axes = [2 * i + 1 for i in range(k)]       # bits[k-1]..bits[0]
    other_axes = [a for a in range(len(dims)) if a not in two_axes]
    perm = other_axes + two_axes
    inv = [0] * len(dims)
    for pos, a in enumerate(perm):
        inv[a] = pos
    return tuple(dims), tuple(perm), tuple(inv), rows // dim, dim


def _layer_kernel(re_ref, im_ref, mre_ref, mim_ref, tre_ref, tim_ref,
                  xre_ref, xim_ref, ore_ref, oim_ref, *, stages,
                  block_rows, batched: bool = False, fast: bool = False):
    from jax.experimental import pallas as pl

    # batched form: the grid grows a LEADING batch dimension and state
    # blocks carry a unit batch axis — grid (B, row_blocks), block
    # (1, block_rows, 128). The row base comes from grid axis 1, so every
    # row-indexed stage (controls, rowdiag tables, rowk regroups) sees the
    # same per-STATE row coordinates as the unbatched kernel.
    if batched:
        re = re_ref[0]
        im = im_ref[0]
        rows = block_rows
        base = pl.program_id(1) * rows
    else:
        re = re_ref[:]
        im = im_ref[:]
        rows = block_rows
        base = pl.program_id(0) * rows
    acc = re.dtype  # f32 accumulate on TPU; f64 under x64 interpret
    for st in stages:
        tag = st[0]
        if tag in ("lane", "clane"):
            _, mi, row_mask, row_want = st
            mre_t = mre_ref[mi, :, :].T
            mim_t = mim_ref[mi, :, :].T
            # out = v @ M^T (columns of M index the input lane), complex
            # via 4 real MXU matmuls on (rows,128)x(128,128).
            # Precision.HIGHEST: the TPU MXU defaults to bf16 inputs,
            # which costs ~1e-4 per layer; HIGHEST selects the f32
            # passes.
            # FAST tier: Precision.DEFAULT (one bf16-input MXU pass
            # where HIGHEST pays six) with bf16-split compensated
            # accumulation — the STATE operand splits error-free into a
            # bf16 hi plane plus the f32 residual, each rides its own
            # cheap pass, and the small residual partial sums combine
            # FIRST so their correction lands in one f32 add instead of
            # drowning term-by-term in the dominant sums. The remaining
            # drift is the per-gate MATRIX rounding the tier error
            # model budgets conservatively at 5e-4/gate
            # (docs/accuracy.md "Precision tiers").
            if fast:
                lp = jax.lax.Precision.DEFAULT

                def _fdot(v, m):
                    hi = v.astype(jnp.bfloat16).astype(acc)
                    lo = (v - hi).astype(acc)
                    return (jnp.dot(hi, m, preferred_element_type=acc,
                                    precision=lp),
                            jnp.dot(lo, m, preferred_element_type=acc,
                                    precision=lp))

                rr_h, rr_l = _fdot(re, mre_t)
                ii_h, ii_l = _fdot(im, mim_t)
                ri_h, ri_l = _fdot(re, mim_t)
                ir_h, ir_l = _fdot(im, mre_t)
                new_re = (rr_h - ii_h) + (rr_l - ii_l)
                new_im = (ri_h + ir_h) + (ri_l + ir_l)
            else:
                hp = jax.lax.Precision.HIGHEST
                new_re = (jnp.dot(re, mre_t, preferred_element_type=acc,
                                  precision=hp)
                          - jnp.dot(im, mim_t, preferred_element_type=acc,
                                    precision=hp))
                new_im = (jnp.dot(re, mim_t, preferred_element_type=acc,
                                  precision=hp)
                          + jnp.dot(im, mre_t, preferred_element_type=acc,
                                    precision=hp))
            new_re = new_re.astype(re.dtype)
            new_im = new_im.astype(im.dtype)
            if row_mask:
                # the row index is already in row-bit coordinates (bit p
                # of the row index = qubit p+7); masks were shifted down
                # by LANE_QUBITS at collection time
                g = _global_row(base, (rows, 1), 0)
                cond = (g & row_mask) == row_want
                re = jnp.where(cond, new_re, re)
                im = jnp.where(cond, new_im, im)
            else:
                re, im = new_re, new_im
        elif tag == "row":
            (_, stride, (ar, ai, br, bi, cr, ci, dr, di),
             lane_mask, lane_want, row_mask, row_want) = st
            blocks = rows // (2 * stride)
            sre = re.reshape(blocks, 2, stride, 128)
            sim = im.reshape(blocks, 2, stride, 128)
            up_re, lo_re = sre[:, 0], sre[:, 1]
            up_im, lo_im = sim[:, 0], sim[:, 1]
            nu_re = ar * up_re - ai * up_im + br * lo_re - bi * lo_im
            nu_im = ar * up_im + ai * up_re + br * lo_im + bi * lo_re
            nl_re = cr * up_re - ci * up_im + dr * lo_re - di * lo_im
            nl_im = cr * up_im + ci * up_re + dr * lo_im + di * lo_re
            if lane_mask or row_mask:
                shape = (blocks, stride, 128)
                cond = None
                if row_mask:
                    # row index of the UP half; the target bit is 0 there
                    # and control masks never include the target bit, so
                    # the condition holds for both halves of the pair
                    blk = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                    s = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                    g_up = base + blk * (2 * stride) + s
                    cond = (g_up & row_mask) == row_want
                if lane_mask:
                    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
                    lcond = (lane & lane_mask) == lane_want
                    cond = lcond if cond is None else cond & lcond
                nu_re = jnp.where(cond, nu_re, up_re)
                nu_im = jnp.where(cond, nu_im, up_im)
                nl_re = jnp.where(cond, nl_re, lo_re)
                nl_im = jnp.where(cond, nl_im, lo_im)
            re = jnp.stack([nu_re, nl_re], axis=1).reshape(rows, 128)
            im = jnp.stack([nu_im, nl_im], axis=1).reshape(rows, 128)
        elif tag == "rowk":
            # k-qubit dense gate on row bits (the
            # multiControlledMultiQubitUnitaryLocal analogue,
            # QuEST_cpu.c:1820-1901): static reshape/transpose brings the
            # k target bits adjacent, then 2^k x 2^k unrolled complex
            # MACs mix the groups. bits ascend; gate-index bit j = bits[j].
            (_, bits, uflat, lane_mask, lane_want,
             row_mask, row_want) = st
            k = len(bits)
            dim = 1 << k
            rlog = int(np.log2(rows))
            # split rows at the target bits: dims MSB->LSB, '2' axes at
            # positions 1, 3, ... (for bits[k-1], bits[k-2], ...)
            dims = []
            prev = rlog
            for b in reversed(bits):
                dims += [1 << (prev - b - 1), 2]
                prev = b
            dims.append(1 << prev)
            two_axes = [2 * i + 1 for i in range(k)]   # bits[k-1]..bits[0]
            other_axes = [a for a in range(len(dims)) if a not in two_axes]
            perm = other_axes + two_axes
            groups = rows // dim

            def regroup(x):
                x = x.reshape(*dims, 128)
                x = jnp.transpose(x, tuple(perm) + (len(dims),))
                return x.reshape(groups, dim, 128)

            def ungroup(x):
                inv = [0] * len(dims)
                for pos, a in enumerate(perm):
                    inv[a] = pos
                x = x.reshape(*[dims[a] for a in perm], 128)
                x = jnp.transpose(x, tuple(inv) + (len(dims),))
                return x.reshape(rows, 128)

            gre, gim = regroup(re), regroup(im)
            slre = [gre[:, g, :] for g in range(dim)]
            slim = [gim[:, g, :] for g in range(dim)]
            nre, nim = [], []
            for gp in range(dim):
                ar = ai = None
                for g in range(dim):
                    ur, ui = uflat[gp * dim + g]
                    if ur == 0.0 and ui == 0.0:
                        continue
                    tr = ur * slre[g] - ui * slim[g]
                    ti = ur * slim[g] + ui * slre[g]
                    ar = tr if ar is None else ar + tr
                    ai = ti if ai is None else ai + ti
                z = jnp.zeros((groups, 128), re.dtype)
                nre.append(z if ar is None else ar)
                nim.append(z if ai is None else ai)
            if lane_mask or row_mask:
                cond = None
                if row_mask:
                    # reconstruct the row index with target bits zeroed
                    # (controls never include targets) from the group
                    # index: bit m of the group enumerates the m-th
                    # non-target row bit, ascending
                    gidx = jax.lax.broadcasted_iota(
                        jnp.int32, (groups, 128), 0)
                    nontgt = [p for p in range(rlog) if p not in bits]
                    row0 = jnp.zeros((groups, 128), jnp.int32)
                    for m, p in enumerate(nontgt):
                        row0 = row0 | (((gidx >> m) & 1) << p)
                    cond = ((base + row0) & row_mask) == row_want
                if lane_mask:
                    lane = jax.lax.broadcasted_iota(
                        jnp.int32, (groups, 128), 1)
                    lcond = (lane & lane_mask) == lane_want
                    cond = lcond if cond is None else cond & lcond
                nre = [jnp.where(cond, nre[g], slre[g])
                       for g in range(dim)]
                nim = [jnp.where(cond, nim[g], slim[g])
                       for g in range(dim)]
            re = ungroup(jnp.stack(nre, axis=1))
            im = ungroup(jnp.stack(nim, axis=1))
        elif tag == "rowmxu":
            # MXU-shaped fused contraction: the j row target bits pack
            # with the 128-lane axis into one (2^j * 128)-dim axis and
            # the whole fused group is a single systolic-array matmul
            # over it — (groups, 2^j*128) x (2^j*128, 2^j*128) — where
            # the row/rowk stages pay 2^k VPU MACs per amplitude
            # (ROADMAP item 4: the FAST bf16 tier rides the MXU).
            _, bits, xi, xdim = st
            dims, perm, inv, groups, gdim = _row_regroup_plan(rows, bits)
            flat = gdim * 128

            def mx_regroup(x):
                x = x.reshape(*dims, 128)
                x = jnp.transpose(x, perm + (len(dims),))
                return x.reshape(groups, flat)

            def mx_ungroup(x):
                x = x.reshape(*[dims[a] for a in perm], 128)
                x = jnp.transpose(x, inv + (len(dims),))
                return x.reshape(rows, 128)

            mre_t = xre_ref[xi, :xdim, :xdim].T
            mim_t = xim_ref[xi, :xdim, :xdim].T
            new_re, new_im = _mxu_matmuls(mx_regroup(re), mx_regroup(im),
                                          mre_t, mim_t, acc, fast)
            re = mx_ungroup(new_re.astype(re.dtype))
            im = mx_ungroup(new_im.astype(im.dtype))
        else:  # rowdiag
            _, toff, bits = st
            g = _global_row(base, (rows, 1), 0)
            cfg = jnp.zeros((rows, 1), jnp.int32)
            for j, b in enumerate(bits):
                cfg = cfg | (((g >> b) & 1) << j)
            fre = jnp.zeros((rows, 128), re.dtype)
            fim = jnp.zeros((rows, 128), im.dtype)
            for c in range(1 << len(bits)):
                sel = cfg == c
                fre = jnp.where(sel, tre_ref[toff + c, :][None, :], fre)
                fim = jnp.where(sel, tim_ref[toff + c, :][None, :], fim)
            new_re = re * fre - im * fim
            new_im = re * fim + im * fre
            re, im = new_re, new_im
    if batched:
        ore_ref[0] = re
        oim_ref[0] = im
    else:
        ore_ref[:] = re
        oim_ref[:] = im


def layer_kernel_plan(layer: LayerOp, num_qubits: int,
                      block_rows: int = DEFAULT_BLOCK_ROWS):
    """The static kernel plan for one fused layer: validated stage
    descriptors plus the stacked matrix/table operands. Shared by
    :func:`apply_layer` and the VMEM-budget tests (which need the EXACT
    per-chip stage chains the collector emits, without tracing).

    Returns ``(kstages, mats, tables, xmats, block_rows, total_rows)``
    — ``xmats`` are the MXU-tile contraction operators of the layer's
    ``rowmxu`` stages (variable dim; stacked zero-padded by the
    operand prep).
    """
    total_rows = (1 << num_qubits) // 128
    if total_rows < 1:
        raise ValueError("fused layers need at least 7 qubits")
    block_rows = min(block_rows, total_rows)
    hi = max_mid_qubit(block_rows)

    # static stage plan + stacked matrix/table operands
    mats: list[np.ndarray] = []
    tables: list[np.ndarray] = []
    xmats: list[np.ndarray] = []
    kstages: list[tuple] = []
    for st in layer.stages:
        if st[0] in ("lane", "clane"):
            if st[0] == "lane":
                m, row_mask, row_want = st[1], 0, 0
            else:
                _, m, row_mask, row_want = st
            kstages.append(("lane", len(mats), int(row_mask), int(row_want)))
            mats.append(np.ascontiguousarray(m))
        elif st[0] == "row":
            _, q, u, lane_mask, lane_want, row_mask, row_want = st
            if not LANE_QUBITS <= q <= hi:
                raise ValueError(
                    f"row-gate target {q} outside [{LANE_QUBITS}, {hi}]")
            u = np.asarray(u)
            kstages.append((
                "row", 1 << (q - LANE_QUBITS),
                (float(u[0, 0].real), float(u[0, 0].imag),
                 float(u[0, 1].real), float(u[0, 1].imag),
                 float(u[1, 0].real), float(u[1, 0].imag),
                 float(u[1, 1].real), float(u[1, 1].imag)),
                int(lane_mask), int(lane_want),
                int(row_mask), int(row_want)))
        elif st[0] == "rowk":
            _, bits, u, lane_mask, lane_want, row_mask, row_want = st
            bits = tuple(int(b) for b in bits)
            if bits and bits[-1] + LANE_QUBITS > hi:
                raise ValueError(
                    f"rowk bit {bits[-1]} outside block row range")
            u = np.asarray(u)
            kstages.append((
                "rowk", bits,
                tuple((float(z.real), float(z.imag)) for z in u.reshape(-1)),
                int(lane_mask), int(lane_want),
                int(row_mask), int(row_want)))
        elif st[0] == "rowmxu":
            _, bits, m = st
            bits = tuple(int(b) for b in bits)
            if bits and bits[-1] + LANE_QUBITS > hi:
                raise ValueError(
                    f"rowmxu bit {bits[-1]} outside block row range")
            # quest: allow-host-sync(static stage plan: host matrix)
            m = np.asarray(m)
            dim = (1 << len(bits)) * (1 << LANE_QUBITS)
            if m.shape != (dim, dim):
                raise ValueError(
                    f"rowmxu matrix shape {m.shape} != {(dim, dim)}")
            kstages.append(("rowmxu", bits, len(xmats), dim))
            xmats.append(np.ascontiguousarray(m))
        else:
            _, table, bits = st
            kstages.append(("rowdiag", len(tables), tuple(int(b)
                                                          for b in bits)))
            tables.extend(np.asarray(table))
    return kstages, mats, tables, xmats, block_rows, total_rows


def choose_block_rows(kstages, mstack, tstack, block_rows: int,
                      itemsize: int, vmem_limit: int,
                      xstack=None) -> tuple[int, int]:
    """Shrink ``block_rows`` until the Mosaic working-set estimate fits
    ``vmem_limit`` (halving trades grid steps for VMEM), respecting the
    pairing floor: a row stage pairing rows at ``stride`` needs its whole
    ``2*stride`` pair group inside one block — never shrink below that
    (the collector validated targets against the PRE-shrink hi).

    Returns ``(block_rows, estimated_bytes)`` — the estimate may still
    exceed the limit when the floor binds.
    """
    min_block = max([2 * st[1] for st in kstages if st[0] == "row"]
                    + [2 << st[1][-1] for st in kstages
                       if st[0] in ("rowk", "rowmxu") and st[1]],
                    default=8)
    est = _vmem_estimate(block_rows, kstages, mstack, tstack, itemsize,
                         xstack)
    while block_rows > max(8, min_block) and est > vmem_limit:
        block_rows //= 2
        est = _vmem_estimate(block_rows, kstages, mstack, tstack,
                             itemsize, xstack)
    return block_rows, est


def _layer_operands(layer: LayerOp, num_qubits: int, block_rows: int,
                    rdtype):
    """Shared operand prep for the (batched and unbatched) layer calls:
    validated stage plan, stacked matrix/table operands as split-plane
    jnp arrays, and the VMEM-fitted block size.

    Mosaic scoped-VMEM budget: the stage chain keeps ~2 live (rows,128)
    plane pairs per stage (Mosaic does not fully reuse buffers across
    stage boundaries), so a long brickwork layer can outgrow the 16 MB
    default limit. Raise the limit toward the chip's real VMEM and, if
    the estimate still exceeds it, halve the block until it fits
    (choose_block_rows). The estimator is a conservative model, not a
    measurement; a compile for a described chip
    (tests/test_chip_compile.py) refuses a kernel the chip would.
    """
    kstages, mats, tables, xmats, block_rows, total_rows = \
        layer_kernel_plan(layer, num_qubits, block_rows)
    mstack = (np.stack(mats) if mats
              else np.zeros((1, 128, 128), np.complex128))
    tstack = (np.stack(tables) if tables
              else np.zeros((1, 128), np.complex128))
    if xmats:
        # the MXU-tile operators may mix dims (one per row-bit count);
        # stack zero-padded to the max — the kernel slices [:dim, :dim]
        xdim = max(m.shape[0] for m in xmats)
        xstack = np.zeros((len(xmats), xdim, xdim), np.complex128)
        for i, m in enumerate(xmats):
            xstack[i, :m.shape[0], :m.shape[1]] = m
    else:
        xstack = np.zeros((1, 8, 8), np.complex128)
    mre = jnp.asarray(mstack.real, rdtype)
    mim = jnp.asarray(mstack.imag, rdtype)
    tre = jnp.asarray(tstack.real, rdtype)
    tim = jnp.asarray(tstack.imag, rdtype)
    xre = jnp.asarray(xstack.real, rdtype)
    xim = jnp.asarray(xstack.imag, rdtype)
    itemsize = np.dtype(rdtype).itemsize
    vmem_limit = int(os.environ.get("QUEST_PALLAS_VMEM_LIMIT",
                                    100 * 1024 * 1024))
    block_rows, _ = choose_block_rows(kstages, mstack, tstack, block_rows,
                                      itemsize, vmem_limit, xstack)
    return (kstages, mstack, tstack, xstack, mre, mim, tre, tim, xre,
            xim, block_rows, total_rows, vmem_limit)


def _compiler_kwargs(interpret: bool, vmem_limit: int) -> dict:
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=vmem_limit)}


def to_planes(state: jnp.ndarray):
    """A flat complex state as its ``(rows, 128)`` re/im float planes,
    the storage the single-state kernels read and write."""
    rdtype = jnp.float32 if state.dtype == jnp.complex64 else jnp.float64
    rows = state.shape[0] // 128
    return (jnp.real(state).astype(rdtype).reshape(rows, 128),
            jnp.imag(state).astype(rdtype).reshape(rows, 128))


def from_planes(re: jnp.ndarray, im: jnp.ndarray) -> jnp.ndarray:
    """The flat complex state of a plane pair (inverse of
    :func:`to_planes`)."""
    return jax.lax.complex(re, im).reshape(-1)


def apply_layer(state: jnp.ndarray, num_qubits: int, layer: LayerOp,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = False,
                fast: bool = False) -> jnp.ndarray:
    """Apply a fused layer to a flat complex state (traceable; call under
    jit — the pallas_call compiles into the surrounding program).

    ``fast=True`` selects the FAST precision tier's lane stage:
    bf16-input (``Precision.DEFAULT``) MXU matmuls with bf16-split
    compensated f32 accumulation instead of the full-f32 ``HIGHEST``
    passes — the per-tier trade the budget API prices
    (:func:`quest_tpu.profiling.choose_tier`)."""
    re, im = apply_layer_planes(*to_planes(state), num_qubits, layer,
                                block_rows=block_rows, interpret=interpret,
                                fast=fast)
    return from_planes(re, im).astype(state.dtype)


def apply_layer_planes(re: jnp.ndarray, im: jnp.ndarray, num_qubits: int,
                       layer: LayerOp,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       interpret: bool = False, fast: bool = False):
    """:func:`apply_layer` on the ``(rows, 128)`` re/im planes of a state;
    returns the new plane pair."""
    from jax.experimental import pallas as pl

    rdtype = re.dtype
    (kstages, mstack, tstack, xstack, mre, mim, tre, tim, xre, xim,
     block_rows, total_rows, vmem_limit) = _layer_operands(
        layer, num_qubits, block_rows, rdtype)
    kernel = functools.partial(_layer_kernel, stages=tuple(kstages),
                               block_rows=block_rows, fast=fast)
    state_spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    mat_spec = pl.BlockSpec(mstack.shape, lambda i: (0, 0, 0))
    tab_spec = pl.BlockSpec(tstack.shape, lambda i: (0, 0))
    xmat_spec = pl.BlockSpec(xstack.shape, lambda i: (0, 0, 0))
    # the kernel's name is the op name a device trace shows
    out_re, out_im = pl.pallas_call(
        kernel,
        name=f"pallas_layer_{layer.members}gates",
        grid=(total_rows // block_rows,),
        in_specs=[state_spec, state_spec, mat_spec, mat_spec,
                  tab_spec, tab_spec, xmat_spec, xmat_spec],
        out_specs=[state_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((total_rows, 128), rdtype)] * 2,
        interpret=interpret,
        **_compiler_kwargs(interpret, vmem_limit),
    )(re, im, mre, mim, tre, tim, xre, xim)
    return out_re, out_im


# A row gate's targets are row bits 3 and above (qubit 10 and above), so
# every partner sub-block of the split view holds whole (8, 128) tiles.
ROWGATE_MIN_QUBIT = LANE_QUBITS + 3
ROWGATE_MAX_TARGETS = 4


def rowgate_eligible(kind: str, ctrl_mask: int,
                     targets: Sequence[int]) -> bool:
    """Whether a planned item runs as a row-gate pass
    (:func:`apply_rowgate_planes`): a dense uncontrolled operator on 1 to
    :data:`ROWGATE_MAX_TARGETS` targets, all at qubit
    :data:`ROWGATE_MIN_QUBIT` or above."""
    return (kind == "u" and not ctrl_mask
            and 1 <= len(targets) <= ROWGATE_MAX_TARGETS
            and min(targets) >= ROWGATE_MIN_QUBIT)


def _rowgate_layout(num_qubits: int, targets: tuple, block_rows: int):
    """The row-gate pass's view and blocks.

    The row index splits at the target bits (``core.apply.split_shape``,
    descending); each target axis is blocked whole (2), and the other
    axes, from the lowest up, take what is left of ``block_rows >> k``
    rows. Returns ``(shape, block, axes)``: the view's shape without the
    lane axis, the block size per axis (1 = squeezed) and, for each kept
    axis of the block, ``("t", j)`` for the axis of ``targets[j]``,
    ``("c", size)`` for a blocked axis above the lowest target, or
    ``("r", size)`` for the axis below it (second-minor)."""
    from ..core.apply import split_shape

    k = len(targets)
    desc = sorted((t - LANE_QUBITS for t in targets), reverse=True)
    shape = split_shape(num_qubits - LANE_QUBITS, desc)
    gate_bit = {t - LANE_QUBITS: j for j, t in enumerate(targets)}
    block = [2] * len(shape)
    left = max(1, block_rows >> k)
    for a in range(2 * k, -1, -2):
        block[a] = min(shape[a], left)
        left //= block[a]
    axes = []
    for a, b in enumerate(block):
        if a % 2:
            axes.append(("t", gate_bit[desc[a // 2]]))
        elif a == 2 * k:
            axes.append(("r", b))
        elif b > 1:
            axes.append(("c", b))
    return shape, tuple(block), tuple(axes)


def _rowgate_kernel(u_ref, re_ref, im_ref, ore_ref, oim_ref, *, axes,
                    sub, terms):
    """``out_r = sum_m u[r, m] x_m`` over the ``2^k`` partner sub-blocks
    of one block, on the VPU, ``sub`` rows of each at a time. ``u_ref``
    holds ``u``'s real parts, then its imaginary parts, row-major;
    ``terms[r]`` lists the columns ``m`` that row ``r`` sums. The sums
    are written with ``lax`` primitives: a dense 4-target operator is
    about 2,000 of them, and each ``jnp`` operator costs about 1 ms of
    tracing, which a program's set-up pays for every kernel it traces."""
    from jax.experimental import pallas as pl
    lax = jax.lax

    dim = len(terms)
    off = dim * dim
    used = sorted({m for row in terms for m in row})
    extents = [a[1] for a in axes if a[0] == "c"] + [axes[-1][1] // sub]
    steps = int(np.prod(extents))
    tile = (sub, 128)

    def body(i, carry):
        pos = []
        for e in reversed(extents):
            pos.append(i % e)
            i = i // e
        pos.reverse()
        rows = pl.ds(pl.multiple_of(pos[-1] * sub, sub), sub)

        def index(m):
            lead = iter(pos[:-1])
            idx = []
            for kind, v in axes:
                if kind == "t":
                    idx.append((m >> v) & 1)
                elif kind == "c":
                    idx.append(next(lead))
                else:
                    idx.append(rows)
            return tuple(idx) + (slice(None),)

        def coef(j):
            return lax.broadcast_in_dim(u_ref[j], tile, ())

        xr = {m: re_ref[index(m)] for m in used}
        xi = {m: im_ref[index(m)] for m in used}
        for r, row in enumerate(terms):
            acc_r = acc_i = None
            for m in row:
                a = coef(r * dim + m)
                b = coef(off + r * dim + m)
                tr = lax.sub(lax.mul(a, xr[m]), lax.mul(b, xi[m]))
                ti = lax.add(lax.mul(a, xi[m]), lax.mul(b, xr[m]))
                acc_r = tr if acc_r is None else lax.add(acc_r, tr)
                acc_i = ti if acc_i is None else lax.add(acc_i, ti)
            if acc_r is None:
                acc_r = acc_i = jnp.zeros(tile, ore_ref.dtype)
            ore_ref[index(r)] = acc_r
            oim_ref[index(r)] = acc_i
        return carry

    jax.lax.fori_loop(0, steps, body, 0)


def apply_rowgate_planes(re: jnp.ndarray, im: jnp.ndarray, num_qubits: int,
                         u, targets: Sequence[int],
                         interpret: bool = False):
    """Apply a dense uncontrolled ``2^k x 2^k`` operator (``k <= 4``,
    every target at qubit 10 or above; bit ``j`` of ``u``'s index
    addresses ``targets[j]``) to the ``(rows, 128)`` re/im planes of a
    state in one HBM pass, in place. Returns the new plane pair.

    The planes are viewed split at the target row bits; one grid step
    reads the ``2^k`` partner sub-blocks of about
    :data:`DEFAULT_BLOCK_ROWS` rows in all and writes their mix back over
    them. ``u`` is an operand in scalar memory, so one kernel serves
    every operator of the same target geometry, a traced one (a
    parameterised gate) included; a host matrix's zero entries are left
    out of the sums."""
    targets = tuple(int(t) for t in targets)
    if not rowgate_eligible("u", 0, targets):
        raise ValueError(f"row-gate targets {targets} need 1 to "
                         f"{ROWGATE_MAX_TARGETS} qubits, all >= "
                         f"{ROWGATE_MIN_QUBIT}")
    dim = 1 << len(targets)
    if isinstance(u, np.ndarray):
        terms = tuple(tuple(int(m) for m in np.flatnonzero(u[r]))
                      for r in range(dim))
        uop = jnp.asarray(np.concatenate([u.real.ravel(), u.imag.ravel()]),
                          re.dtype)
    else:
        terms = (tuple(range(dim)),) * dim
        uj = jnp.asarray(u)
        uop = jnp.concatenate([jnp.real(uj).ravel(),
                               jnp.imag(uj).ravel()]).astype(re.dtype)
    return _rowgate_call(uop, re, im, num_qubits=int(num_qubits),
                         targets=targets, terms=terms,
                         interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("num_qubits", "targets",
                                             "terms", "interpret"))
def _rowgate_call(uop, re, im, *, num_qubits, targets, terms, interpret):
    """The row-gate ``pallas_call`` of :func:`apply_rowgate_planes`. Jitted
    on the geometry, so the items of one circuit that share it share one
    trace and one lowering of the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k = len(targets)
    total_rows = re.shape[0]
    shape, block, axes = _rowgate_layout(
        num_qubits, targets, min(DEFAULT_BLOCK_ROWS, total_rows))
    rblock = [a[1] for a in axes if a[0] == "r"][0]
    kernel = functools.partial(_rowgate_kernel, axes=axes,
                               sub=min(rblock, max(8, 128 >> k)),
                               terms=terms)
    grid_axes = [a for a in range(len(shape)) if shape[a] > block[a]]
    grid = tuple(shape[a] // block[a] for a in grid_axes) or (1,)

    def index_map(*g):
        pos = dict(zip(grid_axes, g))
        return tuple(pos.get(a, 0) for a in range(len(shape))) + (0,)

    state_spec = pl.BlockSpec(
        tuple(pl.squeezed if b == 1 else b for b in block) + (128,),
        index_map)
    view = shape + (128,)
    out_re, out_im = pl.pallas_call(
        kernel,
        name=f"pallas_rowgate_{k}q",
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), state_spec,
                  state_spec],
        out_specs=[state_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct(view, re.dtype)] * 2,
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(uop, re.reshape(view), im.reshape(view))
    return (out_re.reshape(total_rows, 128),
            out_im.reshape(total_rows, 128))


def apply_layer_batched(states: jnp.ndarray, num_qubits: int, layer: LayerOp,
                        block_rows: int = DEFAULT_BLOCK_ROWS,
                        interpret: bool = False,
                        fast: bool = False) -> jnp.ndarray:
    """Apply a fused layer to a BATCH of flat complex states
    ``(batch, 2^n)`` in one ``pallas_call``.

    The kernel grid grows a leading batch dimension — ``(batch,
    row_blocks)`` with state blocks of ``(1, block_rows, 128)`` — so the
    batched ensemble engine keeps the fused-layer pass instead of
    falling back to the per-gate XLA twin (``jax.vmap`` has no batching
    rule for a compiled ``pallas_call``; growing the grid is the
    TPU-native answer). Per-grid-step VMEM working set is identical to
    the unbatched kernel: the batch axis only adds grid steps."""
    from jax.experimental import pallas as pl

    batch = states.shape[0]
    rdtype = jnp.float32 if states.dtype == jnp.complex64 else jnp.float64
    (kstages, mstack, tstack, xstack, mre, mim, tre, tim, xre, xim,
     block_rows, total_rows, vmem_limit) = _layer_operands(
        layer, num_qubits, block_rows, rdtype)
    re = jnp.real(states).astype(rdtype).reshape(batch, total_rows, 128)
    im = jnp.imag(states).astype(rdtype).reshape(batch, total_rows, 128)
    kernel = functools.partial(_layer_kernel, stages=tuple(kstages),
                               block_rows=block_rows, batched=True,
                               fast=fast)
    state_spec = pl.BlockSpec((1, block_rows, 128), lambda b, i: (b, i, 0))
    mat_spec = pl.BlockSpec(mstack.shape, lambda b, i: (0, 0, 0))
    tab_spec = pl.BlockSpec(tstack.shape, lambda b, i: (0, 0))
    xmat_spec = pl.BlockSpec(xstack.shape, lambda b, i: (0, 0, 0))
    out_re, out_im = pl.pallas_call(
        kernel,
        name=f"pallas_layer_b{batch}_{layer.members}gates",
        grid=(batch, total_rows // block_rows),
        in_specs=[state_spec, state_spec, mat_spec, mat_spec,
                  tab_spec, tab_spec, xmat_spec, xmat_spec],
        out_specs=[state_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((batch, total_rows, 128),
                                        rdtype)] * 2,
        interpret=interpret,
        **_compiler_kwargs(interpret, vmem_limit),
    )(re, im, mre, mim, tre, tim, xre, xim)
    return jax.lax.complex(out_re, out_im).reshape(batch, -1).astype(
        states.dtype)


class _ExecCache:
    """Tiny thread-safe keyed executable cache for the standalone
    kernel entries below — the same ``_cached(key, builder)`` idiom
    (and the same LRU bound class) as the engine caches, so quest-lint
    QL002 checks these insertions' key completeness too."""

    def __init__(self, maxsize: int = 16):
        import threading
        self._lock = threading.Lock()
        self._maxsize = maxsize
        self._c = None

    def _cached(self, key, builder):
        from ..circuits import _BoundedExecutableCache
        with self._lock:
            if self._c is None:
                self._c = _BoundedExecutableCache(self._maxsize)
            fn = self._c.get(key)
        if fn is not None:
            return fn
        fn = builder()
        with self._lock:
            self._c[key] = fn
        return fn


_MXU_EXEC = _ExecCache(int(os.environ.get("QUEST_TPU_MXU_TILE_CACHE",
                                          "16")))


def apply_mxu_tile(state: jnp.ndarray, num_qubits: int, u: np.ndarray,
                   targets: Sequence[int], fast: bool = False,
                   interpret: bool = False,
                   block_rows: int = DEFAULT_BLOCK_ROWS) -> jnp.ndarray:
    """Apply ONE dense uncontrolled gate as an MXU-shaped contraction:
    the gate (static host matrix, any mix of lane and row targets within
    the block range) embeds over ``(lane qubits + its row bits)`` into a
    ``(2^j * 128)``-tile operator and runs as systolic-array matmuls in
    one HBM pass — the standalone form of the ``rowmxu`` layer stage
    (bench off/on rows and parity tests drive it directly; compiled
    programs get the same shape through the layer collector).

    The jitted executable is cached per ``(geometry, dtype, tier mode)``
    — the MATRIX is an argument, so one executable serves every gate of
    the same shape."""
    from jax.experimental import pallas as pl

    n = int(num_qubits)
    targets = tuple(int(t) for t in targets)
    bits = tuple(sorted(t - LANE_QUBITS for t in targets
                        if t >= LANE_QUBITS))
    total_rows = (1 << n) // 128
    if total_rows < 1:
        raise ValueError("MXU tiles need at least 7 qubits")
    block_rows = min(block_rows, total_rows)
    if bits and bits[-1] + LANE_QUBITS > max_mid_qubit(block_rows):
        raise ValueError(
            f"row target {bits[-1] + LANE_QUBITS} outside the "
            f"{block_rows}-row block range")
    m = mxu_group_matrix(u, targets, bits)
    dim = m.shape[0]
    rdtype = jnp.float32 if state.dtype == jnp.complex64 else jnp.float64
    dt_token = str(np.dtype(rdtype))
    tier_tok = "fast" if fast else "highest"
    vmem_limit = int(os.environ.get("QUEST_PALLAS_VMEM_LIMIT",
                                    100 * 1024 * 1024))

    def build():
        kernel = functools.partial(
            _layer_kernel, stages=(("rowmxu", bits, 0, dim),),
            block_rows=block_rows, fast=fast)
        state_spec = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
        dummy_spec = pl.BlockSpec((1, 1, 1), lambda i: (0, 0, 0))
        tab_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
        xmat_spec = pl.BlockSpec((1, dim, dim), lambda i: (0, 0, 0))

        def fn(re, im, xre, xim):
            z = jnp.zeros((1, 1, 1), rdtype)
            zt = jnp.zeros((1, 1), rdtype)
            return pl.pallas_call(
                kernel,
                name=f"pallas_mxu_tile_{dim}",
                grid=(total_rows // block_rows,),
                in_specs=[state_spec, state_spec, dummy_spec, dummy_spec,
                          tab_spec, tab_spec, xmat_spec, xmat_spec],
                out_specs=[state_spec, state_spec],
                out_shape=[jax.ShapeDtypeStruct((total_rows, 128),
                                                rdtype)] * 2,
                interpret=interpret,
                **_compiler_kwargs(interpret, vmem_limit),
            )(re, im, z, z, zt, zt, xre, xim)

        return jax.jit(fn)

    call = _MXU_EXEC._cached(
        ("mxu_tile", n, bits, block_rows, dt_token, tier_tok,
         bool(interpret)), build)
    re = jnp.real(state).astype(rdtype).reshape(total_rows, 128)
    im = jnp.imag(state).astype(rdtype).reshape(total_rows, 128)
    xre = jnp.asarray(m.real, rdtype)[None]
    xim = jnp.asarray(m.imag, rdtype)[None]
    out_re, out_im = call(re, im, xre, xim)
    return jax.lax.complex(out_re, out_im).reshape(-1).astype(state.dtype)


def _kraus_kernel(re_ref, im_ref, kre_ref, kim_ref, p_ref, u_ref,
                  ore_ref, oim_ref, *, num_ops, block_rows):
    """Fused per-trajectory Kraus draw + apply + renormalise: ONE kernel
    replaces the XLA chain categorical-draw -> stacked-operator gather
    -> apply -> rsqrt renorm. The draw is inverse-CDF over the channel
    probabilities against the trajectory's uniform (scalar unrolled —
    K is the Kraus count, 2-4 for every physical channel), the selected
    lane-embedded operator is blended by exact one-hot weights, the
    renormalisation ``1/sqrt(p_j)`` folds into the operator, and the
    state streams through VMEM exactly once."""
    from jax.experimental import pallas as pl

    # the probabilities and uniforms of every trajectory sit whole in
    # scalar memory; this grid step reads its trajectory's row
    t = pl.program_id(0)
    re = re_ref[0]
    im = im_ref[0]
    acc = re.dtype
    total = p_ref[t, 0]
    for k in range(1, num_ops):
        total = total + p_ref[t, k]
    # cap the threshold STRICTLY below the total: fl(u * total) can
    # round up to `total` at u -> 1, where every prefix would count and
    # the clamp would select branch K-1 even at p_{K-1} == 0 — a
    # zero-probability draw the XLA categorical never makes, rsqrt'd
    # into a garbage trajectory. With uu < total the selected branch
    # (the first prefix sum exceeding uu) always carries positive
    # probability; prefixes that EQUAL uu are counted as used up, so a
    # leading zero-probability branch is skipped at u == 0 too.
    uu = jnp.minimum(u_ref[t, 0] * total,
                     total - total * jnp.finfo(acc).eps)
    cum = p_ref[t, 0] * 0.0
    cnt = jnp.int32(0)
    for k in range(num_ops):
        cum = cum + p_ref[t, k]
        cnt = cnt + (cum <= uu).astype(jnp.int32)
    jidx = jnp.minimum(cnt, num_ops - 1)
    psel = p_ref[t, 0] * 0.0
    for k in range(num_ops):
        psel = psel + (jidx == k).astype(acc) * p_ref[t, k]
    scale = jax.lax.rsqrt(jnp.maximum(psel, jnp.finfo(acc).tiny))
    mre = (jidx == 0).astype(acc) * kre_ref[0]
    mim = (jidx == 0).astype(acc) * kim_ref[0]
    for k in range(1, num_ops):
        w = (jidx == k).astype(acc)
        mre = mre + w * kre_ref[k]
        mim = mim + w * kim_ref[k]
    mre = mre * scale
    mim = mim * scale
    new_re, new_im = _mxu_matmuls(re, im, mre.T, mim.T, acc, False)
    ore_ref[0] = new_re.astype(re.dtype)
    oim_ref[0] = new_im.astype(im.dtype)


def fused_kraus_apply_batched(states: jnp.ndarray, num_qubits: int,
                              kstack: np.ndarray, probs: jnp.ndarray,
                              u01: jnp.ndarray,
                              block_rows: int = DEFAULT_BLOCK_ROWS,
                              interpret: bool = False) -> jnp.ndarray:
    """Draw + apply one Kraus channel for a whole trajectory batch in
    ONE ``pallas_call``: ``states`` is the ``(T, 2^n)`` complex batch,
    ``kstack`` the ``(K, 128, 128)`` LANE-EMBEDDED operator stack (all
    channel targets below qubit 7 — :func:`embed_lane_matrix` per
    operator), ``probs`` the ``(T, K)`` physical channel probabilities
    (one reduced-density pass, computed upstream), and ``u01`` the
    ``(T,)`` per-trajectory uniforms driving the inverse-CDF draw.
    Grid ``(T, row_blocks)``; traceable — call under jit."""
    from jax.experimental import pallas as pl

    T = states.shape[0]
    n = int(num_qubits)
    K = int(kstack.shape[0])
    total_rows = (1 << n) // 128
    if total_rows < 1:
        raise ValueError("the fused Kraus kernel needs at least 7 qubits")
    block_rows = min(block_rows, total_rows)
    rdtype = jnp.float32 if states.dtype == jnp.complex64 \
        else jnp.float64
    vmem_limit = int(os.environ.get("QUEST_PALLAS_VMEM_LIMIT",
                                    100 * 1024 * 1024))
    re = jnp.real(states).astype(rdtype).reshape(T, total_rows, 128)
    im = jnp.imag(states).astype(rdtype).reshape(T, total_rows, 128)
    kre = jnp.asarray(np.ascontiguousarray(kstack.real), rdtype)
    kim = jnp.asarray(np.ascontiguousarray(kstack.imag), rdtype)
    p2 = jnp.asarray(probs, rdtype).reshape(T, K)
    u2 = jnp.asarray(u01, rdtype).reshape(T, 1)
    kernel = functools.partial(_kraus_kernel, num_ops=K,
                               block_rows=block_rows)
    state_spec = pl.BlockSpec((1, block_rows, 128), lambda t, i: (t, i, 0))
    k_spec = pl.BlockSpec((K, 128, 128), lambda t, i: (0, 0, 0))
    # TPU blocks need (8, 128)-divisible minor dims or the whole array:
    # the (T, K) probabilities and (T, 1) uniforms go whole into SMEM
    if interpret:
        p_spec = pl.BlockSpec((T, K), lambda t, i: (0, 0))
        u_spec = pl.BlockSpec((T, 1), lambda t, i: (0, 0))
    else:
        from jax.experimental.pallas import tpu as pltpu
        p_spec = u_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_re, out_im = pl.pallas_call(
        kernel,
        name=f"pallas_kraus_t{T}_k{K}",
        grid=(T, total_rows // block_rows),
        in_specs=[state_spec, state_spec, k_spec, k_spec, p_spec,
                  u_spec],
        out_specs=[state_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((T, total_rows, 128),
                                        rdtype)] * 2,
        interpret=interpret,
        **_compiler_kwargs(interpret, vmem_limit),
    )(re, im, kre, kim, p2, u2)
    return jax.lax.complex(out_re, out_im).reshape(T, -1).astype(
        states.dtype)


def _vmem_estimate(block_rows: int, kstages, mstack, tstack,
                   itemsize: int, xstack=None) -> int:
    """Conservative Mosaic working-set model for one grid step: in + out
    plane pairs with double-buffering (x2), ~2 extra live plane pairs per
    stage (a rowk stage keeps its 2^k group slices live, so it weighs
    2^(k-1) plain stages; a rowmxu stage keeps its regrouped planes —
    one full pair — live next to the contraction), plus the stacked
    operand buffers (the MXU-tile stack included)."""
    plane_pair = 2 * block_rows * 128 * itemsize
    weight = sum((1 << len(st[1])) // 2 if st[0] == "rowk"
                 else 2 if st[0] == "rowmxu" else 1
                 for st in kstages)
    xbytes = 2 * int(np.prod(xstack.shape)) * itemsize \
        if xstack is not None else 0
    return (4 * plane_pair + 2 * weight * plane_pair
            + 2 * int(np.prod(mstack.shape)) * itemsize
            + 2 * int(np.prod(tstack.shape)) * itemsize + xbytes)
