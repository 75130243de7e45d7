"""Compensated reductions for single-precision accuracy parity.

The reference leans on Kahan summation for its distributed probability
reductions (``statevec_calcTotalProb``, ``QuEST_cpu_distributed.c:87-109``)
and offers a quad-precision build when double isn't enough
(``QuEST_precision.h:28-65``). On TPU, float64 is unavailable in hardware,
so single-precision registers need error-compensated reductions to approach
the reference's 1e-10-class accuracy for scalar results.

Three error-free transformations, all branch-free vector ops (VPU-friendly,
no loop-carried dependency — sequential Kahan would serialise under XLA):

1. **TwoSum cascade** (`sum_compensated`): log2(n) halving levels; each
   level recovers the exact rounding error of every pairwise add (Knuth
   TwoSum) into a correction stream. Total extra memory traffic ~1x input.
2. **Veltkamp split products** (`_split` / `dot_pair`): a*b is computed as
   four exactly-representable partial products (12-bit x 12-bit significand
   pieces), so dot products and |amp|^2 sums accumulate true products, not
   f32-rounded ones.
3. **Pair-return** (`*_pair` functions): the final (sum, error) pair is
   returned unadded; the API layer combines the two floats in host double
   precision, dodging the final f32 rounding (~6e-8 relative) entirely.

Measured (tools/accuracy_table.py): naive f32 totalProb at 2^20 amps is
~1e-7 off; the pair path is exact to the f32 state's true sum (<1e-15),
leaving per-gate amplitude drift as the only residual vs an f64 golden.

Under a sharded mesh everything here is elementwise + reduce, so it runs
shard-local with the last log2(n_devices) cascade levels lowering to XLA
collectives — the same psum-replaces-MPI_Allreduce story as plain sums.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

__all__ = ["sum_compensated", "sum_pair", "dot_pair", "vdot_pair",
           "vdot_compensated", "pauli_masks", "pauli_term_bucket",
           "pauli_sum_operands", "pauli_sum_expvals_sv",
           "pauli_sum_expvals_dm", "pauli_sum_total_sv",
           "pauli_sum_total_dm", "pauli_apply_sv", "pauli_sum_apply_sv",
           "welford_wave", "welford_merge",
           "welford_stderr", "score_surrogate"]


def _two_sum(a, b):
    """Knuth TwoSum: s = fl(a+b) and the exact rounding error e
    (a + b == s + e in exact arithmetic). Branch-free."""
    s = a + b
    b_virtual = s - a
    a_virtual = s - b_virtual
    e = (a - a_virtual) + (b - b_virtual)
    return s, e


def _split(x):
    """Veltkamp split: x == hi + lo with hi, lo each carrying at most half
    of the significand bits, so pairwise products of pieces are exact."""
    bits = 12 if x.dtype == jnp.float32 else 27
    c = x * float((1 << bits) + 1)
    hi = c - (c - x)
    return hi, x - hi


# the row length of the first reduction levels (see sum_pair)
_ROW = 1 << 20


def sum_pair(x):
    """Compensated sum of a real array; returns the unadded (sum, err) pair
    so callers can combine at higher precision.

    Each level adds two halves of the array, and each level is its own
    pass: fused, the levels form one expression over the whole input (a
    strided even/odd pairing compiled for 11 minutes into 479 MB of TPU
    code at 2^28 amplitudes). The last axis is viewed as rows of up to
    2^20 and halved along the rows first, so that on a mesh every pair
    lies on one device: whole halves of a sharded array pair amplitudes held
    by different chips, and a 30-qubit state on four chips then asked
    for 16 GB per chip. The input is its own pass too: fused into the
    first level, the error-free products that feed it (``_two_prod``)
    were contracted into FMAs on the CPU and lost their exactness."""
    x = lax.optimization_barrier(jnp.atleast_1d(x))
    err = jnp.zeros((), dtype=x.dtype)
    # rows along the last axis only: merging it with a leading (plane)
    # axis would interleave the shards of a mesh
    m = x.shape[-1]
    row = min(m & -m, _ROW)               # a power of two dividing m
    x = x.reshape(x.shape[:-1] + (m // row, row))
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        s, e = _two_sum(x[..., :h], x[..., h:])
        # the e's are O(eps)·|s| each; their naive sum contributes only a
        # second-order O(eps²·n) error to the final result
        err = err + jnp.sum(e)
        x = lax.optimization_barrier(s)
    x = x.reshape(-1)
    while x.shape[0] > 1:
        n = x.shape[0]
        if n % 2:
            x = jnp.concatenate([x, jnp.zeros((1,), dtype=x.dtype)])
            n += 1
        s, e = _two_sum(x[:n // 2], x[n // 2:])
        err = err + jnp.sum(e)
        x = lax.optimization_barrier(s)
    return x[0], err


def sum_compensated(x) -> jnp.ndarray:
    """Compensated sum of a real 1-D array (shape static under jit)."""
    s, e = sum_pair(x)
    return s + e


def dot_pair(a, b):
    """sum(a*b) for real arrays with exact partial products: returns the
    (sum, err) pair. The four exact partial products of each element fold
    into one rounded sum plus its error terms before the reduction — four
    separate streams would hold four state-sized temporaries (13 GB at
    2^28 amplitudes); the error terms are O(eps) each, so their naive sum
    costs only a second-order error."""
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    s1, e1 = _two_sum(a_hi * b_hi, a_hi * b_lo)
    s2, e2 = _two_sum(a_lo * b_hi, a_lo * b_lo)
    s, e3 = _two_sum(s1, s2)
    total, err = sum_pair(s)
    return total, err + jnp.sum(e1 + e2 + e3)


def vdot_pair(a, b):
    """<a|b> for complex vectors; returns ((re, re_err), (im, im_err))."""
    ar, ai = jnp.real(a), jnp.imag(a)
    br, bi = jnp.real(b), jnp.imag(b)
    re_s1, re_e1 = dot_pair(ar, br)
    re_s2, re_e2 = dot_pair(ai, bi)
    im_s1, im_e1 = dot_pair(ar, bi)
    im_s2, im_e2 = dot_pair(ai, br)
    re, re_c = _two_sum(re_s1, re_s2)
    im, im_c = _two_sum(im_s1, -im_s2)
    return (re, re_c + re_e1 + re_e2), (im, im_c + im_e1 - im_e2)


def vdot_compensated(a, b) -> jnp.ndarray:
    """<a|b> with compensated accumulation, collapsed back to the input
    dtype (jit-internal use; the pair API is the full-accuracy path)."""
    (re, re_e), (im, im_e) = vdot_pair(a, b)
    return jnp.asarray((re + re_e) + 1j * (im + im_e), dtype=a.dtype)


# ---------------------------------------------------------------------------
# term-batched Pauli-sum reduction (device-resident observables)
# ---------------------------------------------------------------------------
#
# A Pauli string P = prod_q sigma_{c_q} is fully described by three bit
# masks (x, y, z); its action on a basis state is
#
#     P |k> = i^|y| (-1)^{popcount(k & (y|z))} |k ^ (x|y)>,
#
# so <psi|P|psi> is ONE xor-gather + sign-flip + reduce pass — no gate
# applications, no per-term workspace state. The masks are plain integer
# DATA, not static trace arguments: one compiled executable serves every
# Hamiltonian of a given (bucketed) term count, the term loop is a
# ``lax.map`` (sequential scan, no unrolled trace, compile time O(1) in
# the term count), and the whole sum leaves the device as a single
# scalar. This is what lets ``calcExpecPauliSum`` and
# ``CompiledCircuit.expectation_sweep`` evaluate a 100-term Hamiltonian
# over a 64-point sweep with ONE device->host transfer, where the
# reference pays one workspace round-trip per term per point
# (``QuEST_common.c:464-491``).


def pauli_masks(codes_flat, num_qubits: int):
    """Flat pauli codes (term-major, code of qubit q of term t at
    ``codes_flat[t*n + q]``; 0=I 1=X 2=Y 3=Z) -> (xmask, ymask, zmask)
    int64 arrays of shape ``(num_terms,)``. Host-side."""
    codes = np.asarray(codes_flat, dtype=np.int64).reshape(-1, num_qubits)
    bits = np.int64(1) << np.arange(num_qubits, dtype=np.int64)
    return ((codes == 1) @ bits, (codes == 2) @ bits, (codes == 3) @ bits)


def pauli_term_bucket(num_terms: int) -> int:
    """Static term-count bucket: next power of two at or above (floor 8).
    Term masks are data, so the only recompile key left is the mask
    array's SHAPE — bucketing it means one executable per power-of-two
    band of Hamiltonian sizes. Padding terms are all-identity with
    coefficient zero (their expectation, the state norm, is multiplied
    away exactly)."""
    b = 8
    while b < num_terms:
        b <<= 1
    return b


def pauli_sum_operands(codes_flat, num_qubits: int, coeffs):
    """The full device-operand set for a Pauli-sum reduction: masks from
    :func:`pauli_masks`, term count padded to :func:`pauli_term_bucket`
    with zero-coefficient identity terms. ONE encoder for every consumer
    (``calcExpecPauliSum``, ``CompiledCircuit.expectation_sweep``), so
    the mask convention cannot desynchronise between call sites.
    Returns ``(xmask, ymask, zmask, coeffs)`` numpy arrays of the
    bucketed length."""
    xm, ym, zm = pauli_masks(codes_flat, num_qubits)
    num_terms = xm.shape[0]
    bucket = pauli_term_bucket(num_terms)
    coeffs = np.pad(np.asarray(coeffs, dtype=np.float64)[:num_terms],
                    (0, bucket - num_terms))
    if bucket > num_terms:
        xm, ym, zm = (np.pad(m, (0, bucket - num_terms))
                      for m in (xm, ym, zm))
    return xm, ym, zm, coeffs


def _phase_weight(ymask, dtype):
    """(re, im) of i^popcount(y) — the Pauli string's global unit."""
    ph = lax.population_count(ymask) % 4
    wr = jnp.asarray([1.0, 0.0, -1.0, 0.0], dtype)[ph]
    wi = jnp.asarray([0.0, 1.0, 0.0, -1.0], dtype)[ph]
    return wr, wi


def pauli_sum_expvals_sv(z, xmask, ymask, zmask, compensated: bool = False):
    """Per-term <z|P_t|z> for a flat complex statevector ``z`` and mask
    arrays of shape ``(T,)``. Returns a real ``(T,)`` vector; traceable,
    masks are data. Each term is one xor-gather pass over the state.

    ``compensated=True`` accumulates each term through the
    Veltkamp-split/TwoSum pair machinery (:func:`dot_pair`) instead of a
    naive f32 reduce — the SINGLE-compensated precision tier's
    observable path (~4x the memory traffic per term; exact to the f32
    state's true sum, docs/accuracy.md §1). The FAST tier takes the
    naive branch: its budget already absorbs the ~1e-7 reduction error."""
    idx = jnp.arange(z.shape[0])
    rdtype = jnp.real(z).dtype
    zr, zi = jnp.real(z), jnp.imag(z)

    def one(masks):
        xm, ym, zm = (m.astype(idx.dtype) for m in masks)
        j = idx ^ (xm | ym)
        sign = (1 - 2 * (lax.population_count(j & (ym | zm)) & 1)
                ).astype(rdtype)
        if compensated:
            # acc = sum(conj(z) * z[j] * sign), each real dot error-free
            zjr, zji = zr[j] * sign, zi[j] * sign
            re_s1, re_e1 = dot_pair(zr, zjr)
            re_s2, re_e2 = dot_pair(zi, zji)
            im_s1, im_e1 = dot_pair(zr, zji)
            im_s2, im_e2 = dot_pair(zi, zjr)
            acc_re = (re_s1 + re_s2) + (re_e1 + re_e2)
            acc_im = (im_s1 - im_s2) + (im_e1 - im_e2)
        else:
            acc = jnp.sum(jnp.conj(z) * z[j] * sign)
            acc_re, acc_im = jnp.real(acc), jnp.imag(acc)
        wr, wi = _phase_weight(ym, rdtype)
        return wr * acc_re - wi * acc_im

    return lax.map(one, (xmask, ymask, zmask))


def pauli_sum_expvals_dm(flat, num_qubits: int, xmask, ymask, zmask,
                         compensated: bool = False):
    """Per-term Tr(P_t rho) for a flat density vector
    (``flat[r + c*2^n]``, columns on the high bits). Each term reads only
    the ``2^n`` entries ``rho[r^m, r]`` — a diagonal-sized gather, NOT a
    full ``2^(2n)`` pass (the round-2 path applied P as gates to the
    whole flat vector per term). ``compensated=True`` runs the
    diagonal-sized sum through the TwoSum cascade (:func:`sum_pair`;
    the SINGLE-compensated tier — no split products needed: the gather
    entries are used unmultiplied)."""
    dim = 1 << num_qubits
    mat = flat.reshape(dim, dim)      # mat[c, r] = rho[r, c]
    rows = jnp.arange(dim)
    rdtype = jnp.real(flat).dtype

    def one(masks):
        xm, ym, zm = (m.astype(rows.dtype) for m in masks)
        j = rows ^ (xm | ym)          # r ^ m: the paired row index
        sign = (1 - 2 * (lax.population_count(j & (ym | zm)) & 1)
                ).astype(rdtype)
        picked = mat[rows, j] * sign          # sum_r rho[r^m, r] * sign
        if compensated:
            re_s, re_e = sum_pair(jnp.real(picked))
            im_s, im_e = sum_pair(jnp.imag(picked))
            acc_re, acc_im = re_s + re_e, im_s + im_e
        else:
            acc = jnp.sum(picked)
            acc_re, acc_im = jnp.real(acc), jnp.imag(acc)
        wr, wi = _phase_weight(ym, rdtype)
        return wr * acc_re - wi * acc_im

    return lax.map(one, (xmask, ymask, zmask))


def pauli_apply_sv(z, xmask, ymask, zmask):
    """``P|z>`` for ONE Pauli string given as scalar bit masks: the same
    xor-gather + sign + ``i^|y|`` convention as
    :func:`pauli_sum_expvals_sv` (one definition of the mask action —
    the expectation of the applied state reproduces the reduction's
    value bit for bit), but returning the full transformed statevector
    instead of the scalar. One gather pass, no per-qubit gate loop —
    the Trotter-step kernel (:mod:`quest_tpu.ops.dynamics`) composes
    ``exp(-i theta P)`` from this plus the identity. Masks are DATA
    (traced scalars), so one compiled step serves every Hamiltonian of
    a given term bucket."""
    idx = jnp.arange(z.shape[0])
    rdtype = jnp.real(z).dtype
    xm, ym, zm = (jnp.asarray(m).astype(idx.dtype)
                  for m in (xmask, ymask, zmask))
    j = idx ^ (xm | ym)
    # (P z)[k] = i^|y| (-1)^{popcount(j & (y|z))} z[j] with
    # j = k ^ (x|y) — the source basis state carries the Z/Y parity,
    # the same ``j``-side popcount the expvals kernel takes, so
    # <z|pauli_apply_sv(z)> == pauli_sum_expvals_sv bit for bit
    sign = (1 - 2 * (lax.population_count(j & (ym | zm)) & 1)
            ).astype(rdtype)
    wr, wi = _phase_weight(ym, rdtype)
    return z[j] * sign * lax.complex(wr, wi).astype(z.dtype)


def pauli_sum_apply_sv(z, xmask, ymask, zmask, coeffs):
    """``H|z> = sum_t coeffs[t] * P_t|z>`` — one xor-gather pass per
    term through a ``lax.scan`` accumulator (sequential, compile time
    O(1) in the term count; masks are data). The Lanczos ground-state
    kernel's matrix-vector product."""

    def body(acc, operands):
        xm, ym, zm, c = operands
        return acc + c.astype(jnp.real(z).dtype) * pauli_apply_sv(
            z, xm, ym, zm), None

    init = jnp.zeros_like(z)
    acc, _ = lax.scan(body, init,
                      (jnp.asarray(xmask), jnp.asarray(ymask),
                       jnp.asarray(zmask), jnp.asarray(coeffs)))
    return acc


def pauli_sum_total_sv(z, xmask, ymask, zmask, coeffs,
                       compensated: bool = False):
    """sum_t coeffs[t] * <z|P_t|z> (real scalar, device-resident)."""
    vals = pauli_sum_expvals_sv(z, xmask, ymask, zmask,
                                compensated=compensated)
    return jnp.sum(vals.astype(coeffs.dtype) * coeffs)


def pauli_sum_total_dm(flat, num_qubits: int, xmask, ymask, zmask, coeffs,
                       compensated: bool = False):
    """sum_t coeffs[t] * Tr(P_t rho) (real scalar, device-resident)."""
    vals = pauli_sum_expvals_dm(flat, num_qubits, xmask, ymask, zmask,
                                compensated=compensated)
    return jnp.sum(vals.astype(coeffs.dtype) * coeffs)


# ---------------------------------------------------------------------------
# device-resident running statistics (trajectory convergence loop)
# ---------------------------------------------------------------------------
#
# The trajectory engine (ops/trajectories.py) runs stochastic ensembles
# in WAVES and stops when the standard error of the running mean fits the
# caller's sampling budget. The running (count, mean, M2) triple lives on
# the device — each wave executable folds its new per-trajectory values
# in with Chan's parallel-merge rule, so the only device->host traffic
# per wave is the 3-scalar (per row) carry the stop decision reads.
# Padded rows (device-multiple wave buckets) carry weight 0 and drop out
# of the statistics EXACTLY, not approximately.


def welford_wave(vals, weights):
    """(count, mean, M2) of one wave of per-trajectory values under a
    0/1 ``weights`` mask (padded wave rows contribute nothing). ``vals``
    may be ``(W,)``, ``(B, W)``, or ``(B, C, W)`` (the gradient wave
    loop's per-component form: C = params + 1) — always reduced over
    the last axis; weights broadcast against it."""
    w = jnp.broadcast_to(weights.astype(vals.dtype), vals.shape)
    n = jnp.sum(w, axis=-1)
    safe = jnp.maximum(n, 1.0)
    mean = jnp.sum(vals * w, axis=-1) / safe
    m2 = jnp.sum(w * (vals - mean[..., None]) ** 2, axis=-1)
    return n, mean, m2


def welford_merge(a, b):
    """Chan's parallel combine of two (count, mean, M2) triples (scalar
    or elementwise over matching shapes): exact pooled statistics, no
    pass over the underlying samples."""
    na, ma, sa = a
    nb, mb, sb = b
    n = na + nb
    safe = jnp.maximum(n, 1.0)
    delta = mb - ma
    mean = ma + delta * nb / safe
    m2 = sa + sb + delta * delta * na * nb / safe
    return n, mean, m2


def score_surrogate(value, logq, baseline=0.0):
    """The differentiation surrogate for a stochastic-trajectory
    estimator: ``value + (stop_grad(value) - stop_grad(baseline)) *
    (logq - stop_grad(logq))``.

    A trajectory's value ``v_j(theta)`` is drawn with a
    parameter-dependent measure ``p_j(theta)`` (the Kraus draw
    probabilities read the evolving state), so the pathwise derivative
    alone — ``E[dv_j]`` — misses the measure term ``sum_j v_j dp_j``
    and is a BIASED estimate of ``d/dtheta E[v]``. The surrogate's
    primal is exactly ``value`` (the added term is identically zero),
    while its gradient is the pathwise term PLUS the score-function
    (REINFORCE) correction ``v_j * d log p_j`` — together the unbiased
    total derivative, so the trajectory-gradient mean converges to the
    density-path gradient at the usual O(1/sqrt(T)). ``logq`` is the
    accumulated log-probability of every channel draw the trajectory
    took (normalised per channel).

    ``baseline`` is the standard REINFORCE variance-reduction control
    variate: any value independent of THIS draw (the gradient wave
    loop passes the running mean of earlier waves) leaves the
    expectation of the score term unchanged — ``E[b * dlogp] = b *
    d(sum_j p_j) = 0`` — while centring the ``v_j`` weights, which
    shrinks the score term's variance roughly by ``Var[v - b] /
    Var[v]``. Always wrapped in ``stop_gradient``: the baseline must
    never contribute a pathwise derivative of its own."""
    sg = lax.stop_gradient
    return value + (sg(value) - sg(baseline)) * (logq - sg(logq))


def welford_stderr(n, m2):
    """Standard error of the mean from a (count, M2) pair (inf below two
    samples — a one-draw ensemble carries no error estimate). Works on
    scalars or arrays (numpy or jnp)."""
    n = np.asarray(n, dtype=np.float64)
    m2 = np.asarray(m2, dtype=np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        se = np.sqrt(m2 / np.maximum(n - 1.0, 1e-300) / np.maximum(n, 1.0))
    return np.where(n >= 2.0, se, np.inf)
