"""Shard-local inverse-CDF sampling (``sampleOutcomes`` on a mesh).

The single-device sampler cumsums the full probability vector; under GSPMD
that lowering materialises full-state-sized buffers on every device
(measured: a 2x-state f32 buffer in the compiled HLO at 20q / 8 devices),
which cannot scale to pod-sized registers. This shard_map program keeps
every buffer shard-local — the sampling analogue of the reference's
rank-local reductions (``statevec_calcTotalProb``,
``QuEST_cpu_distributed.c:87-109``):

1. each device cumsums only its own chunk; the exclusive prefix over
   devices comes from an all_gather of D scalars,
2. every device draws the same uniforms (same key, replicated), and claims
   the draws landing in its half-open interval ``[ecum[d], ecum[d+1])`` of
   cumulative probability — the intervals partition ``[0, T)``, so each
   draw is claimed by exactly one shard (the last shard also claims
   ``>= T`` round-up strays),
3. one psum pair combines the (shard, local-index) claims.

Memory per device: one chunk pass + ``m`` scalars. Collectives: one
``all_gather`` of D scalars + two ``psum(m)`` — independent of register
size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ..env import AMP_AXIS

__all__ = ["sample_sharded", "sample_batched", "sample_mixture",
           "shot_bucket"]


# Bounded: an unbounded cache keyed on raw shot counts compiles and pins
# a fresh shard_map executable (plus its mesh object) FOREVER per
# distinct num_samples — a shot-count sweep leaks compilations without
# limit (ADVICE r5). Shot counts are bucketed to the next power of two
# at or above, so the practical key space is ~log2(max shots) per mesh
# and 32 entries cover every realistic mix of meshes and widths.
@functools.lru_cache(maxsize=32)
def _sampler(mesh, num_samples: int, density: bool, num_qubits: int):
    def body(planes, key):
        if density:
            # local rows of the 2^n x 2^n matrix; global row r0+j holds
            # its diagonal element at column r0+j — a shard-local gather
            dim = 1 << num_qubits
            rows = planes.shape[1] // dim
            d = planes.reshape(2, rows, dim)
            r0 = lax.axis_index(AMP_AXIS) * rows
            j = jnp.arange(rows)
            probs = jnp.maximum(d[0, j, r0 + j], 0.0)
        else:
            probs = planes[0] * planes[0] + planes[1] * planes[1]
        local_cum = jnp.cumsum(probs)
        totals = lax.all_gather(local_cum[-1], AMP_AXIS)        # (D,)
        ecum = jnp.concatenate([jnp.zeros((1,), totals.dtype),
                                jnp.cumsum(totals)])
        i = lax.axis_index(AMP_AXIS)
        lo, hi = ecum[i], ecum[i + 1]
        total = ecum[-1]
        draws = jax.random.uniform(key, (num_samples,),
                                   dtype=local_cum.dtype) * total
        mine = (draws >= lo) & (draws < hi)
        mine = mine | ((i == totals.shape[0] - 1) & (draws >= total))
        loc = jnp.searchsorted(local_cum, draws - lo, side="right")
        loc = jnp.minimum(loc, probs.shape[0] - 1).astype(jnp.int32)
        return (lax.psum(jnp.where(mine, i, 0).astype(jnp.int32), AMP_AXIS),
                lax.psum(jnp.where(mine, loc, 0), AMP_AXIS),
                total)

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(None, AMP_AXIS), P()),
        out_specs=(P(), P(), P()), check_vma=False))


def shot_bucket(num_samples: int) -> int:
    """Static shot-count bucket: the next power of two at or above
    ``num_samples`` (floor 16). One compiled program then serves every
    shot count in (bucket/2, bucket]; surplus draws are discarded
    host-side — they are iid, so the kept prefix is an exact
    ``num_samples``-shot draw. Public because the serving runtime's
    coalescer (:mod:`quest_tpu.serve.coalesce`) groups shot requests by
    this same band — two requests share a sampling executable exactly
    when they share a bucket."""
    b = 16
    while b < num_samples:
        b <<= 1
    return b


_shot_bucket = shot_bucket   # pre-serve internal name (kept for callers)


def sample_sharded(planes: jax.Array, key, num_samples: int, density: bool,
                   num_qubits: int, mesh):
    """Draw ``num_samples`` basis indices from a SHARDED register's
    distribution. ``planes`` is the flat (2, N) re/im state (the full
    density vector for mixed registers — the diagonal is extracted
    shard-locally). Returns ``(indices int64 ndarray, total)`` with the
    shard/local split recombined in host int64, so the device program
    never needs 64-bit indices even at pod widths. Shot counts are
    bucketed (``shot_bucket``) so a sweep over counts reuses one
    compiled program per power-of-two band."""
    bucket = shot_bucket(int(num_samples))
    shard, loc, total = _sampler(mesh, bucket, bool(density),
                                 int(num_qubits))(planes, key)
    n_dev = int(np.prod(mesh.devices.shape))
    per_shard = (1 << num_qubits) // n_dev
    idx = (np.asarray(shard, dtype=np.int64)[:num_samples] * per_shard
           + np.asarray(loc, dtype=np.int64)[:num_samples])
    return idx, float(total)


# Batch-keyed shot sampler for the ensemble engine: one vmapped
# inverse-CDF executable draws num_samples outcomes from EVERY state of a
# (B, 2, N) batch, each batch element under its own fold of the key.
# Bounded + bucketed exactly like the mesh `_sampler` above (ADVICE r5):
# shot counts share `shot_bucket`'s power-of-two bands, so a shot-count
# sweep reuses one executable per band instead of pinning a fresh
# compilation per distinct count — and the two caches are independent
# (batched draws never populate mesh `_sampler` entries, or vice versa).
@functools.lru_cache(maxsize=32)
def _batch_sampler(num_samples: int):
    def body(planes, key):
        probs = planes[0] * planes[0] + planes[1] * planes[1]
        cum = jnp.cumsum(probs)
        draws = jax.random.uniform(key, (num_samples,),
                                   dtype=cum.dtype) * cum[-1]
        idx = jnp.searchsorted(cum, draws, side="right")
        return (jnp.minimum(idx, probs.shape[0] - 1).astype(jnp.int32),
                cum[-1])

    return jax.jit(jax.vmap(body, in_axes=(0, 0)))


def sample_batched(planes: jax.Array, key, num_samples: int):
    """Draw ``num_samples`` basis outcomes from EACH state of a batch.

    ``planes``: ``(B, 2, N)`` packed re/im planes (the batched engine's
    output shape). ``key`` is split per batch element so the B shot
    streams are independent. Returns ``(indices, totals)``: int64
    ``(B, num_samples)`` basis indices and the ``(B,)`` state norms
    (pre-normalisation totals, for zero-norm guards) — one device pass
    and two transfers (index block + totals) for the whole shot batch,
    where per-point ``sampleOutcomes`` loops pay one round-trip per
    point."""
    if int(num_samples) < 1:
        raise ValueError("num_samples must be >= 1")
    bucket = shot_bucket(int(num_samples))
    keys = jax.random.split(key, planes.shape[0])
    idx, totals = _batch_sampler(bucket)(planes, keys)
    return (np.asarray(idx, dtype=np.int64)[:, :num_samples],
            np.asarray(totals))


def sample_mixture(planes: jax.Array, key, num_samples: int):
    """Draw ``num_samples`` basis outcomes from the uniform MIXTURE of a
    trajectory ensemble: ``planes`` is the ``(T, 2, N)`` batch a
    trajectory sweep produced (every trajectory carries weight 1/T —
    draws are unit-norm by construction), and the shot budget is
    STRATIFIED evenly over the trajectories (ceil(S/T) iid draws each,
    interleaved trajectory-major and trimmed to S). Stratification is an
    unbiased — strictly variance-reduced — sampling of the mixture
    distribution, and it reuses the bucketed batch sampler, so the whole
    noisy-circuit shot block costs the same two transfers as a clean
    ``sample_batched`` call. Returns ``(indices int64[num_samples],
    totals (T,))``."""
    if int(num_samples) < 1:
        raise ValueError("num_samples must be >= 1")
    num_traj = planes.shape[0]
    per = -(-int(num_samples) // num_traj)
    idx, totals = sample_batched(planes, key, per)
    # interleave (trajectory-major round-robin) so a trimmed prefix
    # still spreads over all trajectories instead of starving the tail
    flat = np.asarray(idx, dtype=np.int64).T.reshape(-1)[:num_samples]
    return flat, np.asarray(totals)
