"""Execution environment: device mesh, precision, randomness.

TPU-native replacement for ``QuESTEnv`` (``QuEST.h:200-204``) and the
per-backend ``createQuESTEnv`` implementations (MPI init
``QuEST_cpu_distributed.c:128-157``, GPU probe ``QuEST_gpu.cu:353-367``):
there is no build-time backend fork — one environment object carries

- a :class:`jax.sharding.Mesh` over the amplitude axis (``None`` = single
  device), replacing rank/numRanks bookkeeping; a single-device env may
  name its ``device``, so that replicas on one host each keep their state
  and executables on their own chip;
- the numeric :class:`~quest_tpu.config.Precision` (runtime, not compile-time);
- a single ``jax.random`` key, split per draw — the analogue of the
  rank-0-seeded, broadcast mt19937 stream (``QuEST_cpu_distributed.c:1318-1329``):
  in SPMD there is one logical program, so agreement is automatic.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from .config import Precision, default_precision

__all__ = ["QuESTEnv", "create_quest_env", "destroy_quest_env",
           "initialize_multihost", "default_compensated"]

AMP_AXIS = "amps"


@dataclasses.dataclass
class QuESTEnv:
    """Runtime environment handle (mesh + precision + RNG)."""

    precision: Precision
    mesh: Optional[Mesh] = None
    key: jax.Array = None  # type: ignore[assignment]
    # error-compensated scalar reductions (TwoSum cascade,
    # ops/reductions.py) — the runtime analogue of the reference's Kahan
    # summation (``QuEST_cpu_distributed.c:87-109``); restores
    # 1e-10-class totals/inner-products for single-precision registers
    compensated: bool = False
    # the one device of a mesh-less env (None = JAX's default device)
    device: Optional[jax.Device] = None

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape)) if self.mesh is not None else 1

    @property
    def rank(self) -> int:
        """Process index (0 on single-host; mirrors QuESTEnv.rank)."""
        return jax.process_index()

    @property
    def is_multihost(self) -> bool:
        """True when the mesh spans more than one controller process —
        the TPU-pod analogue of the reference's multi-node MPI run
        (``QuEST_cpu_distributed.c:128-157``). Data paths switch to
        shard-local construction + allgather reads (qureg.py) and the
        default seed is agreed by rank-0 broadcast (:meth:`seed_default`)."""
        return jax.process_count() > 1

    @property
    def num_ranks(self) -> int:
        return self.num_devices

    @property
    def devices(self) -> list:
        """The devices this env's arrays and executables live on."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return [self.device if self.device is not None
                else jax.devices()[0]]

    def sharding(self, sharded: bool = True):
        """Sharding for a packed (2, 2^N) state array: the amplitude
        axis is split on its leading (high-qubit) bits — the chunkId-prefix
        layout of ``QuEST.h:169-177`` — and the re/im plane axis is
        replicated. A mesh-less env gives its own device's sharding, or
        None when it names no device."""
        if self.mesh is None:
            return None if self.device is None \
                else SingleDeviceSharding(self.device)
        spec = PartitionSpec(None, AMP_AXIS) if sharded else PartitionSpec()
        return NamedSharding(self.mesh, spec)

    def sharding_flat(self):
        """Sharding for a flat (2^N,) amplitude vector (jit-internal
        complex form): leading bits over the mesh axis."""
        if self.mesh is None:
            return self.sharding()
        return NamedSharding(self.mesh, PartitionSpec(AMP_AXIS))

    def default_device(self):
        """Context that makes this env's device JAX's default, so arrays
        built without an input (fresh sweep states, parameter vectors)
        land on it too. A no-op for a mesh or an env without a device."""
        if self.mesh is None and self.device is not None:
            return jax.default_device(self.device)
        return contextlib.nullcontext()

    def seed(self, seeds: Sequence[int]) -> None:
        """Re-seed the measurement RNG (``seedQuEST`` ``QuEST.h:1858``)."""
        key = jax.random.key(int(seeds[0]) & 0xFFFFFFFF)
        for s in seeds[1:]:
            key = jax.random.fold_in(key, int(s) & 0xFFFFFFFF)
        self.key = key

    def seed_default(self) -> None:
        """Seed from time and pid (``seedQuESTDefault``
        ``QuEST_common.c:181-213``). Multi-host: every process must hold
        the SAME key (one logical SPMD program), so rank 0's seed is
        broadcast — the reference's ``MPI_Bcast`` of the mt19937 key
        (``QuEST_cpu_distributed.c:1318-1329``)."""
        seeds = [int(time.time() * 1e6) & 0xFFFFFFFF, os.getpid()]
        if self.is_multihost:
            from jax.experimental import multihost_utils
            seeds = [int(s) for s in
                     multihost_utils.broadcast_one_to_all(np.asarray(seeds))]
        self.seed(seeds)

    def next_key(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub

    def sync(self) -> None:
        """Barrier analogue (``syncQuESTEnv``): SPMD programs need no explicit
        barrier; block until async dispatch drains instead."""
        jax.effects_barrier()

    def report(self) -> str:
        plats = {d.platform for d in jax.devices()}
        lines = [
            "QuEST-TPU execution environment:",
            f"  backend devices: {len(jax.devices())} ({', '.join(sorted(plats))})",
            f"  mesh: {'none (single device)' if self.mesh is None else str(self.mesh.shape)}",
            f"  precision: {self.precision.name} ({self.precision.complex_dtype})",
        ]
        return "\n".join(lines)


def default_compensated(precision: Precision) -> bool:
    """The ONE definition of the compensated-reductions default: on for
    single precision (where naive f32 accumulation falls ~5 decades
    short of the reference's 1e-10 scalar tolerance), off for double
    and the dd tiers (already exact enough). Shared by
    :func:`create_quest_env` and the router's replica-env builder
    (:func:`quest_tpu.serve.router.replica_envs`) so replica
    environments can never drift from the primary's default."""
    return precision.quest_prec == 1


def create_quest_env(
    num_devices: Optional[int] = None,
    precision: Optional[Precision] = None,
    seed: Optional[Sequence[int]] = None,
    compensated: Optional[bool] = None,
    device: Optional[jax.Device] = None,
) -> QuESTEnv:
    """Create the execution environment (``createQuESTEnv`` ``QuEST.h:785``).

    ``num_devices=None`` uses all local devices when more than one is present
    (as the reference's MPI build uses all ranks), else single-device.
    ``device`` pins a single-device env to that device (one chip of a
    multi-chip host) instead of JAX's default one.
    ``compensated=None`` enables TwoSum-compensated scalar reductions
    automatically for single precision (where naive float32 accumulation
    falls ~5 decades short of the reference's 1e-10 tolerance) and disables
    them for double.
    """
    precision = precision or default_precision()
    if (precision.quest_prec == 4 and precision.real_dtype == "float64"
            and not jax.config.jax_enable_x64):
        raise ValueError(
            "QUAD64 needs jax_enable_x64; without it JAX silently "
            "downcasts the f64 planes and the quad tier quietly "
            "degrades — use QUAD (f32 planes) on x64-less backends")
    if compensated is None:
        compensated = default_compensated(precision)
    devices = jax.devices()
    if device is not None:
        if num_devices not in (None, 1):
            raise ValueError("device= names the one device of a "
                             "single-device env; use num_devices=1")
        num_devices = 1
    n = len(devices) if num_devices is None else num_devices
    if n > len(devices):
        raise ValueError(f"requested {n} devices but only {len(devices)} available")
    mesh = None
    if n > 1:
        if n & (n - 1):
            raise ValueError("the device count must be a power of 2 "
                             "(amplitude sharding halves per device)")
        mesh = Mesh(np.asarray(devices[:n]), (AMP_AXIS,))
    env = QuESTEnv(precision=precision, mesh=mesh, compensated=compensated,
                   device=device)
    if seed is not None:
        env.seed(seed)
    else:
        env.seed_default()
    return env


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Join a multi-controller (multi-host) run BEFORE creating the env —
    the analogue of ``MPI_Init`` (``QuEST_cpu_distributed.c:128-157``).

    Thin wrapper over :func:`quest_tpu.parallel.multihost.bootstrap`
    (``jax.distributed.initialize``): on TPU pods all arguments
    auto-detect from the runtime; on CPU/GPU clusters pass the
    coordinator endpoint and process coordinates. After this,
    ``jax.devices()`` spans every host's chips, ``create_quest_env()``
    meshes over all of them, and the amplitude axis shards across the pod
    with XLA collectives riding ICI/DCN — no further code changes; the
    same SPMD program runs on every process, and the layout planner
    prices each collective by the interconnect tier it crosses
    (``parallel/multihost.py`` + the two-tier
    :class:`~quest_tpu.profiling.CommCostModel`). Exercised end-to-end by
    ``tests/test_multihost.py``: 2- and 4-process coordinator-connected
    CPU runs building one global mesh (sharded circuit, psum reductions,
    broadcast seed agreement, allgathered reads)."""
    from .parallel.multihost import bootstrap
    bootstrap(coordinator_address, num_processes=num_processes,
              process_id=process_id)


def destroy_quest_env(env: QuESTEnv) -> None:
    """No-op (buffers are GC-managed); kept for API parity
    (``destroyQuESTEnv`` ``QuEST.h:795``)."""
