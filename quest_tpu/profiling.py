"""Tracing and profiling hooks.

The reference has none built in (SURVEY.md §5: only the timing harness in
`tests/benchmarks/rotate_benchmark.test` and the env reports). The TPU build
adds:

- :func:`trace` — context manager around the JAX profiler; the resulting
  trace opens in TensorBoard/Perfetto and shows every gate as a named XLA
  region;
- :class:`GateStats` — lightweight host-side counters: per-gate-name call
  counts and wall time of the (async-dispatched) API calls, plus a
  rotate-benchmark-style ``probe`` that times a gate across every target
  qubit (mean/std/min/max — the reference benchmark's statistics,
  `rotate_benchmark.test:40-60`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import defaultdict
from typing import Callable, Optional, Sequence

import jax

__all__ = ["trace", "GateStats", "DispatchStats", "probe_gate",
           "CommCostModel", "DEFAULT_COMM_MODEL", "comm_model",
           "measure_comm_model", "invalidate_comm_model",
           "TierErrorModel", "DEFAULT_TIER_MODEL",
           "tier_error_model", "measure_tier_model", "modeled_tier_error",
           "engine_tiers", "choose_tier", "tier_runtime_tol"]


# ---------------------------------------------------------------------------
# collective cost model (the layout planner's objective function)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommCostModel:
    """Two-tier linear latency/bandwidth model for one mesh collective:
    ``seconds = alpha + beta * bytes_on_the_wire`` per device, with a
    separate (alpha, beta) for collectives that cross the HOST boundary.

    The layout planner (:mod:`quest_tpu.parallel.layout`) prices every
    candidate data movement with this model and minimizes modeled comm
    TIME rather than relayout count:

    - a relayout trading ``k`` device-index bits against ``k`` chunk-local
      bits is one ``all_to_all`` over groups of ``2^k`` devices — each
      device keeps ``1/2^k`` of its chunk and ships the rest, so
      ``bytes = chunk_bytes * (2^k - 1) / 2^k`` (plus a full-chunk
      ``ppermute`` when a residual device-bit permutation remains);
    - a cross-shard 1q pair exchange (``apply_1q_cross_shard``) ships the
      whole chunk once: ``bytes = chunk_bytes``.

    **Tiers**: intra-host collectives ride ICI/shared memory
    (``alpha_s``/``beta_s_per_byte``); any collective whose exchanged
    device bits include an *inter-host* bit (the top ``host_bits``
    positions — :mod:`quest_tpu.parallel.multihost`) rides DCN and is
    priced with ``inter_alpha_s``/``inter_beta_s_per_byte``. The inter
    fields default to ``None`` = same as intra, so every single-host
    model (and every pre-two-tier caller) behaves exactly as before.

    ``alpha``/``beta`` default to a conservative interconnect model
    (:data:`DEFAULT_COMM_MODEL`); :func:`measure_comm_model` calibrates
    each tier per mesh with a tiny collective microbenchmark and caches
    the fit per ``(mesh fingerprint, tier)``. Decisions only depend on
    cost *ratios*, so plans stay deterministic for any non-degenerate
    (alpha >= 0, beta > 0) fit.
    """

    alpha_s: float              # per-collective launch latency (seconds)
    beta_s_per_byte: float      # per-byte transfer time (seconds/byte)
    source: str = "default"     # "default" | "measured"
    # inter-host (DCN) tier; None = fall back to the intra values, which
    # keeps every single-tier construction/call site bit-identical
    inter_alpha_s: Optional[float] = None
    inter_beta_s_per_byte: Optional[float] = None

    def tier(self, inter: bool = False) -> tuple[float, float]:
        """(alpha, beta) of one tier; the inter tier falls back to intra
        when uncalibrated."""
        if inter and self.inter_alpha_s is not None:
            return (self.inter_alpha_s,
                    self.inter_beta_s_per_byte
                    if self.inter_beta_s_per_byte is not None
                    else self.beta_s_per_byte)
        if inter and self.inter_beta_s_per_byte is not None:
            return (self.alpha_s, self.inter_beta_s_per_byte)
        return (self.alpha_s, self.beta_s_per_byte)

    @staticmethod
    def all_to_all_bytes(chunk_bytes: float, k: int) -> float:
        """Per-device bytes shipped by a k-bit relayout exchange."""
        if k <= 0:
            return 0.0
        return chunk_bytes * ((1 << k) - 1) / float(1 << k)

    @staticmethod
    def ppermute_bytes(chunk_bytes: float) -> float:
        """Per-device bytes shipped by a whole-chunk pair exchange."""
        return float(chunk_bytes)

    def all_to_all_seconds(self, chunk_bytes: float, k: int,
                           inter: bool = False) -> float:
        if k <= 0:
            return 0.0
        alpha, beta = self.tier(inter)
        return alpha + beta * self.all_to_all_bytes(chunk_bytes, k)

    def ppermute_seconds(self, chunk_bytes: float,
                         inter: bool = False) -> float:
        alpha, beta = self.tier(inter)
        return alpha + beta * self.ppermute_bytes(chunk_bytes)


# ~50 GB/s per-link bandwidth with a few-microsecond launch cost: the
# shape of both ICI links and a shared-memory host "mesh". The inter-host
# tier models DCN: ~25 GB/s effective per host pair with tens of
# microseconds of launch+routing latency — the order-of-magnitude gap
# mpiQulacs measures between Tofu-D intra-group and inter-group hops
# (arXiv:2203.16044 §IV). The planner's decisions are ratio-based, so the
# default is safe wherever no measurement has run.
DEFAULT_COMM_MODEL = CommCostModel(alpha_s=5e-6, beta_s_per_byte=2e-11,
                                   inter_alpha_s=5e-5,
                                   inter_beta_s_per_byte=4e-10)

# calibration cache, keyed (mesh device fingerprint, tier). A FAILED or
# degenerate fit caches the default-tier values too — the microbenchmark
# must never silently re-run on every compile (the pre-two-tier code
# returned the default UNCACHED on failure, re-paying the bench each
# call on boxes where the fit degenerates).
_COMM_MODEL_CACHE: dict = {}


def _mesh_cache_key(mesh, tier: str = "intra") -> tuple:
    devs = mesh.devices.reshape(-1)
    return (len(devs), devs[0].platform,
            getattr(devs[0], "device_kind", ""), tier)


def _model_pinned() -> bool:
    """``QUEST_TPU_COMM_MODEL=default`` pins :data:`DEFAULT_COMM_MODEL`
    deterministically — no microbenchmark ever runs (the escape hatch
    for test processes and reproducible planning)."""
    import os
    return os.environ.get("QUEST_TPU_COMM_MODEL", "") == "default"


def _measure_tier(mesh, pairs, probe_bytes, trials) -> Optional[tuple]:
    """(alpha, beta) fitted from a ppermute microbench over ``pairs``,
    or None on failure/degenerate fit."""
    import numpy as np
    try:
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from .env import AMP_AXIS
        n_dev = int(np.prod(mesh.devices.shape))
        times = []
        for nbytes in probe_bytes:
            n_f32 = max(n_dev, (nbytes // 4) * n_dev)
            x = jax.device_put(
                np.zeros(n_f32, dtype=np.float32),
                jax.sharding.NamedSharding(mesh, P(AMP_AXIS)))

            def body(local):
                return jax.lax.ppermute(local, AMP_AXIS, pairs)

            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(AMP_AXIS),),
                                   out_specs=P(AMP_AXIS), check_vma=False))
            fn(x).block_until_ready()          # compile + warm-up
            t0 = time.perf_counter()
            for _ in range(trials):
                x = fn(x)
            x.block_until_ready()
            times.append((time.perf_counter() - t0) / trials)
        b0, b1 = (float(b) for b in probe_bytes)
        t0_, t1_ = times
        beta = (t1_ - t0_) / (b1 - b0)
        alpha = t0_ - beta * b0
        if beta <= 0.0 or not np.isfinite(alpha) or not np.isfinite(beta):
            return None
        return (max(alpha, 0.0), beta)
    # quest: allow-broad-except(calibration boundary: a failed or
    # degenerate microbench fit must fall back to the default model,
    # never break compile)
    except Exception:
        return None


def measure_comm_model(mesh, probe_bytes=(1 << 14, 1 << 19),
                       trials: int = 5) -> CommCostModel:
    """Fit (alpha, beta) per interconnect tier from tiny ``ppermute``
    microbenchmarks on ``mesh``.

    The *intra* tier times a neighbour ring inside each host group; when
    the mesh spans processes (:func:`quest_tpu.parallel.multihost.
    host_topology`), the *inter* tier additionally times a cross-host
    pairing. Each tier's fit is cached per ``(mesh fingerprint, tier)``
    — including failed fits, which pin that tier's DEFAULT values — so
    the microbenchmark runs at most once per process per tier, never
    again. ``QUEST_TPU_COMM_MODEL=default`` skips measurement entirely
    and returns :data:`DEFAULT_COMM_MODEL`."""
    import numpy as np
    if _model_pinned():
        return DEFAULT_COMM_MODEL
    from .parallel.multihost import host_topology
    n_dev = int(np.prod(mesh.devices.shape))
    topo = host_topology(mesh)
    per_host = max(1, topo.devices_per_host)
    # the host grouping shapes both the pairings and which tiers exist,
    # so it is part of every cache key — flipping QUEST_TPU_FORCE_HOSTS
    # mid-process must not serve a stale single-tier model
    hosttag = f":h{topo.num_hosts}"
    mkey = _mesh_cache_key(mesh, "model" + hosttag)
    if mkey in _COMM_MODEL_CACHE:
        return _COMM_MODEL_CACHE[mkey]

    ikey = _mesh_cache_key(mesh, "intra" + hosttag)
    if ikey not in _COMM_MODEL_CACHE:
        if per_host > 1:
            # neighbour ring inside each host group: (i -> i+1) mod group
            pairs = tuple(
                (i, (i // per_host) * per_host + (i + 1) % per_host)
                for i in range(n_dev))
            fit = _measure_tier(mesh, pairs, probe_bytes, trials)
        else:
            # one device per host: every link crosses hosts, there is
            # nothing intra to time (and host_bits == shard bits means
            # the intra tier is never consulted) — pin the default
            fit = None
        _COMM_MODEL_CACHE[ikey] = fit if fit is not None else (
            DEFAULT_COMM_MODEL.alpha_s, DEFAULT_COMM_MODEL.beta_s_per_byte,
            "default")
    intra = _COMM_MODEL_CACHE[ikey]

    inter = None
    if topo.is_multihost and topo.num_hosts > 1:
        xkey = _mesh_cache_key(mesh, "inter" + hosttag)
        if xkey not in _COMM_MODEL_CACHE:
            pairs = tuple((i, (i + per_host) % n_dev) for i in range(n_dev))
            fit = _measure_tier(mesh, pairs, probe_bytes, trials)
            if fit is None:
                # derive the pinned inter tier FROM the intra fit at the
                # default DCN/ICI ratios rather than using the absolute
                # default values: a measured intra alpha above the
                # default inter alpha would otherwise invert the tiers
                # and make the planner PREFER host-crossing collectives
                ra = DEFAULT_COMM_MODEL.inter_alpha_s \
                    / DEFAULT_COMM_MODEL.alpha_s
                rb = DEFAULT_COMM_MODEL.inter_beta_s_per_byte \
                    / DEFAULT_COMM_MODEL.beta_s_per_byte
                fit_d = (intra[0] * ra, intra[1] * rb, "default")
                _COMM_MODEL_CACHE[xkey] = fit_d
            else:
                # clamp a measured inter fit to no FASTER than intra —
                # timing noise must never invert the tier ordering
                _COMM_MODEL_CACHE[xkey] = (max(fit[0], intra[0]),
                                           max(fit[1], intra[1]))
        inter = _COMM_MODEL_CACHE[xkey]

    measured = len(intra) == 2 or (inter is not None and len(inter) == 2)
    if not measured:
        model = DEFAULT_COMM_MODEL
    else:
        model = CommCostModel(
            alpha_s=intra[0], beta_s_per_byte=intra[1],
            source="measured",
            inter_alpha_s=inter[0] if inter is not None else None,
            inter_beta_s_per_byte=inter[1] if inter is not None else None)
    _COMM_MODEL_CACHE[mkey] = model
    return model


def invalidate_comm_model() -> int:
    """Drop every cached :func:`measure_comm_model` fit so the next
    plan recalibrates — the drift monitor's opt-in recalibration hook
    (:func:`quest_tpu.telemetry.profile.enable_recalibration`): when
    measured collective time departs the modeled cost by more than the
    drift threshold, the cached fit is the stale thing to throw away.
    Returns the number of cache entries dropped."""
    n = len(_COMM_MODEL_CACHE)
    _COMM_MODEL_CACHE.clear()
    return n


def comm_model(env=None, measure: Optional[bool] = None) -> CommCostModel:
    """The cost model for ``env``'s mesh: the cached per-mesh calibration
    when one exists, measuring one when asked, else
    :data:`DEFAULT_COMM_MODEL`.

    ``measure=None`` (the compile path's default) auto-calibrates on
    TPU-class meshes — real interconnects whose alpha/beta the default
    model cannot know — and keeps the default on host (CPU) meshes,
    where the virtual devices timeshare one memory system and a timing
    fit adds cross-process nondeterminism for no information.
    ``QUEST_TPU_COMM_CALIBRATE=1``/``0`` overrides either way;
    ``QUEST_TPU_COMM_MODEL=default`` pins the default model
    unconditionally (tests, reproducible planning). The fit runs once
    per process per ``(mesh fingerprint, tier)`` (cached, failures
    included)."""
    import os
    mesh = getattr(env, "mesh", None) if env is not None else None
    if mesh is None:
        return DEFAULT_COMM_MODEL
    if _model_pinned():
        return DEFAULT_COMM_MODEL
    from .parallel.multihost import host_topology
    mkey = _mesh_cache_key(
        mesh, f"model:h{host_topology(mesh).num_hosts}")
    if mkey in _COMM_MODEL_CACHE:
        return _COMM_MODEL_CACHE[mkey]
    if measure is None:
        flag = os.environ.get("QUEST_TPU_COMM_CALIBRATE")
        if flag is not None:
            measure = flag not in ("0", "", "off")
        else:
            measure = mesh.devices.reshape(-1)[0].platform == "tpu"
    if measure:
        return measure_comm_model(mesh)
    return DEFAULT_COMM_MODEL


# ---------------------------------------------------------------------------
# precision-tier error model (the budget API's objective function)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TierErrorModel:
    """Calibrated per-tier drift model: the modeled max amplitude error
    of one program execution at a tier is ``drift_per_gate[tier] *
    num_gates`` (floored at ``floor`` — shallow circuits still carry one
    rounding). Linear-in-depth is deliberately conservative: the
    measured tables (docs/accuracy.md) grow sublinearly because
    rotation-phase errors largely cancel.

    ``drift_per_gate`` maps tier name -> per-gate constant, seeded from
    the ladder's measured figures (:data:`quest_tpu.config.TIER_LADDER`)
    and refined per backend by :func:`measure_tier_model` (a cached
    microbenchmark, the :func:`measure_comm_model` pattern). A refined
    fit is clamped to never fall BELOW the measurement — the model may
    over-estimate error (choosing a slower tier than strictly needed)
    but must never promise accuracy the backend cannot deliver.
    """

    drift_per_gate: dict
    floor: float = 1e-15
    source: str = "default"      # "default" | "measured"
    # silicon-calibrated per-tier execution cost (seconds per gate pass
    # of the calibration workload, measured on the LIVE backend — the
    # MXU pass count each tier actually pays, including the compensated
    # tiers' extra reduction traffic). Empty = unmeasured; the CPU
    # proxy never fills it.
    cost_per_gate: dict = dataclasses.field(default_factory=dict)
    cost_source: str = "none"    # "none" | "silicon"

    def error(self, tier, num_gates: int) -> float:
        from .config import tier_by_name
        tier = tier_by_name(tier)
        per_gate = self.drift_per_gate.get(tier.name,
                                           tier.drift_per_gate)
        return max(per_gate * max(int(num_gates), 1), self.floor)

    def cost_ratio(self, tier) -> float:
        """Measured cost of one gate pass at ``tier`` relative to the
        FAST rung (1.0 when uncalibrated) — the reduction trade priced
        by measured silicon instead of a CPU proxy."""
        from .config import tier_by_name
        tier = tier_by_name(tier)
        base = self.cost_per_gate.get("fast")
        mine = self.cost_per_gate.get(tier.name)
        if not base or not mine:
            return 1.0
        return mine / base


def _default_tier_model() -> TierErrorModel:
    from .config import TIER_LADDER
    return TierErrorModel(
        drift_per_gate={t.name: t.drift_per_gate for t in TIER_LADDER})


DEFAULT_TIER_MODEL = _default_tier_model()

# calibration cache, keyed on the backend fingerprint — the microbench
# must run at most once per process per backend (failed fits pin the
# default seeds, the _COMM_MODEL_CACHE discipline). Locked: unlike the
# comm-model cache (compile-time only), this one is reachable from
# SimulationService.submit(error_budget=...) — a documented thread-safe
# entry — so concurrent first submits must not each pay the bench
import threading as _threading
_TIER_MODEL_CACHE: dict = {}
_TIER_MODEL_LOCK = _threading.Lock()


def _tier_model_pinned() -> bool:
    """``QUEST_TPU_TIER_MODEL=default`` pins the seed constants
    deterministically — no microbenchmark ever runs (tests,
    reproducible tier selection)."""
    import os
    return os.environ.get("QUEST_TPU_TIER_MODEL", "") == "default"


def _tier_silicon_auto() -> bool:
    """Silicon cost calibration defaults ON for accelerator backends
    (real MXUs whose pass counts a CPU proxy cannot price) and OFF on
    hosts; ``QUEST_TPU_TIER_SILICON=1/0`` overrides."""
    import os
    import jax as jax_
    flag = os.environ.get("QUEST_TPU_TIER_SILICON")
    if flag is not None:
        return flag not in ("0", "", "off")
    return jax_.default_backend() == "tpu"


def _mesh_fingerprint(env) -> tuple:
    """The env's device fingerprint — backend, device kind, device
    count — the :func:`measure_comm_model` cache-key discipline, so a
    model measured on one mesh shape is never served to another."""
    import jax as jax_
    try:
        dev = jax_.devices()[0]
        kind = getattr(dev, "device_kind", "")
    except (RuntimeError, IndexError):
        kind = ""
    return (jax_.default_backend(), kind,
            int(getattr(env, "num_devices", 1)))


def measure_tier_model(env, num_qubits: int = 8, layers: int = 4,
                       silicon: Optional[bool] = None) -> TierErrorModel:
    """Refine the per-tier drift constants with a tiny fixed-workload
    microbenchmark: a seeded brickwork circuit runs at each
    engine-executable tier and its state is compared against the most
    accurate tier available; the measured max|Δ|/gate refines each
    tier's constant (never below the measurement; never below the
    model floor).

    ``silicon`` (default: auto — on for accelerator backends, off on
    hosts; ``QUEST_TPU_TIER_SILICON`` overrides) additionally TIMES
    each tier's executable on the live backend — device-synced
    best-of-trials seconds per gate pass — so the reduction trade
    (compensated pair-path tiers pay real extra passes, the FAST rung's
    bf16 matmuls pay fewer MXU passes than HIGHEST's six-pass form) is
    priced by measured silicon rather than a CPU proxy; the figures
    land in :attr:`TierErrorModel.cost_per_gate` /
    :meth:`~TierErrorModel.cost_ratio`.

    Cached per mesh fingerprint (backend, device kind, device count,
    storage dtype, silicon flag — the :func:`measure_comm_model`
    discipline), failures included (they pin the seeds), so the bench
    runs at most once per process per fingerprint."""
    import numpy as np_
    if _tier_model_pinned():
        return DEFAULT_TIER_MODEL
    if silicon is None:
        silicon = _tier_silicon_auto()
    key = _mesh_fingerprint(env) + (
        str(np_.dtype(env.precision.real_dtype)), bool(silicon))
    with _TIER_MODEL_LOCK:
        if key in _TIER_MODEL_CACHE:
            return _TIER_MODEL_CACHE[key]
        return _measure_tier_model_locked(env, key, num_qubits, layers,
                                          silicon)


def _measure_tier_model_locked(env, key, num_qubits, layers, silicon):
    import numpy as np_
    try:
        from .circuits import Circuit
        from .config import TIER_LADDER
        rng = np_.random.default_rng(20260803)
        c = Circuit(num_qubits)
        n_gates = 0
        for _ in range(layers):
            for q in range(num_qubits):
                c.ry(q, float(rng.uniform(0, 2 * np_.pi)))
                n_gates += 1
            for q in range(0, num_qubits - 1, 2):
                c.cnot(q, q + 1)
                n_gates += 1
        cc = c.compile(env, pallas=False)
        tiers = engine_tiers(env)
        pm = np_.zeros((1, 0))
        states = {t.name: np_.asarray(cc.sweep(pm, tier=t))[0]
                  for t in tiers}
        oracle = states[tiers[-1].name]
        drift = dict(DEFAULT_TIER_MODEL.drift_per_gate)
        for t in tiers[:-1]:
            meas = float(np_.max(np_.abs(states[t.name] - oracle)))
            # 4x headroom over the measurement; never promise better
            # than the seed claims the hardware can do... the seed may
            # only be LOWERED when the backend measures cleaner by a
            # decade (e.g. FAST on CPU, where DEFAULT matmuls stay f32)
            refined = max(4.0 * meas / n_gates, DEFAULT_TIER_MODEL.floor)
            drift[t.name] = max(refined, drift[t.name] / 10.0) \
                if refined < drift[t.name] else refined
        cost: dict = {}
        if silicon:
            import jax as jax_
            trials = 3
            for t in tiers:
                # warmed above (the drift sweep compiled each tier);
                # time device-synced best-of-trials on the LIVE backend
                best = None
                for _ in range(trials):
                    t0 = time.perf_counter()
                    out = cc.sweep(pm, tier=t)
                    jax_.block_until_ready(out)
                    dt = time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                cost[t.name] = best / max(n_gates, 1)
        model = TierErrorModel(
            drift_per_gate=drift, source="measured",
            cost_per_gate=cost,
            cost_source="silicon" if cost else "none")
    # quest: allow-broad-except(calibration boundary: tier-model
    # measurement failure falls back to the conservative default)
    except Exception:
        model = DEFAULT_TIER_MODEL
    _TIER_MODEL_CACHE[key] = model
    return model


def tier_error_model(env=None, measure: Optional[bool] = None
                     ) -> TierErrorModel:
    """The tier error model for ``env``: the cached per-backend
    calibration when one exists, measuring one when asked, else the
    seed constants. ``measure=None`` auto-calibrates only on TPU-class
    backends (real MXUs whose bf16 drift the seeds cannot know exactly);
    host (CPU) runs keep the deterministic defaults.
    ``QUEST_TPU_TIER_MODEL=default`` pins the seeds unconditionally."""
    import os
    import jax as jax_
    if env is None or _tier_model_pinned():
        return DEFAULT_TIER_MODEL
    if measure is None:
        flag = os.environ.get("QUEST_TPU_TIER_CALIBRATE")
        if flag is not None:
            measure = flag not in ("0", "", "off")
        else:
            measure = jax_.default_backend() == "tpu"
    if measure:
        return measure_tier_model(env)
    return DEFAULT_TIER_MODEL


def modeled_tier_error(tier, num_gates: int, model: Optional[
        TierErrorModel] = None) -> float:
    """Modeled max amplitude error of one ``num_gates``-gate program
    execution at ``tier``."""
    return (model or DEFAULT_TIER_MODEL).error(tier, num_gates)


def engine_tiers(env) -> tuple:
    """The ladder rungs the BATCHED ENGINE can execute on this env, in
    rank order. FAST and SINGLE always run (f32 planes); DOUBLE and
    QUAD need x64 (without it JAX would silently downcast the f64
    planes — the same guard as the QUAD64 env check) AND an f64 STORAGE
    precision — results leave the engine as env-dtype planes, so on an
    f32 env a DOUBLE execution would round straight back to f32 on exit
    (and QUAD's ~48-bit dd significand would too) and silently violate
    the budget that selected the tier. QUAD executes through the
    engine's double-double runner (``CompiledCircuit.
    _dd_batched_runner``) as a per-dispatch tier, so the serving
    ladder's escalation tops out at the genuinely highest rung instead
    of silently excluding it."""
    import jax as jax_
    import numpy as np_
    from .config import DOUBLE_TIER, FAST_TIER, QUAD_TIER, SINGLE_TIER
    tiers = [FAST_TIER, SINGLE_TIER]
    if jax_.config.jax_enable_x64 and env is not None and \
            np_.dtype(env.precision.real_dtype) == np_.dtype(np_.float64):
        tiers.append(DOUBLE_TIER)
        tiers.append(QUAD_TIER)
    return tuple(tiers)


def choose_tier(error_budget: float, num_gates: int, env=None,
                model: Optional[TierErrorModel] = None,
                tiers: Optional[Sequence] = None):
    """The budget API's selector: the CHEAPEST (lowest-rank) tier whose
    modeled error fits ``error_budget``, over the engine-executable
    ladder for ``env`` (or an explicit ``tiers`` subset).

    Monotone by construction: the ladder is rank-ordered with
    non-increasing drift, so a tighter budget can only move the choice
    UP the ladder, never to a faster tier. Raises ``ValueError`` when
    no available tier fits — an unmeetable budget is a caller error the
    submit/compile boundary must surface, not a silently-wrong answer."""
    if not (error_budget > 0.0):
        raise ValueError(f"error_budget must be > 0, got {error_budget!r}")
    model = model or (tier_error_model(env) if env is not None
                      else DEFAULT_TIER_MODEL)
    ladder = tuple(tiers) if tiers is not None else engine_tiers(env)
    for t in sorted(ladder, key=lambda t: t.rank):
        if model.error(t, num_gates) <= error_budget:
            return t
    best = min((model.error(t, num_gates) for t in ladder), default=None)
    raise ValueError(
        f"error budget {error_budget:g} is unmeetable on this "
        f"environment: the most accurate available tier models "
        f"{best:g} over {num_gates} gates (enable x64 for the DOUBLE "
        f"tier, or use the double-double compile_dd path)")


def tier_runtime_tol(tier, num_gates: int,
                     model: Optional[TierErrorModel] = None,
                     headroom: float = 8.0) -> float:
    """The runtime fidelity monitor's norm/trace drift threshold for one
    tier: ``headroom`` times the modeled per-run error, floored at the
    health guard's default 1e-6 (shallow f64 programs must not trip on
    benign rounding) and capped at 2e-2 (a drift past two percent is
    never in-budget at ANY tier — it is a numerical fault whatever the
    model says)."""
    err = modeled_tier_error(tier, num_gates, model)
    return float(min(max(headroom * err, 1e-6), 2e-2))


@dataclasses.dataclass
class DispatchStats:
    """Compile-time dispatch accounting for one compiled program: how
    many recorded gates went in, how many kernels (fused groups, folded
    diagonals, layers, relayouts) the final plan dispatches. Produced by
    :meth:`CompiledCircuit.dispatch_stats`; ``bench.py`` machine-emits
    these fields next to gates/sec so the fusion win is parseable."""

    gates_in: int            # ops recorded on the circuit
    kernels_out: int         # op items in the final plan
    relayouts: int           # planned all-to-all relayouts
    fused_groups: int = 0    # dense fusion groups of >= 2 gates
    diag_folds: int = 0      # diagonal gates folded into shared factors
    commuted_diagonals: int = 0  # diagonals deferred past a dense run
    max_group_gates: int = 0     # largest gates-per-group count
    # communication-planner accounting (quest_tpu/parallel/layout.py):
    cross_shard_exchanges: int = 0  # 1q pair-exchange items in the plan
    # collective ops the program issues per run (each relayout's
    # all_to_all and residual ppermute, each pair exchange) — the
    # communication planner's primary observable
    collective_launches: int = 0
    swaps_absorbed: int = 0      # SWAP gates composed into the layout perm
    collectives_fused: int = 0   # relayout pairs merged into one exchange
    comm_bytes_planned: float = 0.0  # mesh-total collective bytes per run
    comm_bytes_saved: float = 0.0    # vs the count-based planner's plan
    # multi-host (two-tier) accounting (quest_tpu/parallel/multihost.py):
    num_hosts: int = 1               # controller processes the mesh spans
    inter_host_collectives: int = 0  # planned collectives crossing hosts
    comm_bytes_inter_planned: float = 0.0  # mesh-total DCN bytes per run
    comm_bytes_inter_saved: float = 0.0    # vs the reordering-off plan
    # batched ensemble engine accounting (set by the last sweep /
    # expectation_sweep / sample_sweep on the compiled circuit):
    batch_size: int = 0              # points in the last batched run
    host_syncs_avoided: int = 0      # device->host transfers vs per-point
    batch_sharding_mode: str = "none"  # "none" | "batch" | "amp"
    # device-resident dynamics accounting (evolve_sweep/ground_sweep):
    # Trotter/imaginary-time steps the last dynamics dispatch iterated
    # inside ONE executable (batch x steps; 0 for non-dynamics runs)
    evolve_steps_fused: int = 0
    # keyed executable cache accounting (serving workloads cycle
    # (form, donation, mode, dtype, tier) keys; the cache is LRU-bounded
    # — QUEST_TPU_BATCH_CACHE — so long-lived services can't pin one
    # executable per key forever):
    batched_cache_size: int = 0        # live entries in the bounded cache
    batched_cache_evictions: int = 0   # executables dropped by the bound
    # single-chip Pallas routing: plan items run as row-gate passes
    # (ops/pallas_kernels.apply_rowgate_planes; 0 where Pallas is off)
    rowgate_passes: int = 0
    # precision-tier accounting (config.PrecisionTier; "env" = the
    # legacy per-environment precision, no tier selected):
    precision_tier: str = "env"        # compile-time tier of this program
    modeled_tier_error: float = 0.0    # the budget model's per-run bound

    @property
    def dispatches(self) -> int:
        """Kernels the device runs per program execution (op passes plus
        relayout and pair exchanges) — the number the fusion pass and the
        communication planner exist to shrink."""
        return self.kernels_out + self.relayouts + self.cross_shard_exchanges

    def as_dict(self) -> dict:
        return {"gates_in": self.gates_in,
                "kernels_out": self.kernels_out,
                "relayouts": self.relayouts,
                "dispatches": self.dispatches,
                "fused_groups": self.fused_groups,
                "diag_folds": self.diag_folds,
                "commuted_diagonals": self.commuted_diagonals,
                "max_group_gates": self.max_group_gates,
                "cross_shard_exchanges": self.cross_shard_exchanges,
                "swaps_absorbed": self.swaps_absorbed,
                "collectives_fused": self.collectives_fused,
                "collective_launches": self.collective_launches,
                "comm_bytes_planned": self.comm_bytes_planned,
                "comm_bytes_saved": self.comm_bytes_saved,
                "num_hosts": self.num_hosts,
                "inter_host_collectives": self.inter_host_collectives,
                "comm_bytes_inter_planned": self.comm_bytes_inter_planned,
                "comm_bytes_inter_saved": self.comm_bytes_inter_saved,
                "batch_size": self.batch_size,
                "host_syncs_avoided": self.host_syncs_avoided,
                "batch_sharding_mode": self.batch_sharding_mode,
                "evolve_steps_fused": self.evolve_steps_fused,
                "batched_cache_size": self.batched_cache_size,
                "batched_cache_evictions": self.batched_cache_evictions,
                "rowgate_passes": self.rowgate_passes,
                "precision_tier": self.precision_tier,
                "modeled_tier_error": self.modeled_tier_error}


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Profile everything inside the block to ``logdir``."""
    jax.profiler.start_trace(logdir, create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class _Entry:
    calls: int = 0
    seconds: float = 0.0


class GateStats:
    """Count and time API-level gate calls.

    Use as a context manager around user code; it monkey-wraps the public
    gate functions of :mod:`quest_tpu.api` for the duration. Times are
    dispatch times (JAX is async); call :meth:`synced` around a block to
    include device completion.
    """

    GATE_NAMES = (
        "hadamard", "pauliX", "pauliY", "pauliZ", "sGate", "tGate",
        "phaseShift", "rotateX", "rotateY", "rotateZ", "rotateAroundAxis",
        "compactUnitary", "unitary", "controlledNot", "controlledPauliY",
        "controlledPhaseShift", "controlledPhaseFlip", "controlledRotateX",
        "controlledRotateY", "controlledRotateZ", "controlledCompactUnitary",
        "controlledUnitary", "multiControlledUnitary", "swapGate",
        "sqrtSwapGate", "multiRotateZ", "twoQubitUnitary", "multiQubitUnitary",
        "measure", "collapseToOutcome",
    )

    def __init__(self):
        self.entries: dict[str, _Entry] = defaultdict(_Entry)
        self._saved: dict[str, Callable] = {}

    def __enter__(self):
        import quest_tpu
        from . import api
        for name in self.GATE_NAMES:
            fn = getattr(api, name)
            self._saved[name] = fn

            def wrapped(*args, _fn=fn, _name=name, **kw):
                t0 = time.perf_counter()
                out = _fn(*args, **kw)
                e = self.entries[_name]
                e.calls += 1
                e.seconds += time.perf_counter() - t0
                return out

            setattr(api, name, wrapped)
            setattr(quest_tpu, name, wrapped)
        return self

    def __exit__(self, *exc):
        import quest_tpu
        from . import api
        for name, fn in self._saved.items():
            setattr(api, name, fn)
            setattr(quest_tpu, name, fn)
        self._saved.clear()
        return False

    @property
    def total_calls(self) -> int:
        return sum(e.calls for e in self.entries.values())

    def report(self) -> str:
        lines = [f"{'gate':<28}{'calls':>8}{'total s':>12}{'per call us':>14}"]
        for name, e in sorted(self.entries.items(),
                              key=lambda kv: -kv[1].seconds):
            per = e.seconds / e.calls * 1e6 if e.calls else 0.0
            lines.append(f"{name:<28}{e.calls:>8}{e.seconds:>12.4f}{per:>14.1f}")
        return "\n".join(lines)


def probe_gate(qureg, gate_fn: Callable, num_trials: int = 20,
               targets: Optional[range] = None) -> dict:
    """rotate_benchmark-equivalent: time ``gate_fn(qureg, target)`` over every
    target qubit, ``num_trials`` each; returns per-target mean/std/min/max
    seconds (device-synced)."""
    import numpy as np
    targets = targets or range(qureg.num_qubits_represented)
    results = {}
    for t in targets:
        gate_fn(qureg, t)                      # warm the compile cache
        qureg.state.block_until_ready()
        times = []
        for _ in range(num_trials):
            t0 = time.perf_counter()
            gate_fn(qureg, t)
            qureg.state.block_until_ready()
            times.append(time.perf_counter() - t0)
        arr = np.asarray(times)
        results[int(t)] = {"mean": float(arr.mean()), "std": float(arr.std()),
                           "min": float(arr.min()), "max": float(arr.max())}
    return results
